"""Fast CPU smoke for the mx.kernels Pallas tier (seconds, not minutes).

Proves every leg of the kernel tier on the host backend (where the
kernels run through the Pallas interpreter — same numerics, no TPU),
with one parseable JSON line on stdout:

  1. flash    — fused flash-attention fwd AND grads (custom_vjp) match
                the XLA lowering (parallel.ring_attention.attention) on
                causal and non-causal f32 problems;
  2. softmax  — pallas_row_softmax grads match jnp.softmax grads (the
                custom_vjp reuses the saved row max/sum);
  3. routing  — kernels.attention counts kernels.flash_attention on a
                supported shape and kernels.fallback (with XLA-equal
                output) when the kv slice exceeds the VMEM budget;
  4. perf     — a routed attention wrapped by mx.perf registers a
                "kernels"-family program whose record carries
                cost_analysis FLOPs;
  5. stack    — runtime.scan_stack builds the 8-layer transformer loss
                with less trace+compile time under scan than unroll, at
                equal loss.

Usage: JAX_PLATFORMS=cpu python tools/check_kernels.py
Wired as a `not slow` test in tests/test_kernels.py.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_VMEM_DEFAULT = 2097152  # keep in sync with the kernels.vmem_budget knob


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        from mxnet_tpu import config as _cfg
        from mxnet_tpu import kernels, perf, telemetry
        from mxnet_tpu.models.transformer import (TransformerLM,
                                                  TransformerLMConfig)
        from mxnet_tpu.ops.pallas_kernels import (flash_attention,
                                                  pallas_row_softmax)
        from mxnet_tpu.parallel.ring_attention import (
            attention as xla_attention)
        result["backend"] = jax.default_backend()
        telemetry.reset()
        perf.reset()
        rng = np.random.RandomState(0)

        # 1. flash fwd + bwd parity vs the XLA lowering, causal + not
        _cfg.set("kernels.enabled", True)
        B, H, S, D = 1, 2, 32, 16
        q, k, v = (jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
                   for _ in range(3))
        cot = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
        flash = {}
        for causal in (False, True):
            # `causal` rides in by closure: a trace-time static, which
            # the jit-purity pass knows never taints the kernel's
            # `if causal:` specialization
            def ref_fwd(q, k, v):
                return xla_attention(q, k, v, causal=causal)

            def ker_fwd(q, k, v):
                return flash_attention(q, k, v, causal=causal)

            def ref_loss(q, k, v):
                return jnp.sum(ref_fwd(q, k, v) * cot)

            def ker_loss(q, k, v):
                return jnp.sum(ker_fwd(q, k, v) * cot)

            o_ref = jax.jit(ref_fwd)(q, k, v)
            o_ker = jax.jit(ker_fwd)(q, k, v)
            fwd_diff = float(jnp.max(jnp.abs(o_ref - o_ker)))
            g_ref = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
            g_ker = jax.jit(jax.grad(ker_loss, argnums=(0, 1, 2)))(q, k, v)
            bwd_diff = max(float(jnp.max(jnp.abs(a - b)))
                           for a, b in zip(g_ref, g_ker))
            assert fwd_diff < 2e-6, (causal, fwd_diff)
            assert bwd_diff < 2e-5, (causal, bwd_diff)
            flash["causal" if causal else "full"] = {
                "fwd_maxdiff": fwd_diff, "bwd_maxdiff": bwd_diff}
        result["flash"] = flash

        # 2. differentiable row softmax: grads vs jnp.softmax
        x = jnp.asarray(rng.randn(32, 64), jnp.float32)
        xcot = jnp.asarray(rng.randn(32, 64), jnp.float32)
        g_pal = jax.jit(jax.grad(
            lambda x: jnp.sum(pallas_row_softmax(x) * xcot)))(x)
        g_jnp = jax.jit(jax.grad(
            lambda x: jnp.sum(jax.nn.softmax(x, axis=-1) * xcot)))(x)
        sm_diff = float(jnp.max(jnp.abs(g_pal - g_jnp)))
        assert sm_diff < 2e-6, sm_diff
        result["softmax"] = {"bwd_maxdiff": sm_diff}

        # 3. routing counters: supported → flash, over-budget kv → XLA
        flash_ctr = telemetry.counter("kernels.flash_attention")
        fb_ctr = telemetry.counter("kernels.fallback")
        f0, b0 = flash_ctr.value, fb_ctr.value
        out_on = kernels.attention(q, k, v, causal=True)
        assert flash_ctr.value == f0 + 1, "flash not routed"
        _cfg.set("kernels.vmem_budget", 64)   # kv slice can't fit now
        out_fb = kernels.attention(q, k, v, causal=True)
        _cfg.set("kernels.vmem_budget", _VMEM_DEFAULT)
        assert fb_ctr.value == b0 + 1, "fallback not counted"
        o_xla = xla_attention(q, k, v, causal=True)
        assert bool(jnp.all(out_fb == o_xla)), "fallback differs from XLA"
        assert float(jnp.max(jnp.abs(out_on - o_xla))) < 2e-6
        result["routing"] = {"flash_count": flash_ctr.value,
                             "fallback_count": fb_ctr.value}

        # 4. perf: the "kernels" family registers with compiler FLOPs
        attn = perf.wrap(
            jax.jit(lambda q, k, v: kernels.attention(q, k, v, causal=True)),
            "kernels", "smoke/attention")
        jax.block_until_ready(attn(q, k, v))
        rec = perf.program("kernels", "smoke/attention")
        assert rec is not None and rec["family"] == "kernels", rec
        assert rec["flops"] > 0 and rec["phases_ms"], rec
        result["perf"] = {"flops": rec["flops"]}

        # 5. scan beats unroll on trace+compile, at equal loss
        _cfg.set("kernels.enabled", False)
        deep = TransformerLMConfig(vocab_size=64, num_layers=8,
                                   d_model=32, num_heads=2, d_ff=64,
                                   max_len=16, dtype=jnp.float32)
        model = TransformerLM(deep)
        params = model.init(jax.random.PRNGKey(3))
        tok = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
        stack = {}
        for mode in ("unroll", "scan"):
            _cfg.set("runtime.stack_mode", mode)
            fn = perf.wrap(jax.jit(model.loss), "kernels",
                           "smoke/stack/" + mode)
            loss = fn(params, tok, tok)
            jax.block_until_ready(loss)
            ph = perf.program("kernels", "smoke/stack/" + mode)["phases_ms"]
            stack[mode] = {
                "loss": float(loss),
                "build_ms": round(ph.get("trace_ms", 0.0) +
                                  ph.get("lower_ms", 0.0) +
                                  ph.get("compile_ms", 0.0), 1)}
        assert abs(stack["scan"]["loss"] - stack["unroll"]["loss"]) < 1e-6, \
            stack
        assert stack["scan"]["build_ms"] < stack["unroll"]["build_ms"], stack
        result["stack"] = stack

        result.update(ok=True,
                      elapsed_s=round(time.perf_counter() - t_main, 2))
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        import traceback
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
        result["trace"] = traceback.format_exc()[-1500:]
    finally:
        try:
            from mxnet_tpu import config as _cfg
            _cfg.set("kernels.enabled", False)
            _cfg.set("kernels.vmem_budget", _VMEM_DEFAULT)
            _cfg.set("runtime.stack_mode", "scan")
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
