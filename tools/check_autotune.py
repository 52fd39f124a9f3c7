"""Fast CPU smoke for mx.perf.autotune (seconds, not minutes).

Proves the measured-search → persist → reload contract on the host
backend (kernels run through the Pallas interpreter — same numerics,
no TPU), with one parseable JSON line on stdout:

  1. attention — in 'measure' mode a default-source routed
                 ``kernels.attention`` call triggers the block_q search
                 once: candidates measured against the XLA lowering,
                 parity checked, the winner written through to the
                 tuning cache (``autotune.search``/``measure`` count);
  2. fused     — ``kernels.fused_step_enabled`` triggers the fused
                 optimizer-epilogue on/off search for SGD(+momentum)
                 and records a parity-gated verdict;
  3. stack     — ``autotune.search_stack`` sweeps the
                 runtime.stack_mode × runtime.remat grid over a tiny
                 scanned stack's value_and_grad and persists the
                 fastest (mode, remat), which ``runtime.stack_tuning``
                 then reports while both knobs sit at defaults;
  4. reload    — after ``autotune.reset()`` (the in-process stand-in
                 for a fresh process; tests/test_autotune.py does the
                 real subprocess round-trip) the same lookups come back
                 from disk:
                 ``autotune.cache_hit`` > 0 and ZERO new
                 ``autotune.measure`` — the applied pick is the
                 persisted winner, re-measured never.

Usage: JAX_PLATFORMS=cpu python tools/check_autotune.py
Wired as a `not slow` test in tests/test_autotune.py.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    t_main = time.perf_counter()
    result = {"ok": False}
    try:
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu import autotune, config, kernels, runtime, telemetry

        result["backend"] = jax.default_backend()
        cache = os.path.join(tempfile.mkdtemp(prefix="mxtpu_autotune_"),
                             "autotune.json")
        config.set("perf.autotune_cache", cache)
        config.set("perf.autotune", "measure")
        telemetry.reset()
        autotune.reset()
        rng = np.random.RandomState(0)

        # 1. attention: default-source tier-on routes through the
        # measured gate; 'measure' mode searches even on the interpreter
        assert config.source("kernels.enabled") == "default", \
            "smoke needs the graduated default (MXNET_TPU_KERNELS unset)"
        q, k, v = (jnp.asarray(rng.randn(1, 2, 32, 16), jnp.float32)
                   for _ in range(3))
        out = kernels.attention(q, k, v, causal=True)
        jax.block_until_ready(out)
        searches = telemetry.counter("autotune.search").value
        measures = telemetry.counter("autotune.measure").value
        assert searches >= 1, searches
        assert measures >= 2, measures  # baseline + >=1 flash candidate
        assert os.path.exists(cache), cache
        with open(cache) as f:
            persisted = json.load(f)
        att_entries = {kk: vv for kk, vv in persisted["entries"].items()
                       if kk.startswith("attention|")}
        assert att_entries, persisted
        att = next(iter(att_entries.values()))
        assert att["impl"] in ("flash", "xla"), att
        assert "baseline_ms" in att and att.get("candidates"), att
        result["attention"] = {"impl": att["impl"],
                               "block_q": att.get("block_q"),
                               "speedup": att.get("speedup"),
                               "parity": att.get("parity"),
                               "measures": measures}

        # 2. fused optimizer epilogue on/off verdict
        opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
        fused_on = kernels.fused_step_enabled(opt)
        with open(cache) as f:
            persisted = json.load(f)
        fkey = [kk for kk in persisted["entries"]
                if kk.startswith("fused_step|fused/sgd/mom|")]
        assert fkey, persisted["entries"].keys()
        fentry = persisted["entries"][fkey[0]]
        assert fentry["impl"] in ("fused", "xla"), fentry
        assert fused_on == (fentry["impl"] == "fused"), (fused_on, fentry)
        result["fused"] = {"impl": fentry["impl"],
                           "speedup": fentry.get("speedup"),
                           "parity": fentry.get("parity")}

        # 3. stack_mode x remat sweep over a tiny scanned stack
        L, D = 3, 16
        Ws = jnp.asarray(rng.randn(L, D, D) * 0.1, jnp.float32)
        x0 = jnp.asarray(rng.randn(4, D), jnp.float32)

        def make_step():
            def loss(ws, x):
                def body(carry, w):
                    return jnp.tanh(carry @ w), None
                h, _ = runtime.scan_stack(body, x, ws)
                return jnp.sum(h * h)
            return jax.value_and_grad(loss)

        sentry = autotune.search_stack(make_step, (Ws, x0),
                                       site="check_autotune")
        assert sentry["knobs"], sentry
        assert len(sentry["candidates"]) == len(runtime.stack_candidates())
        # knob sources restored: both still defaults after the sweep
        assert config.source("runtime.stack_mode") == "default"
        assert config.source("runtime.remat") == "default"
        result["stack"] = {"winner": sentry["impl"],
                           "candidates": sentry["candidates"]}

        # 4. reload: fresh in-memory state, same cache file — every pick
        # comes back from disk with ZERO new measurements
        autotune.reset()
        telemetry.reset()
        out2 = kernels.attention(q, k, v, causal=True)
        jax.block_until_ready(out2)
        fused_on2 = kernels.fused_step_enabled(opt)
        assert fused_on2 == fused_on, (fused_on2, fused_on)
        hits = telemetry.counter("autotune.cache_hit").value
        measures2 = telemetry.counter("autotune.measure").value
        searches2 = telemetry.counter("autotune.search").value
        applied = telemetry.counter("autotune.applied").value
        assert hits >= 2, hits
        assert measures2 == 0, measures2
        assert searches2 == 0, searches2
        assert applied >= 2, applied
        np.testing.assert_allclose(np.asarray(out2), np.asarray(out),
                                   rtol=1e-6, atol=1e-6)
        result["reload"] = {"cache_hit": hits, "applied": applied,
                            "measure": measures2}

        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — smoke reports, not raises
        import traceback
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
        result["traceback"] = traceback.format_exc(limit=8)
    result["elapsed_s"] = round(time.perf_counter() - t_main, 2)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
