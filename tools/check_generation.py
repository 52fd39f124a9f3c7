"""Fast CPU smoke for mx.serving generation (< 5s).

Proves the token-level continuous-batching path end-to-end on the host
backend, with one parseable JSON line on stdout:

  1. bitwise — mixed prompt lengths and token budgets submitted
               concurrently, so sequences EXIT mid-flight (short budgets
               finish while long ones keep decoding) and queued prefills
               JOIN the running batch; every returned token stream is
               BITWISE equal to the eager greedy-decode oracle
               (``TransformerLM.greedy_decode`` — no cache, full
               re-forward per token);
  2. compiles — ``serving.compiles`` after ``start()`` equals the
               program-family size (prefill buckets + decode widths) and
               stays FLAT across the ragged traffic;
  3. exhaustion — a tiny page pool forces head-of-line waits: the
               ``serving.kv_pool_exhausted`` counter moves, yet every
               request still completes bitwise;
  4. gates   — plain ``load_model``/``submit`` refuse the generation
               artifact/model with typed errors;
  5. kernel  — the main artifact is exported v5 with the kernel tier
               explicitly ON and a concrete ``decode_batch``, so every
               decode step runs the Pallas paged-attention kernel
               (``meta["paged"]`` verdicts + the
               ``kernels.paged_attention`` counter prove it) and leg 1's
               bitwise assert doubles as the kernel-parity acceptance;
  6. sampling — the same artifact carries temperature/top-k/top-p: one
               seed replayed twice yields ONE stream, a seed sweep at
               high temperature yields distinct streams, temperature 0
               stays the bitwise oracle;
  7. int8 KV — a ``kv_quantized=True`` artifact serves the same traffic
               with half-size pages; next-token logits drift from the
               f32-KV run stays within ``quant.error_budget``.

Usage: JAX_PLATFORMS=cpu python tools/check_generation.py
Wired as a `not slow` test in tests/test_generation.py.
"""
from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

VOCAB = 89
# A single-core runner pays every XLA compile serially; the
# budget calibrated for the normal >=2-core CI box doubles there.
# (Six decode programs here hold the paged kernel, and the interpreter's
# program for its page copies takes ~1.5 s to build each.)
# The budget is held on the process's own CPU seconds (all threads; ~36 on
# an idle 8-core box, where the wall clock reads ~21): the driver runs the
# wrapper test beside five other xdist workers, and a wall clock then
# measures the neighbours.
BUDGET_S = 90.0 if (os.cpu_count() or 1) >= 2 else 180.0
PAGE_SIZE = 8
MAX_CONTEXT = 16
#: (prompt_len, max_new) mix: ragged lengths across two prefill buckets,
#: budgets that finish at different iterations (mid-flight exits/joins)
TRAFFIC = ((3, 6), (7, 2), (4, 9), (8, 4), (2, 11), (6, 7))
PROMPT_BUCKETS = (4, 8)


def main():
    t_main, c_main = time.perf_counter(), time.process_time()
    import numpy as np
    result = {"ok": False}
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_generation_")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry
        from mxnet_tpu.models.transformer import (TransformerLM,
                                                  TransformerLMConfig)
        result["backend"] = jax.default_backend()

        cfg = TransformerLMConfig(
            vocab_size=VOCAB, num_layers=2, d_model=16, num_heads=2,
            d_ff=32, max_len=MAX_CONTEXT, dtype=jnp.float32)
        model = TransformerLM(cfg)
        # host-side param init (model.init burns ~1s of the 5s budget
        # compiling jax.random); pos_embed amplified so greedy streams
        # vary with position (a fixed-point stream would be a vacuous
        # parity check)
        prng = np.random.default_rng(0)
        L, D, F, V = 2, cfg.d_model, cfg.d_ff, VOCAB
        H, Dh = cfg.num_heads, cfg.head_dim

        def mk(*shape):
            return jnp.asarray(
                prng.normal(0.0, 0.02, size=shape).astype(np.float32))

        params = {
            "embed": mk(V, D),
            "pos_embed": mk(MAX_CONTEXT, D) * 25.0,
            "final_norm": jnp.ones((D,), jnp.float32),
            "layers": {
                "ln1": jnp.ones((L, D), jnp.float32),
                "wqkv": mk(L, D, 3, H, Dh),
                "wo": mk(L, H, Dh, D),
                "ln2": jnp.ones((L, D), jnp.float32),
                "w1": mk(L, D, F),
                "w2": mk(L, F, D),
            },
        }

        # 5: explicit kernel tier + concrete decode batch — the export
        # traces decode through kernels.paged_attention and bakes the
        # routing verdict into meta["paged"], so leg 1's bitwise assert
        # exercises the Pallas kernel (interpreted on CPU), not the XLA
        # fallback
        mx.config.set("kernels.enabled", True)
        prefix = os.path.join(tmpdir, "lm")
        mx.deploy.export_generation(model, params, prefix,
                                    page_size=PAGE_SIZE,
                                    max_context=MAX_CONTEXT,
                                    prompt_buckets=PROMPT_BUCKETS,
                                    sampling=True, decode_batch=4)

        # 4: the generation artifact refuses the one-shot load path,
        # typed
        try:
            mx.deploy.load_model(prefix)
            raise AssertionError("load_model accepted a v5 artifact")
        except ValueError:
            pass

        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, VOCAB, size=p).astype(np.int32)
                   for p, _ in TRAFFIC]

        # tiny pool: covers only ~2 in-flight requests while 4 decode
        # slots are free, so the 6-request burst must head-of-line wait
        # on PAGES (not slots) and recycle pages mid-run
        pool_pages = 2 * math.ceil(
            (max(p + n for p, n in TRAFFIC)) / PAGE_SIZE)
        srv = mx.serving.Server()
        mx.config.set("serving.kv_pages", pool_pages)
        mx.config.set("serving.decode_slots", 4)
        engine = srv.register("lm", prefix, generate=True)

        compiles0 = telemetry.counter("serving.compiles").value
        srv.start()
        family = (len(engine.predictor.prompt_buckets)
                  + len(engine.predictor.decode_widths))
        compiled = telemetry.counter("serving.compiles").value - compiles0
        assert compiled == family, \
            "start() compiled %d programs for a family of %d" \
            % (compiled, family)

        # 4: submit() refuses the generation model, typed
        try:
            srv.submit("lm", np.zeros((1, 4), np.int32))
            raise AssertionError("submit() accepted a generation model")
        except mx.serving.ServingError:
            pass

        # 1+3: burst the whole mix at once — queued prefills JOIN the
        # running decode batch, short budgets EXIT mid-flight while long
        # ones keep decoding, and the tiny pool forces page waits
        oracle = [model.greedy_decode(params, pr, n)
                  for pr, (_, n) in zip(prompts, TRAFFIC)]
        paged0 = telemetry.counter("kernels.paged_attention").value
        futs = [srv.submit_generate("lm", pr, n)
                for pr, (_, n) in zip(prompts, TRAFFIC)]
        streams = [f.result(timeout=30) for f in futs]
        mismatch = sum(0 if np.array_equal(s, o) else 1
                       for s, o in zip(streams, oracle))
        assert mismatch == 0, \
            "%d generated stream(s) diverged from the eager oracle" \
            % mismatch

        traffic_compiles = telemetry.counter("serving.compiles").value \
            - compiles0
        assert traffic_compiles == family, \
            "ragged generation traffic caused %d extra compile(s)" \
            % (traffic_compiles - family)
        exhausted = telemetry.counter("serving.kv_pool_exhausted").value
        assert exhausted > 0, \
            "tiny pool (%d pages) never hit kv_pool_exhausted" % pool_pages
        with engine._cond:
            free = len(engine._free)
        assert free == pool_pages, \
            "finished sequences leaked pages: %d/%d free" % (free,
                                                             pool_pages)

        # 5: the export-time routing verdict says every decode width ran
        # the Pallas kernel, and the engine counted one
        # kernels.paged_attention per decode iteration served by it
        routes = dict(engine.predictor.paged_routes)
        bad = {w: r for w, r in routes.items()
               if r.get("impl") != "paged"}
        assert routes and not bad, \
            "decode widths not served by the paged kernel: %r" % (bad,)
        paged_iters = telemetry.counter(
            "kernels.paged_attention").value - paged0
        assert paged_iters > 0, \
            "kernels.paged_attention never moved — decode iterations " \
            "did not run the Pallas kernel"
        result["paged_kernel"] = {
            "routes": {w: r["impl"] for w, r in routes.items()},
            "decode_iterations": int(paged_iters)}

        # 6: sampling determinism — one seed is ONE stream; a high-
        # temperature seed sweep actually moves tokens; temperature 0
        # stays bitwise greedy (leg 1 already proved the oracle)
        sp = prompts[0]
        rep = [srv.generate("lm", sp, 5, temperature=5.0, seed=42,
                            timeout=30) for _ in range(2)]
        assert np.array_equal(rep[0], rep[1]), \
            "same seed produced different streams: %r vs %r" \
            % (rep[0].tolist(), rep[1].tolist())
        sweep_futs = [srv.submit_generate("lm", sp, 5, temperature=5.0,
                                          seed=1000 + i)
                      for i in range(8)]
        sweep = {tuple(f.result(timeout=30).tolist())
                 for f in sweep_futs}
        assert len(sweep) >= 2, \
            "8-seed sweep at temperature 5.0 collapsed to one stream"
        result["sampling"] = {"replay_ok": True,
                              "distinct_of_8": len(sweep)}

        result["bitwise"] = {
            "requests": len(TRAFFIC), "mismatches": mismatch,
            "tokens": int(sum(len(s) for s in streams))}
        result["compiles"] = {
            "prompt_buckets": list(engine.predictor.prompt_buckets),
            "decode_widths": list(engine.predictor.decode_widths),
            "compiled": traffic_compiles}
        result["kv_pool"] = {"pages": pool_pages,
                             "exhausted_waits": int(exhausted)}
        result["tokens_generated"] = int(
            telemetry.counter("serving.tokens_generated").value)

        # 7: int8 KV pages — the kv_quantized artifact serves the same
        # greedy traffic end-to-end, and the per-step next-token logits
        # drift vs the f32-KV decode stays inside quant.error_budget
        # (the acceptance gate is numeric, not bitwise)
        prefixq = os.path.join(tmpdir, "lmq")
        mx.deploy.export_generation(model, params, prefixq,
                                    page_size=PAGE_SIZE,
                                    max_context=MAX_CONTEXT,
                                    prompt_buckets=PROMPT_BUCKETS,
                                    kv_quantized=True)
        engq = srv.register("lmq", prefixq, generate=True)
        assert engq.predictor.kv_quantized, "meta lost kv.quantized"
        futq = [srv.submit_generate("lmq", pr, n)
                for pr, (_, n) in zip(prompts, TRAFFIC)]
        doneq = [f.result(timeout=30) for f in futq]
        assert all(len(s) > 0 for s in doneq)

        budget = float(mx.config.get("quant.error_budget"))
        plen, steps = 7, 4
        pr7 = prompts[1][:plen]
        drift = 0.0
        for quantized in (False, True):
            kv = model.init_kv_pages(4, PAGE_SIZE, quantized=quantized)
            toks = np.zeros((1, 8), np.int32)
            toks[0, :plen] = pr7
            table = np.asarray([[0, 1]], np.int32)
            kv, ids, logits = model.prefill(
                params, kv, jnp.asarray(toks),
                jnp.asarray([plen], np.int32), jnp.asarray(table),
                PAGE_SIZE, return_logits=True)
            seq = [np.asarray(logits)[0]]
            pos = plen
            for _ in range(steps):
                kv, ids, logits = model.decode_step(
                    params, kv, ids, jnp.asarray([pos], np.int32),
                    jnp.asarray(table), PAGE_SIZE, return_logits=True)
                seq.append(np.asarray(logits)[0])
                pos += 1
            if not quantized:
                ref = seq
            else:
                scale = max(float(np.max(np.abs(r))) for r in ref)
                drift = max(
                    float(np.max(np.abs(q - r))) / max(scale, 1e-6)
                    for q, r in zip(seq, ref))
        assert drift <= budget, \
            "int8 KV logit drift %.4f exceeds quant.error_budget %.3f" \
            % (drift, budget)
        result["int8_kv"] = {"requests": len(doneq),
                             "logit_drift": round(drift, 6),
                             "error_budget": budget}

        srv.stop()
        ttft = telemetry.timer("serving.ttft_ms").stats()
        result["ttft_ms_p50"] = round(ttft["p50"], 3)
        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        # the process's own CPU seconds, all threads: what the smoke cost
        # whatever else shares the machine
        result["cpu_s"] = round(time.process_time() - c_main, 3)
        assert result["cpu_s"] < BUDGET_S, \
            "smoke exceeded the %.0fs cpu budget: %.3fs" \
            % (BUDGET_S, result["cpu_s"])
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
