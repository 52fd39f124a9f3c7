"""Fast CPU smoke for mx.serving continuous batching (seconds on an idle
box; ``elapsed_s`` is reported, not asserted).

Proves the serving layer end-to-end on the host backend, with one
parseable JSON line on stdout:

  1. rows    — N caller threads submit ragged mixed-size requests
               concurrently; every scattered output row equals the row
               the unbatched ``StableHLOPredictor.predict`` produces to
               f32 rounding (ROW_RTOL).  Not bitwise: a padded bucket is
               another program shape than the request's own, and XLA
               promises no two shapes the same bits;
  2. compiles — ``serving.compiles`` after ``start()`` equals the bucket
               count, and stays FLAT across the ragged traffic (no
               request shape ever reaches the compiler);
  3. drain   — queued requests all resolve through ``stop()`` (graceful
               drain), and a post-stop ``submit()`` raises ServingError;
  4. chunking — a request larger than the top bucket splits and
               re-concatenates transparently.

Usage: JAX_PLATFORMS=cpu python tools/check_serving.py
Wired as a `not slow` test in tests/test_serving.py.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

MAX_BATCH = 8
FEATURES = 6
N_THREADS = 4
SIZES = (1, 3, 2, 5, 4, 8, 7, 1)   # per-thread ragged request mix
# a row served from a padded bucket vs the same row predicted alone: two
# program shapes, equal to a few f32 ulps of the dot's partial sums
ROW_RTOL, ROW_ATOL = 1e-5, 1e-7


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_serving_")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import telemetry
        from mxnet_tpu.gluon import nn
        result["backend"] = jax.default_backend()

        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        example = mx.nd.random.uniform(shape=(MAX_BATCH, FEATURES))
        net(example)
        prefix = os.path.join(tmpdir, "mlp")
        mx.deploy.export_model(net, prefix, example)
        pred = mx.deploy.StableHLOPredictor(prefix)
        assert pred.dynamic_batch, "smoke model must export dynamic-batch"

        srv = mx.serving.Server(max_batch=MAX_BATCH, max_queue_delay_ms=4.0)
        srv.register("mlp", prefix)
        compiles0 = telemetry.counter("serving.compiles").value
        srv.start()
        buckets = srv._models["mlp"].buckets
        compiled = telemetry.counter("serving.compiles").value - compiles0
        assert compiled == len(buckets), \
            "start() compiled %d programs for %d buckets" % (compiled,
                                                             len(buckets))

        # 1+2: concurrent ragged traffic — same rows, flat compiles
        rng = np.random.RandomState(0)
        inputs = [[rng.uniform(size=(s, FEATURES)).astype(np.float32)
                   for s in SIZES] for _ in range(N_THREADS)]
        expect = [[pred.predict(a) for a in reqs] for reqs in inputs]
        results = [[None] * len(SIZES) for _ in range(N_THREADS)]
        errors = []

        def worker(t):
            try:
                futs = [srv.submit("mlp", a) for a in inputs[t]]
                results[t] = [f.result(timeout=30) for f in futs]
            except BaseException as exc:  # noqa: BLE001
                errors.append("%s: %s" % (type(exc).__name__, exc))

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, "submit worker failed: %s" % errors[0]
        mismatch = sum(
            0 if np.allclose(r, e, rtol=ROW_RTOL, atol=ROW_ATOL) else 1
            for rs, es in zip(results, expect) for r, e in zip(rs, es))
        assert mismatch == 0, \
            "%d request outputs diverged from unbatched predict" % mismatch
        traffic_compiles = telemetry.counter("serving.compiles").value \
            - compiles0
        assert traffic_compiles == len(buckets), \
            "ragged traffic caused %d extra compile(s)" \
            % (traffic_compiles - len(buckets))
        result["rows"] = {"threads": N_THREADS,
                          "requests": N_THREADS * len(SIZES),
                          "mismatches": mismatch}
        result["compiles"] = {"buckets": list(buckets),
                              "compiled": traffic_compiles,
                              "dispatches": telemetry.counter(
                                  "serving.batch_dispatches").value}

        # 4: oversized request chunks through the top bucket transparently
        big = rng.uniform(size=(MAX_BATCH * 2 + 3,
                                FEATURES)).astype(np.float32)
        out = srv.predict("mlp", big, timeout=30)
        assert np.allclose(out, pred.predict(big), rtol=ROW_RTOL,
                           atol=ROW_ATOL), \
            "chunked oversized request diverged"
        result["chunking"] = {"rows": int(big.shape[0])}

        # 3: stop() drains every queued request; post-stop submit rejects
        futs = [srv.submit("mlp", inputs[0][0]) for _ in range(6)]
        srv.stop()
        drained = sum(1 for f in futs if f.result(timeout=5) is not None)
        assert drained == len(futs), \
            "stop() left %d queued request(s) unresolved" \
            % (len(futs) - drained)
        try:
            srv.submit("mlp", inputs[0][0])
            raise AssertionError("submit after stop() did not raise")
        except mx.serving.ServingError:
            pass
        result["drain"] = {"queued": len(futs), "drained": drained}

        qd = telemetry.timer("serving.queue_delay_ms").stats()
        result["queue_delay_ms_p99"] = round(qd["p99"], 3)
        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
