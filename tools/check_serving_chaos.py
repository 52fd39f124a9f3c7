"""Fast CPU chaos smoke for mx.serving fault tolerance (seconds on an idle
box; ``elapsed_s`` is reported, not asserted).

Proves the PR-7 hardening end-to-end on the host backend, with one
parseable JSON line on stdout:

  1. breaker  — under a deterministic ``serving_dispatch:3@step=3`` fault
                schedule the per-model circuit breaker opens after 2
                consecutive dispatch failures, fails a submit fast with
                CircuitOpenError while open, goes half-open after the
                cooldown (probe fails → re-opens), then closes on the
                next successful probe; every surviving result is BITWISE
                equal to unbatched ``StableHLOPredictor.predict``;
  2. crash    — a poisoned queue entry crashes the batcher thread: the
                queued request's future fails with the CAUSAL exception
                (not a hang), ``serving.batcher_crashes`` increments, the
                supervisor restarts the loop under the resilience retry
                budget, and the very next predict is served bitwise;
  3. overload — with ``serving_slow:1@step=1`` holding the batcher inside
                a dispatch, submits past ``max_pending=3`` shed with
                ServerOverloadedError (exactly 3), a 1ms-deadline request
                expires at batch-formation time with DeadlineExceededError
                (never dispatched), and the queued survivors complete
                bitwise — shed + deadline counts match the schedule.

Zero hung futures: every future created anywhere above must be done by
the end of the run.

Usage: JAX_PLATFORMS=cpu python tools/check_serving_chaos.py
Wired as a `not slow` test in tests/test_serving_chaos.py.
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

MAX_BATCH = 8
FEATURES = 6
COOLDOWN_MS = 150.0


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tracked = []  # every future ever created; all must be done at the end
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_serving_chaos_")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import config, telemetry
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.serving import (CircuitOpenError,
                                       DeadlineExceededError,
                                       ServerOverloadedError, _Request)
        result["backend"] = jax.default_backend()

        config.set("resilience.fault_seed", 3)
        config.set("resilience.retry_base_s", 0.001)  # fast crash-restart

        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        example = mx.nd.random.uniform(shape=(MAX_BATCH, FEATURES))
        net(example)
        prefix = os.path.join(tmpdir, "mlp")
        mx.deploy.export_model(net, prefix, example)
        pred = mx.deploy.StableHLOPredictor(prefix)

        rng = np.random.RandomState(0)
        xs = [rng.uniform(size=(1, FEATURES)).astype(np.float32)
              for _ in range(16)]
        expect = [pred.predict(x) for x in xs]

        def wait(fut):
            tracked.append(fut)
            return fut.result(timeout=10)

        # 1. breaker lifecycle under a scripted dispatch-fault window:
        # opportunities 3, 4, 5 fail → open after 2 (threshold), the
        # half-open probe re-opens once, then closes
        srv = mx.serving.Server(max_batch=MAX_BATCH, max_queue_delay_ms=0.0,
                                breaker_threshold=2,
                                breaker_cooldown_ms=COOLDOWN_MS)
        srv.register("mlp", prefix)
        srv.start()
        config.set("resilience.faults", "serving_dispatch:3@step=3")
        assert np.array_equal(wait(srv.submit("mlp", xs[0])), expect[0])
        assert np.array_equal(wait(srv.submit("mlp", xs[1])), expect[1])
        for i in (2, 3):  # opportunities 3 and 4: injected failures
            fut = srv.submit("mlp", xs[i])
            tracked.append(fut)
            exc = fut.exception(timeout=10)
            assert isinstance(exc, OSError), \
                "dispatch %d: expected InjectedFault, got %r" % (i, exc)
        assert srv.stats()["breakers"]["mlp"] == "open", srv.stats()
        assert telemetry.counter("serving.breaker_open").value == 1
        try:  # while open and cooling: submit fails fast, no dispatch
            srv.submit("mlp", xs[4])
            raise AssertionError("open breaker accepted a submit")
        except CircuitOpenError:
            pass
        time.sleep(COOLDOWN_MS / 1e3 + 0.05)
        fut = srv.submit("mlp", xs[5])  # half-open probe: opportunity 5
        tracked.append(fut)
        assert isinstance(fut.exception(timeout=10), OSError)
        assert srv.stats()["breakers"]["mlp"] == "open", \
            "failed probe did not re-open the breaker"
        assert telemetry.counter("serving.breaker_open").value == 2
        time.sleep(COOLDOWN_MS / 1e3 + 0.05)
        # fault window exhausted: this probe succeeds and closes it
        assert np.array_equal(wait(srv.submit("mlp", xs[6])), expect[6])
        assert srv.stats()["breakers"]["mlp"] == "closed"
        injected = telemetry.counter(
            "resilience.injected.serving_dispatch").value
        assert injected == 3, injected
        result["breaker"] = {
            "opens": 2, "injected_failures": int(injected),
            "final_state": srv.stats()["breakers"]["mlp"]}

        # 2. forced batcher crash: poison the queue so _loop dies popping
        # it; the co-queued victim fails with the causal exception, the
        # supervisor restarts, and the next request is served bitwise
        config.set("resilience.faults", "")
        from concurrent.futures import Future
        victim = _Request("mlp", xs[7], Future())
        tracked.append(victim.future)
        with srv._cond:
            srv._pending.append(None)    # poison: crashes the batcher
            srv._pending.append(victim)
            srv._cond.notify_all()
        exc = victim.future.exception(timeout=10)
        assert isinstance(exc, AttributeError), \
            "victim future got %r, not the causal crash exception" % (exc,)
        crashes = telemetry.counter("serving.batcher_crashes").value
        assert crashes == 1, crashes
        out = srv.predict("mlp", xs[8], timeout=10)  # restarted batcher
        assert np.array_equal(out, expect[8]), "post-restart predict diverged"
        assert srv.stats()["batcher_alive"]
        srv.stop()
        result["crash"] = {"crashes": int(crashes), "restarted": True,
                           "victim_error": type(exc).__name__}

        # 3. shed + deadline under a slow dispatch: serving_slow holds the
        # batcher inside dispatch #1 for ~250ms while we script the queue
        srv2 = mx.serving.Server(max_batch=MAX_BATCH,
                                 max_queue_delay_ms=0.0, max_pending=3)
        srv2.register("mlp", prefix)
        srv2.start()
        config.set("resilience.faults", "serving_slow:1@step=1")
        slow0 = telemetry.counter("resilience.injected.serving_slow").value
        f_slow = srv2.submit("mlp", xs[9])
        tracked.append(f_slow)
        deadline = time.perf_counter() + 5.0
        while telemetry.counter(
                "resilience.injected.serving_slow").value <= slow0:
            assert time.perf_counter() < deadline, "slow fault never fired"
            time.sleep(0.001)
        # batcher is now sleeping inside the dispatch; queue is empty
        f_q1 = srv2.submit("mlp", xs[10])
        f_q2 = srv2.submit("mlp", xs[11])
        f_dl = srv2.submit("mlp", xs[12], deadline_ms=1.0)
        tracked += [f_q1, f_q2, f_dl]
        shed = 0
        for i in (13, 14, 15):  # queue is at max_pending=3: all shed
            try:
                tracked.append(srv2.submit("mlp", xs[i]))
            except ServerOverloadedError:
                shed += 1
        assert shed == 3, "expected 3 shed submits, got %d" % shed
        time.sleep(0.002)  # let the 1ms deadline lapse, batcher still slow
        assert np.array_equal(f_slow.result(timeout=10), expect[9])
        # q1 and q2 waited out the slow dispatch together and share one
        # 2-row bucket: another program shape than the 1-row oracle, so
        # they agree to f32 rounding, not bit for bit
        assert np.allclose(f_q1.result(timeout=10), expect[10],
                           rtol=1e-5, atol=1e-7)
        assert np.allclose(f_q2.result(timeout=10), expect[11],
                           rtol=1e-5, atol=1e-7)
        exc = f_dl.exception(timeout=10)
        assert isinstance(exc, DeadlineExceededError), \
            "deadline request got %r" % (exc,)
        assert telemetry.counter("serving.shed_requests").value == 3
        assert telemetry.counter("serving.deadline_exceeded").value == 1
        srv2.stop()
        result["overload"] = {
            "shed": int(telemetry.counter("serving.shed_requests").value),
            "deadline_exceeded": int(telemetry.counter(
                "serving.deadline_exceeded").value)}

        hung = sum(1 for f in tracked if not f.done())
        assert hung == 0, "%d future(s) left hanging" % hung
        result["futures"] = {"tracked": len(tracked), "hung": hung}

        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config as _cfg
            _cfg.set("resilience.faults", "")
            _cfg.set("resilience.retry_base_s", 0.05)
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
