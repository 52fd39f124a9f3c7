"""mx.serving fault tolerance (PR 7): admission control / load shedding,
per-request deadlines (queue expiry + predict-timeout cancellation),
per-model circuit breaker lifecycle and isolation, supervised batcher
crash-restart (and fail-fast once the restart budget is spent), chunked
dispatch failure propagation, stop(drain=False) promptness, leaked-thread
start() refusal, load_server partial-failure unwind, the watchdog serving
stall probe, telemetry-report shed/deadline/breaker columns + the
overload_shedding anomaly, and the tools/check_serving_chaos.py smoke as
a subprocess.
"""
import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, deploy, gluon, serving, telemetry, tracing
from mxnet_tpu.serving import _Request

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import telemetry_report  # noqa: E402

FEATURES = 6


def _mlp(seed=3):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    return net


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    """One exported dynamic-batch MLP shared by the module's servers."""
    prefix = str(tmp_path_factory.mktemp("serving_chaos") / "mlp")
    net = _mlp()
    example = mx.nd.random.uniform(shape=(8, FEATURES))
    net(example)
    deploy.export_model(net, prefix, example)
    return prefix


@pytest.fixture(autouse=True)
def _clean_knobs():
    """Every test leaves the fault harness and retry policy at defaults."""
    yield
    config.set("resilience.faults", "")
    config.set("resilience.retry_attempts", 3)
    config.set("resilience.retry_base_s", 0.05)


def _reqs(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.uniform(size=(s, FEATURES)).astype(np.float32)
            for s in sizes]


def _hold_batcher(srv, x):
    """Submit one request under an armed ``serving_slow`` fault and wait
    until the batcher is inside the slow dispatch (the injected counter
    bumps BEFORE the sleep), leaving the queue empty and the batcher
    occupied for ~250ms."""
    c0 = telemetry.counter("resilience.injected.serving_slow").value
    fut = srv.submit("m", x)
    deadline = time.perf_counter() + 10.0
    while telemetry.counter(
            "resilience.injected.serving_slow").value <= c0:
        assert time.perf_counter() < deadline, "slow fault never fired"
        time.sleep(0.001)
    return fut


# ----------------------------------------------- admission & deadlines
def test_shed_past_max_pending_is_retryable(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=0.0,
                         max_pending=2)
    srv.register("m", artifact)
    srv.start()
    try:
        config.set("resilience.faults", "serving_slow:1@step=1")
        s0 = telemetry.counter("serving.shed_requests").value
        slow = _hold_batcher(srv, _reqs((1,))[0])
        q = [srv.submit("m", a) for a in _reqs((1, 1))]  # fills the bound
        with pytest.raises(serving.ServerOverloadedError) as exc_info:
            srv.submit("m", _reqs((1,))[0])
        # retryable by contract: call_with_retry backs off on OSError
        assert isinstance(exc_info.value, OSError)
        assert telemetry.counter("serving.shed_requests").value - s0 == 1
        for f in [slow] + q:
            assert f.result(timeout=10).shape == (1, 4)
    finally:
        srv.stop()


def test_deadline_expires_in_queue_never_dispatches(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    srv.start()
    try:
        config.set("resilience.faults", "serving_slow:1@step=1")
        d0 = telemetry.counter("serving.batch_dispatches").value
        x0 = telemetry.counter("serving.deadline_exceeded").value
        slow = _hold_batcher(srv, _reqs((1,))[0])
        doomed = srv.submit("m", _reqs((1,))[0], deadline_ms=1.0)
        time.sleep(0.002)  # deadline lapses while the batcher is slow
        with pytest.raises(serving.DeadlineExceededError):
            doomed.result(timeout=10)
        assert slow.result(timeout=10).shape == (1, 4)
        # only the slow request was dispatched; the expired one never was
        assert telemetry.counter("serving.batch_dispatches").value - d0 == 1
        assert telemetry.counter(
            "serving.deadline_exceeded").value - x0 == 1
    finally:
        srv.stop()


def test_predict_timeout_cancels_queued_request(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    srv.start()
    try:
        config.set("resilience.faults", "serving_slow:1@step=1")
        d0 = telemetry.counter("serving.batch_dispatches").value
        slow = _hold_batcher(srv, _reqs((1,))[0])
        with pytest.raises(serving.DeadlineExceededError):
            srv.predict("m", _reqs((1,))[0], timeout=0.05)
        assert slow.result(timeout=10).shape == (1, 4)
        time.sleep(0.05)  # would-be second dispatch window
        # the timed-out request was cancelled in queue, not dispatched
        assert telemetry.counter("serving.batch_dispatches").value - d0 == 1
    finally:
        srv.stop()


# ------------------------------------------------------ circuit breaker
def test_breaker_opens_isolates_and_recovers(artifact, tmp_path):
    other = str(tmp_path / "other")
    net = _mlp(seed=11)
    example = mx.nd.random.uniform(shape=(4, FEATURES))
    net(example)
    deploy.export_model(net, other, example)
    srv = serving.Server(max_batch=4, max_queue_delay_ms=0.0,
                         breaker_threshold=2, breaker_cooldown_ms=100.0)
    srv.register("m", artifact)
    srv.register("b", other)
    srv.start()
    try:
        b0 = telemetry.counter("serving.breaker_open").value
        config.set("resilience.faults", "serving_dispatch:2@step=1")
        for _ in range(2):  # threshold consecutive failures on model m
            fut = srv.submit("m", _reqs((1,))[0])
            assert isinstance(fut.exception(timeout=10), OSError)
        assert srv.stats()["breakers"]["m"] == "open"
        assert telemetry.counter("serving.breaker_open").value - b0 == 1
        with pytest.raises(serving.CircuitOpenError):
            srv.submit("m", _reqs((1,))[0])
        # isolation: the other model keeps serving while m's breaker is open
        assert srv.predict("b", _reqs((2,))[0], timeout=10).shape == (2, 4)
        assert srv.stats()["breakers"]["b"] == "closed"
        time.sleep(0.15)  # cooldown: next dispatch is the half-open probe
        assert srv.predict("m", _reqs((1,))[0], timeout=10).shape == (1, 4)
        assert srv.stats()["breakers"]["m"] == "closed"
    finally:
        srv.stop()


# -------------------------------------------------- batcher supervision
def test_batcher_crash_fails_pending_and_restarts(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    srv.start()
    try:
        config.set("resilience.retry_base_s", 0.001)
        c0 = telemetry.counter("serving.batcher_crashes").value
        victim = _Request("m", _reqs((1,))[0], Future())
        with srv._cond:
            srv._pending.append(None)  # poison: the batcher crashes on it
            srv._pending.append(victim)
            srv._cond.notify_all()
        # the co-queued future fails with the CAUSAL exception, not a hang
        assert isinstance(victim.future.exception(timeout=10),
                          AttributeError)
        assert telemetry.counter(
            "serving.batcher_crashes").value - c0 == 1
        # the supervisor restarted the loop: the next request is served
        out = srv.predict("m", _reqs((2,))[0], timeout=10)
        assert out.shape == (2, 4)
        assert srv.stats()["batcher_alive"]
    finally:
        srv.stop()


def test_submit_after_batcher_death_raises_not_hangs(artifact):
    config.set("resilience.retry_attempts", 1)  # one crash = budget spent
    config.set("resilience.retry_base_s", 0.001)
    srv = serving.Server(max_batch=8, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    srv.start()
    try:
        with srv._cond:
            srv._pending.append(None)
            srv._cond.notify_all()
        deadline = time.perf_counter() + 10.0
        while srv._batcher_dead is None:
            assert time.perf_counter() < deadline, "supervisor never died"
            time.sleep(0.001)
        with pytest.raises(serving.ServingError, match="restart budget"):
            srv.submit("m", _reqs((1,))[0])
        assert not srv.stats()["batcher_alive"]
    finally:
        srv.stop()


def test_chunk_dispatch_failure_fails_combined_exactly_once(artifact):
    srv = serving.Server(max_batch=2, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    srv.start()
    try:
        c0 = telemetry.counter("serving.batcher_crashes").value
        # 5 rows over max_batch=2 → chunks of 2, 2, 1; the second chunk's
        # dispatch is the injected failure
        config.set("resilience.faults", "serving_dispatch:1@step=2")
        combined = srv.submit("m", _reqs((5,))[0])
        exc = combined.exception(timeout=10)
        assert isinstance(exc, OSError), exc
        # the surviving chunks' set_result on an already-failed combined
        # future must not blow up the batcher (done()-guarded scatter)
        assert telemetry.counter("serving.batcher_crashes").value == c0
        assert srv.predict("m", _reqs((1,))[0], timeout=10).shape == (1, 4)
    finally:
        srv.stop()


# ------------------------------------------------------------ lifecycle
def test_stop_without_drain_fails_pending_promptly(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    srv.start()
    config.set("resilience.faults", "serving_slow:1@step=1")
    slow = _hold_batcher(srv, _reqs((1,))[0])
    abandoned = [srv.submit("m", a) for a in _reqs((1, 1, 1))]
    t0 = time.perf_counter()
    srv.stop(drain=False)
    for f in abandoned:
        assert isinstance(f.exception(timeout=5), serving.ServingError)
    assert time.perf_counter() - t0 < 5.0
    # the in-flight slow request still completes (it had left the queue)
    assert slow.result(timeout=10).shape == (1, 4)


def test_start_refuses_next_to_leaked_thread(artifact):
    srv = serving.Server(max_batch=4, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    gate = threading.Event()
    zombie = threading.Thread(target=gate.wait, daemon=True)
    zombie.start()
    srv._leaked_thread = zombie  # as stop() leaves it after a join timeout
    with pytest.raises(serving.ServingError, match="missed its stop"):
        srv.start()
    gate.set()
    zombie.join(timeout=5)
    srv.start()  # a dead leaked thread clears; restart is safe again
    try:
        assert srv.predict("m", _reqs((1,))[0], timeout=10).shape == (1, 4)
    finally:
        srv.stop()


def test_load_server_unwinds_on_partial_failure(artifact, tmp_path,
                                                monkeypatch):
    created = []
    real = serving.Server

    class Recording(real):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            created.append(self)

    monkeypatch.setattr(serving, "Server", Recording)
    prefixes = {"good": artifact, "bad": str(tmp_path / "missing")}
    with pytest.raises(Exception):
        serving.load_server(prefixes)
    assert len(created) == 1
    # the successfully registered model was unwound before the raise
    assert created[0].models() == []


# ------------------------------------------------------ watchdog probe
def test_stall_probe_reports_open_requests_and_breakers(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=0.0)
    srv.register("m", artifact)
    srv.start()
    try:
        config.set("resilience.faults", "serving_slow:1@step=1")
        slow = _hold_batcher(srv, _reqs((1,))[0])
        queued = srv.submit("m", _reqs((2,))[0])
        time.sleep(0.05)  # queue non-empty, no dispatch completed yet
        stalls = tracing.check_stall_probes(0.02)
        assert srv._probe_name in stalls, stalls
        info = stalls[srv._probe_name]
        assert info["pending"] >= 1
        assert info["batcher_alive"] is True
        assert info["breakers"] == {"m": "closed"}
        assert info["open_requests"][0]["model"] == "m"
        assert info["since_last_dispatch_s"] >= 0.02
        for f in (slow, queued):
            f.result(timeout=10)
        # healthy again: an empty queue reports no stall
        assert srv._probe_name not in tracing.check_stall_probes(0.02)
    finally:
        srv.stop()
    # stop() unregisters the probe
    assert srv._probe_name not in tracing.check_stall_probes(0.0)


def test_watchdog_report_carries_stalls_section(tmp_path):
    path = str(tmp_path / "report.json")
    tracing.dump_watchdog_report(
        path=path, stalls={"serving-x": {"pending": 3}})
    with open(path) as f:
        rec = json.load(f)
    tracing.validate_watchdog_report(rec)  # extra key stays schema-valid
    assert rec["stalls"] == {"serving-x": {"pending": 3}}


# --------------------------------------------- telemetry report columns
def _serving_rec(model="m", qd=1.0, budget=2.0, **kw):
    rec = {"event": "serving", "model": model, "requests": 3, "rows": 6,
           "bucket": 8, "fill": 0.75, "queue_delay_ms": qd,
           "wall_ms": 0.5, "budget_ms": budget}
    rec.update(kw)
    return rec


def test_report_shed_deadline_breaker_columns():
    recs = [_serving_rec(shed=i, deadline_exceeded=1, breaker="closed")
            for i in range(3)]
    recs[-1]["breaker"] = "open"
    s = telemetry_report.summarize(recs)
    t = s["serving"]["m"]
    # cumulative tallies reduce with max(); breaker is the last state seen
    assert t["shed"] == 2 and t["deadline_exceeded"] == 1
    assert t["breaker"] == "open"
    out = telemetry_report.render(s)
    assert "shed" in out and "ddl" in out and "breaker" in out


def test_report_overload_shedding_anomaly():
    # 12 dispatches x 3 requests = 36 dispatched, 12 shed → 25% > 10%
    recs = [_serving_rec(shed=i + 1) for i in range(12)]
    s = telemetry_report.summarize(recs)
    kinds = {a["kind"] for a in s["anomalies"]}
    assert "overload_shedding" in kinds
    # a light shed share stays unflagged (2 / 38 ≈ 5%)
    ok = telemetry_report.summarize(
        [_serving_rec(shed=min(i, 2)) for i in range(12)])
    assert {a["kind"] for a in ok["anomalies"]} == set()


def test_report_without_fault_fields_still_summarizes():
    # PR-6 era logs carry no shed/deadline/breaker fields: zero defaults
    s = telemetry_report.summarize([_serving_rec() for _ in range(3)])
    t = s["serving"]["m"]
    assert t["shed"] == 0 and t["deadline_exceeded"] == 0
    assert t["breaker"] is None
    assert "qd_p99ms" in telemetry_report.render(s)


# ------------------------------------------------------- smoke wrapper
def test_check_serving_chaos_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "tools", "check_serving_chaos.py")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["breaker"]["final_state"] == "closed"
    assert report["breaker"]["opens"] == 2
    assert report["crash"]["restarted"]
    assert report["overload"] == {"shed": 3, "deadline_exceeded": 1}
    assert report["futures"]["hung"] == 0
