"""Causal spans, Chrome sink, cross-thread propagation, watchdog, merge.

Covers the tracing PR: span parent/child identity in the emitted Chrome
trace, the near-zero-overhead-off contract, contextvars propagation across
the io.py prefetch-thread hop, the hang-watchdog flight recorder (report
schema, open-span ages, ring contents, re-arm backoff), truncated-trace
loading, the telemetry error-record hook, tools/trace_merge.py two-plane
output, and the tools/check_tracing.py smoke as a subprocess.
"""
import glob
import gzip
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, telemetry, tracing

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import trace_merge  # noqa: E402


@pytest.fixture(autouse=True)
def _tracing_off():
    """Each test starts with sink + watchdog off and a zeroed registry."""
    config.set("tracing.sink", "")
    config.set("tracing.watchdog", 0)
    telemetry.reset()
    yield
    config.set("tracing.sink", "")
    config.set("tracing.watchdog", 0)
    config.set("tracing.watchdog_dir", "")
    telemetry.reset()


def _events(path):
    return tracing.validate_trace_events(tracing.load_trace(str(path)))


# ---------------------------------------------------------------- spans
def test_span_is_only_the_annotation_when_off(monkeypatch):
    """No session, sink or watchdog: ``span()`` is the profiler annotation
    alone — it allocates no id, touches no lock, joins no registry and
    sets no context."""
    class _Untouchable:
        def __enter__(self):
            raise AssertionError("a lock was taken on the off path")

        def __exit__(self, *exc):
            return False

    for lock in ("_ID_LOCK", "_OPEN_LOCK", "_SINK_LOCK", "_RING_LOCK"):
        monkeypatch.setattr(tracing, lock, _Untouchable())
    next_id = tracing._NEXT_ID[0]
    s = tracing.span("anything", cat="test", rows=3)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    assert not isinstance(s, tracing.Span)
    with s as entered:
        entered.set(more=1)
        # it carries no identity and is not the context's current span
        assert entered.trace_id is None and entered.span_id is None
        assert tracing.current_span() is None
        assert tracing.open_spans.__globals__["_OPEN"] == {}
    assert tracing._NEXT_ID[0] == next_id


def test_span_nesting_ids_in_chrome_trace(tmp_path):
    trace = tmp_path / "t.trace.json"
    config.set("tracing.sink", "chrome:%s" % trace)
    assert tracing.enabled() and tracing.sink_path() == str(trace)
    with tracing.span("root", cat="test") as root:
        with tracing.span("child", cat="test", extra=7) as child:
            assert tracing.current_span() is child
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
        assert tracing.current_span() is root
    config.set("tracing.sink", "")
    xs = _events(trace)
    by_name = {e["name"]: e for e in xs}
    assert set(by_name) == {"root", "child"}
    r, c = by_name["root"]["args"], by_name["child"]["args"]
    assert r["parent_id"] is None
    assert c["parent_id"] == r["span_id"]
    assert c["trace_id"] == r["trace_id"]
    assert c["extra"] == 7
    assert by_name["child"]["cat"] == "test"
    # the child fits inside the root on the timeline
    assert by_name["root"]["ts"] <= by_name["child"]["ts"]
    assert by_name["child"]["dur"] <= by_name["root"]["dur"]


def test_span_error_recorded_in_trace(tmp_path):
    trace = tmp_path / "err.trace.json"
    config.set("tracing.sink", "chrome:%s" % trace)
    with pytest.raises(ValueError):
        with tracing.span("failing"):
            raise ValueError("bad shard")
    config.set("tracing.sink", "")
    (e,) = _events(trace)
    assert e["args"]["error"] == "ValueError: bad shard"


def test_sibling_spans_share_trace_new_spans_after_root_do_not(tmp_path):
    trace = tmp_path / "sib.trace.json"
    config.set("tracing.sink", "chrome:%s" % trace)
    with tracing.span("step"):
        with tracing.span("fwd"):
            pass
        with tracing.span("bwd"):
            pass
    with tracing.span("next_step"):
        pass
    config.set("tracing.sink", "")
    by_name = {e["name"]: e["args"] for e in _events(trace)}
    assert by_name["fwd"]["trace_id"] == by_name["bwd"]["trace_id"] \
        == by_name["step"]["trace_id"]
    assert by_name["next_step"]["trace_id"] != by_name["step"]["trace_id"]


def test_module_step_emits_causal_tree(tmp_path):
    trace = tmp_path / "mod.trace.json"
    config.set("module.fused_step", "auto")
    config.set("tracing.sink", "chrome:%s" % trace)
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    h = mx.sym.FullyConnected(data, num_hidden=8, name="fc0")
    out = mx.sym.SoftmaxOutput(h, label, name="softmax")
    mod = mx.mod.Module(out)
    mod.bind([("data", (4, 6))], [("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer()
    rng = np.random.RandomState(0)
    batch = mx.io.DataBatch(
        [mx.nd.array(rng.randn(4, 6).astype(np.float32))],
        [mx.nd.array((rng.rand(4) * 3).astype(np.float32))])
    for _ in range(2):
        mod.train_step(batch)
    config.set("tracing.sink", "")
    xs = _events(trace)
    steps = [e for e in xs if e["name"] == "module.step"]
    assert len(steps) == 2
    step_ids = {e["args"]["span_id"]: e for e in steps}
    dispatches = [e for e in xs if e["name"] == "module.fused_dispatch"]
    assert len(dispatches) == 2
    for d in dispatches:
        parent = step_ids[d["args"]["parent_id"]]
        assert d["args"]["trace_id"] == parent["args"]["trace_id"]


# ---------------------------------------------- cross-thread propagation
def test_prefetch_worker_span_carries_parent_trace(tmp_path):
    """satellite: the io.py prefetch thread's spans must keep the trace_id
    of the context that STARTED the prefetcher — the ThreadedIter hop."""
    trace = tmp_path / "pf.trace.json"
    config.set("tracing.sink", "chrome:%s" % trace)
    base = mx.io.NDArrayIter(
        data=np.zeros((8, 2), np.float32),
        label=np.zeros((8,), np.float32), batch_size=4)
    with tracing.span("epoch") as epoch:
        pf = mx.io.PrefetchingIter(base)
        batches = list(pf)
    assert len(batches) == 2
    config.set("tracing.sink", "")
    xs = _events(trace)
    prefetch = [e for e in xs if e["name"] == "io.prefetch"]
    assert prefetch, [e["name"] for e in xs]
    epoch_ev = next(e for e in xs if e["name"] == "epoch")
    for e in prefetch:
        assert e["args"]["trace_id"] == epoch.trace_id \
            == epoch_ev["args"]["trace_id"]
        assert e["args"]["parent_id"] == epoch.span_id
        # emitted from the worker thread, not the consumer
        assert e["tid"] != epoch_ev["tid"]


def test_wrap_context_plain_thread():
    config.set("tracing.watchdog_dir", "")  # keep spans live w/o sink
    config.set("tracing.watchdog", 30)      # arm so span() is not a noop
    seen = {}

    def worker():
        with tracing.span("inner") as s:
            seen["trace_id"] = s.trace_id
            seen["parent_id"] = s.parent_id

    with tracing.span("outer") as outer:
        t = threading.Thread(target=tracing.wrap_context(worker))
        t.start()
        t.join()
    config.set("tracing.watchdog", 0)
    assert seen["trace_id"] == outer.trace_id
    assert seen["parent_id"] == outer.span_id


# -------------------------------------------------------------- watchdog
def test_watchdog_fires_report_with_open_span_and_ring(tmp_path):
    config.set("tracing.watchdog_dir", str(tmp_path))
    config.set("tracing.watchdog", 0.05)
    # a completed step lands in the ring, then the stall begins
    with telemetry.step_scope("module", samples=4):
        pass
    with tracing.span("stuck.allreduce", cat="collective"):
        deadline = time.perf_counter() + 2.0
        reports = []
        while not reports and time.perf_counter() < deadline:
            time.sleep(0.01)
            reports = glob.glob(
                os.path.join(str(tmp_path), "watchdog_report_*.json"))
    config.set("tracing.watchdog", 0)
    assert reports, "watchdog never fired"
    with open(reports[0]) as f:
        rec = json.load(f)
    tracing.validate_watchdog_report(rec)
    assert rec["deadline_s"] == 0.05
    assert rec["last_step_age_s"] >= 0.05
    names = [s["name"] for s in rec["open_spans"]]
    assert "stuck.allreduce" in names
    stuck = next(s for s in rec["open_spans"]
                 if s["name"] == "stuck.allreduce")
    assert stuck["age_s"] > 0
    assert any(e["kind"] == "step" for e in rec["ring"])
    assert any("test_tracing" in ln for t in rec["threads"]
               for ln in t["stack"]), "report lost the stalled stack"
    assert telemetry.counter("tracing.watchdog_fires").value >= 1


def test_watchdog_backoff_limits_reports(tmp_path):
    """One persistent stall must NOT produce a report per deadline — the
    re-fire spacing grows exponentially."""
    config.set("tracing.watchdog_dir", str(tmp_path))
    config.set("tracing.watchdog", 0.05)
    telemetry._TRACING_STEP_HOOK("module", 1, 0.001)  # reset progress
    time.sleep(0.6)  # 12x the deadline
    config.set("tracing.watchdog", 0)
    n = len(glob.glob(os.path.join(str(tmp_path), "watchdog_report_*.json")))
    # naive re-fire would give ~12; backoff (1x, 3x, 7x...) allows <= 4
    assert 1 <= n <= 4, n


def test_failing_step_is_progress_and_ringed(tmp_path):
    """An exception loop is not a hang: the watchdog sees failing steps as
    progress, and the flight recorder tags them step_error."""
    config.set("tracing.watchdog_dir", str(tmp_path))
    config.set("tracing.watchdog", 0.2)
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.45:
        with pytest.raises(RuntimeError):
            with telemetry.step_scope("module", samples=1):
                raise RuntimeError("shard oom")
        time.sleep(0.02)
    config.set("tracing.watchdog", 0)
    assert glob.glob(
        os.path.join(str(tmp_path), "watchdog_report_*.json")) == []
    errs = [e for e in tracing.ring_events() if e["kind"] == "step_error"]
    assert errs and errs[-1]["error"] == "RuntimeError: shard oom"


def test_dump_watchdog_report_on_demand(tmp_path):
    path = str(tmp_path / "manual.json")
    out = tracing.dump_watchdog_report(path=path)
    assert out == path
    with open(path) as f:
        tracing.validate_watchdog_report(json.load(f))


def test_validate_watchdog_report_rejects(tmp_path):
    path = str(tmp_path / "r.json")
    tracing.dump_watchdog_report(path=path)
    with open(path) as f:
        good = json.load(f)
    tracing.validate_watchdog_report(dict(good))
    for broken in (
            {k: v for k, v in good.items() if k != "threads"},
            dict(good, event="step"),
            dict(good, threads=[]),
            dict(good, threads=[{"name": "t", "stack": []}]),
            "not a dict"):
        with pytest.raises(ValueError):
            tracing.validate_watchdog_report(broken)


# ------------------------------------------------------- trace loading
def test_load_trace_tolerates_truncation(tmp_path):
    trace = tmp_path / "cut.trace.json"
    config.set("tracing.sink", "chrome:%s" % trace)
    for i in range(3):
        with tracing.span("s%d" % i):
            pass
    config.set("tracing.sink", "")  # closes the array properly
    full = tracing.load_trace(str(trace))
    assert [e["name"] for e in full
            if e.get("ph") == "X"] == ["s0", "s1", "s2"]
    # simulate a SIGKILL mid-write: the file ends half-way through the s2
    # event line, with no closing "]"
    text = trace.read_text()
    trace.write_text(text[:text.find('"s2"') + 2])
    events = tracing.load_trace(str(trace))
    x_cut = [e for e in events if e.get("ph") == "X"]
    assert [e["name"] for e in x_cut] == ["s0", "s1"]


# ------------------------------------------------------------ trace_merge
def _synthetic_device_dir(tmp_path):
    run = os.path.join(str(tmp_path), "xp", "plugins", "profile", "r0")
    os.makedirs(run)
    events = [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/device:TPU:1"}},
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "python"}},
        {"ph": "X", "pid": 7, "tid": 0, "name": "fusion.1",
         "ts": 10_000, "dur": 900},
        {"ph": "X", "pid": 9, "tid": 0, "name": "all-reduce.3",
         "ts": 10_400, "dur": 300},
        {"ph": "X", "pid": 1, "tid": 0, "name": "host_noise",
         "ts": 10_000, "dur": 5_000},
    ]
    with gzip.open(os.path.join(run, "x.trace.json.gz"), "wt") as f:
        json.dump({"traceEvents": events}, f)
    return os.path.join(str(tmp_path), "xp")


def test_trace_merge_two_planes(tmp_path):
    host = tmp_path / "host.trace.json"
    config.set("tracing.sink", "chrome:%s" % host)
    with tracing.span("module.step"):
        with tracing.span("executor.forward"):
            pass
    config.set("tracing.sink", "")
    out = tmp_path / "merged.trace.json"
    rc = trace_merge.main([str(host), _synthetic_device_dir(tmp_path),
                           "-o", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    host_x = [e for e in xs if e["pid"] == trace_merge.HOST_PID]
    dev_x = [e for e in xs if e["pid"] >= trace_merge.DEVICE_PID_BASE]
    assert {e["name"] for e in host_x} == {"module.step",
                                           "executor.forward"}
    assert {e["name"] for e in dev_x} == {"fusion.1", "all-reduce.3"}
    # the profiler export's own host lane is dropped, not duplicated
    assert not any(e["name"] == "host_noise" for e in xs)
    # two device planes stay distinct
    assert len({e["pid"] for e in dev_x}) == 2
    # default align: both planes rebased to start at ~0
    assert min(e["ts"] for e in host_x) == 0
    assert min(e["ts"] for e in dev_x) == 0
    # plane naming survives for the viewer
    names = {m["args"]["name"] for m in events
             if m.get("ph") == "M" and m.get("name") == "process_name"}
    assert "mxnet_tpu host" in names
    assert "/device:TPU:0" in names


def test_trace_merge_align_none_keeps_timestamps(tmp_path):
    host = tmp_path / "h.trace.json"
    config.set("tracing.sink", "chrome:%s" % host)
    with tracing.span("s"):
        pass
    config.set("tracing.sink", "")
    host_events = trace_merge.load_chrome_trace(str(host))
    raw_ts = [e["ts"] for e in host_events if e.get("ph") == "X"]
    merged, stats = trace_merge.merge_traces(host_events, [], align="none")
    kept = [e["ts"] for e in merged if e.get("ph") == "X"]
    assert kept == raw_ts
    assert stats["device_events"] == 0


def test_load_chrome_trace_truncated_array(tmp_path):
    p = tmp_path / "trunc.json"
    p.write_text('[\n{"ph": "X", "name": "a", "pid": 1, "tid": 0, '
                 '"ts": 1, "dur": 1},\n{"ph": "X", "name": "b", "pi')
    events = trace_merge.load_chrome_trace(str(p))
    assert [e["name"] for e in events] == ["a"]


# ------------------------------------------------------------ smoke wiring
def test_check_tracing_smoke():
    """Subprocess wiring for tools/check_tracing.py — spans, watchdog and
    merge must hold from a clean interpreter, exactly how CI invokes it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "check_tracing.py")],
        capture_output=True, text=True, timeout=180, env=env, cwd=root)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["trace"]["steps"] == 3, report
    assert report["report"]["open_spans"] >= 1, report
    assert {"check.outer", "check.inner", "module.step"} \
        <= set(report["profiler"]["spans"]), report
    assert report["elapsed_s"] > 0, report   # reported, no budget (D9)
