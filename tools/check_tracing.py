"""Fast CPU smoke for the tracing pipeline (a few seconds).

Proves the causal-span stack end-to-end on the host backend, with one
parseable JSON line on stdout:

  1. spans    — with ``tracing.sink`` (MXNET_TPU_TRACE) on, a tiny Module
                train loop emits schema-valid Chrome trace events whose
                parent_ids link executor.forward/backward under their
                module.step root;
  2. watchdog — a deliberately-stalled "step" under a short
                ``tracing.watchdog`` (MXNET_TPU_WATCHDOG) deadline produces
                a flight-recorder report: thread stacks, the stalled span
                OPEN with its age, and the span/step event ring;
  3. merge    — tools/trace_merge.py folds the host trace and a synthetic
                device capture into one two-plane Chrome trace;
  4. profiler — a session started by ``jax.profiler.start_trace`` alone
                finds the framework's spans on its host plane, nested,
                with their arguments as the events' stats.

Usage: JAX_PLATFORMS=cpu python tools/check_tracing.py
Wired as a `not slow` test in tests/test_tracing.py.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

STEPS = 3
WD_DEADLINE = 0.15
STALL_TIMEOUT = 2.0


def build_module(mx):
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    h = mx.sym.FullyConnected(data, num_hidden=8, name="fc0")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=3, name="head")
    out = mx.sym.SoftmaxOutput(h, label, name="softmax")
    mod = mx.mod.Module(out)
    mod.bind([("data", (4, 8))], [("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    return mod


def write_synthetic_device_trace(tdir):
    """A minimal jax.profiler-shaped export: one device plane (pid 7) with
    two op events, one host plane (pid 1) trace_merge must DROP."""
    d = os.path.join(tdir, "xplane", "plugins", "profile", "run0")
    os.makedirs(d)
    path = os.path.join(d, "host.trace.json.gz")
    trace = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 7,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "python"}},
        {"ph": "X", "name": "fusion.1", "pid": 7, "tid": 0,
         "ts": 500.0, "dur": 120.0},
        {"ph": "X", "name": "copy.2", "pid": 7, "tid": 0,
         "ts": 650.0, "dur": 30.0},
        {"ph": "X", "name": "host_noise", "pid": 1, "tid": 0,
         "ts": 510.0, "dur": 10.0},
    ]}
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)
    return os.path.join(tdir, "xplane")


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tdir = tempfile.mkdtemp(prefix="mxtpu_tracing_")
    trace_path = os.path.join(tdir, "run.trace.json")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import config, tracing
        import trace_merge
        result["backend"] = jax.default_backend()

        # the sink is armed before the train loop (step events reach the
        # flight-recorder ring whenever sink OR watchdog is on); the
        # watchdog itself is armed only after the loop, so the first-step
        # COMPILE (slower than any sane deadline) is not reported as a hang
        config.set("module.fused_step", "auto")
        config.set("tracing.sink", "chrome:" + trace_path)
        config.set("tracing.watchdog_dir", tdir)
        assert tracing.enabled(), "sink knob did not enable the chrome sink"

        rng = np.random.RandomState(0)
        batch = mx.io.DataBatch(
            [mx.nd.array(rng.randn(4, 8).astype(np.float32))],
            [mx.nd.array((rng.rand(4) * 3).astype(np.float32))])
        mod = build_module(mx)
        for _ in range(STEPS):
            mod.train_step(batch)
        jax.block_until_ready(
            [w._data for w in mod.get_params()[0].values()])

        # 2. deliberately stall inside an open span until the watchdog
        # files its report (poll, so a fast fire wastes no budget)
        config.set("tracing.watchdog", WD_DEADLINE)
        deadline = time.perf_counter() + STALL_TIMEOUT
        reports = []
        with tracing.span("stalled.collective", cat="collective"):
            while not reports and time.perf_counter() < deadline:
                time.sleep(0.02)
                reports = glob.glob(
                    os.path.join(tdir, "watchdog_report_*.json"))
        assert reports, "watchdog fired no report within %.1fs" \
            % STALL_TIMEOUT
        with open(reports[0]) as f:
            report = json.load(f)
        tracing.validate_watchdog_report(report)
        open_names = {s["name"]: s for s in report["open_spans"]}
        assert "stalled.collective" in open_names, report["open_spans"]
        assert open_names["stalled.collective"]["age_s"] > 0
        ring_kinds = {e["kind"] for e in report["ring"]}
        assert "step" in ring_kinds, ring_kinds  # train steps pre-stall
        assert report["last_step_age_s"] >= WD_DEADLINE
        result["report"] = {
            "path": os.path.basename(reports[0]),
            "threads": len(report["threads"]),
            "open_spans": len(report["open_spans"]),
            "ring_events": len(report["ring"]),
            "last_step_age_s": report["last_step_age_s"]}

        # 1. close the sink, then audit the emitted span causality
        config.set("tracing.watchdog", 0)
        config.set("tracing.sink", "")
        events = tracing.load_trace(trace_path)
        xs = tracing.validate_trace_events(events)
        by_id = {e["args"]["span_id"]: e for e in xs}
        roots = [e for e in xs if e["name"] == "module.step"]
        assert len(roots) == STEPS, [e["name"] for e in xs]
        children = [e for e in xs
                    if e["args"]["parent_id"] in
                    {r["args"]["span_id"] for r in roots}]
        child_names = {e["name"] for e in children}
        assert "module.fused_dispatch" in child_names, child_names
        for e in children:
            parent = by_id[e["args"]["parent_id"]]
            assert parent["args"]["trace_id"] == e["args"]["trace_id"]
        result["trace"] = {"span_events": len(xs),
                           "steps": len(roots),
                           "child_kinds": sorted(child_names)}

        # 3. two-plane merge with a synthetic device capture
        xplane = write_synthetic_device_trace(tdir)
        merged_path = os.path.join(tdir, "merged.trace.json")
        trace_merge.main([trace_path, xplane, "-o", merged_path])
        with open(merged_path) as f:
            merged = json.load(f)["traceEvents"]
        pids = {e["pid"] for e in merged if e.get("ph") == "X"}
        assert trace_merge.HOST_PID in pids, pids
        assert trace_merge.DEVICE_PID_BASE in pids, pids
        dev_names = {e["name"] for e in merged
                     if e.get("ph") == "X"
                     and e["pid"] == trace_merge.DEVICE_PID_BASE}
        assert dev_names == {"fusion.1", "copy.2"}, dev_names
        result["merge"] = {"events": len(merged), "planes": sorted(pids)}

        # 4. the same spans reach a profiler session nobody told
        # mx.profiler about, with their arguments as the event's stats
        prof_dir = os.path.join(tdir, "prof")
        jax.profiler.start_trace(prof_dir)
        try:
            with tracing.span("check.outer", cat="test", step=7):
                with tracing.span("check.inner", cat="test") as sp:
                    sp.set(rows=3)
                mod.train_step(batch)
        finally:
            jax.profiler.stop_trace()
        (xplane_pb,) = glob.glob(os.path.join(
            prof_dir, "plugins", "profile", "*", "*.xplane.pb"))
        found = {}
        for plane in jax.profiler.ProfileData.from_file(xplane_pb).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("check.", "module.")):
                        found[ev.name] = (ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          dict(ev.stats))
        assert {"check.outer", "check.inner", "module.step"} <= set(found), \
            sorted(found)
        outer, inner = found["check.outer"], found["check.inner"]
        assert outer[0] <= inner[0] and inner[1] <= outer[1], (outer, inner)
        assert int(outer[2]["step"]) == 7 and int(inner[2]["rows"]) == 3
        result["profiler"] = {"spans": sorted(found)}

        # reported, not asserted: a loaded machine is not a fault of the
        # pipeline (ROADMAP D9)
        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config as _cfg
            _cfg.set("tracing.watchdog", 0)
            _cfg.set("tracing.sink", "")
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
