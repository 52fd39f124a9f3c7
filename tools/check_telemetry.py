"""Fast CPU smoke for the telemetry pipeline (< 30s).

Proves the observability stack end-to-end on the host backend, with one
parseable JSON line on stdout:

  1. sink     — enabling ``telemetry.sink`` (the MXNET_TPU_TELEMETRY knob)
                makes 20 fused Module train steps write 20 schema-valid
                "step" records, all path="fused", exactly one compile;
  2. report   — tools/telemetry_report.py summarizes the run and flags NO
                anomalies on this clean fixed-shape workload;
  3. profiler — profiler.dumps() renders the registry ("Telemetry timers"
                and "Gauges" sections present, module step timer fed).

Usage: JAX_PLATFORMS=cpu python tools/check_telemetry.py
Wired as a `not slow` test in tests/test_telemetry.py.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

STEPS = 20


def build_module(mx):
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    h = data
    for i, width in enumerate((64, 64)):
        h = mx.sym.FullyConnected(h, num_hidden=width, name="fc%d" % i)
        h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=5, name="head")
    out = mx.sym.SoftmaxOutput(h, label, name="softmax")
    mod = mx.mod.Module(out)
    mod.bind([("data", (32, 16))], [("softmax_label", (32,))])
    mod.init_params()
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    return mod


def main():
    import numpy as np
    result = {"ok": False}
    log_path = os.path.join(tempfile.mkdtemp(prefix="mxtpu_telemetry_"),
                            "steps.jsonl")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import config, profiler, telemetry
        import telemetry_report
        result["backend"] = jax.default_backend()

        config.set("module.fused_step", "auto")
        config.set("telemetry.sink", "jsonl:" + log_path)
        assert telemetry.enabled(), "sink knob did not enable the step log"
        telemetry.reset()

        rng = np.random.RandomState(0)
        X = rng.randn(32, 16).astype(np.float32)
        Y = (rng.rand(32) * 5).astype(np.float32)
        batch = mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(Y)])
        mod = build_module(mx)
        for _ in range(STEPS):
            mod.train_step(batch)
            # block OUTSIDE the step scope so each record's wall time is
            # the real step (async dispatch alone is µs-scale noise) and
            # its host_syncs delta stays 0
            jax.block_until_ready(
                [w._data for w in mod.get_params()[0].values()])

        # 1. sink: 20 schema-valid fused step records
        records, bad = telemetry_report.load_records(log_path)
        assert bad == 0, "%d malformed lines" % bad
        steps = [r for r in records if r.get("event") == "step"]
        assert len(steps) == STEPS, "expected %d step records, got %d" \
            % (STEPS, len(steps))
        for rec in steps:
            telemetry.validate_step_record(rec)
        paths = {r["path"] for r in steps}
        assert paths == {"fused"}, paths
        assert sum(r["compiles"] for r in steps) == 1, \
            [r["compiles"] for r in steps]
        assert [r["step"] for r in steps] == list(range(1, STEPS + 1))

        # 2. report: a clean fixed-shape run flags nothing it counts
        # (recompiles, sync transfers).  What it times — a p99 against a
        # p50 over twenty sub-millisecond steps — is the machine's load,
        # not the pipeline's: reported, not asserted (ROADMAP D9)
        summary = telemetry_report.summarize(records)
        timed = ("latency_blowup", "falling_throughput", "mfu_collapse")
        result["timing_anomalies"] = [a for a in summary["anomalies"]
                                      if a["kind"] in timed]
        counted = [a for a in summary["anomalies"] if a["kind"] not in timed]
        assert counted == [], counted
        assert summary["sources"]["module"]["steps"] == STEPS
        result["summary"] = summary["sources"]["module"]

        # 3. profiler UX: registry sections render
        text = profiler.dumps()
        assert "Telemetry timers" in text, text[:400]
        assert "Gauges" in text, text[:400]
        assert "module.step" in text, text[:400]
        c = profiler.counters()
        assert c["fused_steps"] == STEPS, c
        result.update(ok=True, steps=STEPS,
                      wall_ms_p50=summary["sources"]["module"]
                      ["wall_ms_p50"])
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config as _cfg
            _cfg.set("telemetry.sink", "")
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
