"""Fast CPU smoke for the mx.numerics plane (seconds on an idle box;
``elapsed_s`` is reported, not asserted).

Proves the three numerics stories end-to-end on the host backend, with
one parseable JSON line on stdout:

  1. capture  — per-layer taps on a 2-layer transformer step collect a
                stats vector per site in topological order, all finite
                on clean weights, and the plain (collector-less) path
                still returns the same logits;
  2. nanguard — poisoning ONE layer's weights with a NaN localizes:
                ``first_nonfinite`` names exactly the poisoned site
                (layer 0 stays clean, layer 1 flags), which is the
                forensics replay's root-cause primitive;
  3. drift    — an int8 export's stats twin samples runtime amax under
                serving traffic: calibrated-range traffic keeps the
                ``quant.drift_ratio`` gauges near 1.0 with zero trips,
                then perturbed (10x) traffic pushes the EWMA past the
                threshold — gauge flips, ``quant.drift_trips`` bumps,
                and a ``quant_drift`` event lands in telemetry.

Usage: JAX_PLATFORMS=cpu python tools/check_numerics.py
Wired as a `not slow` test in tests/test_numerics.py.
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

DRIFT_THRESHOLD = 1.5


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_num_")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import mxnet_tpu as mx  # noqa: F401 — registers ops
        from mxnet_tpu import config, numerics, quantization, serving
        from mxnet_tpu import gluon, telemetry
        from mxnet_tpu.models.transformer import (TransformerLM,
                                                  TransformerLMConfig)
        result["backend"] = jax.default_backend()

        # 1: per-layer taps on a 2-layer transformer step
        cfg = TransformerLMConfig(vocab_size=32, num_layers=2, d_model=16,
                                  d_ff=32, num_heads=2, max_len=16,
                                  dtype=jnp.float32)
        lm = TransformerLM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        toks = jnp.ones((2, 8), jnp.int32)
        with numerics.collect() as sink:
            logits = lm.apply(params, toks)
        host = numerics.expand_stats(dict(sink))
        sites = list(host)
        assert sites == ["layer_out[0]", "layer_out[1]"], sites
        assert all(v[numerics.STAT_FIELDS.index("nonfinite")] == 0.0
                   for v in host.values()), host
        plain = lm.apply(params, toks)  # no ambient collector: same math
        np.testing.assert_allclose(np.asarray(logits), np.asarray(plain),
                                   rtol=1e-6)
        result["capture"] = {"sites": sites,
                            "amax_layer0": float(host[sites[0]][0])}

        # 2: NaN in layer 1's weights localizes to layer_out[1] by name
        poisoned = jax.tree_util.tree_map(lambda x: x, params)
        w2 = np.asarray(poisoned["layers"]["w2"]).copy()
        w2[1, 0, 0] = np.nan  # layer index 1 only
        poisoned["layers"]["w2"] = jnp.asarray(w2)
        with numerics.collect() as sink:
            lm.apply(poisoned, toks)
        host = numerics.expand_stats(dict(sink))
        first = numerics.first_nonfinite(host)
        nf = numerics.STAT_FIELDS.index("nonfinite")
        assert first == "layer_out[1]", \
            "NaN mislocalized to %r" % (first,)
        assert host["layer_out[0]"][nf] == 0.0, \
            "clean layer flagged non-finite"
        result["nanguard"] = {
            "poisoned_site": "layer_out[1]",
            "first_nonfinite": first,
            "nonfinite_count": float(host[first][nf])}

        # 3: drift gauges flip when serving traffic leaves the
        # calibrated range of an int8 model
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
        net.initialize()
        rng = np.random.RandomState(0)
        cal = quantization.calibrate(
            net, [rng.uniform(-1, 1, size=(8, 6)).astype(np.float32)
                  for _ in range(3)])
        prefix = os.path.join(tmpdir, "int8")
        paths = quantization.export_quantized(net, prefix, cal)
        assert prefix + "-stats.stablehlo" in paths, paths

        events_path = os.path.join(tmpdir, "events.jsonl")
        config.set("telemetry.sink", "jsonl:" + events_path)
        config.set("quant.drift_every", 1)
        config.set("quant.drift_threshold", DRIFT_THRESHOLD)
        srv = serving.Server(max_batch=8, max_queue_delay_ms=2.0)
        try:
            srv.register("int8", prefix, quantized=True)
            srv.start()
            for _ in range(2):  # calibrated-range traffic: no trip
                srv.predict(
                    "int8",
                    rng.uniform(-1, 1, size=(4, 6)).astype(np.float32),
                    timeout=30)
            snap = telemetry.snapshot()
            in_range = {k: v for k, v in snap["gauges"].items()
                        if k.startswith("quant.drift_ratio.int8.")}
            assert in_range, snap["gauges"]
            trips0 = telemetry.counter("quant.drift_trips").value
            assert trips0 == 0, "drift tripped on calibrated traffic"
            for _ in range(10):  # perturbed (10x) traffic: EWMA crosses
                srv.predict(
                    "int8",
                    rng.uniform(-10, 10, size=(4, 6)).astype(np.float32),
                    timeout=30)
            trips = telemetry.counter("quant.drift_trips").value
            assert trips > 0, "perturbed traffic never tripped drift"
            snap = telemetry.snapshot()
            drifted = {k: round(v, 3) for k, v in snap["gauges"].items()
                       if k.startswith("quant.drift_ratio.int8.")
                       and v > DRIFT_THRESHOLD}
            assert drifted, snap["gauges"]
            telemetry.flush()
            with open(events_path) as fh:
                events = [json.loads(line) for line in fh
                          if '"quant_drift"' in line]
            assert events, "no quant_drift record in the telemetry sink"
            assert events[0]["model"] == "int8", events[0]
            result["drift"] = {
                "calibrated_ratio_max": round(max(in_range.values()), 3),
                "drifted_gauges": drifted,
                "trips": int(trips)}
        finally:
            srv.stop()

        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config
            config.unset("quant.drift_every")
            config.unset("quant.drift_threshold")
            config.unset("numerics.capture")
            config.set("telemetry.sink", "")
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
