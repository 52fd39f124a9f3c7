"""Fast CPU smoke for mx.quantization PTQ + quantized serving (seconds on an idle
box; ``elapsed_s`` is reported, not asserted).

Proves the INT8 pipeline end-to-end on the host backend, with one
parseable JSON line on stdout:

  1. calibrate — representative batches produce a Calibration manifest
               covering every quantizable site, with telemetry amax
               gauges published;
  2. accuracy — the exported v3 artifact's outputs stay within the
               ``quant.error_budget`` of the fp32 export on ragged
               request sizes (the guardrail's contract, re-checked
               post-load);
  3. int8    — the serialized program really contains int8 tensors (the
               structural win on CPU: int8 dot_general in the HLO) and
               the params .npz ships real int8 payloads + ::scale arrays;
  4. serving — ``serving.Server.register(..., quantized=True)`` serves
               the artifact through the same bucketed batcher:
               ``serving.compiles`` equals the bucket count and stays
               FLAT across ragged traffic, ``stats()`` flags the model
               quantized, and quantized dispatches are counted.

Usage: JAX_PLATFORMS=cpu python tools/check_quantization.py
Wired as a `not slow` test in tests/test_quantization.py.
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
FEATURES = 12
SIZES = (1, 3, 2, 5, 4, 8, 7, 1)   # ragged request mix


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_quant_")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import quantization, telemetry
        from mxnet_tpu.gluon import nn
        result["backend"] = jax.default_backend()

        mx.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(32, activation="relu"), nn.Dense(4))
        net.initialize()

        # 1: calibrate over representative batches
        rng = np.random.RandomState(0)
        batches = [rng.uniform(-1, 1, size=(MAX_BATCH, FEATURES))
                   .astype(np.float32) for _ in range(4)]
        cal = quantization.calibrate(net, batches, mode="entropy")
        assert len(cal.sites) == 2, cal.sites
        assert all(v > 0 for v in cal.thresholds.values()), cal.thresholds
        result["calibrate"] = {"sites": len(cal.sites),
                               "batches": cal.num_batches,
                               "mode": cal.mode}

        # export both flavors from the same block
        fp32_prefix = os.path.join(tmpdir, "fp32")
        q_prefix = os.path.join(tmpdir, "int8")
        mx.deploy.export_model(net, fp32_prefix, batches[0])
        quantization.export_quantized(net, q_prefix, cal)
        fp32 = mx.deploy.load_model(fp32_prefix)
        qpred = mx.deploy.load_model(q_prefix, quantized=True)
        assert qpred.quantized and qpred.dynamic_batch

        # 3: real int8 payloads + int8 program
        z = np.load(q_prefix + "-params.npz")
        int8_params = [n for n in z.files if z[n].dtype == np.int8]
        scales = [n for n in z.files
                  if n.endswith(quantization.SCALE_SUFFIX)]
        assert int8_params and len(scales) == len(int8_params), z.files
        from jax import export as jexport
        with open(q_prefix + "-model.stablehlo", "rb") as f:
            mlir = jexport.deserialize(f.read()).mlir_module()
        assert "i8" in mlir, "no int8 tensors in the exported program"
        result["int8"] = {"params": int8_params, "hlo_has_i8": True}

        # 2: quantized outputs within the error budget on ragged sizes
        budget = float(mx.config.get("quant.error_budget"))
        worst = 0.0
        for s in SIZES:
            x = rng.uniform(-1, 1, size=(s, FEATURES)).astype(np.float32)
            f = fp32.predict(x)
            q = qpred.predict(x)
            worst = max(worst, float(np.linalg.norm(q - f)
                                     / max(np.linalg.norm(f), 1e-12)))
        assert worst <= budget, \
            "quantized serving error %.4f exceeds budget %.4f" % (worst,
                                                                  budget)
        result["accuracy"] = {"worst_rel_error": round(worst, 5),
                              "budget": budget,
                              "meta_measured": qpred.meta["measured_error"]}

        # 4: quantized serving — flat compiles across ragged traffic
        srv = mx.serving.Server(max_batch=MAX_BATCH, max_queue_delay_ms=4.0)
        srv.register("mlp_int8", q_prefix, quantized=True)
        compiles0 = telemetry.counter("serving.compiles").value
        srv.start()
        buckets = srv._models["mlp_int8"].buckets
        assert srv.stats()["quantized"]["mlp_int8"] is True
        qd0 = telemetry.counter("serving.quantized_dispatches").value
        outs = []
        for s in SIZES:
            x = rng.uniform(-1, 1, size=(s, FEATURES)).astype(np.float32)
            outs.append((x, srv.predict("mlp_int8", x, timeout=30)))
        srv.stop()
        compiled = telemetry.counter("serving.compiles").value - compiles0
        assert compiled == len(buckets), \
            "ragged traffic compiled %d programs for %d buckets" \
            % (compiled, len(buckets))
        qdisp = telemetry.counter("serving.quantized_dispatches").value - qd0
        assert qdisp > 0, "no quantized dispatch was counted"
        mism = sum(0 if np.array_equal(o, qpred.predict(x)) else 1
                   for x, o in outs)
        assert mism == 0, \
            "%d served outputs diverged from unbatched predict" % mism
        result["serving"] = {"buckets": list(buckets),
                             "compiled": compiled,
                             "quantized_dispatches": qdisp,
                             "requests": len(SIZES)}

        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
