"""mx.quantization INT8 PTQ pipeline: KL-threshold degenerate-histogram
fallbacks, telemetry-driven calibration manifests, int8-recolored exports
(real int8 payloads + per-channel scales, int8 dot_general in the HLO),
the accuracy guardrail, excluded sites, quantized multi-bucket serving,
the quant.* knob validation, and the tools/check_quantization.py smoke as
a subprocess.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import deploy, gluon, quantization, serving, telemetry
from mxnet_tpu.contrib.quantization import _kl_threshold, calib_thresholds


def _mlp(out=4):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(out))
    net.initialize()
    return net


def _batches(n=3, batch=8, feat=6, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.uniform(-1, 1, size=(batch, feat)).astype(np.float32)
            for _ in range(n)]


# ----------------------------------------- per-row KV-page quantization

def test_quantize_rows_roundtrip_and_zero_rows():
    """quantize_rows: per-row symmetric int8 over the LAST axis — one
    f32 scale per row (the int8 KV page layout), dequant error bounded
    by half an int8 step, all-zero rows exactly preserved."""
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 5, 4).astype(np.float32))
    q, s = quantization.quantize_rows(x)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == (2, 5)
    back = np.asarray(quantization.dequantize_rows(q, s))
    step = np.asarray(s)[..., None]
    assert np.all(np.abs(back - np.asarray(x)) <= step * 0.5 + 1e-7)
    z = jnp.zeros((3, 4), jnp.float32)
    qz, sz = quantization.quantize_rows(z)
    assert np.all(np.asarray(qz) == 0)
    assert np.array_equal(
        np.asarray(quantization.dequantize_rows(qz, sz)), np.asarray(z))


# ------------------------------------------- S1: KL degenerate histograms

def test_kl_threshold_all_zero_histogram_falls_back():
    """An all-zero histogram has no KL landscape: naive amax + fallback
    counter, no divide-by-zero."""
    before = telemetry.counter("quantization.calib_fallback").value
    edges = np.linspace(0.0, 2.5, 101)
    t = _kl_threshold(np.zeros(100), edges)
    assert t == pytest.approx(2.5)
    assert telemetry.counter("quantization.calib_fallback").value \
        == before + 1
    assert telemetry.counter(
        "quantization.calib_fallback.all_zero").value >= 1


def test_kl_threshold_single_bin_falls_back():
    """A constant activation (one populated bin) likewise returns the
    naive amax instead of an arbitrary clip point."""
    before = telemetry.counter("quantization.calib_fallback").value
    hist = np.zeros(100)
    hist[7] = 42.0
    t = _kl_threshold(hist, np.linspace(0.0, 1.0, 101))
    assert t == pytest.approx(1.0)
    assert telemetry.counter("quantization.calib_fallback").value \
        == before + 1
    assert telemetry.counter(
        "quantization.calib_fallback.single_bin").value >= 1


def test_calib_thresholds_entropy_on_constant_tensor():
    """End-to-end through calib_thresholds: a constant tensor used to hit
    the degenerate KL search; now it lands on the naive amax."""
    t = calib_thresholds({"a": np.full(512, 0.75, np.float32)},
                         mode="entropy")
    assert t["a"] == pytest.approx(0.75, rel=0.02)


def test_calib_thresholds_drops_nonfinite_samples():
    a = np.array([0.5, np.nan, 1.5, np.inf, -np.inf], np.float32)
    t = calib_thresholds({"a": a}, mode="naive")
    assert t["a"] == pytest.approx(1.5)


# --------------------------------------------------- calibration runner

def test_calibrate_produces_manifest_with_telemetry(tmp_path):
    net = _mlp()
    batches = _batches()
    b0 = telemetry.counter("quantization.calib_batches").value
    cal = quantization.calibrate(net, batches, mode="naive")
    assert cal.mode == "naive"
    assert sorted(cal.thresholds) == ["FullyConnected_0",
                                      "FullyConnected_1"]
    assert all(v > 0 for v in cal.thresholds.values())
    # the first site's amax is the observed input |max| under naive mode
    want = max(float(np.abs(b).max()) for b in batches)
    assert cal.thresholds["FullyConnected_0"] == pytest.approx(want,
                                                              rel=1e-5)
    # site -> weight map covers both Dense layers
    weights = {s["weight"] for s in cal.sites}
    assert len(weights) == 2 and None not in weights
    assert telemetry.counter("quantization.calib_batches").value \
        == b0 + len(batches)
    g = telemetry.snapshot()["gauges"]
    assert "quantization.amax.FullyConnected_0" in g
    # manifest round-trips via JSON
    path = cal.save(str(tmp_path / "cal.json"))
    loaded = quantization.Calibration.load(path)
    assert loaded.thresholds == pytest.approx(cal.thresholds)
    assert loaded.sites == cal.sites


def test_calibrate_rejects_bad_mode_and_empty_batches():
    net = _mlp()
    with pytest.raises(ValueError, match="naive.*entropy"):
        quantization.calibrate(net, _batches(), mode="bogus")
    with pytest.raises(ValueError, match="at least one batch"):
        quantization.calibrate(net, [])


def test_calibrate_requires_a_quantizable_op():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Activation("relu"))
    net.initialize()
    with pytest.raises(quantization.QuantizationError,
                       match="no quantizable op"):
        quantization.calibrate(net, _batches())


def test_quant_knob_validation():
    """quant.calib_mode rejects unknown modes at set() time and reverts
    (the nanguard knob-validator contract)."""
    assert mx.config.get("quant.calib_mode") == "entropy"
    with pytest.raises(ValueError, match="naive.*entropy"):
        mx.config.set("quant.calib_mode", "int4")
    assert mx.config.get("quant.calib_mode") == "entropy"
    mx.config.set("quant.calib_mode", "naive")
    try:
        assert mx.config.get("quant.calib_mode") == "naive"
    finally:
        mx.config.set("quant.calib_mode", "entropy")


# ------------------------------------------------- the quantize transform

def test_export_quantized_roundtrip_within_budget(tmp_path):
    net = _mlp()
    batches = _batches()
    cal = quantization.calibrate(net, batches)
    prefix = str(tmp_path / "q")
    paths = quantization.export_quantized(net, prefix, cal)
    assert all(os.path.exists(p) for p in paths)
    pred = quantization.load_quantized(prefix)
    assert pred.quantized and pred.dynamic_batch
    budget = float(mx.config.get("quant.error_budget"))
    # ragged sizes through the dynamic-batch artifact stay within budget
    for rows in (1, 3, 8, 11):
        x = np.random.RandomState(rows).uniform(
            -1, 1, size=(rows, 6)).astype(np.float32)
        f = net(mx.nd.array(x)).asnumpy()
        q = pred.predict(x)
        rel = np.linalg.norm(q - f) / max(np.linalg.norm(f), 1e-12)
        assert rel <= budget, (rows, rel)
    assert pred.meta["measured_error"] <= budget


def test_exported_artifact_ships_real_int8_payloads(tmp_path):
    net = _mlp()
    cal = quantization.calibrate(net, _batches())
    prefix = str(tmp_path / "q")
    quantization.export_quantized(net, prefix, cal)
    z = np.load(prefix + "-params.npz")
    qnames = [n for n in z.files if z[n].dtype == np.int8]
    assert len(qnames) == 2          # both Dense weights
    for n in qnames:
        s = z[n + quantization.SCALE_SUFFIX]
        assert s.dtype == np.float32
        assert s.shape == (z[n].shape[0], 1)   # per-output-channel
        assert np.abs(z[n]).max() <= 127
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["format_version"] == deploy.QUANTIZED_FORMAT_VERSION == 3
    assert meta["quantized"] is True
    assert sorted(meta["quantized_params"]) == sorted(qnames)
    assert meta["calibration"]["mode"] == cal.mode


def test_exported_program_contains_int8_dot(tmp_path):
    """The structural win on CPU: the serialized StableHLO really
    contracts in int8 (the MXU-native path on TPU)."""
    from jax import export as jexport
    net = _mlp()
    cal = quantization.calibrate(net, _batches())
    prefix = str(tmp_path / "q")
    quantization.export_quantized(net, prefix, cal)
    with open(prefix + "-model.stablehlo", "rb") as f:
        mlir = jexport.deserialize(f.read()).mlir_module()
    assert "i8" in mlir
    # fp32 export of the same block has no int8 anywhere
    fp32_prefix = str(tmp_path / "f")
    deploy.export_model(net, fp32_prefix, _batches()[0])
    with open(fp32_prefix + "-model.stablehlo", "rb") as f:
        fp32_mlir = jexport.deserialize(f.read()).mlir_module()
    assert "tensor<32x16xi8" not in fp32_mlir


def test_guardrail_refuses_past_error_budget(tmp_path):
    net = _mlp()
    cal = quantization.calibrate(net, _batches())
    prefix = str(tmp_path / "never")
    before = telemetry.counter("quantization.guardrail_rejects").value
    with pytest.raises(quantization.QuantizationError,
                       match="error budget|budget"):
        quantization.export_quantized(net, prefix, cal, error_budget=1e-9)
    # nothing was written — a failing artifact must not reach disk
    assert not any(os.path.exists(prefix + s) for s in
                   ("-model.stablehlo", "-meta.json", "-params.npz"))
    assert telemetry.counter("quantization.guardrail_rejects").value \
        == before + 1


def test_excluded_sites_stay_fp32(tmp_path):
    net = _mlp()
    cal = quantization.calibrate(net, _batches())
    # excluding everything makes the recolored function exactly fp32
    assert quantization.quantized_error(
        net, cal, excluded=("FullyConnected",)) == 0.0
    # excluding one site keeps ITS weight fp32 in the artifact
    site0 = cal.sites[0]["name"]
    prefix = str(tmp_path / "part")
    quantization.export_quantized(net, prefix, cal, excluded=(site0,))
    z = np.load(prefix + "-params.npz")
    w0 = cal.sites[0]["weight"]
    w1 = cal.sites[1]["weight"]
    assert z[w0].dtype == np.float32
    assert z[w1].dtype == np.int8
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["excluded"] == [site0]
    assert meta["quantized_params"] == [w1]


def test_registry_ops_restored_after_transform():
    """The recording/recolor patches must never leak: the shared Operator
    objects carry their original fns after calibrate/export, even when a
    forward inside the patch raises."""
    from mxnet_tpu.ops import registry
    originals = {n: registry.get(n).fn
                 for n in quantization.QUANTIZABLE_OPS}
    net = _mlp()
    quantization.calibrate(net, _batches())
    for n, fn in originals.items():
        assert registry.get(n).fn is fn
    plan = quantization._SitePlan()

    def boom(op_name, orig_fn):
        def fail(*a, **k):
            raise RuntimeError("boom")
        return fail

    with pytest.raises(RuntimeError, match="boom"):
        with quantization._patched_ops(plan, boom):
            net(mx.nd.array(_batches()[0]))
    for n, fn in originals.items():
        assert registry.get(n).fn is fn


# --------------------------------------------------- quantized serving

def test_quantized_serving_flat_compiles_and_flags(tmp_path):
    net = _mlp()
    cal = quantization.calibrate(net, _batches())
    prefix = str(tmp_path / "srv")
    quantization.export_quantized(net, prefix, cal)
    pred = quantization.load_quantized(prefix)

    log = str(tmp_path / "events.jsonl")
    mx.config.set("telemetry.sink", "jsonl:%s" % log)
    srv = serving.Server(max_batch=8, max_queue_delay_ms=2.0)
    try:
        srv.register("mlp_q", prefix, quantized=True)
        assert srv.stats()["quantized"]["mlp_q"] is True
        compiles0 = telemetry.counter("serving.compiles").value
        qd0 = telemetry.counter("serving.quantized_dispatches").value
        srv.start()
        buckets = srv._models["mlp_q"].buckets
        rng = np.random.RandomState(4)
        for rows in (1, 3, 2, 5, 8, 7, 1, 4):
            x = rng.uniform(-1, 1, size=(rows, 6)).astype(np.float32)
            out = srv.predict("mlp_q", x, timeout=30)
            np.testing.assert_array_equal(out, pred.predict(x))
        compiled = telemetry.counter("serving.compiles").value - compiles0
        assert compiled == len(buckets), \
            "ragged traffic compiled %d for %d buckets" % (compiled,
                                                           len(buckets))
        assert telemetry.counter(
            "serving.quantized_dispatches").value > qd0
    finally:
        srv.stop()
        mx.config.set("telemetry.sink", "")
    with open(log) as f:
        recs = [json.loads(l) for l in f if l.strip()]
    dispatches = [r for r in recs if r.get("event") == "serving"]
    assert dispatches and all(r["quantized"] is True for r in dispatches)


def test_serving_register_rejects_mismatched_flag(tmp_path):
    net = _mlp()
    cal = quantization.calibrate(net, _batches())
    qprefix = str(tmp_path / "q")
    quantization.export_quantized(net, qprefix, cal)
    fprefix = str(tmp_path / "f")
    deploy.export_model(net, fprefix, _batches()[0])
    srv = serving.Server(max_batch=8)
    with pytest.raises(ValueError, match="quantized=True"):
        srv.register("q_as_fp32", qprefix)
    with pytest.raises(ValueError, match="plain fp32"):
        srv.register("fp32_as_q", fprefix, quantized=True)


def test_quantized_params_count_int8_staging_bytes(tmp_path):
    """Loading a v3 artifact stages real int8 payloads: the
    io.staged_int8_bytes counter attributes the upload volume."""
    net = _mlp()
    cal = quantization.calibrate(net, _batches())
    prefix = str(tmp_path / "q")
    quantization.export_quantized(net, prefix, cal)
    before = telemetry.counter("io.staged_int8_bytes").value
    quantization.load_quantized(prefix)
    staged = telemetry.counter("io.staged_int8_bytes").value - before
    z = np.load(prefix + "-params.npz")
    want = sum(z[n].nbytes for n in z.files if z[n].dtype == np.int8)
    assert staged == want


# ------------------------------------------------------- smoke wrapper

def test_check_quantization_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "tools", "check_quantization.py")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["accuracy"]["worst_rel_error"] <= \
        report["accuracy"]["budget"]
    assert report["int8"]["hlo_has_i8"]
    assert report["serving"]["compiled"] == \
        len(report["serving"]["buckets"])
