"""mx.numerics — in-program tensor statistics, nanguard forensics, and
quantization drift monitoring.

Covers the numerics PR: the stats vector math (finite-masked amax/rms,
non-finite counting, bf16 overflow/underflow fractions), the capture-knob
grammar and its epoch-neutrality (toggling never evicts program caches),
the fused-Module and SPMD step seams (instrumented VARIANT programs — the
plain program's compiled bytes stay identical and ``fused_compiles`` stays
flat across capture toggles), scan-carried per-layer transformer taps,
first-non-finite localization in topological order, nanguard forensics
replay on the abort path, and the quantization drift EWMA fed by the
serving stats twin."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, numerics, resilience, telemetry


@pytest.fixture(autouse=True)
def _numerics_off():
    def reset():
        config.unset("numerics.capture")
        config.unset("quant.drift_every")
        config.unset("quant.drift_threshold")
        config.set("resilience.nanguard", "")
        config.set("resilience.faults", "")
        resilience.reset_nanguard()
        numerics.reset()
        telemetry.reset()
    reset()
    yield
    reset()


# ------------------------------------------------------------- stats math

def test_summarize_fields():
    x = np.array([1.0, -3.0, 0.5, np.nan, np.inf], np.float32)
    s = numerics.stats_dict(numerics.summarize(jnp.asarray(x)))
    assert s["amax"] == pytest.approx(3.0)      # non-finites masked out
    assert s["amin"] == pytest.approx(0.5)      # smallest nonzero |finite|
    assert s["nonfinite"] == 2.0
    assert s["bf16_overflow"] == 0.0


def test_summarize_bf16_fractions():
    # 3.4e38 is a valid float32 past the bf16 max (~3.39e38): 2/4 overflow
    big = np.array([1.0, 3.4e38, 3.4e38, 1.0], np.float32)
    s = numerics.stats_dict(numerics.summarize(jnp.asarray(big)))
    assert s["bf16_overflow"] == pytest.approx(0.5)
    tiny = np.array([1.0, 1e-39, 1.0, 1.0], np.float32)  # 1/4 underflow
    s = numerics.stats_dict(numerics.summarize(jnp.asarray(tiny)))
    assert s["bf16_underflow"] == pytest.approx(0.25)


def test_summarize_all_finite_clean():
    s = numerics.stats_dict(numerics.summarize(jnp.ones((4, 4))))
    assert s["nonfinite"] == 0.0
    assert s["amax"] == 1.0 and s["rms"] == pytest.approx(1.0)


# ----------------------------------------------------- knob and cadence

def test_capture_knob_grammar():
    assert numerics.configure("") == 0
    assert numerics.configure("off") == 0
    assert numerics.configure("step:1") == 1
    assert numerics.configure("step:10") == 10
    for bad in ("step:0", "step:-3", "always", "step:x"):
        with pytest.raises(ValueError):
            numerics.configure(bad)


def test_capture_knob_rejected_value_reverts():
    config.set("numerics.capture", "step:2")
    with pytest.raises(ValueError):
        config.set("numerics.capture", "bogus")
    # reject-and-revert drops the override (the repo-wide knob pattern)
    assert config.get("numerics.capture") == ""


def test_capture_knob_is_epoch_neutral():
    """Toggling capture must NOT bump the config epoch — epoch-keyed
    program caches (fused step, embedding) would otherwise be
    evicted by an observability toggle."""
    e0 = config.epoch()
    config.set("numerics.capture", "step:4")
    config.unset("numerics.capture")
    config.set("quant.drift_every", 3)
    config.set("quant.drift_threshold", 2.0)
    assert config.epoch() == e0


def test_should_capture_cadence():
    config.set("numerics.capture", "step:3")
    got = [numerics.should_capture("t") for _ in range(7)]
    assert got == [True, False, False, True, False, False, True]
    # counter only advances while the knob is on
    config.unset("numerics.capture")
    assert not numerics.should_capture("t")
    config.set("numerics.capture", "step:3")
    assert not numerics.should_capture("t")  # resumes mid-cycle


def test_capture_token_off_is_empty():
    assert numerics.capture_token(False) == ()
    assert numerics.capture_token(True) == ("numerics",)


# ------------------------------------------------ collector and ordering

def test_tap_outside_collector_is_identity():
    x = jnp.ones(3)
    assert numerics.tap("nope", x) is x
    assert not numerics.collecting()


def test_collector_sites_and_topological_order():
    with numerics.collect() as sink:
        numerics.tap("a", jnp.ones(2))
        numerics.tap("b", jnp.full((2,), np.nan))
        numerics.tap("a", jnp.ones(2))          # dedup -> a#2
        numerics.tap("ids", jnp.ones(2, jnp.int32))  # int: skipped
    host = numerics.expand_stats(dict(sink))
    assert list(host) == ["a", "b", "a#2"]
    assert numerics.first_nonfinite(host) == "b"


def test_first_nonfinite_prefers_topological_order():
    # site registration order (trace order) wins over dict/name order
    with numerics.collect() as sink:
        numerics.tap("z_early", jnp.full((2,), np.inf))
        numerics.tap("a_late", jnp.full((2,), np.nan))
    host = numerics.expand_stats(dict(sink))
    assert numerics.first_nonfinite(host) == "z_early"


def test_publish_poll_latest():
    stats = {"s": numerics.summarize(jnp.ones(4))}
    numerics.publish("unit", 7, stats)
    numerics.poll("unit", block=True)
    step, host = numerics.latest("unit")
    assert step == 7 and "s" in host
    assert numerics.latest("missing") is None


def test_listener_fires_on_drain():
    seen = []
    numerics.add_listener(lambda src, step, host: seen.append((src, step)))
    try:
        numerics.publish("unit", 1, {"s": numerics.summarize(jnp.ones(2))})
        numerics.poll("unit", block=True)
    finally:
        numerics.remove_listener(numerics._LISTENERS[-1]
                                 if numerics._LISTENERS else (lambda: 0))
    assert ("unit", 1) in seen


# -------------------------------------------------- fused Module seam

def _mlp_softmax():
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    h = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(h, label, name="softmax")


def _fused_module(steps, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(64, 10)).astype(np.float32)
    Y = np.argmax(X[:, :3], axis=1).astype(np.float32)
    mod = mx.mod.Module(_mlp_softmax())
    mod.bind([("data", (16, 10))], [("softmax_label", (16,))])
    mod.init_params(mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    it = mx.io.NDArrayIter(X, Y, batch_size=16)
    done = 0
    while done < steps:
        for batch in it:
            if done == steps:
                break
            mod.train_step(batch)
            done += 1
        it.reset()
    return mod


def test_fused_module_capture_sites():
    prev = config.get("module.fused_step")
    config.set("module.fused_step", "on")
    config.set("numerics.capture", "step:1")
    try:
        _fused_module(3)
        numerics.poll("module", block=True)
        step, host = numerics.latest("module")
        assert step == 3
        sites = list(host)
        # forward op sites in topological order, then grads, then updates
        assert sites[:4] == ["fc1", "relu1", "fc2", "softmax"]
        assert "grad.fc1_weight" in sites and "update.fc2_bias" in sites
        for v in host.values():
            assert v.shape == (len(numerics.STAT_FIELDS),)
            assert v[3] == 0.0  # all finite
    finally:
        config.set("module.fused_step", prev)


def _program_text(compiled):
    """Optimized HLO without its source-location tables: from which line
    of this file a trace started is no part of the program (and whether
    those frames reach the text at all depends on how deep the stack
    below them happens to be)."""
    import re
    head, tables, body = compiled.as_text().partition("\n\nFileNames\n")
    if tables:
        body = body.split("\n\n\n", 1)[1]
    return head + re.sub(r" stack_frame_id=\d+", "", body)


def test_capture_off_byte_identical_and_compiles_flat():
    """The plain fused program compiled in a run that never captured and
    one compiled after capture toggles are byte-identical; toggling the
    knob neither evicts the plain program nor compiles a new one."""
    from mxnet_tpu import profiler
    prev = config.get("module.fused_step")
    config.set("module.fused_step", "on")
    try:
        mod_clean = _fused_module(2)
        (key_a, prog_a), = mod_clean._exec._fused_cache.items()
        text_a = _program_text(prog_a._compiled)

        # capture on: the instrumented VARIANT is a second cache entry
        config.set("numerics.capture", "step:1")
        mod_b = _fused_module(2, seed=1)
        c0 = profiler.counters().get("fused_compiles", 0)
        assert len(mod_b._exec._fused_cache) == 1  # instrumented only yet
        # toggle off: the next step builds/uses the PLAIN variant; the
        # instrumented one stays cached
        config.unset("numerics.capture")
        exec_b = mod_b._exec
        it = mx.io.NDArrayIter(np.zeros((16, 10), np.float32),
                               np.zeros((16,), np.float32), batch_size=16)
        mod_b.train_step(next(it))
        assert len(exec_b._fused_cache) == 2
        plain = [v for k, v in exec_b._fused_cache.items()
                 if "numerics" not in k]
        assert len(plain) == 1
        text_b = _program_text(plain[0]._compiled)
        assert text_a == text_b, "capture toggles changed the OFF program"

        # flat: re-toggling runs cached variants, zero new compiles
        c1 = profiler.counters().get("fused_compiles", 0)
        config.set("numerics.capture", "step:1")
        it.reset()
        mod_b.train_step(next(it))
        config.unset("numerics.capture")
        it.reset()
        mod_b.train_step(next(it))
        assert profiler.counters().get("fused_compiles", 0) == c1
        assert c1 == c0 + 1  # exactly the one plain build above
    finally:
        config.set("module.fused_step", prev)


# ------------------------------------------------------- SPMD seam

def _spmd_trainer(lr=0.01):
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.parallel.trainer import SPMDTrainer
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4), nn.Dense(1))
    net.initialize(mx.init.Xavier())
    return SPMDTrainer(net, L2Loss(), "sgd", {"learning_rate": lr})


def test_spmd_capture_sites_and_variant_cache():
    from mxnet_tpu import profiler
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, size=(16, 4)).astype(np.float32)
    y = x.sum(axis=1, keepdims=True).astype(np.float32)
    tr = _spmd_trainer()
    config.set("numerics.capture", "step:2")
    for _ in range(4):
        tr.step(x, y)
    numerics.poll("spmd", block=True)
    step, host = numerics.latest("spmd")
    assert step == 3  # steps 1 and 3 captured (first captured-era step)
    sites = list(host)
    assert sites[0] == "out" and sites[1] == "loss"
    assert any(s.startswith("grad.") for s in sites)
    assert any(s.startswith("update.") for s in sites)
    # two cached variants, keyed by the numerics token
    toks = {k[1] for k in tr._jitted}
    assert toks == {(), ("numerics",)}
    c0 = profiler.counters().get("fused_compiles", 0)
    tr.step(x, y)  # capture step -> cached instrumented variant
    tr.step(x, y)  # plain step -> cached plain variant
    assert profiler.counters().get("fused_compiles", 0) == c0


def test_transformer_scan_taps_per_layer():
    from mxnet_tpu.models.transformer import (TransformerLM,
                                              TransformerLMConfig)
    cfg = TransformerLMConfig(vocab_size=32, num_layers=3, d_model=16,
                              d_ff=32, num_heads=2, max_len=16,
                              dtype=jnp.float32)
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    toks = jnp.ones((2, 8), jnp.int32)
    with numerics.collect() as sink:
        lm.apply(params, toks)
    host = numerics.expand_stats(dict(sink))
    assert list(host) == ["layer_out[0]", "layer_out[1]", "layer_out[2]"]
    # the plain path is unaffected (no ambient collector)
    out = lm.apply(params, toks)
    assert out.shape == (2, 8, 32)


def test_transformer_unroll_mode_taps_match_scan():
    from mxnet_tpu.models.transformer import (TransformerLM,
                                              TransformerLMConfig)
    cfg = TransformerLMConfig(vocab_size=32, num_layers=2, d_model=16,
                              d_ff=32, num_heads=2, max_len=16,
                              dtype=jnp.float32)
    lm = TransformerLM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    toks = jnp.ones((2, 8), jnp.int32)
    with numerics.collect() as s_scan:
        lm.apply(params, toks)
    config.set("runtime.stack_mode", "unroll")
    try:
        with numerics.collect() as s_unroll:
            lm.apply(params, toks)
    finally:
        config.unset("runtime.stack_mode")
    a = numerics.expand_stats(dict(s_scan))
    b = numerics.expand_stats(dict(s_unroll))
    assert list(a) == list(b)
    for site in a:
        np.testing.assert_allclose(a[site], b[site], rtol=1e-5, atol=1e-6)


def test_embedding_lookup_capture():
    from mxnet_tpu.parallel.embedding import ShardedEmbedding
    config.set("numerics.capture", "step:1")
    emb = ShardedEmbedding(32, 8)
    emb.lookup(np.array([[1, 2, 3, 1]], np.int32))
    numerics.poll("embedding", block=True)
    _, host = numerics.latest("embedding")
    assert "embedding.rows" in host


def test_gluon_eager_capture():
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer, nn
    from mxnet_tpu.gluon.loss import L2Loss
    config.set("numerics.capture", "step:1")
    net = nn.Dense(4, in_units=3)
    net.initialize(mx.init.Xavier())
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    x = mx.nd.array(np.ones((8, 3), np.float32))
    y = mx.nd.array(np.zeros((8, 4), np.float32))
    with autograd.record():
        loss = L2Loss()(net(x), y)
    loss.backward()
    tr.step(8)
    numerics.poll("gluon", block=True)
    _, host = numerics.latest("gluon")
    assert any(s.startswith("grad.") for s in host)
    assert any(s.startswith("update.") for s in host)


# ------------------------------------------------- nanguard forensics

def test_spmd_nanguard_abort_runs_forensics():
    config.set("resilience.nanguard", "abort")
    config.set("resilience.faults", "nan:1@step=2")
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, size=(16, 4)).astype(np.float32)
    y = x.sum(axis=1, keepdims=True).astype(np.float32)
    tr = _spmd_trainer()
    with pytest.raises(resilience.NonFiniteStepError):
        for _ in range(6):
            tr.step(x, y)
            resilience.poll_streaks(block=True)
    recs = numerics.forensics_records()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["source"] == "spmd"
    # the loss-path stats: "out" is the first site in topological order
    assert rec["first_nonfinite_site"] == "out"
    assert "out" in rec["nonfinite_sites"]
    snap = telemetry.snapshot()
    assert snap["gauges"]["numerics.first_nonfinite_site.spmd"] == "out"


def test_forensics_without_replay_is_noop():
    assert numerics.run_forensics("nothing-held") is None
    assert numerics.forensics_records() == []


# ------------------------------------------------- quantization drift

def test_update_quant_drift_ewma_and_trip():
    thresholds = {"fc_0": 1.0, "fc_1": 2.0}
    ewma = {}
    # sample at the calibrated range: no trip
    drifted = numerics.update_quant_drift(
        "m", ("fc_0", "fc_1"), np.array([1.0, 2.0]), thresholds, ewma,
        threshold_ratio=1.5)
    assert drifted == []
    trips0 = telemetry.counter("quant.drift_trips").value
    # sustained 3x on fc_0 pushes its EWMA past the threshold
    for _ in range(8):
        drifted = numerics.update_quant_drift(
            "m", ("fc_0", "fc_1"), np.array([3.0, 2.0]), thresholds, ewma,
            threshold_ratio=1.5)
    assert drifted == ["fc_0"]
    # a trip is edge-triggered: one counter bump, not one per sample
    assert telemetry.counter("quant.drift_trips").value == trips0 + 1
    snap = telemetry.snapshot()
    assert snap["gauges"]["quant.drift_ratio.m.fc_0"] > 1.5
    assert snap["gauges"]["quant.drift_ratio.m.fc_1"] == pytest.approx(
        1.0, abs=1e-6)


def test_update_quant_drift_skips_uncalibrated_sites():
    ewma = {}
    drifted = numerics.update_quant_drift(
        "m", ("a", "b"), np.array([9.0, 9.0]), {"a": 0.0}, ewma,
        threshold_ratio=1.5)
    assert drifted == [] and ewma == {}


def test_obs_renders_drift_gauge_with_two_labels():
    from mxnet_tpu import obs
    telemetry.gauge("quant.drift_ratio.mymodel.fc_0").set(1.25)
    text = obs.render_prometheus()
    assert ('mxnet_tpu_quant_drift_ratio{model="mymodel",site="fc_0"} 1.25'
            in text)


def test_telemetry_report_quant_drift_anomaly():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import telemetry_report
    recs = [{"event": "quant_drift", "model": "m", "site": "fc_0",
             "ratio": 2.5, "threshold": 1.5},
            {"event": "quant_drift", "model": "m", "site": "fc_0",
             "ratio": 1.9, "threshold": 1.5}]
    summ = telemetry_report.summarize(recs)
    drift = [a for a in summ["anomalies"] if a["kind"] == "quant_drift"]
    assert len(drift) == 1
    assert "2.500x" in drift[0]["detail"]
    assert summ["other_events"] == 0


def test_export_quantized_ships_stats_twin(tmp_path):
    import json
    import os
    from mxnet_tpu import gluon, quantization
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    rng = np.random.RandomState(0)
    batches = [rng.uniform(-1, 1, size=(8, 6)).astype(np.float32)
               for _ in range(3)]
    cal = quantization.calibrate(net, batches)
    prefix = str(tmp_path / "twin")
    paths = quantization.export_quantized(net, prefix, cal)
    assert prefix + "-stats.stablehlo" in paths
    meta = json.load(open(prefix + "-meta.json"))
    assert meta["stats_sites"] == ["FullyConnected_0", "FullyConnected_1"]
    assert all(os.path.exists(p) for p in paths)


def test_serving_drift_probe_end_to_end(tmp_path):
    from mxnet_tpu import gluon, quantization, serving
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    rng = np.random.RandomState(0)
    batches = [rng.uniform(-1, 1, size=(8, 6)).astype(np.float32)
               for _ in range(3)]
    cal = quantization.calibrate(net, batches)
    prefix = str(tmp_path / "drift")
    quantization.export_quantized(net, prefix, cal)
    config.set("quant.drift_every", 1)
    srv = serving.Server(max_batch=8, max_queue_delay_ms=2.0)
    try:
        srv.register("drifty", prefix, quantized=True)
        srv.start()
        for _ in range(2):
            srv.predict("drifty",
                        rng.uniform(-1, 1, size=(4, 6)).astype(np.float32),
                        timeout=30)
        snap = telemetry.snapshot()
        in_range = [k for k in snap["gauges"] if k.startswith(
            "quant.drift_ratio.drifty.")]
        assert in_range, snap["gauges"]
        trips0 = telemetry.counter("quant.drift_trips").value
        for _ in range(8):
            srv.predict("drifty",
                        rng.uniform(-10, 10,
                                    size=(4, 6)).astype(np.float32),
                        timeout=30)
        assert telemetry.counter("quant.drift_trips").value > trips0
        entry = srv._models["drifty"]
        assert entry.drift_sites and entry.drift_ewma
    finally:
        srv.stop()


# ------------------------------------------------------- tool smoke

def test_check_numerics_smoke():
    """Subprocess wiring for tools/check_numerics.py — capture taps,
    NaN localization, and the drift flip must hold from a clean
    interpreter, exactly how CI invokes it."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "check_numerics.py")],
        capture_output=True, text=True, timeout=180, env=env, cwd=root)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["nanguard"]["first_nonfinite"] == "layer_out[1]", report
    assert report["drift"]["trips"] >= 1, report
    assert report["drift"]["drifted_gauges"], report
