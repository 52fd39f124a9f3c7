"""mx.serving continuous batching: batched rows equal to unbatched predict,
bucket-bounded compiles, batching policy (coalescing window, cap-filled
immediate dispatch), graceful drain, LRU model table, fixed-batch
artifacts, oversized-request chunking, telemetry-report serving table +
queue-delay anomaly, and the tools/check_serving.py smoke as a subprocess.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import deploy, gluon, serving, telemetry

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
    prefix = str(tmp_path_factory.mktemp("serving") / "mlp")
    net = _mlp()
    example = mx.nd.random.uniform(shape=(8, FEATURES))
    net(example)
    deploy.export_model(net, prefix, example)
    return prefix


def _reqs(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.uniform(size=(s, FEATURES)).astype(np.float32)
            for s in sizes]


def test_concurrent_ragged_rows_and_flat_compiles(artifact):
    """Rows served from padded buckets equal the unbatched predict to f32
    rounding — a bucket is another program shape than the request's own,
    and XLA promises no two shapes the same bits."""
    pred = deploy.StableHLOPredictor(artifact)
    srv = serving.Server(max_batch=8, max_queue_delay_ms=3.0)
    srv.register("m", artifact)
    c0 = telemetry.counter("serving.compiles").value
    srv.start()
    try:
        buckets = srv._models["m"].buckets
        assert buckets == (1, 2, 4, 8)  # pow2 policy of max_batch
        assert telemetry.counter("serving.compiles").value - c0 == \
            len(buckets)
        per_thread = [_reqs((1, 3, 2, 5, 8, 4), seed=t) for t in range(3)]
        expect = [[pred.predict(a) for a in reqs] for reqs in per_thread]
        got = [None] * len(per_thread)

        def worker(t):
            futs = [srv.submit("m", a) for a in per_thread[t]]
            got[t] = [f.result(timeout=30) for f in futs]

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(len(per_thread))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for rs, es in zip(got, expect):
            for r, e in zip(rs, es):
                np.testing.assert_allclose(r, e, rtol=1e-5, atol=1e-7)
        # ragged traffic never reached the compiler
        assert telemetry.counter("serving.compiles").value - c0 == \
            len(buckets)
    finally:
        srv.stop()


def test_queue_delay_coalesces_into_one_dispatch(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=250.0)
    srv.register("m", artifact)
    srv.start()
    try:
        d0 = telemetry.counter("serving.batch_dispatches").value
        futs = [srv.submit("m", a) for a in _reqs((2, 3, 2))]
        for f in futs:
            f.result(timeout=30)
        # all three waited out the window together in ONE bucketed batch
        assert telemetry.counter("serving.batch_dispatches").value - d0 == 1
    finally:
        srv.stop()


def test_full_batch_dispatches_before_deadline(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=10_000.0)
    srv.register("m", artifact)
    srv.start()
    try:
        t0 = time.perf_counter()
        futs = [srv.submit("m", a) for a in _reqs((4, 4))]
        for f in futs:
            f.result(timeout=30)
        # rows == max_batch fills the bucket: no waiting out the window
        assert time.perf_counter() - t0 < 5.0
    finally:
        srv.stop()


def test_stop_drains_and_rejects_new_submits(artifact):
    srv = serving.Server(max_batch=8, max_queue_delay_ms=50.0)
    srv.register("m", artifact)
    srv.start()
    futs = [srv.submit("m", a) for a in _reqs((1, 2, 3, 1, 2))]
    srv.stop()
    for f in futs:
        assert f.result(timeout=5).shape[1] == 4
    with pytest.raises(serving.ServingError):
        srv.submit("m", _reqs((1,))[0])


def test_oversized_request_chunks_bitwise(artifact):
    pred = deploy.StableHLOPredictor(artifact)
    srv = serving.Server(max_batch=4, max_queue_delay_ms=1.0)
    srv.register("m", artifact)
    srv.start()
    try:
        big = _reqs((11,), seed=9)[0]
        assert np.array_equal(srv.predict("m", big, timeout=30),
                              pred.predict(big))
    finally:
        srv.stop()


def test_lru_eviction_bounds_the_model_table(artifact, tmp_path):
    prefixes = {}
    for name in ("a", "b", "c"):
        prefixes[name] = str(tmp_path / name)
        net = _mlp(seed=ord(name))
        example = mx.nd.random.uniform(shape=(4, FEATURES))
        net(example)
        deploy.export_model(net, prefixes[name], example)
    srv = serving.Server(max_batch=4, max_queue_delay_ms=1.0, max_models=2)
    srv.register("a", prefixes["a"])
    srv.register("b", prefixes["b"])
    srv._entry("a")  # LRU touch: b is now least recently used
    srv.register("c", prefixes["c"])
    assert srv.models() == ["a", "c"]
    srv.start()
    try:
        with pytest.raises(serving.ServingError, match="unknown model"):
            srv.submit("b", _reqs((1,))[0])
        # evicted models re-register cleanly
        srv.register("b", prefixes["b"])
        assert srv.predict("b", _reqs((2,))[0], timeout=30).shape == (2, 4)
    finally:
        srv.stop()


def test_fixed_batch_artifact_serves_via_single_bucket(artifact, tmp_path):
    prefix = str(tmp_path / "fixed")
    net = _mlp(seed=17)
    example = mx.nd.random.uniform(shape=(4, FEATURES))
    net(example)
    deploy.export_model(net, prefix, example, dynamic_batch=False)
    pred = deploy.StableHLOPredictor(prefix)
    assert not pred.dynamic_batch
    srv = serving.Server(max_batch=16, max_queue_delay_ms=1.0)
    srv.register("fixed", prefix)
    srv.start()
    try:
        # the one exported shape IS the bucket set; smaller requests pad
        assert srv._models["fixed"].buckets == (4,)
        x = _reqs((2,), seed=21)[0]
        assert np.array_equal(srv.predict("fixed", x, timeout=30),
                              pred.predict(np.concatenate([x, x]))[:2])
    finally:
        srv.stop()


def test_submit_validates_shape_and_dtype(artifact):
    srv = serving.Server(max_batch=4, max_queue_delay_ms=1.0)
    srv.register("m", artifact)
    srv.start()
    try:
        with pytest.raises(ValueError, match="item shape"):
            srv.submit("m", np.zeros((2, FEATURES + 1), np.float32))
        with pytest.raises(ValueError, match="dtype"):
            srv.submit("m", np.zeros((2, FEATURES), np.float64))
        with pytest.raises(serving.ServingError, match="unknown model"):
            srv.submit("nope", np.zeros((2, FEATURES), np.float32))
    finally:
        srv.stop()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(artifact, tmp_path,
                                              monkeypatch, from_env):
    """Server.start() turns on jax's persistent compile cache: in the
    directory JAX_COMPILATION_CACHE_DIR names (no directory set in code),
    else at the one fixed path inside the checkout — never a mkdtemp."""
    import glob
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from mxnet_tpu import runtime
    cache = str(tmp_path / "xla_cache")
    fixed = str(tmp_path / "checkout" / ".jax_cache")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    if from_env:
        # jax reads the variable when it is imported; a process started
        # with it set sees exactly this config value
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
        jax.config.update("jax_compilation_cache_dir", cache)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(runtime, "cache_root", lambda: fixed)
    # the test's bucket programs are tiny: lift jax's floors so they
    # are written at all (the floors are jax's own, not this repo's)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    monkeypatch.setattr(runtime, "_CACHE_CONFIGURED", [None])
    try:
        srv = serving.Server(max_batch=4, max_queue_delay_ms=1.0)
        srv.register("m", artifact)
        srv.start()
        try:
            srv.predict("m", np.zeros((2, FEATURES), np.float32),
                        timeout=30)
        finally:
            srv.stop()
        want = cache if from_env else fixed
        assert jax.config.jax_compilation_cache_dir == want
        assert runtime.configure_compile_cache() == want
        # one persisted XLA binary per bucket program (1, 2, 4)
        assert len(glob.glob(os.path.join(want, "*-cache"))) >= 3
        other = fixed if from_env else cache
        assert not os.path.exists(other)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_default_cache_root_is_fixed_and_inside_the_checkout():
    from mxnet_tpu import runtime
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert runtime.cache_root() == os.path.join(root, ".jax_cache")
    ignored = open(os.path.join(root, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_register_rejects_paramless_artifact(artifact, tmp_path):
    prefix = str(tmp_path / "noparams")
    net = _mlp(seed=23)
    example = mx.nd.random.uniform(shape=(2, FEATURES))
    net(example)
    deploy.export_model(net, prefix, example, include_params=False)
    srv = serving.Server()
    with pytest.raises(serving.ServingError, match="include_params"):
        srv.register("noparams", prefix)


# --------------------------------------------- telemetry report serving
def _serving_rec(model="m", qd=1.0, budget=2.0, **kw):
    rec = {"event": "serving", "model": model, "requests": 3, "rows": 6,
           "bucket": 8, "fill": 0.75, "queue_delay_ms": qd,
           "wall_ms": 0.5, "budget_ms": budget}
    rec.update(kw)
    return rec


def test_report_serving_table():
    s = telemetry_report.summarize(
        [_serving_rec(qd=0.1 * i) for i in range(12)])
    t = s["serving"]["m"]
    assert t["dispatches"] == 12 and t["requests"] == 36
    assert t["buckets"] == [8] and t["fill_mean"] == 0.75
    assert t["queue_delay_ms_p99"] == 1.1
    assert s["other_events"] == 0
    assert s["anomalies"] == []


def test_report_queue_delay_anomaly():
    # p99 queue delay way past the batching budget across >= 10 dispatches
    recs = [_serving_rec(qd=50.0, budget=2.0) for _ in range(12)]
    s = telemetry_report.summarize(recs)
    kinds = {a["kind"] for a in s["anomalies"]}
    assert "queue_delay_blowup" in kinds
    # delays inside the budget (or under the floor) never flag
    ok = telemetry_report.summarize(
        [_serving_rec(qd=1.5, budget=2.0) for _ in range(12)])
    assert ok["anomalies"] == []


def test_report_render_includes_serving(capsys):
    out = telemetry_report.render(telemetry_report.summarize(
        [_serving_rec() for _ in range(3)]))
    assert "qd_p99ms" in out and "m " in out


# ------------------------------------------------------- smoke wrapper
def test_check_serving_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "check_serving.py")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["rows"]["mismatches"] == 0
    assert report["compiles"]["compiled"] == \
        len(report["compiles"]["buckets"])
    assert report["drain"]["drained"] == report["drain"]["queued"]
