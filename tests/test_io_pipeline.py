"""Device-resident input pipeline: staging helpers, bucketed padding,
DevicePrefetcher ring semantics, zero caller-thread H2D in steady state,
pad-masked training equivalence, Module recompile regression, prefetch
worker shutdown robustness, telemetry/report wiring, and the
tools/check_io_pipeline.py smoke as a subprocess.
"""
import json
import os
import queue
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, telemetry
from mxnet_tpu import io as mio

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import telemetry_report  # noqa: E402


@pytest.fixture(autouse=True)
def _io_defaults():
    """Each test starts from the default pipeline knobs and a zeroed
    telemetry registry (counters here are the assertions' substrate)."""
    telemetry.reset()
    yield
    config.set("io.device_prefetch", True)
    config.set("io.pad_buckets", "pow2")
    config.set("io.prefetch_depth", 2)
    config.set("io.decode_workers", 0)
    config.set("resilience.faults", "")
    telemetry.reset()


def _ragged_iter(rows=28, batch=8, features=6, seed=0):
    """Raw-numpy host iterator with a ragged final batch (rows % batch)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    Y = rng.randn(rows).astype(np.float32)

    class RawIter(mio.DataIter):
        def __init__(self):
            super().__init__(batch)
            self.pos = 0

        def reset(self):
            self.pos = 0

        def next(self):
            if self.pos >= rows:
                raise StopIteration
            d = X[self.pos:self.pos + batch]
            l = Y[self.pos:self.pos + batch]
            self.pos += batch
            return mio.DataBatch([d], [l], pad=0)

    return RawIter()


# ------------------------------------------------------- staging helpers
def test_is_staged_and_ensure_staged_passthrough():
    host = np.ones((4, 3), np.float32)
    assert not mio.is_staged(host)
    staged = mio.ensure_staged(host)
    assert isinstance(staged, jax.Array)
    assert mio.is_staged(staged)
    before = telemetry.counter("io.h2d_sync").value
    again = mio.ensure_staged(staged)
    assert again is staged  # already placed: zero copies, zero counters
    assert telemetry.counter("io.h2d_sync").value == before
    # NDArray payloads unwrap to their device array
    nd = mx.nd.array(host)
    assert mio.is_staged(nd)
    assert isinstance(mio.ensure_staged(nd), jax.Array)


def test_ensure_staged_counts_sync_by_source():
    host = np.zeros((2, 2), np.float32)
    mio.ensure_staged(host, source="spmd")
    mio.ensure_staged(host, source="spmd")
    mio.ensure_staged(host, source="module")
    assert telemetry.counter("io.h2d_sync").value == 3
    assert telemetry.counter("io.h2d_sync.spmd").value == 2
    assert telemetry.counter("io.h2d_sync.module").value == 1
    assert telemetry.counter("io.staged_bytes").value >= 3 * host.nbytes


def test_ensure_staged_places_on_requested_device():
    dev = jax.devices()[0]
    out = mio.ensure_staged(np.ones((2, 2), np.float32), placement=dev)
    assert out.devices() == {dev}
    assert mio.is_staged(out, dev)
    # lazy callable placement resolves at staging time
    out2 = mio.ensure_staged(np.ones(3, np.float32), placement=lambda: dev)
    assert out2.devices() == {dev}


def test_bucket_sizes_policies():
    assert mio._bucket_sizes("off", 8) == ()
    assert mio._bucket_sizes("none", 8) == ()
    assert mio._bucket_sizes("", 8) == ()
    assert mio._bucket_sizes("full", 8) == (8,)
    assert mio._bucket_sizes("pow2", 8) == (1, 2, 4, 8)
    assert mio._bucket_sizes("pow2", 6) == (1, 2, 4, 6)
    with pytest.raises(ValueError):
        mio._bucket_sizes("fibonacci", 8)


def test_repad_descs_both_forms():
    descs = [mio.DataDesc("data", (5, 3), np.float32, "NC"),
             ("label", (5,))]
    out = mio.DevicePrefetcher._repad_descs(descs, 8)
    assert out[0] == mio.DataDesc("data", (8, 3), np.float32, "NC")
    assert out[1][0] == "label" and tuple(out[1][1]) == (8,)
    assert mio.DevicePrefetcher._repad_descs(None, 8) is None


# ------------------------------------------------- DevicePrefetcher ring
def test_device_prefetcher_pads_ragged_tail_full():
    dp = mio.DevicePrefetcher(_ragged_iter(), buckets="full")
    batches = list(dp)
    assert len(batches) == 4
    shapes = {tuple(b.data[0].shape) for b in batches}
    assert shapes == {(8, 6)}, shapes  # one shape for the whole epoch
    assert [b.pad for b in batches] == [0, 0, 0, 4]
    # wrap-pad fill rows repeat the batch's own leading rows
    tail = np.asarray(batches[-1].data[0])
    np.testing.assert_array_equal(tail[4:], tail[:4])
    # the padded tail shape was already seen -> a recompile was avoided
    assert telemetry.counter("io.pad_recompiles_avoided").value >= 1


def test_device_prefetcher_pow2_buckets():
    # 21 rows @ batch 8 -> 8, 8, then a 5-row tail padded up to bucket 8
    dp = mio.DevicePrefetcher(_ragged_iter(rows=21), buckets="pow2")
    batches = list(dp)
    assert [tuple(b.data[0].shape)[0] for b in batches] == [8, 8, 8]
    assert [b.pad for b in batches] == [0, 0, 3]
    # 20 rows -> the 4-row tail IS a pow2 bucket: no padding needed
    dp = mio.DevicePrefetcher(_ragged_iter(rows=20), buckets="pow2")
    assert [b.pad for b in dp] == [0, 0, 0]


def test_device_prefetcher_buckets_off_keeps_ragged_shape():
    dp = mio.DevicePrefetcher(_ragged_iter(), buckets="off")
    batches = list(dp)
    assert batches[-1].data[0].shape[0] == 4
    assert batches[-1].pad == 0


def test_device_prefetcher_stages_to_placement():
    dev = jax.devices()[0]
    dp = mio.DevicePrefetcher(_ragged_iter(), placement=dev, buckets="full")
    batches = list(dp)
    for b in batches:
        assert isinstance(b.data[0], jax.Array)
        assert mio.is_staged(b.data[0], dev)
        assert mio.is_staged(b.label[0], dev)
    assert telemetry.counter("io.h2d_async").value == 8  # 4 data + 4 label
    assert telemetry.counter("io.h2d_sync").value == 0  # all off-thread


def test_device_prefetch_off_still_pads_host_side():
    config.set("io.device_prefetch", False)
    dp = mio.DevicePrefetcher(_ragged_iter(), buckets="full")
    batches = list(dp)
    assert all(isinstance(b.data[0], np.ndarray) for b in batches)
    assert batches[-1].data[0].shape == (8, 6)  # padding still applies
    assert batches[-1].pad == 4
    assert telemetry.counter("io.h2d_async").value == 0


def test_device_prefetcher_reset_joins_worker():
    leaked0 = telemetry.counter("io.prefetch_thread_leaked").value
    dp = mio.DevicePrefetcher(_ragged_iter(), buckets="full")
    seen = 0
    for _ in dp:  # abandon the epoch with the ring still live
        seen += 1
        if seen == 2:
            break
    dp.reset()
    assert sum(1 for _ in dp) == 4
    dp.reset()
    assert sum(1 for _ in dp) == 4
    assert telemetry.counter("io.prefetch_thread_leaked").value == leaked0


def test_device_prefetcher_lazy_placement_resolves_late():
    """A lazy placement callable that returns None is re-invoked on later
    batches instead of cached (regression: None was frozen at the first
    batch and every batch silently staged to the default device)."""
    dev = jax.devices()[0]
    calls = {"n": 0}

    def placement():
        calls["n"] += 1
        return None if calls["n"] == 1 else dev

    dp = mio.DevicePrefetcher(_ragged_iter(), placement=placement,
                              buckets="full")
    batches = list(dp)
    assert len(batches) == 4
    # first worker iteration saw None: that batch stays host-side so the
    # consumer stages it to the REAL device (no default-device detour)
    assert isinstance(batches[0].data[0], np.ndarray)
    for b in batches[1:]:
        assert isinstance(b.data[0], jax.Array)
        assert mio.is_staged(b.data[0], dev)
    assert calls["n"] == 2  # resolved on batch 2, then cached


def test_device_prefetcher_reset_refuses_leaked_worker(monkeypatch):
    dp = mio.DevicePrefetcher(_ragged_iter(), buckets="full")
    next(iter(dp))
    monkeypatch.setattr(mio, "_shutdown_prefetch_worker",
                        lambda *a, **k: False)
    with pytest.raises(RuntimeError, match="refusing"):
        dp.reset()
    dp._stop.set()  # let the (healthy) worker wind down


def test_prefetching_iter_reset_refuses_leaked_worker(monkeypatch):
    X = np.zeros((8, 2), np.float32)
    pf = mio.PrefetchingIter(mx.io.NDArrayIter(X, np.zeros(8, np.float32),
                                               batch_size=4))
    next(iter(pf))
    monkeypatch.setattr(mio, "_shutdown_prefetch_worker",
                        lambda *a, **k: False)
    with pytest.raises(RuntimeError, match="refusing"):
        pf.reset()
    pf._stop.set()


def test_pad_failure_counts_fallback(monkeypatch):
    """A dense batch that fails to wrap-pad passes through at natural
    shape but is COUNTED (io.pad_fallback), never silently swallowed."""
    def boom(self, arr, target):
        raise ValueError("synthetic pad failure")

    monkeypatch.setattr(mio.DevicePrefetcher, "_pad_rows", boom)
    config.set("io.device_prefetch", False)
    dp = mio.DevicePrefetcher(_ragged_iter(), buckets="full")
    batches = list(dp)
    # only the 4-row ragged tail attempts padding; it falls back unpadded
    assert batches[-1].data[0].shape[0] == 4
    assert batches[-1].pad == 0
    assert telemetry.counter("io.pad_fallback").value == 1


def test_device_prefetcher_worker_exception_propagates():
    class BoomIter(mio.DataIter):
        def __init__(self):
            super().__init__(4)
            self.n = 0

        def next(self):
            self.n += 1
            if self.n > 1:
                raise RuntimeError("decode exploded")
            return mio.DataBatch([np.zeros((4, 2), np.float32)], pad=0)

    dp = mio.DevicePrefetcher(BoomIter(), buckets="off")
    it = iter(dp)
    next(it)  # first batch is fine
    with pytest.raises(RuntimeError, match="decode exploded"):
        next(it)  # the failure surfaces instead of hanging the consumer


def test_shutdown_leak_path_surfaces_stuck_worker():
    release = threading.Event()
    stuck = threading.Thread(target=release.wait, daemon=True)
    stuck.start()
    before = telemetry.counter("io.prefetch_thread_leaked").value
    ok = mio._shutdown_prefetch_worker(stuck, threading.Event(),
                                       queue.Queue(), deadline_s=0.3)
    assert ok is False
    assert telemetry.counter("io.prefetch_thread_leaked").value == before + 1
    release.set()
    stuck.join(timeout=5)


def test_prefetching_iter_depth_knob_and_reset():
    config.set("io.prefetch_depth", 3)
    X = np.arange(40, dtype=np.float32).reshape(20, 2)
    Y = np.arange(20, dtype=np.float32)
    pf = mio.PrefetchingIter(mx.io.NDArrayIter(X, Y, batch_size=4))
    assert pf._queue.maxsize == 3  # depth defaults from the config knob
    consumed = 0
    for _ in pf:  # partial consumption, then a mid-stream reset
        consumed += 1
        if consumed == 2:
            break
    pf.reset()
    assert sum(1 for _ in pf) == 5
    pf.reset()
    assert sum(1 for _ in pf) == 5


# ------------------------------------------------ trainer integration
def _mini_net_and_trainer(seed=11, lr=0.05, mesh=None):
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import SPMDTrainer

    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()

    def l2(out, label):
        return ((out - label.reshape((-1, 1))) ** 2).mean(axis=1)

    tr = SPMDTrainer(net, l2, "sgd", {"learning_rate": lr}, mesh=mesh)
    mx.random.seed(seed)
    return net, tr


def test_spmd_steady_state_zero_sync_h2d():
    """The acceptance-criteria assertion: with device prefetch on, fused
    steps perform ZERO synchronous device_put on the caller thread."""
    _, tr = _mini_net_and_trainer()
    dp = mio.DevicePrefetcher(_ragged_iter(),
                              placement=lambda: tr.batch_sharding,
                              buckets="full")
    syncs = []
    for b in dp:
        before = telemetry.counter("io.h2d_sync").value
        tr.step(b.data[0], b.label[0], pad=b.pad)
        syncs.append(telemetry.counter("io.h2d_sync").value - before)
    assert syncs == [0, 0, 0, 0], syncs
    assert telemetry.counter("io.h2d_async").value > 0


def test_spmd_padded_masked_matches_unpadded_bitwise():
    """Bucketed padding + static pad masking is numerically INVISIBLE:
    loss and updated params match the unpadded step bitwise on CPU."""
    rng = np.random.RandomState(4)
    # 8 valid rows (divides the conftest dp mesh) wrap-padded to 16
    data = rng.randn(8, 6).astype(np.float32)
    label = rng.randn(8).astype(np.float32)
    idx = np.arange(8) % 8
    padded_d = np.concatenate([data, data[idx]], axis=0)
    padded_l = np.concatenate([label, label[idx]], axis=0)

    from mxnet_tpu.parallel import data_parallel_mesh

    def run(d, l, pad):
        # each run is fully sequential: deferred gluon param init draws
        # values at the first step, so seeding must bracket construction
        # AND stepping for the two runs to share an RNG stream.  Single
        # device: pad rows contribute exact zeros to the grad reduction,
        # so params stay bitwise; multi-device partial sums regroup.
        _, tr = _mini_net_and_trainer(
            mesh=data_parallel_mesh(jax.devices()[:1]))
        losses = [float(tr.step(d, l, pad=pad)) for _ in range(3)]
        params = [np.asarray(v._data if hasattr(v, "_data") else v)
                  for _, v in sorted(tr.params.items())]
        return losses, params

    ref_losses, ref_params = run(data, label, 0)
    pad_losses, pad_params = run(padded_d, padded_l, 8)
    assert [np.float32(x).tobytes() for x in pad_losses] == \
        [np.float32(x).tobytes() for x in ref_losses]
    for a, b in zip(pad_params, ref_params):
        assert a.tobytes() == b.tobytes()


def test_spmd_pad_requires_per_sample_loss():
    from mxnet_tpu.parallel import SPMDTrainer
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    net = nn.Dense(2, in_units=3)
    net.initialize()
    # a loss that pre-reduces to a scalar cannot be pad-masked
    tr = SPMDTrainer(net, lambda o, l: ((o - l) ** 2).mean(), "sgd",
                     {"learning_rate": 0.1})
    with pytest.raises(ValueError, match="per-sample"):
        tr.step(np.zeros((8, 3), np.float32),
                np.zeros((8, 2), np.float32), pad=1)


def test_spmd_compiles_one_program_per_pad_bucket():
    from mxnet_tpu import profiler
    _, tr = _mini_net_and_trainer()
    d = np.zeros((8, 6), np.float32)
    l = np.zeros(8, np.float32)
    profiler.reset_counters()
    tr.step(d, l, pad=0)
    tr.step(d, l, pad=0)
    assert profiler.counters()["fused_compiles"] == 1
    tr.step(d, l, pad=3)  # new static pad -> one more program
    tr.step(d, l, pad=3)  # ...cached after that
    assert profiler.counters()["fused_compiles"] == 2


def test_module_ragged_tail_recompile_regression():
    """fused_compiles stays flat across an epoch ending in a partial batch
    when the DevicePrefetcher buckets it; without bucketing the ragged
    tail costs a second compile."""
    from mxnet_tpu import profiler

    def run_epochs(buckets):
        prev = config.get("module.fused_step")
        config.set("module.fused_step", "auto")
        try:
            rng = np.random.RandomState(2)
            X = rng.randn(40, 10).astype(np.float32)
            Y = (rng.rand(40) * 3).astype(np.float32)

            class RawIter(mio.DataIter):
                def __init__(self):
                    super().__init__(16)
                    self.pos = 0

                def reset(self):
                    self.pos = 0

                def next(self):
                    if self.pos >= 40:
                        raise StopIteration
                    d = X[self.pos:self.pos + 16]
                    l = Y[self.pos:self.pos + 16]
                    self.pos += 16
                    return mio.DataBatch([d], [l], pad=0)

            mod = mx.mod.Module(_mlp())
            mod.bind([("data", (16, 10))], [("softmax_label", (16,))])
            mod.init_params(initializer=None, arg_params=_mlp_params())
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.05})
            profiler.reset_counters()
            dp = mio.DevicePrefetcher(RawIter(), buckets=buckets)
            for epoch in range(2):
                if epoch:
                    dp.reset()
                for batch in dp:
                    mod.train_step(batch)
            return profiler.counters()
        finally:
            config.set("module.fused_step", prev)

    c = run_epochs("full")
    assert c["fused_compiles"] == 1, c  # 2 epochs x (2 full + 1 padded)
    assert c["fused_steps"] == 6, c
    c = run_epochs("off")
    assert c["fused_compiles"] == 2, c  # the ragged tail retraced


def _mlp():
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    h = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=3, name="fc2")
    return mx.sym.SoftmaxOutput(h, label, name="softmax")


def _mlp_params(seed=7):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": mx.nd.array(rng.randn(32, 10).astype(np.float32)
                                      * 0.1),
            "fc1_bias": mx.nd.array(np.zeros(32, np.float32)),
            "fc2_weight": mx.nd.array(rng.randn(3, 32).astype(np.float32)
                                      * 0.1),
            "fc2_bias": mx.nd.array(np.zeros(3, np.float32))}


def test_gluon_trainer_batch_placement():
    from mxnet_tpu.gluon import Trainer, nn
    mx.random.seed(0)
    net = nn.Dense(2, in_units=3)
    net.initialize()
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    net(mx.nd.array(np.zeros((2, 3), np.float32)))  # materialize params
    placement = tr.batch_placement()
    assert placement is not None
    staged = mio.ensure_staged(np.zeros((2, 3), np.float32), placement)
    assert mio.is_staged(staged, placement)


# ------------------------------------------------ telemetry + reporting
def test_step_record_carries_h2d_sync(tmp_path):
    log = tmp_path / "steps.jsonl"
    config.set("telemetry.sink", "jsonl:%s" % log)
    try:
        _, tr = _mini_net_and_trainer()
        host_d = np.zeros((8, 6), np.float32)
        host_l = np.zeros(8, np.float32)
        tr.step(host_d, host_l)  # host numpy: sync-staged on this thread
        dp = mio.DevicePrefetcher(_ragged_iter(rows=8),
                                  placement=lambda: tr.batch_sharding,
                                  buckets="full")
        for b in dp:
            tr.step(b.data[0], b.label[0], pad=b.pad)  # pre-staged
    finally:
        config.set("telemetry.sink", "")
    recs = [json.loads(line) for line in log.read_text().splitlines()]
    steps = [r for r in recs if r.get("event") == "step"]
    assert steps[0]["h2d_sync"] == 2  # data + label staged synchronously
    assert steps[-1]["h2d_sync"] == 0  # device-resident batch
    for r in steps:
        telemetry.validate_step_record(r)


def _rec(step, h2d_sync, compiles=0):
    return {"event": "step", "ts": 1.0 + step, "source": "spmd",
            "step": step, "path": "fused", "wall_ms": 5.0,
            "compiles": compiles, "host_syncs": 0, "h2d_sync": h2d_sync}


def test_report_flags_sync_h2d_reappearing():
    recs = [_rec(1, 2, compiles=1)]  # compile step: excluded from steady
    recs += [_rec(i, 0) for i in range(2, 9)]  # device-resident streak
    recs += [_rec(9, 3), _rec(10, 0)]  # ...then sync H2D reappears
    s = telemetry_report.summarize(recs)
    kinds = {a["kind"] for a in s["anomalies"]}
    assert "sync_h2d_steady" in kinds
    assert s["sources"]["spmd"]["sync_h2d"] == 5


def test_report_always_sync_is_not_flagged():
    # host-side prefetch syncs every step: that is its normal operating
    # mode, not an anomaly (keeps tools/check_telemetry.py clean runs green)
    recs = [_rec(i, 2) for i in range(1, 12)]
    s = telemetry_report.summarize(recs)
    assert {a["kind"] for a in s["anomalies"]} == set()
    assert s["sources"]["spmd"]["sync_h2d"] == 22


def test_report_short_zero_run_not_established():
    # fewer than 5 steady zeros never "establishes" device residency
    recs = [_rec(i, 0) for i in range(1, 4)] + [_rec(4, 1)]
    s = telemetry_report.summarize(recs)
    assert "sync_h2d_steady" not in {a["kind"] for a in s["anomalies"]}


# ------------------------------------------------------- smoke wrapper
def test_check_io_pipeline_smoke():
    pytest.importorskip("PIL")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "check_io_pipeline.py")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["overlap"]["sync_h2d_on"] == 0
    assert report["drain"]["leaked"] == 0
    assert report["decode"]["retries"] == 2
