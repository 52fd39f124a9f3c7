"""mx.parallel.embedding — mesh-sharded embedding tables with deduplicated
row-sparse lookup/update (docs/PERF_NOTES.md "Sharded embeddings").

The bitwise contract is asserted at the primitive level (lookup/update on
the SAME deduplicated row gradients): a vocab-sharded table under
``shard_map`` must answer and update bitwise-identically to the
single-device dense-resident path, including repeated ids and
sentinel-padded rows.  Trainer-level comparisons flip only the routing
(``embedding.sharded``) and therefore compile two DIFFERENT XLA programs;
those assert bitwise losses/dense params and ulp-tight tables — the last
ulp is compiler fusion/reassociation, not semantics (see
test_trainer_sparse_matches_dense_single_device).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, gluon, profiler, telemetry
from mxnet_tpu.parallel import (ShardedEmbedding, SPMDTrainer, dedup_ids,
                                lookup_unique, update_unique, make_mesh)

VOCAB, DIM, B = 64, 4, 8


def _mesh(n):
    if len(jax.devices()) < n:
        pytest.skip("needs %d host devices" % n)
    return make_mesh({"dp": n}, jax.devices()[:n])


def _ids_with_dups_and_sentinel():
    """An id batch exercising every contract: repeated ids (Zipf-ish),
    all-identical rows, and trailing sentinel-padded rows (id == VOCAB)."""
    rng = np.random.RandomState(5)
    ids = rng.randint(0, VOCAB, (B, 3)).astype(np.int32)
    ids[3, :] = 9                 # a fully repeated row
    ids[-2:, :] = VOCAB           # sentinel-padded tail
    return ids


# ------------------------------------------------------------- primitives
def test_dedup_ids_static_shape_and_inverse():
    ids = np.array([[5, 3, 5], [3, 3, 7]], np.int32)
    uniq, inv = dedup_ids(ids, size=6, sentinel=VOCAB)
    uniq, inv = np.asarray(uniq), np.asarray(inv)
    assert uniq.shape == (6,) and inv.shape == (6,)
    assert uniq.tolist() == [3, 5, 7, VOCAB, VOCAB, VOCAB]
    # the inverse map reconstructs the flat input exactly
    assert uniq[inv].tolist() == [5, 3, 5, 3, 3, 7]


def test_dedup_ids_all_identical():
    ids = np.full((4, 4), 11, np.int32)
    uniq, inv = dedup_ids(ids, size=16, sentinel=VOCAB)
    uniq = np.asarray(uniq)
    assert uniq[0] == 11 and (uniq[1:] == VOCAB).all()
    assert (np.asarray(inv) == 0).all()


@pytest.mark.parametrize("shards", [2, 4])
def test_lookup_unique_sharded_bitwise(shards):
    """Sharded gather (owner row + psum of zeros) == single-device gather,
    bitwise, with sentinel ids answered as zero rows."""
    mesh = _mesh(shards)
    rng = np.random.RandomState(0)
    table = rng.randn(VOCAB, DIM).astype(np.float32)
    uniq = jnp.asarray([0, 9, 9, 31, VOCAB - 1, VOCAB, VOCAB], jnp.int32)
    dense = np.asarray(lookup_unique(jnp.asarray(table), uniq))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharded_tbl = jax.device_put(table, NamedSharding(mesh, P("dp")))
    sharded = np.asarray(lookup_unique(sharded_tbl, uniq, mesh, "dp"))
    assert sharded.tobytes() == dense.tobytes()
    assert (sharded[:5] == table[[0, 9, 9, 31, VOCAB - 1]]).all()
    assert (sharded[5:] == 0).all()  # sentinel rows are zeros


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_embedding_bitwise_vs_single_device(shards, opt_name):
    """THE acceptance contract: sharded lookup + update are bitwise-equal
    to the single-device path on the same ids — repeated ids summed
    identically, sentinel-padded rows dropped, untouched rows untouched."""
    mesh_n, mesh_1 = _mesh(shards), _mesh(1)
    kw = dict(optimizer=opt_name, seed=3, init_scale=0.5)
    emb_n = ShardedEmbedding(VOCAB, DIM, mesh=mesh_n, **kw)
    emb_1 = ShardedEmbedding(VOCAB, DIM, mesh=mesh_1, **kw)
    assert emb_n.axis == "dp" and emb_1.axis is None
    t0 = np.asarray(emb_n.table)
    assert t0.tobytes() == np.asarray(emb_1.table).tobytes()

    ids = _ids_with_dups_and_sentinel()
    out_n = np.asarray(emb_n.lookup(ids))
    out_1 = np.asarray(emb_1.lookup(ids))
    assert out_n.shape == (B, 3, DIM)
    assert out_n.tobytes() == out_1.tobytes()
    assert (out_n[ids < VOCAB] == t0[ids[ids < VOCAB]]).all()
    assert (out_n[ids == VOCAB] == 0).all()  # sentinel rows -> zeros

    rng = np.random.RandomState(1)
    grad = rng.randn(B, 3, DIM).astype(np.float32)
    for step in range(3):  # several steps so adam moments accumulate
        emb_n.update(ids, grad + step, lr=0.1)
        emb_1.update(ids, grad + step, lr=0.1)
    tn, t1 = np.asarray(emb_n.table), np.asarray(emb_1.table)
    assert tn.tobytes() == t1.tobytes()
    touched = np.unique(ids[ids < VOCAB])
    untouched = np.setdiff1d(np.arange(VOCAB), touched)
    assert tn[untouched].tobytes() == t0[untouched].tobytes()
    assert np.abs(tn[touched] - t0[touched]).max() > 1e-4


def test_sharded_update_matches_dense_sgd_step():
    """For stateless SGD (wd=0) the lazy row update coincides with a full
    dense step on the scatter-summed gradient — bitwise, so the sharded
    path IS the dense path restricted to touched rows."""
    mesh = _mesh(2)
    emb = ShardedEmbedding(VOCAB, DIM, mesh=mesh, optimizer="sgd",
                           seed=3, init_scale=0.5)
    t0 = np.asarray(emb.table)
    ids = _ids_with_dups_and_sentinel()
    rng = np.random.RandomState(1)
    grad = rng.randn(B, 3, DIM).astype(np.float32)
    emb.update(ids, grad, lr=0.1)
    # dense reference: sequential scatter-add (np.add.at) then w -= lr*g
    g = np.zeros((VOCAB, DIM), np.float32)
    flat_ids, flat_g = ids.ravel(), grad.reshape(-1, DIM)
    keep = flat_ids < VOCAB
    np.add.at(g, flat_ids[keep], flat_g[keep])
    expect = t0 - np.float32(0.1) * g
    assert np.asarray(emb.table).tobytes() == expect.tobytes()


def test_update_unique_drops_sentinel_rows():
    """Sentinel ids map to an out-of-range row index and the .at[] scatter
    DROPS them — the masking is jax OOB semantics, not a branch."""
    from mxnet_tpu import optimizer as opt_mod
    opt = opt_mod.create("sgd")
    table = jnp.ones((8, 2), jnp.float32)
    uniq = jnp.asarray([2, 8, 8], jnp.int32)  # one real row, two sentinels
    grads = jnp.ones((3, 2), jnp.float32)
    new, _ = update_unique(opt, table, None, uniq, grads,
                           jnp.float32(0.5), jnp.float32(0.0), 1)
    new = np.asarray(new)
    assert (new[2] == 0.5).all()
    assert (np.delete(new, 2, axis=0) == 1.0).all()


def test_sharded_embedding_compile_cache_and_telemetry():
    """Program cache is keyed by ids shape: ragged batches padded to one
    bucket reuse a single compile; telemetry counters/gauges feed."""
    mesh = _mesh(2)
    emb = ShardedEmbedding(VOCAB, DIM, mesh=mesh, optimizer="sgd")
    compiles = telemetry.counter("embedding.lookup_compiles")
    gathered = telemetry.counter("embedding.gathered_rows")
    c0, g0 = compiles.value, gathered.value
    rng = np.random.RandomState(0)
    for _ in range(3):  # same shape, different data -> one compile
        emb.lookup(rng.randint(0, VOCAB, (B, 3)).astype(np.int32))
    assert compiles.value - c0 == 1
    assert gathered.value - g0 == 3 * B * 3
    emb.lookup(rng.randint(0, VOCAB, (B, 5)).astype(np.int32))
    assert compiles.value - c0 == 2  # new bucket -> one more
    ratio = telemetry.gauge("embedding.unique_ratio").value
    assert 0.0 < ratio <= 1.0
    ids = np.full((B, 3), 7, np.int32)
    emb.lookup(ids)  # all-identical ids
    assert telemetry.gauge("embedding.unique_ratio").value == \
        pytest.approx(1.0 / (B * 3))


def test_sharded_embedding_config_epoch_invalidates_programs():
    """embedding.unique_size is baked into the lookup program at trace
    time (it sizes the dedup buffer): flipping the knob must rebuild the
    program, not serve the stale compile.  The cache is keyed by
    config.epoch() — the cache.stale-knob-key contract the mxlint
    compile-cache pass enforces (docs/ANALYSIS.md pass family 5)."""
    mesh = _mesh(2)
    emb = ShardedEmbedding(VOCAB, DIM, mesh=mesh, optimizer="sgd")
    # few unique ids, so a capped dedup buffer still holds all of them
    ids = np.random.RandomState(1).choice(
        [3, 5, 7], size=(B, 3)).astype(np.int32)
    out0 = np.asarray(emb.lookup(ids))
    config.set("embedding.unique_size", 8)
    try:
        out1 = np.asarray(emb.lookup(ids))
        # same shape hit the cache, but the epoch moved: every surviving
        # entry is keyed by the NEW epoch (old-epoch programs evicted)
        assert emb._progs, "program cache unexpectedly empty"
        assert all(k[-1] == config.epoch() for k in emb._progs)
        np.testing.assert_array_equal(out0, out1)
    finally:
        config.set("embedding.unique_size", 0)


def test_unique_size_knob_caps_capacity_and_rejects_negative():
    from mxnet_tpu.parallel.embedding import unique_capacity
    assert unique_capacity(24) == 24
    config.set("embedding.unique_size", 8)
    try:
        assert unique_capacity(24) == 8
        assert unique_capacity(4) == 4
    finally:
        config.set("embedding.unique_size", 0)
    with pytest.raises(ValueError):
        config.set("embedding.unique_size", -1)
    assert config.get("embedding.unique_size") == 0  # reverted


# ---------------------------------------------------------- fused trainer
def _build_net(vocab=VOCAB, dim=DIM):
    mx.random.seed(7)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Embedding(vocab, dim, sparse_grad=True))
        net.add(gluon.nn.Flatten())
        net.add(gluon.nn.Dense(1))
    net.initialize(mx.init.Xavier())
    return net


def _trainer_run(sharded, mesh, batches, labels, pads, opt="sgd",
                 opt_params=None):
    config.set("embedding.sharded", sharded)
    try:
        net = _build_net()
        tr = SPMDTrainer(net, gluon.loss.L2Loss(), opt,
                         opt_params or {"learning_rate": 0.1}, mesh=mesh)
        losses = [float(tr.step(d, l, pad=p))
                  for d, l, p in zip(batches, labels, pads)]
        # strip the auto-incremented name-scope prefix so runs compare
        params = {n.split("_", 1)[1]: np.asarray(v)
                  for n, v in tr.params.items()}
        return losses, params
    finally:
        config.set("embedding.sharded", True)


def _trainer_batches():
    rng = np.random.RandomState(0)
    batches = [rng.randint(0, VOCAB, (B, 3)).astype(np.int32)
               for _ in range(4)]
    batches[1][:, :] = 5        # every id identical
    batches[2][-2:, :] = 3      # wrap-padded tail rows, masked via pad=2
    labels = [rng.randn(B, 1).astype(np.float32) for _ in range(4)]
    return batches, labels, [0, 0, 2, 0]


def test_trainer_sparse_matches_dense_single_device():
    """Flipping embedding.sharded flips ONLY the gradient routing: same
    losses (bitwise), bitwise dense params; the table agrees to the last
    ulp — two different XLA programs may fuse/reassociate the final
    ``w - lr*g`` differently, so the table bound is ulps, not bytes."""
    mesh = _mesh(1)
    batches, labels, pads = _trainer_batches()
    la, a = _trainer_run(True, mesh, batches, labels, pads)
    lb, b = _trainer_run(False, mesh, batches, labels, pads)
    assert [np.float32(x).tobytes() for x in la] == \
        [np.float32(x).tobytes() for x in lb]
    assert a["dense0_weight"].tobytes() == b["dense0_weight"].tobytes()
    assert a["dense0_bias"].tobytes() == b["dense0_bias"].tobytes()
    ta, tb = a["embedding0_weight"], b["embedding0_weight"]
    np.testing.assert_allclose(ta, tb, rtol=0, atol=1e-7)
    # rows no batch touched must be bitwise-identical: the sparse path
    # never reads them and the dense path adds an exact 0.0
    touched = np.unique(np.concatenate([b_.ravel() for b_ in batches]))
    untouched = np.setdiff1d(np.arange(VOCAB), touched)
    assert ta[untouched].tobytes() == tb[untouched].tobytes()


def test_trainer_sparse_sharded_matches_dense_multi_device():
    """Same comparison on a 2-shard mesh: the table is now vocab-sharded
    and updated under shard_map; losses still match bitwise."""
    mesh = _mesh(2)
    batches, labels, pads = _trainer_batches()
    la, a = _trainer_run(True, mesh, batches, labels, pads)
    lb, b = _trainer_run(False, mesh, batches, labels, pads)
    assert [np.float32(x).tobytes() for x in la] == \
        [np.float32(x).tobytes() for x in lb]
    np.testing.assert_allclose(a["embedding0_weight"],
                               b["embedding0_weight"], rtol=0, atol=1e-7)
    np.testing.assert_allclose(a["dense0_weight"], b["dense0_weight"],
                               rtol=0, atol=1e-7)


def test_trainer_sparse_adam_cross_mesh_sizes():
    """The sparse path trains identically-shaped state across mesh sizes
    (1 device vs 2 shards) — losses and table agree to float32 tolerance
    (cross-device psum ordering costs the last ulp)."""
    batches, labels, pads = _trainer_batches()
    kw = dict(opt="adam", opt_params={"learning_rate": 0.01})
    la, a = _trainer_run(True, _mesh(2), batches, labels, pads, **kw)
    lb, b = _trainer_run(True, _mesh(1), batches, labels, pads, **kw)
    np.testing.assert_allclose(la, lb, rtol=0, atol=1e-6)
    np.testing.assert_allclose(a["embedding0_weight"],
                               b["embedding0_weight"], rtol=0, atol=1e-6)


def test_trainer_sparse_fused_compiles_flat_across_ragged():
    """Ragged index batches padded to one bucket + one pad count reuse one
    fused program; each distinct pad costs exactly one more compile."""
    mesh = _mesh(2)
    config.set("embedding.sharded", True)
    net = _build_net()
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), "sgd",
                     {"learning_rate": 0.1}, mesh=mesh)
    rng = np.random.RandomState(3)
    label = rng.randn(B, 1).astype(np.float32)
    profiler.reset_counters()
    for _ in range(3):  # same shape/pad, fresh ids (incl. dup-heavy)
        tr.step(rng.randint(0, VOCAB, (B, 3)).astype(np.int32), label)
    assert profiler.counters()["fused_compiles"] == 1
    ids = rng.randint(0, VOCAB, (B, 3)).astype(np.int32)
    ids[-2:, :] = VOCAB  # sentinel-padded tail
    tr.step(ids, label, pad=2)
    tr.step(ids, label, pad=2)
    c = profiler.counters()
    assert c["fused_compiles"] == 2, c
    assert c["fused_steps"] == 5, c


def test_trainer_sparse_sentinel_rows_never_touch_table():
    """A batch whose tail rows carry the sentinel id must not read or
    write any table row for them — and must not poison anything with the
    dense gather's OOB fill."""
    mesh = _mesh(2)
    config.set("embedding.sharded", True)
    net = _build_net()
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), "sgd",
                     {"learning_rate": 0.1}, mesh=mesh)
    ids = np.full((B, 3), VOCAB, np.int32)  # EVERY id is the sentinel
    ids[:2, :] = 4                          # except two valid rows
    tr.step(ids, np.ones((B, 1), np.float32), pad=B - 2)  # materialize
    name = next(n for n in tr.params if n.endswith("embedding0_weight"))
    t0 = np.asarray(tr.params[name])
    loss = float(tr.step(ids, np.ones((B, 1), np.float32), pad=B - 2))
    assert np.isfinite(loss)
    t1 = np.asarray(tr.params[name])
    assert t1[4].tobytes() != t0[4].tobytes()
    untouched = np.setdiff1d(np.arange(VOCAB), [4])
    assert t1[untouched].tobytes() == t0[untouched].tobytes()


def test_trainer_sparse_requires_lazy_optimizer():
    mesh = _mesh(1)
    config.set("embedding.sharded", True)
    net = _build_net()
    with pytest.raises(ValueError, match="step_rows"):
        tr = SPMDTrainer(net, gluon.loss.L2Loss(), "adagrad",
                         {"learning_rate": 0.1}, mesh=mesh)
        tr.step(np.zeros((B, 3), np.int32), np.zeros((B, 1), np.float32))


# ------------------------------------------------ gluon.Trainer (eager)
def test_gluon_trainer_multiparam_block_stays_lazy():
    """Regression: in a >1-param block the sparse-grad Embedding's
    RowSparseNDArray gradient must take the lazy step_rows path (counted
    by optimizer.lazy_row_updates) while the Dense params take the dense
    path — and wd>0 must not decay untouched embedding rows."""
    mx.random.seed(11)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Embedding(50, 4, sparse_grad=True))
        net.add(gluon.nn.Flatten())
        net.add(gluon.nn.Dense(1))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5, "wd": 0.1})
    emb_name = next(n for n in net.collect_params()
                    if n.endswith("embedding0_weight"))
    emb_param = net.collect_params()[emb_name]
    w0 = emb_param.data().asnumpy().copy()
    lazy0 = telemetry.counter("optimizer.lazy_row_updates").value
    ids = mx.nd.array(np.array([[3, 7, 3]] * 4, np.float32))
    with mx.autograd.record():
        out = net(ids)
        loss = (out * out).mean()
    loss.backward()
    trainer.step(4)
    assert telemetry.counter("optimizer.lazy_row_updates").value \
        - lazy0 == 1
    w1 = emb_param.data().asnumpy()
    touched = [3, 7]
    untouched = np.setdiff1d(np.arange(50), touched)
    # wd=0.1 on the DENSE path would shrink every row; lazy must not
    assert w1[untouched].tobytes() == w0[untouched].tobytes()
    assert np.abs(w1[touched] - w0[touched]).max() > 1e-6


# ------------------------------------------------------- kvstore dedup
def test_kvstore_row_sparse_pull_dedups_repeated_rows():
    """row_sparse_pull gathers each distinct row once (the dedup counter
    reports the savings) and restores duplicates on output."""
    kv = mx.kv.create("local")
    rng = np.random.RandomState(2)
    val = rng.randn(20, 3).astype(np.float32)
    kv.init("emb", mx.nd.array(val))
    rows = mx.nd.array(np.array([4, 4, 9, 4, 17, 9], np.float32))
    out = mx.nd.sparse.zeros("row_sparse", (20, 3))
    d0 = telemetry.counter("kvstore.rowsparse_dedup_rows").value
    kv.row_sparse_pull("emb", out=out, row_ids=rows)
    assert telemetry.counter("kvstore.rowsparse_dedup_rows").value \
        - d0 == 3  # 6 requested, 3 distinct
    dense = out.tostype("default").asnumpy()
    for r in (4, 9, 17):
        np.testing.assert_array_equal(dense[r], val[r])
    untouched = np.setdiff1d(np.arange(20), [4, 9, 17])
    assert (dense[untouched] == 0).all()


# -------------------------------------------------- prefetcher sentinel
def test_device_prefetcher_pads_int_batches_with_sentinel():
    """Integer index batches flow through DevicePrefetcher with ragged
    tails padded by the SENTINEL id (not wrap-padding), so padded rows
    are dropped by the sparse update instead of re-touching real rows."""
    from mxnet_tpu import io as mio
    ids = np.arange(10, dtype=np.int32).reshape(10, 1) % 7
    lab = np.arange(10, dtype=np.float32).reshape(10, 1)

    class RawIter(mio.DataIter):
        def __init__(self):
            super().__init__(4)
            self.pos = 0

        def reset(self):
            self.pos = 0

        def next(self):
            if self.pos >= 10:
                raise StopIteration
            d = ids[self.pos:self.pos + 4]
            l = lab[self.pos:self.pos + 4]
            self.pos += 4
            return mio.DataBatch([d], [l], pad=0)

    dp = mio.DevicePrefetcher(RawIter(), buckets="full",
                              pad_sentinel=VOCAB)
    batches = [(np.asarray(b.data[0]), np.asarray(b.label[0]), b.pad)
               for b in dp]
    assert [p for _, _, p in batches] == [0, 0, 2]
    tail_ids, tail_lab, _ = batches[-1]
    assert tail_ids.shape == (4, 1)
    assert (tail_ids[-2:] == VOCAB).all()   # int data: sentinel-padded
    assert tail_lab[-2:, 0].tolist() == [8.0, 9.0]  # floats still wrap


# ------------------------------------------------------- smoke wrapper
def test_check_embedding_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools",
                                      "check_embedding.py")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["sharded"]["bitwise"] and report["trainer"]["bitwise"]
    assert report["compiles"]["flat"]
    assert 0.0 < report["dedup"]["unique_ratio"] < 1.0
