"""SPMD layer tests — mesh, ring attention, fused train step.

Reference test analog: tests/python/unittest/test_kvstore.py (single-process
multi-device sync) + tests/nightly/dist_sync_kvstore.py value-exact checks —
here the multi-device substrate is the 8-virtual-device CPU mesh from
conftest.py, the pattern SURVEY.md §4 prescribes.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import (make_mesh, data_parallel_mesh, shard_batch,
                                attention, ring_self_attention_sharded,
                                functionalize, SPMDTrainer)


def test_make_mesh_infer_axis():
    mesh = make_mesh({"dp": -1})
    assert mesh.devices.size == len(jax.devices())
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    assert dict(mesh.shape) == {"dp": 2, "tp": 2, "sp": 2}
    with pytest.raises(ValueError):
        make_mesh({"dp": 3, "tp": 5})


def test_ring_attention_matches_full():
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    B, H, S, D = 2, 4, 16, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D),
                                 jnp.float32) for i in range(3))
    for causal in (True, False):
        ref = attention(q, k, v, causal=causal)
        out = ring_self_attention_sharded(mesh, q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   atol=2e-5, rtol=2e-5)


def test_dp_training_step_matches_single_device():
    """A dp=8 fused step must produce the same update as single-device —
    the dist_sync value-exactness contract (tests/nightly/
    dist_sync_kvstore.py)."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss

    # One net for both runs: SPMDTrainer snapshots parameter values at
    # construction and never writes back until sync(), so the second trainer
    # starts from the same Constant(0.05) init with identical param names.
    net = nn.Dense(4, in_units=6)
    net.initialize(mx.init.Constant(0.05))

    rng = np.random.RandomState(0)
    data = rng.uniform(size=(16, 6)).astype(np.float32)
    label = rng.uniform(size=(16, 4)).astype(np.float32)

    losses = {}
    weights = {}
    for name, mesh in [("multi", data_parallel_mesh()),
                       ("single", data_parallel_mesh(jax.devices()[:1]))]:
        tr = SPMDTrainer(net, L2Loss(), "sgd",
                         {"learning_rate": 0.5}, mesh=mesh)
        for _ in range(3):
            loss = tr.step(data, label)
        losses[name] = float(loss)
        weights[name] = {n: np.asarray(v) for n, v in tr.params.items()}
    assert np.isfinite(losses["multi"])
    np.testing.assert_allclose(losses["multi"], losses["single"], rtol=1e-5)
    for n in weights["multi"]:
        np.testing.assert_allclose(weights["multi"][n], weights["single"][n],
                                   atol=1e-5, rtol=1e-5)


def test_spmd_trainer_converges_and_syncs():
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=4), nn.Dense(1))
    net.initialize(mx.init.Xavier())
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, size=(64, 4)).astype(np.float32)
    y = (x.sum(axis=1, keepdims=True) ** 2).astype(np.float32)
    tr = SPMDTrainer(net, L2Loss(), "adam", {"learning_rate": 0.01})
    first = float(tr.step(x, y))
    for _ in range(60):
        last = float(tr.step(x, y))
    assert last < first * 0.5, (first, last)
    tr.sync()
    out = net(mx.nd.array(x))
    assert out.shape == (64, 1)


def test_functionalize_grads_flow():
    from mxnet_tpu.gluon import nn
    net = nn.Dense(3, in_units=5)
    net.initialize(mx.init.One())
    fn = functionalize(net)
    params = fn.init_values()
    x = jnp.ones((2, 5))

    def loss(p):
        (out,), _ = fn.apply(p, (x,), training=True)
        return jnp.sum(out)

    g = jax.grad(loss)(params)
    assert set(g.keys()) == set(fn.params.keys())
    wname = [n for n in g if n.endswith("weight")][0]
    np.testing.assert_allclose(np.asarray(g[wname]), 2.0, atol=1e-6)


def test_spmd_trainer_lr_schedule_not_frozen():
    """An lr_scheduler must keep working through the fused jitted step —
    lr/wd are traced arguments, not trace-time constants (reference:
    python/mxnet/lr_scheduler.py FactorScheduler semantics)."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss
    from mxnet_tpu.lr_scheduler import FactorScheduler

    rng = np.random.RandomState(2)
    data = rng.uniform(size=(8, 3)).astype(np.float32)
    label = np.zeros((8, 2), np.float32)

    def run(lr, sched):
        net = nn.Dense(2, in_units=3, use_bias=False)
        net.initialize(mx.init.Constant(0.1))
        tr = SPMDTrainer(net, L2Loss(), "sgd",
                         {"learning_rate": lr, "lr_scheduler": sched},
                         mesh=data_parallel_mesh(jax.devices()[:1]))
        for _ in range(4):
            tr.step(data, label)
        (w,) = [np.asarray(v) for n, v in tr.params.items()
                if n.endswith("weight")]
        return w

    # factor=0.5 every step: lr sequence 1.0, 0.5, 0.25, 0.125 of base.
    sched = FactorScheduler(step=1, factor=0.5)
    decayed = run(0.2, sched)
    constant = run(0.2, None)
    # If the schedule were constant-folded both runs would be identical.
    assert not np.allclose(decayed, constant)


def test_spmd_trainer_checkpoint_resume_bitwise(tmp_path):
    """train -> checkpoint -> restore in a NEW trainer -> continue must match
    an uninterrupted run bitwise (reference semantics:
    python/mxnet/model.py:394-442 + gluon/trainer.py:436-465)."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss

    rng = np.random.RandomState(3)
    data = rng.uniform(size=(16, 5)).astype(np.float32)
    label = rng.uniform(size=(16, 2)).astype(np.float32)

    def make():
        # Fixed prefix: param names must be stable across "processes".
        net = nn.Dense(2, in_units=5, prefix="ckpt_dense_")
        net.initialize(mx.init.Constant(0.07))
        return SPMDTrainer(net, L2Loss(), "adam", {"learning_rate": 0.05},
                           mesh=data_parallel_mesh())

    # Uninterrupted: 6 steps.
    tr_full = make()
    for _ in range(6):
        loss_full = tr_full.step(data, label)

    # Interrupted: 3 steps, checkpoint, fresh trainer, restore, 3 more.
    tr_a = make()
    for _ in range(3):
        tr_a.step(data, label)
    ckpt = str(tmp_path / "spmd.ckpt")
    tr_a.save_checkpoint(ckpt)

    tr_b = make()
    tr_b.load_checkpoint(ckpt)
    assert tr_b._step_num == 3
    for _ in range(3):
        loss_b = tr_b.step(data, label)

    np.testing.assert_array_equal(np.asarray(loss_full), np.asarray(loss_b))
    for n in tr_full.params:
        np.testing.assert_array_equal(np.asarray(tr_full.params[n]),
                                      np.asarray(tr_b.params[n]))


def test_shard_batch_places_on_dp():
    mesh = data_parallel_mesh()
    x = np.zeros((16, 3), np.float32)
    arr = shard_batch(mesh, jnp.asarray(x))
    assert arr.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("dp")),
        arr.ndim)


def test_module_vs_spmd_trainer_equivalence():
    """Module.fit's per-batch path and SPMDTrainer's fused step produce the
    same weights given the same init/data/optimizer (VERDICT r2 weak #4).
    """
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    rng = np.random.RandomState(0)
    X = rng.randn(32, 6).astype(np.float32)
    Y = rng.randint(0, 3, (32,)).astype(np.float32)
    W0 = (rng.randn(3, 6) * 0.1).astype(np.float32)
    b0 = np.zeros(3, np.float32)

    # --- Module path: one dense layer + SoftmaxOutput, plain SGD
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    out = mx.sym.SoftmaxOutput(fc, label, name="softmax")
    mod = mx.mod.Module(out)
    mod.bind([("data", (8, 6))], [("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.set_params({"fc_weight": mx.nd.array(W0),
                    "fc_bias": mx.nd.array(b0)}, {})
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    for e in range(3):
        it = mx.io.NDArrayIter(X, Y, batch_size=8)
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
    mod_w = mod.get_params()[0]["fc_weight"].asnumpy()

    # --- SPMDTrainer path: same math via gluon Dense + CE loss
    net = gluon.nn.Dense(3, in_units=6)
    net.initialize()
    net.weight.set_data(mx.nd.array(W0))
    net.bias.set_data(mx.nd.array(b0))
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, mesh=make_mesh({"dp": -1}))
    for e in range(3):
        for s in range(0, 32, 8):
            tr.step(X[s:s + 8], Y[s:s + 8])
    tr.sync()
    spmd_w = net.weight.data().asnumpy()

    np.testing.assert_allclose(spmd_w, mod_w, rtol=1e-4, atol=1e-5)


def test_fused_module_vs_spmd_trainer_equivalence():
    """Module's FUSED train step (one jitted fwd+bwd+update dispatch, the
    default fit path) matches SPMDTrainer's fused step on the same dense
    model — closing the triangle with
    test_module_vs_spmd_trainer_equivalence, which pins the eager path."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, profiler
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    rng = np.random.RandomState(0)
    X = rng.randn(32, 6).astype(np.float32)
    Y = rng.randint(0, 3, (32,)).astype(np.float32)
    W0 = (rng.randn(3, 6) * 0.1).astype(np.float32)
    b0 = np.zeros(3, np.float32)

    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    fc = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    out = mx.sym.SoftmaxOutput(fc, label, name="softmax")
    mod = mx.mod.Module(out)
    mod.bind([("data", (8, 6))], [("softmax_label", (8,))])
    mod.init_params(mx.init.Xavier())
    mod.set_params({"fc_weight": mx.nd.array(W0),
                    "fc_bias": mx.nd.array(b0)}, {})
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    profiler.reset_counters()
    for e in range(3):
        it = mx.io.NDArrayIter(X, Y, batch_size=8)
        for batch in it:
            mod.train_step(batch)
    assert profiler.counters()["fused_steps"] == 12
    mod_w = mod.get_params()[0]["fc_weight"].asnumpy()

    net = gluon.nn.Dense(3, in_units=6)
    net.initialize()
    net.weight.set_data(mx.nd.array(W0))
    net.bias.set_data(mx.nd.array(b0))
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1}, mesh=make_mesh({"dp": -1}))
    for e in range(3):
        for s in range(0, 32, 8):
            tr.step(X[s:s + 8], Y[s:s + 8])
    tr.sync()
    spmd_w = net.weight.data().asnumpy()

    np.testing.assert_allclose(spmd_w, mod_w, rtol=1e-4, atol=1e-5)


def test_spmd_trainer_sharded_checkpoint_resume_bitwise(tmp_path):
    """Orbax sharded checkpoint (every host writes only its shards, no
    gather — SURVEY §5.4's TPU-native layout): train -> save_sharded ->
    restore into a NEW trainer -> continue matches uninterrupted bitwise."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss

    rng = np.random.RandomState(4)
    data = rng.uniform(size=(16, 5)).astype(np.float32)
    label = rng.uniform(size=(16, 2)).astype(np.float32)

    def make():
        net = nn.Dense(2, in_units=5, prefix="ckpt2_dense_")
        net.initialize(mx.init.Constant(0.07))
        return SPMDTrainer(net, L2Loss(), "adam", {"learning_rate": 0.05},
                           mesh=data_parallel_mesh())

    tr_full = make()
    for _ in range(6):
        loss_full = tr_full.step(data, label)

    tr_a = make()
    for _ in range(3):
        tr_a.step(data, label)
    ckpt = str(tmp_path / "spmd_orbax")
    tr_a.save_checkpoint_sharded(ckpt)

    tr_b = make()
    tr_b.load_checkpoint_sharded(ckpt)
    assert tr_b._step_num == 3
    for _ in range(3):
        loss_b = tr_b.step(data, label)

    np.testing.assert_array_equal(np.asarray(loss_full), np.asarray(loss_b))
    for n in tr_full.params:
        np.testing.assert_array_equal(np.asarray(tr_full.params[n]),
                                      np.asarray(tr_b.params[n]))


def test_hwio_weights_layout_value_parity(tmp_path):
    """conv.weights_layout=HWIO (channels-last weights end-to-end,
    docs/PERF_NOTES.md): identical math to the reference OIHW layout —
    same loss curve, same synced-back weights, and single-file
    checkpoints interchange across the knob."""
    import mxnet_tpu.config as cfg
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    rng = np.random.RandomState(0)
    data = rng.uniform(size=(8, 3, 12, 12)).astype(np.float32)
    label = rng.randint(0, 5, (8,)).astype(np.float32)

    def build():
        net = nn.HybridSequential()
        net.add(nn.Conv2D(8, 3, padding=1, in_channels=3),
                nn.Activation("relu"),
                nn.Conv2D(8, 1, in_channels=8),   # the 1x1 the layout targets
                nn.GlobalAvgPool2D(), nn.Flatten(), nn.Dense(5))
        net.initialize(mx.init.Xavier())
        net(mx.nd.array(data))  # resolve shapes identically for both runs
        return net

    mx.random.seed(7)
    net_ref = build()
    mx.random.seed(7)
    net_hwio = build()
    for (a, pa), (b, pb) in zip(net_ref.collect_params().items(),
                                net_hwio.collect_params().items()):
        np.testing.assert_array_equal(pa.data().asnumpy(),
                                      pb.data().asnumpy())

    def train(net, layout):
        cfg.set("conv.weights_layout", layout)
        try:
            tr = SPMDTrainer(net, SoftmaxCrossEntropyLoss(), "sgd",
                             {"learning_rate": 0.1, "momentum": 0.9},
                             mesh=make_mesh({"dp": -1}))
            losses = [float(np.asarray(tr.step(data, label)))
                      for _ in range(3)]
            tr.sync()
            return tr, losses
        finally:
            cfg.set("conv.weights_layout", "ref")

    tr_ref, losses_ref = train(net_ref, "ref")
    tr_hwio, losses_hwio = train(net_hwio, "HWIO")
    assert tr_hwio._hwio_names, "HWIO trainer found no conv weights"
    np.testing.assert_allclose(losses_hwio, losses_ref, rtol=2e-5)
    for (n, pr), (_, ph) in zip(net_ref.collect_params().items(),
                                net_hwio.collect_params().items()):
        np.testing.assert_allclose(ph.data().asnumpy(),
                                   pr.data().asnumpy(), rtol=2e-4,
                                   atol=1e-6)

    # checkpoint interop: HWIO-saved file resumes a ref-layout trainer
    ck = str(tmp_path / "hwio.ckpt")
    tr_hwio.save_checkpoint(ck)
    w_hwio = {n: v for n, v in tr_hwio.params.items()}
    tr_ref.load_checkpoint(ck)
    for n in tr_ref.params:
        a = np.asarray(tr_ref.params[n])
        b = np.asarray(w_hwio[n])
        if n in tr_hwio._hwio_names and b.ndim == 4:
            b = b.transpose(3, 2, 0, 1)
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_hyper_array_cache_tracks_schedule():
    """The per-step lr/wd device arrays are reused while the schedule is
    flat (no redundant host->device uploads) but a schedule change busts
    the cache immediately."""
    from mxnet_tpu.parallel.trainer import _opt_hyper_arrays
    import mxnet_tpu.optimizer as opt
    o = opt.create("sgd", learning_rate=0.1)
    cache = {}
    l1, w1 = _opt_hyper_arrays(o, 3, cache)
    l2, w2 = _opt_hyper_arrays(o, 3, cache)
    assert l1 is l2 and w1 is w2
    o.set_learning_rate(0.05)
    l3, _ = _opt_hyper_arrays(o, 3, cache)
    assert l3 is not l1
    assert abs(float(np.asarray(l3)[0]) - 0.05) < 1e-7


def test_ring_attention_gradient_matches_full():
    """Long-context TRAINING contract (SURVEY §5.7): gradients through
    the sequence-parallel ring equal dense-attention gradients, so
    sp-training is value-exact, not just inference."""
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    B, H, S, D = 2, 4, 16, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(10 + i),
                                 (B, H, S, D), jnp.float32)
               for i in range(3))
    # weight the outputs so the loss is not permutation-blind
    w = jax.random.normal(jax.random.PRNGKey(13), (B, H, S, D),
                          jnp.float32)

    for causal in (True, False):
        def loss_full(q_, k_, v_):
            return jnp.sum(attention(q_, k_, v_, causal=causal) * w)

        def loss_ring(q_, k_, v_):
            return jnp.sum(
                ring_self_attention_sharded(mesh, q_, k_, v_,
                                            causal=causal) * w)

        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_full, g_ring):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-5, rtol=3e-5)


# ------------------------------------------------- spans and device names
def _small_trainer(optimizer="sgd", mesh=None):
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.loss import L2Loss
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu", in_units=4), nn.Dense(1))
    net.initialize(mx.init.Xavier())
    rng = np.random.RandomState(1)
    x = rng.uniform(-1, 1, size=(16, 4)).astype(np.float32)
    y = x.sum(axis=1, keepdims=True).astype(np.float32)
    kw = {"learning_rate": 0.01}
    if optimizer == "sgd":
        kw["momentum"] = 0.9
    return SPMDTrainer(net, L2Loss(), optimizer, kw, mesh=mesh), x, y


def test_trainer_spans_reach_a_bare_profiler_session(tmp_path):
    """``jax.profiler.start_trace`` alone finds ``spmd.step`` and its four
    children on the host plane, one of each per step."""
    from _util import assert_spans_nest, profiled_spans
    tr, x, y = _small_trainer()
    tr.step(x, y)       # compile outside the session

    def run():
        for _ in range(3):
            tr.step(x, y)
        jax.block_until_ready(tr.params)

    spans = profiled_spans(run, tmp_path, ("spmd.",))
    children = ("spmd.shard_batch", "spmd.prepare", "spmd.dispatch",
                "spmd.post")
    for child in children:
        assert_spans_nest(spans, child, "spmd.step")
    for name in ("spmd.step",) + children:
        assert sum(s[0] == name for s in spans) == 3, name
    steps = sorted(int(s[3]["step"]) for s in spans if s[0] == "spmd.step")
    assert steps == [2, 3, 4]
    # the children come in the order of the work
    one = sorted((s for s in spans if s[0] in children),
                 key=lambda s: s[1])[:4]
    assert tuple(s[0] for s in one) == children


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
@pytest.mark.parametrize("devices", [1, 2], ids=["one", "dp2"])
def test_step_program_carries_scopes_as_metadata_only(optimizer, devices,
                                                      monkeypatch):
    """The step program names its forward (the backward then reads
    ``transpose(jvp(mx.forward))``), its optimizer epilogue and the Gluon
    blocks it inlines — and is, locations aside, the program it was
    without the names."""
    from _util import lowered_with_and_without_scopes
    from mxnet_tpu import perf
    tr, x, y = _small_trainer(
        optimizer, data_parallel_mesh(jax.devices()[:devices]))
    captured = []
    capture = perf.PerfProgram._capture
    monkeypatch.setattr(
        perf.PerfProgram, "_capture",
        lambda self, args: captured.append((self, args))
        or capture(self, args))

    def lower():
        # a fresh build each time: a cached trace would keep its names
        tr._jitted.clear()
        del captured[:]
        tr.step(x, y)
        (prog, args), = captured
        return prog.fn.lower(*args)

    text = lowered_with_and_without_scopes(lower, monkeypatch)
    for scope in ("jvp(mx.forward)/", "transpose(jvp(mx.forward))/",
                  "mx.opt_update/", "jvp(mx.forward)/net_dense0/",
                  "transpose(jvp(mx.forward))/net_dense1/"):
        assert scope in text, scope
