"""Top-level module parity: attribute/executor/executor_manager/
kvstore_server/log/util/registry/libinfo (reference: python/mxnet/*.py).
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx


def test_attr_scope_annotates_symbols():
    with mx.AttrScope(ctx_group="dev1", lr_mult="0.1"):
        a = mx.sym.Variable("a")
        out = mx.sym.relu(a)
    assert out.attr("ctx_group") == "dev1"
    assert out.attr("lr_mult") == "0.1"
    assert out.attr_dict()[out.name]["ctx_group"] == "dev1"
    # outside the scope: unannotated
    out2 = mx.sym.relu(mx.sym.Variable("b"))
    assert out2.attr("ctx_group") is None
    # nesting merges inner-over-outer
    with mx.AttrScope(ctx_group="dev1"):
        with mx.AttrScope(ctx_group="dev2"):
            inner = mx.sym.relu(mx.sym.Variable("c"))
    assert inner.attr("ctx_group") == "dev2"
    with pytest.raises(ValueError):
        mx.AttrScope(lr_mult=0.1)  # non-string rejected
    # Variables are annotated too (the scope's primary consumers are
    # parameter attrs), and explicit attrs beat the scope
    with mx.AttrScope(lr_mult="0.1", ctx_group="dev1"):
        v = mx.sym.Variable("w", lr_mult="2.0")
    assert v.attr("lr_mult") == "2.0"
    assert v.attr("ctx_group") == "dev1"
    scope = mx.AttrScope(lr_mult="0.1")
    assert scope.get({"lr_mult": "1.0"})["lr_mult"] == "1.0"


def test_executor_and_manager_facades():
    from mxnet_tpu.executor import Executor
    from mxnet_tpu.executor_manager import _split_input_slice
    assert Executor is mx.sym.Executor
    slices = _split_input_slice(10, [1, 1, 2])
    widths = [s.stop - s.start for s in slices]
    assert sum(widths) == 10 and all(w > 0 for w in widths)
    assert widths[2] > widths[0]  # heavier workload gets the bigger slice
    assert slices[0].start == 0 and slices[-1].stop == 10


def test_kvstore_server_role_collapse(monkeypatch):
    import mxnet_tpu.kvstore_server as kvs
    srv = kvs.KVStoreServer(None)
    srv.run()  # no-op, returns
    monkeypatch.setenv("DMLC_ROLE", "server")
    with pytest.raises(SystemExit):
        kvs._init_kvstore_server_module()


def test_server_role_exits_at_import():
    import os, subprocess, sys
    env = dict(os.environ, DMLC_ROLE="server", JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", "import mxnet_tpu"],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "obsolete" in r.stderr


def test_log_get_logger():
    logger = mx.log.get_logger("mxtest", level=logging.INFO)
    assert logger.level == logging.INFO and logger.handlers
    n = len(logger.handlers)
    mx.log.get_logger("mxtest")  # init-once: no handler stacking
    assert len(logger.handlers) == n


def test_registry_register_create():
    from mxnet_tpu.registry import (get_register_func, get_alias_func,
                                    get_create_func)

    class Base:
        def __init__(self, x=1):
            self.x = x

    register = get_register_func(Base, "thing")
    alias = get_alias_func(Base, "thing")
    create = get_create_func(Base, "thing")

    @register
    @alias("short")
    class MyThing(Base):
        pass

    assert isinstance(create("mything"), MyThing)
    assert isinstance(create("short", x=5), MyThing)
    with pytest.raises(ValueError):
        create("nope")
    with pytest.raises(ValueError):
        create(MyThing(), x=9)  # extra args on an instance must raise
    assert create("short", x=5).x == 5
    inst = MyThing()
    assert create(inst) is inst
    assert create('{"thing": "mything", "x": 3}').x == 3


def test_libinfo_and_util():
    assert mx.libinfo.__version__.endswith("tpu")
    from mxnet_tpu.util import set_np, is_np_array, reset_np
    set_np()
    assert is_np_array()
    reset_np()
    assert not is_np_array()


def test_pcc_metric_matches_binary_mcc():
    """PCC on a 2-class confusion equals the binary Matthews correlation."""
    m = mx.metric.PCC()
    rng = np.random.RandomState(0)
    labels = rng.randint(0, 2, 200)
    scores = rng.rand(200, 2)
    preds = scores.argmax(1)
    m.update([mx.nd.array(labels.astype(np.float32))],
             [mx.nd.array(scores.astype(np.float32))])
    tp = int(((preds == 1) & (labels == 1)).sum())
    tn = int(((preds == 0) & (labels == 0)).sum())
    fp = int(((preds == 1) & (labels == 0)).sum())
    fn = int(((preds == 0) & (labels == 1)).sum())
    denom = np.sqrt(float((tp + fp) * (tp + fn) * (tn + fp) * (tn + fn)))
    mcc = (tp * tn - fp * fn) / denom if denom else 0.0
    name, got = m.get()
    assert name == "pcc"
    np.testing.assert_allclose(got, mcc, rtol=1e-10)
    # perfect prediction -> exactly +1
    m2 = mx.metric.PCC()
    m2.update([mx.nd.array([0, 1, 2, 1.0])],
              [mx.nd.array(np.eye(3)[[0, 1, 2, 1]].astype(np.float32))])
    assert abs(m2.get()[1] - 1.0) < 1e-12
    # global scope survives reset_local; local window clears
    m2.reset_local()
    assert np.isnan(m2.get()[1])
    assert abs(m2.get_global()[1] - 1.0) < 1e-12
    # update after reset_local with FEWER classes must not crash
    m2.update([mx.nd.array([0, 1.0])],
              [mx.nd.array(np.eye(2).astype(np.float32))])
    assert abs(m2.get()[1] - 1.0) < 1e-12


def test_fused_rnn_initializer():
    """FusedRNN: inner init on weights; zero biases with the forget-gate
    rows (LSTM i2h, rows H..2H) at forget_bias."""
    init = mx.init.FusedRNN(mx.init.Xavier(), num_hidden=4, num_layers=1,
                            mode="lstm", forget_bias=2.0)
    from mxnet_tpu.initializer import InitDesc
    from mxnet_tpu.ndarray.ndarray import _wrap
    import jax.numpy as jnp
    bias = _wrap(jnp.full((16,), 7.0))
    init(InitDesc("lstm_l0_i2h_bias"), bias)
    b = bias.asnumpy()
    np.testing.assert_array_equal(b[4:8], 2.0)
    np.testing.assert_array_equal(b[:4], 0.0)
    np.testing.assert_array_equal(b[8:], 0.0)
    w = _wrap(jnp.zeros((16, 8)))
    init(InitDesc("lstm_l0_i2h_weight"), w)
    assert float(np.abs(w.asnumpy()).sum()) > 0  # inner init applied


def test_conv_internal_layout_nhwc_parity():
    """The conv.internal_layout=NHWC experiment (docs/PERF_NOTES.md) is
    numerically identical to the native lowering — including grouped
    convs — so the bench can sweep it safely."""
    from mxnet_tpu import gluon
    net = gluon.nn.Conv2D(8, 3, padding=1, in_channels=3)
    net.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(0).rand(
        2, 3, 16, 16).astype(np.float32))
    ref = net(x).asnumpy()
    mx.config.set("conv.internal_layout", "NHWC")
    try:
        out = net(x).asnumpy()
    finally:
        mx.config.set("conv.internal_layout", "native")
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=1e-5)


def test_ctx_group_multi_device_placement():
    """group2ctx model parallelism (reference: tests/python/unittest/
    test_multi_device_exec.py test_ctx_group): stage-annotated params are
    PLACED on their assigned devices, forward still computes correctly
    (the executor inserts the cross-device copies), and grads live beside
    their params."""
    import numpy as np
    with mx.AttrScope(ctx_group="stage1"):
        data = mx.sym.Variable("data")
        fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
        act1 = mx.sym.Activation(fc1, name="relu1", act_type="relu")
    stage1 = set(act1.list_arguments())
    with mx.AttrScope(ctx_group="stage2"):
        fc2 = mx.sym.FullyConnected(act1, name="fc2", num_hidden=4)
        out = mx.sym.SoftmaxOutput(fc2, name="softmax")

    group2ctx = {"stage1": mx.cpu(1), "stage2": mx.cpu(2)}
    for grad_req in ("write", "null"):
        ex = out.simple_bind(mx.cpu(0), group2ctx=group2ctx,
                             grad_req=grad_req, data=(2, 8))
        for arr, name in zip(ex.arg_arrays, out.list_arguments()):
            if name == "data":
                continue  # the batch input follows the caller
            expect = group2ctx["stage1" if name in stage1 else "stage2"]
            dev = next(iter(arr._data.devices()))
            assert dev == expect.jax_device, (name, dev)
        if grad_req == "write":
            for g, name in zip(ex.grad_arrays, out.list_arguments()):
                if name == "data" or g is None:
                    continue
                expect = group2ctx["stage1" if name in stage1 else "stage2"]
                gdev = next(iter(g._data.devices()))
                assert gdev == expect.jax_device, (name, gdev)

    # training across the placement: copy_params_from keeps arrays on
    # their assigned devices, fwd+bwd compute (cross-device copies
    # inserted), and grads stay beside their params after backward
    ex = out.simple_bind(mx.cpu(0), group2ctx=group2ctx, grad_req="write",
                         data=(2, 8))
    ex.copy_params_from(
        {n: mx.nd.array(np.full(a.shape, 0.1, np.float32))
         for n, a in ex.arg_dict.items() if n != "data"},
        allow_extra_params=True)
    for arr, name in zip(ex.arg_arrays, out.list_arguments()):
        if name == "data":
            continue
        expect = group2ctx["stage1" if name in stage1 else "stage2"]
        assert next(iter(arr._data.devices())) == expect.jax_device, name
    res = ex.forward(is_train=True, data=mx.nd.ones((2, 8)),
                     softmax_label=mx.nd.zeros((2,)))[0].asnumpy()
    assert res.shape == (2, 4)
    np.testing.assert_allclose(res.sum(axis=1), np.ones(2), rtol=1e-5)
    ex.backward()
    for g, name in zip(ex.grad_arrays, out.list_arguments()):
        if g is None or name in ("data", "softmax_label"):
            continue
        expect = group2ctx["stage1" if name in stage1 else "stage2"]
        assert next(iter(g._data.devices())) == expect.jax_device, name
        assert float(np.abs(g.asnumpy()).sum()) >= 0  # materialized

    # caller arrays on the WRONG device are refused (reference
    # AssignContext ctx-mismatch check), not silently relocated
    import pytest as _pytest
    w_wrong = mx.nd.ones((16, 8))  # default device, stage1 wants cpu(1)
    with _pytest.raises(ValueError, match="ctx_group"):
        out.bind(mx.cpu(0), args={"data": mx.nd.ones((2, 8)),
                                  "fc1_weight": w_wrong},
                 group2ctx=group2ctx, grad_req="null")
