"""Pluggable graph-pass / subgraph framework (reference:
src/operator/subgraph/subgraph_property.h, build_subgraph.cc;
tests/python/unittest/test_subgraph_op.py)."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.symbol import subgraph
from mxnet_tpu.symbol.symbol import Symbol, _topo


def _eval(sym, **inputs):
    ex = sym.bind(None, {k: mx.nd.array(v) for k, v in inputs.items()})
    return ex.forward()[0].asnumpy()


def test_register_and_apply_pass():
    @subgraph.register_pass("__test_double_consts")
    def double_scalars(sym, **kw):
        def fn(node, new_inputs):
            if node.op == "broadcast_mul":
                out = Symbol(node.kind, node.name, "broadcast_add",
                             dict(node.attrs), new_inputs, node.index)
                out._attr_map = dict(node._attr_map)
                return out
            return None
        return subgraph.rewrite_nodes(sym, fn)

    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    s = a * b
    s2 = subgraph.apply_pass(s, "__test_double_consts")
    x = np.array([2.0, 3.0], np.float32)
    y = np.array([4.0, 5.0], np.float32)
    np.testing.assert_allclose(_eval(s2, a=x, b=y), x + y)
    assert "__test_double_consts" in subgraph.list_passes()


def test_rewrite_preserves_shared_subexpressions():
    a = mx.sym.Variable("a")
    shared = mx.sym.relu(a)
    s = shared + shared * shared
    count_before = sum(1 for n in _topo(s) if n.op == "relu")
    rebuilt = subgraph.rewrite_nodes(s, lambda n, i: None)
    count_after = sum(1 for n in _topo(rebuilt) if n.op == "relu")
    assert count_before == count_after == 1


class _FuseAddRelu(subgraph.SubgraphProperty):
    """Fuse relu(x + y) into a single custom node — the shape of the
    reference's MKLDNN conv+relu fusion property."""

    def select(self, node):
        return node.op in ("broadcast_add", "relu")

    def create_subgraph_node(self, nodes, inputs):
        ops = {n.op for n in nodes}
        if ops == {"relu", "broadcast_add"}:
            from mxnet_tpu.symbol.symbol import _make_op_node
            # LeakyReLU slope 0 == relu; demonstrate an op swap over the
            # fused group
            add = _make_op_node("broadcast_add", list(inputs), {})
            return _make_op_node("Activation", [add],
                                 {"act_type": "relu"})
        # single-op group: keep as-is
        from mxnet_tpu.symbol.symbol import _make_op_node
        return _make_op_node(nodes[0].op, list(inputs),
                             dict(nodes[0].attrs))


def test_subgraph_property_fusion():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    s = mx.sym.relu(a + b)
    fused = subgraph.build_subgraph(s, _FuseAddRelu())
    ops = [n.op for n in _topo(fused) if n.kind == "op"]
    assert "Activation" in ops, ops
    x = np.array([[-1.0, 2.0]], np.float32)
    y = np.array([[0.5, -3.0]], np.float32)
    np.testing.assert_allclose(_eval(fused, a=x, b=y),
                               np.maximum(x + y, 0))


def test_builtin_passes_registered():
    # quantization + AMP register themselves on the pass registry
    import mxnet_tpu.contrib.quantization  # noqa: F401
    import mxnet_tpu.amp  # noqa: F401
    passes = subgraph.list_passes()
    assert "QuantizeGraph" in passes
    assert "AMPLowPrecision" in passes


def test_amp_pass_through_registry():
    a = mx.sym.Variable("a")
    s = mx.sym.FullyConnected(a, num_hidden=4, name="fc")
    recolored = subgraph.apply_pass(s, "AMPLowPrecision",
                                    target_dtype="bfloat16")
    ops = [n.op for n in _topo(recolored) if n.kind == "op"]
    assert "cast" in ops


def test_config_registry():
    """Typed knob registry (reference env_var.md as code; SURVEY 5.6)."""
    import os
    from mxnet_tpu import config
    assert "engine.type" in config.knobs()
    table = config.describe()
    assert "MXNET_ENGINE_TYPE" in table and "NaiveEngine" in table
    # env override
    os.environ["MXNET_PROFILER_AUTOSTART"] = "1"
    try:
        assert config.get("profiler.autostart") is True
    finally:
        del os.environ["MXNET_PROFILER_AUTOSTART"]
    assert config.get("profiler.autostart") is False
    # programmatic override wins
    config.set("engine.bulk_size", 3)
    assert config.get("engine.bulk_size") == 3
    import pytest
    with pytest.raises(KeyError):
        config.set("not.a.knob", 1)


def test_config_source_tracks_override_env_default(monkeypatch):
    from mxnet_tpu import config
    monkeypatch.delenv("MXNET_TPU_REMAT", raising=False)
    config.unset("runtime.remat")
    assert config.source("runtime.remat") == "default"
    monkeypatch.setenv("MXNET_TPU_REMAT", "dots")
    assert config.source("runtime.remat") == "env"
    assert config.get("runtime.remat") == "dots"
    config.set("runtime.remat", "full")
    assert config.source("runtime.remat") == "override"
    config.unset("runtime.remat")
    assert config.source("runtime.remat") == "env"


def test_config_unset_restores_default_and_bumps_epoch():
    import pytest
    from mxnet_tpu import config
    config.unset("runtime.stack_mode")
    e0 = config.epoch()
    config.unset("runtime.stack_mode")      # no override: no-op
    assert config.epoch() == e0
    config.set("runtime.stack_mode", "unroll")
    config.unset("runtime.stack_mode")
    assert config.get("runtime.stack_mode") == "scan"
    assert config.source("runtime.stack_mode") == "default"
    assert config.epoch() > e0
    with pytest.raises(KeyError):
        config.unset("no.such.knob")


def test_config_epoch_follows_the_source():
    """Making a default an explicit choice moves the epoch even when the
    value stays (routing reads the source: kernels._route_reason), and so
    does dropping the override; the same override again does not."""
    from mxnet_tpu import config
    config.unset("kernels.enabled")
    default = config.get("kernels.enabled")
    e0 = config.epoch()
    config.set("kernels.enabled", default)
    assert config.source("kernels.enabled") == "override"
    e1 = config.epoch()
    assert e1 > e0
    config.set("kernels.enabled", default)
    assert config.epoch() == e1
    config.unset("kernels.enabled")
    assert config.get("kernels.enabled") == default
    assert config.epoch() > e1


def test_subgraph_stacked_matches():
    """relu(a + relu(b + c)) — stacked matches must form ONE well-formed
    group whose externals are exactly the outside inputs (regression: the
    first implementation zipped replaced-node inputs against originals)."""
    captured = []

    class Capture(subgraph.SubgraphProperty):
        def select(self, node):
            return node.op in ("broadcast_add", "relu")

        def create_subgraph_node(self, nodes, inputs):
            captured.append(([n.op for n in nodes], len(inputs)))
            from mxnet_tpu.symbol.symbol import _make_op_node
            # reconstruct the group faithfully: in-group inputs come from
            # the already-rebuilt member, externals in group order
            inside = {id(n) for n in nodes}
            rebuilt = {}
            it = iter(inputs)
            out = None
            for n in nodes:
                args = [rebuilt[id(x)] if id(x) in inside else next(it)
                        for x in n.inputs]
                out = _make_op_node(n.op, args, dict(n.attrs))
                rebuilt[id(n)] = out
            return out

    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = mx.sym.Variable("c")
    s = mx.sym.relu(a + mx.sym.relu(b + c))
    fused = subgraph.build_subgraph(s, Capture())
    assert len(captured) == 1, captured
    ops, n_ext = captured[0]
    assert ops == ["broadcast_add", "relu", "broadcast_add", "relu"], ops
    assert n_ext == 3, "externals must be exactly {a, b, c}"
    x = {"a": np.array([0.5, -2.0], np.float32),
         "b": np.array([1.0, 1.0], np.float32),
         "c": np.array([-0.4, 0.2], np.float32)}
    want = np.maximum(x["a"] + np.maximum(x["b"] + x["c"], 0), 0)
    np.testing.assert_allclose(_eval(fused, **x), want)


def test_subgraph_shared_producer_not_absorbed():
    """x = relu(a); s = x + x — a selected producer with TWO consumers must
    NOT be absorbed (its output escapes), and shared compute stays shared."""
    class P(subgraph.SubgraphProperty):
        def select(self, node):
            return node.op in ("relu", "broadcast_add")

        def create_subgraph_node(self, nodes, inputs):
            from mxnet_tpu.symbol.symbol import _make_op_node
            assert len(nodes) == 1, [n.op for n in nodes]
            return _make_op_node(nodes[0].op, list(inputs),
                                 dict(nodes[0].attrs))

    a = mx.sym.Variable("a")
    x = mx.sym.relu(a)
    s = x + x
    fused = subgraph.build_subgraph(s, P())
    relus = [n for n in _topo(fused) if n.op == "relu"]
    assert len(relus) == 1, "shared relu must stay shared"
    av = np.array([-1.0, 3.0], np.float32)
    np.testing.assert_allclose(_eval(fused, a=av),
                               2 * np.maximum(av, 0))


def test_subgraph_head_output_not_absorbed():
    """A selected node that is also a GRAPH HEAD escapes the group even
    with a single op consumer — absorbing it would duplicate its compute
    (regression for the head-escape rule)."""
    class P(subgraph.SubgraphProperty):
        def select(self, node):
            return node.op in ("relu", "broadcast_add")

        def create_subgraph_node(self, nodes, inputs):
            from mxnet_tpu.symbol.symbol import _make_op_node
            inside = {id(n) for n in nodes}
            rebuilt = {}
            it = iter(inputs)
            out = None
            for n in nodes:
                args = [rebuilt[id(x)] if id(x) in inside else next(it)
                        for x in n.inputs]
                out = _make_op_node(n.op, args, dict(n.attrs))
                rebuilt[id(n)] = out
            return out

    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    x = mx.sym.relu(a)
    y = x + b
    g = mx.sym.Group([x, y])
    fused = subgraph.build_subgraph(g, P())
    relus = [n for n in _topo(fused) if n.op == "relu"]
    assert len(relus) == 1, "head relu must stay shared, not duplicated"
    av = np.array([-1.0, 2.0], np.float32)
    bv = np.array([0.5, 0.5], np.float32)
    ex = fused.bind(None, {"a": mx.nd.array(av), "b": mx.nd.array(bv)})
    outs = ex.forward()
    np.testing.assert_allclose(outs[0].asnumpy(), np.maximum(av, 0))
    np.testing.assert_allclose(outs[1].asnumpy(),
                               np.maximum(av, 0) + bv)
