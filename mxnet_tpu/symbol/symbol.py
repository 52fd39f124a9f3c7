"""Symbol — lazy graph-composition API over the op registry.

Reference: python/mxnet/symbol/symbol.py (`Symbol`, compose without data,
bind/simple_bind at symbol.py:1500+ incl. the ``group2ctx`` model-parallel
arg) over the NNVM C++ graph (3rdparty/tvm/nnvm).  The reference keeps a
C++-side node graph and runs optimization passes (src/executor/
graph_executor.cc:388 Init pipeline) before creating engine ops.

TPU-native re-design: a Symbol is an immutable Python DAG node naming a
registered pure op.  "Binding" does not build an executor machine — it traces
the DAG once into a pure jax function and ``jit``s it; XLA then does
everything the reference's pass pipeline did (shape/type propagation at trace
time, memory planning, fusion, scheduling).  Gradient executors come from
``jax.vjp`` of the same traced function, replacing the MXGradient graph pass
(src/nnvm/gradient.cc:104).  Multi-device placement (``group2ctx``) becomes
sharding annotations, not device assignment.
"""
from __future__ import annotations

import json

import numpy as _np
import jax
import jax.numpy as jnp

from ..ops import registry as _registry
from .. import random as _random
from ..base import dtype_np
from ..context import current_context

__all__ = ["Symbol", "Variable", "var", "Group", "load", "load_json",
           "Executor", "zeros", "ones"]


class Symbol:
    """Immutable graph node.

    kind: 'var' (named input), 'op' (registered op applied to inputs),
    'slice' (select one output of a multi-output node), 'group' (tuple of
    heads, reference: mx.sym.Group).
    ``inputs`` entries are Symbols or Python/numpy constants (scalars embed
    directly, matching ``sym + 1``).
    """

    __slots__ = ("kind", "name", "op", "attrs", "inputs", "index", "_attr_map")

    def __init__(self, kind, name, op=None, attrs=None, inputs=(), index=0):
        self.kind = kind
        self.name = name
        self.op = op
        self.attrs = attrs or {}
        self.inputs = list(inputs)
        self.index = index
        self._attr_map = {}

    # ------------------------------------------------------------- identity
    def __repr__(self):
        return "<Symbol %s>" % (self.name,)

    def attr(self, key):
        return self._attr_map.get(key)

    def attr_dict(self):
        out = {}
        for node in _topo(self):
            if node._attr_map:
                out[node.name] = dict(node._attr_map)
        return out

    def _set_attr(self, **kwargs):
        self._attr_map.update(kwargs)
        return self

    # ------------------------------------------------------------ listings
    def list_arguments(self):
        """Names of all variable leaves in topological order (reference:
        Symbol.list_arguments), aux states excluded."""
        return [n.name for n in _topo(self)
                if n.kind == "var" and not _is_aux_name(n.name)]

    def list_auxiliary_states(self):
        return [n.name for n in _topo(self)
                if n.kind == "var" and _is_aux_name(n.name)]

    def list_inputs(self):
        return [n.name for n in _topo(self) if n.kind == "var"]

    def list_outputs(self):
        """One name per actual output — multi-output heads expand to
        ``name_output0..N`` so output_dict/monitor callbacks stay aligned
        with forward()'s output list."""
        names = []
        for h in self._heads():
            n = _node_num_outputs(h)
            if n > 1 and h.kind == "op" and self.kind != "group":
                names.extend("%s_output%d" % (h.name, i) for i in range(n))
            elif h.kind == "var":
                names.append(h.name)
            else:
                names.append(h.name + "_output")
        return names

    @property
    def num_outputs(self):
        return len(self._heads())

    def _heads(self):
        if self.kind == "group":
            return list(self.inputs)
        return [self]

    def __iter__(self):
        heads = self._heads()
        if len(heads) == 1:
            # a single multi-output op iterates its outputs
            n = _node_num_outputs(heads[0])
            if n > 1:
                return iter([heads[0][i] for i in range(n)])
        return iter(heads)

    def __getitem__(self, idx):
        if isinstance(idx, str):
            names = self.list_outputs()
            idx = names.index(idx)
        if self.kind == "group":
            return self.inputs[idx]
        if _node_num_outputs(self) > 1:
            return Symbol("slice", "%s%d" % (self.name, idx),
                          inputs=[self], index=idx)
        if idx != 0:
            raise IndexError("output index %d out of range" % idx)
        return self

    def get_internals(self):
        """Group of every node's outputs (reference: Symbol.get_internals,
        used to tap intermediate features e.g. for fine-tuning)."""
        return Group([n if n.kind == "var" else n
                      for n in _topo(self)])

    def get_children(self):
        ins = [i for i in self.inputs if isinstance(i, Symbol)]
        return Group(ins) if ins else None

    # ----------------------------------------------------------- operators
    def _binop(self, opname, other, reverse=False):
        a, b = (other, self) if reverse else (self, other)
        return _make_op_node(opname, [a, b], {})

    def __add__(self, o): return self._binop("broadcast_add", o)
    def __radd__(self, o): return self._binop("broadcast_add", o, True)
    def __sub__(self, o): return self._binop("broadcast_sub", o)
    def __rsub__(self, o): return self._binop("broadcast_sub", o, True)
    def __mul__(self, o): return self._binop("broadcast_mul", o)
    def __rmul__(self, o): return self._binop("broadcast_mul", o, True)
    def __truediv__(self, o): return self._binop("broadcast_div", o)
    def __rtruediv__(self, o): return self._binop("broadcast_div", o, True)
    def __pow__(self, o): return self._binop("broadcast_power", o)
    def __neg__(self): return _make_op_node("negative", [self], {})
    def __eq__(self, o): return self._binop("broadcast_equal", o)
    def __ne__(self, o): return self._binop("broadcast_not_equal", o)
    def __lt__(self, o): return self._binop("broadcast_lesser", o)
    def __le__(self, o): return self._binop("broadcast_lesser_equal", o)
    def __gt__(self, o): return self._binop("broadcast_greater", o)
    def __ge__(self, o): return self._binop("broadcast_greater_equal", o)
    __hash__ = object.__hash__

    def __getattr__(self, name):
        # method-style op application: sym.reshape(...), sym.mean(...) —
        # mirrors NDArray's generated methods
        if name.startswith("_"):
            raise AttributeError(name)
        try:
            _registry.get(name)
        except AttributeError:
            raise AttributeError("Symbol has no attribute %r" % (name,)) \
                from None

        def method(*args, **kwargs):
            return _make_op_node(name, [self] + list(args), kwargs)
        method.__name__ = name
        return method

    # ----------------------------------------------------- shape/type infer
    def infer_shape(self, *args_shapes, **kwargs):
        """Returns (arg_shapes, out_shapes, aux_shapes) — reference
        Symbol.infer_shape.  Partial: parameter shapes are derived from data
        shapes via per-op reverse rules + jax.eval_shape forward propagation
        (replacing src/executor/infer_graph_attr_pass.cc).  Unknown shapes
        come back as None."""
        if args_shapes:
            kwargs.update(zip(self.list_arguments(), args_shapes))
        known = {n: tuple(v) for n, v in kwargs.items() if v is not None}
        var_shapes, out_shapes = _infer_shapes_partial(self, known)
        args = self.list_arguments()
        aux = self.list_auxiliary_states()
        arg_res = [var_shapes.get(n) for n in args]
        aux_res = [var_shapes.get(n) for n in aux]
        out_res = []
        for h in self._heads():
            n = _node_num_outputs(h)
            if n > 1 and h.kind == "op" and self.kind != "group":
                out_res.extend(out_shapes.get((id(h), i)) for i in range(n))
            else:
                base, idx = _unwrap_slice(h)
                out_res.append(out_shapes.get((id(base), idx)))
        return arg_res, out_res, aux_res

    def infer_type(self, **kwargs):
        """All-float32 default typing (the framework computes in f32/bf16 by
        policy — see mx.amp — rather than per-arg dtype solving)."""
        args = self.list_arguments()
        aux = self.list_auxiliary_states()
        f32 = _np.dtype(_np.float32)
        return ([_np.dtype(kwargs.get(n, f32)) for n in args],
                [f32] * len(self.list_outputs()), [f32] * len(aux))

    # -------------------------------------------------------------- binding
    def simple_bind(self, ctx=None, grad_req="write", type_dict=None,
                    group2ctx=None, shared_exec=None, **kwargs):
        """Allocate arguments from shapes and bind (reference:
        MXExecutorSimpleBindEx, src/c_api/c_api_executor.cc:860)."""
        return self._simple_bind_shapes(kwargs, ctx=ctx, grad_req=grad_req,
                                        type_dict=type_dict,
                                        group2ctx=group2ctx)

    def _simple_bind_shapes(self, shape_map, ctx=None, grad_req="write",
                            type_dict=None, group2ctx=None):
        """Dict-based simple_bind: input names that collide with the
        kwargs API's own parameters (a Variable literally named "ctx")
        bind through here — the C ABI uses this path."""
        arg_shapes, _, aux_shapes = self.infer_shape(**dict(shape_map))
        from ..ndarray.ndarray import _wrap
        args = {}
        for name, shp in zip(self.list_arguments(), arg_shapes):
            if shp is None:
                raise ValueError(
                    "simple_bind could not infer a shape for %r — pass it "
                    "explicitly" % (name,))
            dt = (type_dict or {}).get(name, _np.float32)
            args[name] = _wrap(jnp.zeros(shp, dtype_np(dt)))
        aux = {}
        for name, shp in zip(self.list_auxiliary_states(), aux_shapes):
            if shp is None:
                raise ValueError(
                    "simple_bind could not infer a shape for aux %r" % (name,))
            aux[name] = _wrap(jnp.zeros(shp, _np.float32))
        placement = self._ctx_group_map(group2ctx)
        self._place_groups(args, placement)
        self._place_groups(aux, placement)
        args_grad = None
        if grad_req != "null":
            # grads live beside the params they update (reference: grad
            # arrays share the arg's assigned context)
            args_grad = {n: _wrap(jnp.zeros_like(v._data))
                         for n, v in args.items()}
            self._place_groups(args_grad, placement)
        return Executor(self, ctx or current_context(), args, args_grad,
                        grad_req, aux, placement=placement)

    def _ctx_group_map(self, group2ctx):
        """{var_name: Context} from each variable's ctx_group annotation
        (reference: AssignContext + group2ctx, graph_executor.cc:997)."""
        if not group2ctx:
            return {}
        out = {}
        for node in _topo(self):
            if node.kind != "var":
                continue
            grp = node._attr_map.get("ctx_group")
            if grp is not None and grp in group2ctx:
                out[node.name] = group2ctx[grp]
        return out

    @staticmethod
    def _place_groups(arrays, placement):
        """device_put each named array onto its ctx-group device: params
        RESIDE where the user assigned them (multi-chip memory
        distribution); the Executor inserts the cross-device copies at
        run time like the reference's AssignContext copy nodes."""
        for n, ctx in placement.items():
            if n in arrays:
                arrays[n]._data = jax.device_put(arrays[n]._data,
                                                 ctx.jax_device)

    def bind(self, ctx=None, args=None, args_grad=None, grad_req="write",
             aux_states=None, group2ctx=None, shared_exec=None):
        """Bind with explicit arrays (reference: MXExecutorBindEX,
        src/c_api/c_api_executor.cc:135)."""
        from ..ndarray.ndarray import NDArray, _wrap
        names = self.list_arguments()
        if isinstance(args, (list, tuple)):
            args = dict(zip(names, args))
        args = dict(args or {})
        aux_names = self.list_auxiliary_states()
        if isinstance(aux_states, (list, tuple)):
            aux_states = dict(zip(aux_names, aux_states))
        aux_states = dict(aux_states or {})
        user_owned = {n for pool in (args, aux_states)
                      for n, v in pool.items() if isinstance(v, NDArray)}
        args = {n: (v if isinstance(v, NDArray) else _wrap(jnp.asarray(v)))
                for n, v in args.items()}
        aux_states = {n: (v if isinstance(v, NDArray)
                          else _wrap(jnp.asarray(v)))
                      for n, v in aux_states.items()}
        if isinstance(args_grad, (list, tuple)):
            args_grad = dict(zip(names, args_grad))
        args_grad = dict(args_grad or {}) or None
        if args_grad:
            user_owned |= {n for n, v in args_grad.items()
                           if isinstance(v, NDArray)}
            args_grad = {n: (v if isinstance(v, NDArray)
                             else _wrap(jnp.asarray(v)))
                         for n, v in args_grad.items()}
        placement = self._ctx_group_map(group2ctx)
        # caller-owned NDArrays must already sit on their assigned device
        # (the reference ERRORS on a ctx mismatch rather than silently
        # relocating user data); arrays we wrapped fresh get placed
        for n, c in placement.items():
            for pool in (args, aux_states) + ((args_grad,) if args_grad
                                              else ()):
                v = pool.get(n)
                if v is None:
                    continue
                try:
                    want = c.jax_device
                    dev = next(iter(v._data.devices()))
                except Exception:  # noqa: BLE001 — uncommitted values
                    continue
                if dev == want:
                    continue
                if n in user_owned:
                    raise ValueError(
                        "bind: argument %r lives on %s but its ctx_group "
                        "assigns %s — create it on the assigned device "
                        "(reference AssignContext ctx-mismatch check)"
                        % (n, dev, want))
                v._data = jax.device_put(v._data, want)
        return Executor(self, ctx or current_context(), args, args_grad,
                        grad_req, aux_states, placement=placement)

    def eval(self, ctx=None, **kwargs):
        """One-shot forward (reference: Symbol.eval)."""
        ex = self.bind(ctx, args=kwargs)
        return ex.forward()

    # -------------------------------------------------------- serialization
    def tojson(self):
        """Graph JSON — same concept as the reference's symbol.json
        (MXSymbolSaveToJSON, src/c_api/c_api_symbolic.cc:500); own schema."""
        nodes = _topo(self)
        nid = {id(n): i for i, n in enumerate(nodes)}
        out_nodes = []
        for n in nodes:
            ins = []
            for x in n.inputs:
                if isinstance(x, Symbol):
                    ins.append(["node", nid[id(x)]])
                else:
                    ins.append(["const", _np.asarray(x).tolist()])
            out_nodes.append({
                "kind": n.kind, "name": n.name, "op": n.op,
                "attrs": _json_attrs(n.attrs), "inputs": ins,
                "index": n.index, "attr_map": n._attr_map,
            })
        heads = [nid[id(h)] for h in self._heads()]
        return json.dumps({"nodes": out_nodes, "heads": heads,
                           "format": "mxnet_tpu-symbol-v1"}, indent=2)

    def save(self, fname):
        from .. import resilience as _resilience
        with _resilience.atomic_write(fname, "w") as f:
            f.write(self.tojson())


def _json_attrs(attrs):
    out = {}
    for k, v in attrs.items():
        if isinstance(v, _np.dtype):
            v = v.name
        elif isinstance(v, type):
            v = _np.dtype(v).name
        elif isinstance(v, tuple):
            v = list(v)
        out[k] = v
    return out


def load_json(s):
    from ..compat import is_mxnet_symbol_json, load_mxnet_symbol
    if is_mxnet_symbol_json(s):
        # a REAL Apache-MXNet symbol.json (NNVM graph schema): replay it
        # through the native builders so existing models load as-is
        return load_mxnet_symbol(s)
    data = json.loads(s)
    nodes = []
    for spec in data["nodes"]:
        ins = []
        for kind, val in spec["inputs"]:
            ins.append(nodes[val] if kind == "node" else val)
        n = Symbol(spec["kind"], spec["name"], spec.get("op"),
                   spec.get("attrs") or {}, ins, spec.get("index", 0))
        n._attr_map = spec.get("attr_map") or {}
        nodes.append(n)
    heads = [nodes[i] for i in data["heads"]]
    return heads[0] if len(heads) == 1 else Group(heads)


def load(fname):
    with open(fname) as f:
        return load_json(f.read())


# ------------------------------------------------------------ constructors

def Variable(name, shape=None, dtype=None, init=None, **attr_kwargs):
    s = Symbol("var", name)
    if shape is not None:
        s.attrs["shape"] = tuple(shape)
    if dtype is not None:
        s.attrs["dtype"] = _np.dtype(dtype).name
    # AttrScope annotations apply to Variables too (the scope's primary
    # consumers are parameter attrs: lr_mult/__init__/ctx_group), with
    # explicit per-variable attrs winning over the scope
    from ..attribute import AttrScope
    s._attr_map.update(AttrScope.current_attrs())
    if init is not None:
        # reference Variable(init=...) serializes the initializer into the
        # __init__ attr (python/mxnet/symbol/symbol.py Variable); InitDesc
        # routes it back through Initializer.__call__ at init_params time
        s._attr_map["__init__"] = init if isinstance(init, str) else \
            init.dumps()
    s._attr_map.update({k: str(v) for k, v in attr_kwargs.items()})
    return s


var = Variable


def Group(symbols):
    symbols = list(symbols)
    return Symbol("group", "group", inputs=symbols)


def zeros(shape, dtype="float32", **_):
    return _make_op_node("_zeros_shape", [],
                         {"shape": tuple(shape), "dtype": dtype})


def ones(shape, dtype="float32", **_):
    return _make_op_node("_ones_shape", [],
                         {"shape": tuple(shape), "dtype": dtype})


def _fill_shape(shape):
    # Reference shape semantics: a 0 dim means "unknown, solve at bind"
    # (mx.sym.zeros(shape=(0, H)) is how RNN cells spell batch-agnostic
    # begin_state, python/mxnet/rnn/rnn_cell.py:190-223).  The reference
    # runs bidirectional shape inference to fill it; here inference is
    # forward-only, so unknown dims lower to size 1 and XLA broadcasting
    # carries them — every consumer of a begin_state symbol is broadcast
    # math (broadcast_add/mul, FullyConnected over a batch of 1, the RNN
    # op's explicit state broadcast).
    return tuple(1 if s == 0 else s for s in shape)


_registry.register("_zeros_shape", differentiable=False)(
    lambda shape=(), dtype="float32", **_:
        jnp.zeros(_fill_shape(shape), dtype_np(dtype)))
_registry.register("_ones_shape", differentiable=False)(
    lambda shape=(), dtype="float32", **_:
        jnp.ones(_fill_shape(shape), dtype_np(dtype)))


_NAME_COUNTER = {}


def _auto_name(opname):
    base = opname.lower().lstrip("_")
    i = _NAME_COUNTER.get(base, 0)
    _NAME_COUNTER[base] = i + 1
    return "%s%d" % (base, i)


# Learnable-input slots per layer op.  Reference parity: the NNVM registry
# lists named inputs (FListInputNames) and the Python wrapper auto-creates
# missing weight/bias Variables named "{name}_{slot}"
# (python/mxnet/symbol/symbol.py generated ops).
_OP_INPUT_SLOTS = {
    "FullyConnected": ("data", "weight", "bias"),
    "Convolution": ("data", "weight", "bias"),
    "_contrib_quantized_fully_connected": ("data", "weight", "bias"),
    "_contrib_quantized_conv": ("data", "weight", "bias"),
    "Deconvolution": ("data", "weight", "bias"),
    "BatchNorm": ("data", "gamma", "beta", "moving_mean", "moving_var"),
    "LayerNorm": ("data", "gamma", "beta"),
    "GroupNorm": ("data", "gamma", "beta"),
    "InstanceNorm": ("data", "gamma", "beta"),
    "Embedding": ("data", "weight"),
    # output-loss ops auto-create their label input as "{name}_label"
    # (reference: mx.symbol.SoftmaxOutput(fc, name='sm') binds 'sm_label')
    "SoftmaxOutput": ("data", "label"),
    "LinearRegressionOutput": ("data", "label"),
    "LogisticRegressionOutput": ("data", "label"),
    "MAERegressionOutput": ("data", "label"),
    # fused RNN (reference src/operator/rnn.cc:652): parameters is the flat
    # cuDNN-layout blob; state_cell exists only in lstm mode
    "RNN": ("data", "parameters", "state", "state_cell"),
}


def _make_op_node(opname, inputs, attrs):
    op = _registry.get(opname)  # raises AttributeError for unknown ops
    name = attrs.pop("name", None) or _auto_name(opname)
    slots = _OP_INPUT_SLOTS.get(op.name)
    if slots:
        slot_vals = {}
        for i, x in enumerate(inputs):
            slot_vals[slots[i]] = x
        for s in slots:
            if s in attrs:
                slot_vals[s] = attrs.pop(s)
        no_bias = bool(attrs.get("no_bias", False))
        inputs = []
        for s in slots:
            v = slot_vals.get(s)
            if v is None:
                if s == "bias" and no_bias:
                    inputs.append(None)
                    continue
                if s == "state_cell" and attrs.get("mode", "lstm") != "lstm":
                    inputs.append(None)
                    continue
                if s == "data":
                    raise ValueError("%s: missing data input" % (op.name,))
                v = Variable("%s_%s" % (name, s))
            inputs.append(v)
    else:
        if "data" in attrs and not inputs:
            inputs = [attrs.pop("data")]
    norm_inputs = []
    for x in inputs:
        from ..ndarray.ndarray import NDArray
        if isinstance(x, NDArray):
            x = x._data  # constant capture
        norm_inputs.append(x)
    node = Symbol("op", name, op=op.name, attrs=attrs, inputs=norm_inputs)
    # annotation attrs from the enclosing AttrScope (ctx_group, lr_mult...)
    from ..attribute import AttrScope
    scope_attrs = AttrScope.current_attrs()
    if scope_attrs:
        node._attr_map.update(scope_attrs)
    return node


# Parameter-shape rules: given op attrs + the data-input shape, the shapes of
# learnable inputs.  This is the *reverse* half of the reference's per-op
# FInferShape (e.g. src/operator/nn/fully_connected.cc shape fn deriving
# weight=(num_hidden, in_dim)); the forward half is jax.eval_shape per node.
def _fc_param_shapes(attrs, dshape):
    nh = int(attrs["num_hidden"])
    flatten = attrs.get("flatten", True)
    in_dim = int(_np.prod(dshape[1:])) if flatten else dshape[-1]
    return {1: (nh, in_dim), 2: (nh,)}


def _conv_param_shapes(attrs, dshape):
    nf = int(attrs["num_filter"])
    kernel = tuple(attrs["kernel"])
    groups = int(attrs.get("num_group", 1))
    return {1: (nf, dshape[1] // groups) + kernel, 2: (nf,)}


def _deconv_param_shapes(attrs, dshape):
    nf = int(attrs["num_filter"])
    kernel = tuple(attrs["kernel"])
    return {1: (dshape[1], nf) + kernel, 2: (nf,)}


def _bn_param_shapes(attrs, dshape):
    axis = int(attrs.get("axis", 1))
    c = dshape[axis]
    return {1: (c,), 2: (c,), 3: (c,), 4: (c,)}


def _ln_param_shapes(attrs, dshape):
    axis = int(attrs.get("axis", -1))
    return {1: (dshape[axis],), 2: (dshape[axis],)}


def _in_param_shapes(attrs, dshape):
    return {1: (dshape[1],), 2: (dshape[1],)}


def _emb_param_shapes(attrs, dshape):
    return {1: (int(attrs["input_dim"]), int(attrs["output_dim"]))}


def _rnn_param_shapes(attrs, dshape):
    # data is TNC (T, B, I); parameters is the flat cuDNN-layout blob
    # (reference src/operator/rnn-inl.h GetRnnParamSize)
    from ..rnn._fused_layout import fused_rnn_param_size
    h = int(attrs["state_size"])
    layers = int(attrs.get("num_layers", 1))
    bi = str(attrs.get("bidirectional", False)) in ("True", "true", "1")
    mode = attrs.get("mode", "lstm")
    d = 2 if bi else 1
    total = fused_rnn_param_size(dshape[2], h, layers, mode, bi)
    state = (layers * d, dshape[1], h)
    shapes = {1: (total,), 2: state}
    if mode == "lstm":
        shapes[3] = state
    return shapes


_INT_DATA_OPS = {"Embedding", "one_hot", "take"}

# unary ops that preserve their input's shape — partial shape inference may
# propagate parameter shapes through them
_SHAPE_TRANSPARENT = {"cast", "_sim_quant", "identity", "BlockGrad",
                      "Dropout", "make_loss", "negative", "relu", "abs"}

def _softmax_output_label_shape(attrs, dshape):
    # reference SoftmaxOutput FInferShape: label is (N,) class indices
    return {1: (dshape[0],)}


def _regression_output_label_shape(attrs, dshape):
    # *RegressionOutput: label matches the prediction shape
    return {1: tuple(dshape)}


_PARAM_SHAPE_RULES = {
    "SoftmaxOutput": _softmax_output_label_shape,
    "LinearRegressionOutput": _regression_output_label_shape,
    "LogisticRegressionOutput": _regression_output_label_shape,
    "MAERegressionOutput": _regression_output_label_shape,
    "FullyConnected": _fc_param_shapes,
    "Convolution": _conv_param_shapes,
    "_contrib_quantized_fully_connected": _fc_param_shapes,
    "_contrib_quantized_conv": _conv_param_shapes,
    "Deconvolution": _deconv_param_shapes,
    "BatchNorm": _bn_param_shapes,
    "LayerNorm": _ln_param_shapes,
    "GroupNorm": _in_param_shapes,
    "InstanceNorm": _in_param_shapes,
    "Embedding": _emb_param_shapes,
    "RNN": _rnn_param_shapes,
}


def _infer_shapes_partial(sym, known, dtypes=None):
    """Forward shape propagation with reverse param rules — the TPU-native
    stand-in for the reference's iterative InferShape pass
    (src/executor/infer_graph_attr_pass.cc).  Returns
    {var_name: shape} ∪ known, {(node_id, out_idx): shape}."""
    var_shapes = dict(known)
    out_shapes = {}

    def in_shape(x):
        if not isinstance(x, Symbol):
            a = _np.asarray(x)
            return tuple(a.shape)
        if x.kind == "var":
            if x.name in var_shapes:
                return var_shapes[x.name]
            if "shape" in x.attrs:
                return tuple(x.attrs["shape"])
            return None
        base, idx = _unwrap_slice(x)
        return out_shapes.get((id(base), idx))

    for node in _topo(sym):
        if node.kind == "var":
            s = in_shape(node)
            if s is not None:
                out_shapes[(id(node), 0)] = s
            continue
        if node.kind == "slice":
            s = out_shapes.get((id(node.inputs[0]), node.index))
            if s is not None:
                out_shapes[(id(node), 0)] = s
            continue
        if node.kind != "op":
            continue
        shapes = [in_shape(x) if x is not None else None
                  for x in node.inputs]
        rule = _PARAM_SHAPE_RULES.get(node.op)
        if rule is not None and shapes and shapes[0] is not None:
            derived = rule(node.attrs, shapes[0])
            for i, shp in derived.items():
                if i >= len(node.inputs) or shapes[i] is not None or \
                        not isinstance(node.inputs[i], Symbol):
                    continue
                # follow shape-preserving unary wrappers (cast/_sim_quant/
                # BlockGrad...) down to the parameter variable they wrap —
                # AMP and quantization passes interpose these
                chain = [node.inputs[i]]
                while chain[-1].kind == "op" and \
                        chain[-1].op in _SHAPE_TRANSPARENT and \
                        isinstance(chain[-1].inputs[0], Symbol):
                    chain.append(chain[-1].inputs[0])
                leaf = chain[-1]
                if leaf.kind != "var":
                    continue
                shapes[i] = tuple(shp)
                var_shapes[leaf.name] = tuple(shp)
                for c in chain:
                    out_shapes[(id(c), 0)] = tuple(shp)
        if any(s is None and x is not None
               for s, x in zip(shapes, node.inputs)):
            continue  # unknown inputs: leave this node's outputs unknown
        op = _registry.get(node.op)
        specs = []
        for s, x in zip(shapes, node.inputs):
            if x is None:
                specs.append(None)
            elif isinstance(x, Symbol):
                specs.append(jax.ShapeDtypeStruct(s, _np.float32))
            else:
                specs.append(x)
        if node.op in _INT_DATA_OPS and isinstance(specs[0],
                                                   jax.ShapeDtypeStruct):
            specs[0] = jax.ShapeDtypeStruct(specs[0].shape, _np.int32)
        attrs = dict(node.attrs)
        if node.op in _AUX_UPDATE_RULES or node.op in _STOCHASTIC_OPS:
            attrs["training"] = False
        try:
            res = jax.eval_shape(lambda *a: op.fn(*a, **attrs), *specs)
        except Exception:
            continue
        outs = list(res) if isinstance(res, (tuple, list)) else [res]
        for i, o in enumerate(outs):
            out_shapes[(id(node), i)] = tuple(o.shape)
    return var_shapes, out_shapes


# ----------------------------------------------------------------- traversal

def _topo(sym):
    """Post-order unique traversal."""
    seen = set()
    order = []

    def visit(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        for x in n.inputs:
            if isinstance(x, Symbol):
                visit(x)
        order.append(n)

    visit(sym)
    if sym.kind == "group":
        # identity-based removal: Symbol.__eq__ builds graph nodes, so
        # list.remove's == comparison must never run on Symbols
        order = [n for n in order if n is not sym]
    return order


# Ops whose extra outputs are internal (reference: FNumVisibleOutputs — e.g.
# BatchNorm's (mean, var) outputs exist in the graph but are hidden from the
# user API, src/operator/nn/batch_norm.cc).
_VISIBLE_OUTPUTS = {"BatchNorm": 1}


def _unwrap_slice(x):
    """(base_node, output_index) for a symbol that may be a slice
    selector over a multi-output op."""
    if x.kind == "slice":
        return x.inputs[0], x.index
    return x, 0


def _node_num_outputs(node):
    if node.kind != "op":
        return 1
    if node.op in _VISIBLE_OUTPUTS:
        return _VISIBLE_OUTPUTS[node.op]
    op = _registry.get(node.op)
    n = op.num_outputs
    if n == -1:  # attr-dependent (split)
        return int(node.attrs.get("num_outputs", 1))
    return n


# Aux-state update rules: reference ops mutate their auxiliary inputs inside
# the kernel (e.g. BatchNorm moving stats, src/operator/nn/batch_norm.cc);
# our ops are pure, so the executor applies these write-backs explicitly.
def _bn_aux_update(node, env_in, outs):
    mom = float(node.attrs.get("momentum", 0.9))
    mm, mv = node.inputs[3], node.inputs[4]
    updates = {}
    if isinstance(mm, Symbol) and mm.kind == "var":
        updates[mm.name] = mom * env_in[3] + (1 - mom) * outs[1]
    if isinstance(mv, Symbol) and mv.kind == "var":
        updates[mv.name] = mom * env_in[4] + (1 - mom) * outs[2]
    return updates


_AUX_UPDATE_RULES = {"BatchNorm": _bn_aux_update}

_AUX_SUFFIXES = ("moving_mean", "moving_var", "running_mean", "running_var",
                 "moving_avg")


def _is_aux_name(name):
    return name.endswith(_AUX_SUFFIXES)


_STOCHASTIC_OPS = {"Dropout", "shuffle"}


def _eval_symbol(sym, env, training, aux_updates=None):
    """Interpret the DAG on jax values.  ``env`` maps var name -> array.
    Returns the list of head outputs.  Runs under jit when called from a
    bound Executor — pure apart from the explicit aux_updates dict."""
    from .. import numerics as _numerics
    taps = _numerics.collecting()
    cache = {}

    def value(node, index=0):
        key = (id(node), index)
        if key in cache:
            return cache[key]
        if node.kind == "var":
            if node.name not in env:
                raise ValueError("unbound variable %r" % (node.name,))
            out = env[node.name]
        elif node.kind == "slice":
            out = value(node.inputs[0], node.index)
        elif node.kind == "op":
            op = _registry.get(node.op)
            vals = [value(x) if isinstance(x, Symbol) else x
                    for x in node.inputs]
            attrs = dict(node.attrs)
            if node.op in _STOCHASTIC_OPS or node.op == "Dropout" \
                    or node.op in ("BatchNorm",):
                # the EXECUTOR's is_train decides train-vs-infer semantics;
                # a `training` attr baked into the node at trace/export
                # time (e.g. by a gluon layer's hybrid_forward) must not
                # win — Dropout's always-on behavior is the `mode` attr's
                # job, not `training`'s
                attrs["training"] = training
            res = op.fn(*vals, **attrs)
            multi = isinstance(res, (tuple, list))
            outs = list(res) if multi else [res]
            for i, o in enumerate(outs):
                cache[(id(node), i)] = o
            if taps:
                # per-op-output numerics tap sites (trace-time = the
                # graph's topological order); only instrumented program
                # variants ever evaluate with a collector open
                for i, o in enumerate(outs):
                    _numerics.tap(
                        node.name if not multi
                        else "%s[%d]" % (node.name, i), o)
            if training and aux_updates is not None \
                    and node.op in _AUX_UPDATE_RULES:
                aux_updates.update(
                    _AUX_UPDATE_RULES[node.op](node, vals, outs))
            out = outs[index]
        else:
            raise ValueError("cannot evaluate node kind %r" % (node.kind,))
        cache[key] = out
        return out

    heads = sym._heads()
    outs = []
    for h in heads:
        n = _node_num_outputs(h)
        if n > 1 and h.kind == "op" and sym.kind != "group":
            outs.extend(value(h, i) for i in range(n))
        else:
            outs.append(value(h, h.index if h.kind == "slice" else 0))
    return outs


# ------------------------------------------------------------------ Executor

class Executor:
    """Bound computation (reference: include/mxnet/executor.h over
    GraphExecutor).  forward/backward call into ONE jitted function per
    (training, shape-signature); XLA replaces the reference's memory planning
    + bulked engine ops (src/executor/graph_executor.cc:1016,1288)."""

    def __init__(self, sym, ctx, args, args_grad, grad_req, aux,
                 placement=None):
        self._symbol = sym
        self._ctx = ctx
        self.arg_dict = dict(args or {})
        self.grad_dict = dict(args_grad or {})
        self.aux_dict = dict(aux or {})
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self.arg_dict}
        self.grad_req = grad_req
        self.outputs = []
        self._fwd_cache = {}
        self._bwd_cache = {}
        self._fused_cache = {}
        self._monitor = None
        # ctx-group model parallelism: {name: jax.Device} where the user
        # pinned each param via group2ctx — the single source of truth the
        # forward/backward transfers, grad write-back, and
        # copy_params_from all honor
        self._placement = {}
        for n, c in (placement or {}).items():
            try:
                self._placement[n] = c.jax_device
            except Exception:  # noqa: BLE001 — backendless contexts
                pass

    # internals -----------------------------------------------------------
    def _to_exec_device(self, env):
        """Transfer any array pinned to ANOTHER device onto the executor's
        device before it feeds one jitted program — the reference's
        AssignContext cross-device copy nodes (graph_executor.cc:997).
        Same-device arrays pass through untouched."""
        if not self._placement:
            return env
        ctx = self._ctx if self._ctx is not None else current_context()
        try:
            exec_dev = ctx.jax_device
        except Exception:  # noqa: BLE001 — backendless contexts
            return env
        for n, v in env.items():
            try:
                if isinstance(v, jax.Array) and \
                        next(iter(v.devices())) != exec_dev:
                    env[n] = jax.device_put(v, exec_dev)
            except Exception:  # noqa: BLE001 — tracers/uncommitted values
                pass
        return env

    def _repin(self, name, arr):
        """Keep an array on its ctx-group device (grads and copied-in
        params stay beside the params they belong to)."""
        dev = self._placement.get(name)
        return jax.device_put(arr, dev) if dev is not None else arr

    def _env(self):
        env = {n: v._data for n, v in self.arg_dict.items()}
        env.update({n: v._data for n, v in self.aux_dict.items()})
        return self._to_exec_device(env)

    @property
    def arg_arrays(self):
        """Arg arrays in list_arguments order, None for unbound names —
        the positional correspondence the reference Executor guarantees."""
        return [self.arg_dict.get(n)
                for n in self._symbol.list_arguments()]

    @property
    def aux_arrays(self):
        return [self.aux_dict.get(n)
                for n in self._symbol.list_auxiliary_states()]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n)
                for n in self._symbol.list_arguments()]

    def _fwd_fn(self, training):
        from .. import config as _config
        # knob values bake in at trace: a config mutation (the epoch)
        # retraces
        cache_key = (training, _config.epoch())
        if cache_key not in self._fwd_cache:
            # evict programs compiled under superseded knob epochs
            self._fwd_cache = {k: v for k, v in self._fwd_cache.items()
                               if k[1] == cache_key[1]}
            sym = self._symbol

            def run(env, key):
                with _random.trace_key_scope(key):
                    aux_updates = {}
                    outs = _eval_symbol(sym, env, training, aux_updates)
                    return outs, aux_updates

            self._fwd_cache[cache_key] = jax.jit(run)
        return self._fwd_cache[cache_key]

    # public --------------------------------------------------------------
    def _feed_inputs(self, input_map):
        """Assign forward inputs by name from a dict — the collision-safe
        entry point (names like "is_train" stay legal); forward()'s
        kwargs and the C ABI bridge both route through here."""
        from ..ndarray.ndarray import NDArray, _wrap
        for n, v in input_map.items():
            arr = v._data if isinstance(v, NDArray) else jnp.asarray(v)
            if n in self.arg_dict:
                self.arg_dict[n]._data = arr
            else:
                self.arg_dict[n] = _wrap(arr)

    def forward(self, is_train=False, **kwargs):
        from .. import telemetry as _telemetry
        from .. import tracing as _tracing
        self._feed_inputs(kwargs)
        key = _random.new_eager_seed_key()
        with _telemetry.timer("executor.forward").time(), \
                _tracing.span("executor.forward", cat="executor"):
            outs, aux_updates = self._fwd_fn(bool(is_train))(
                self._env(), key)
        for n, v in aux_updates.items():
            if n in self.aux_dict:
                # pinned aux states (BN stats) stay on their ctx-group device
                self.aux_dict[n]._data = self._repin(n, v)
        from ..ndarray.ndarray import _wrap as _w2
        self.outputs = [_w2(o) for o in outs]
        if self._monitor:
            for name, arr in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor(name, arr)
        return self.outputs

    def _bwd_fn(self, wrt):
        """One jitted program computing outputs AND input gradients —
        forward + backward fuse into a single XLA executable (replacing the
        reference's separate backward graph executor,
        src/executor/graph_executor.cc:91)."""
        from .. import config as _config
        # knobs bake in at trace (see _fwd_fn)
        key_sig = (tuple(wrt), _config.epoch())
        if key_sig not in self._bwd_cache:
            # evict programs compiled under superseded knob epochs (same
            # invalidation contract as _fwd_fn: a config.set between calls
            # must retrace the fused fwd+bwd program too)
            self._bwd_cache = {k: v for k, v in self._bwd_cache.items()
                               if k[1] == key_sig[1]}
            sym = self._symbol

            def run(wrt_vals, rest_env, cts, key):
                def fwd(wv):
                    env = dict(rest_env)
                    env.update(wv)
                    with _random.trace_key_scope(key):
                        return _eval_symbol(sym, env, True, None)

                outs, vjp = jax.vjp(fwd, wrt_vals)
                if cts is None:
                    cts_ = [jnp.ones_like(o) for o in outs]
                else:
                    cts_ = list(cts)
                (grads,) = vjp(cts_)
                return outs, grads

            self._bwd_cache[key_sig] = jax.jit(run,
                                               static_argnames=())
        return self._bwd_cache[key_sig]

    def fused_step_fn(self, wrt, optimizer, feed_sig, instrument=False):
        """ONE jitted program carrying forward + backward + optimizer
        update — the CachedOp ``static_alloc=True`` analog for the symbolic
        path (reference: src/imperative/cached_op.cc StaticForward/
        StaticBackward collapse per-op dispatch; here the whole train
        iteration is a single XLA executable and XLA owns the memory plan).

        ``wrt`` is the ordered tuple of trainable arg names; ``feed_sig``
        the per-batch input shape/dtype signature.  One program per
        (wrt, feed_sig, config-epoch) — parameters, optimizer state and the
        batch are traced pytree arguments, and params/state are DONATED on
        accelerator backends so the update happens in-place in HBM.

        Signature of the returned callable::

            new_params, new_state, aux_updates, outputs = fn(
                wrt_vals, opt_state, rest_env, feeds, key, t, lrs, wds)

        lr/wd arrive as device arrays evaluated eagerly per step (the
        ``_opt_hyper_arrays`` pattern from mxnet_tpu/parallel/trainer.py),
        so lr schedulers keep working instead of constant-folding; ``t`` is
        the traced update count for bias-corrected optimizers (Adam &c).

        ``instrument=True`` builds the numerics-instrumented VARIANT of
        the program (mx.numerics): per-op tap sites inside the forward
        plus grad./update. stats per param ride out as one extra stats
        dict appended to the return tuple.  The variant is a separate
        cache entry — the plain program stays byte-identical to a build
        without taps and toggling the capture knob never evicts it.
        """
        from .. import config as _config
        from .. import numerics as _numerics
        from .. import resilience as _resilience
        sym = self._symbol
        wrt_t = tuple(wrt)
        rescale = float(optimizer.rescale_grad)
        clip = optimizer.clip_gradient
        # nanguard bakes into the trace: when armed the program takes a
        # consecutive-bad-step streak carry and returns it (5-tuple); the
        # happy-path signature is untouched when the knob is off
        guard = _resilience.nanguard_mode()
        # the program closes over the optimizer, so its identity (and the
        # scalars baked in at trace time) is part of the key; cached entries
        # keep their optimizer alive, so id() stays unambiguous
        key_sig = (id(optimizer), rescale, clip, wrt_t, feed_sig, guard) \
            + _numerics.capture_token(instrument) \
            + (_config.epoch(),)
        fn = self._fused_cache.get(key_sig)
        if fn is not None:
            return fn
        # evict programs compiled under superseded knob epochs (same
        # invalidation contract as _fwd_cache/_bwd_cache)
        self._fused_cache = {k: v for k, v in self._fused_cache.items()
                             if k[-1] == key_sig[-1]}

        def run(wrt_vals, opt_state, rest_env, feeds, key, t, lrs, wds,
                streak=None):
            env = dict(rest_env)
            env.update(feeds)

            def fwd(wv):
                e = dict(env)
                e.update(wv)
                aux_updates = {}
                with _random.trace_key_scope(key):
                    if instrument:
                        # tap values traced under vjp are vjp-internal —
                        # they escape through vjp's aux, never the outer
                        # return (a direct return would leak tracers)
                        with _numerics.collect() as fstats:
                            outs = _eval_symbol(sym, e, True, aux_updates)
                        return outs, (aux_updates, dict(fstats))
                    outs = _eval_symbol(sym, e, True, aux_updates)
                return outs, aux_updates

            outs, vjp, aux_updates = jax.vjp(fwd, wrt_vals, has_aux=True)
            stats = None
            if instrument:
                aux_updates, stats = aux_updates
            # out_grads=None semantics: ones cotangents, as in backward()
            (grads,) = vjp([jnp.ones_like(o) for o in outs])
            new_w = {}
            new_s = {}
            # stochastic optimizers (SGLD) draw from the step's traced key
            with _random.trace_key_scope(jax.random.fold_in(key, 1)):
                for i, n in enumerate(wrt_t):
                    g = grads[n] * rescale
                    if clip is not None:
                        g = jnp.clip(g, -clip, clip)
                    if stats is not None:
                        _numerics.record(stats, "grad." + n, g)
                    w, s = optimizer.step(wrt_vals[n], g, opt_state[n],
                                          lrs[i], wds[i], t)
                    new_w[n] = w.astype(wrt_vals[n].dtype)
                    new_s[n] = s
            if stats is not None:
                # pre-guard candidate updates: on a bad step these SHOW
                # the non-finite values forensics is after
                for n in wrt_t:
                    _numerics.record(stats, "update." + n, new_w[n])
            if not guard:
                if stats is not None:
                    return new_w, new_s, aux_updates, outs, stats
                return new_w, new_s, aux_updates, outs
            # non-finite step guard: keep old params/state/aux on a bad
            # step; the check stays on-device (no host sync unless the
            # bad branch actually fires)
            finite = _resilience.all_finite(outs, grads)
            new_streak = _resilience.guarded_streak(finite, streak,
                                                    "module")
            new_w = _resilience.select_tree(finite, new_w, wrt_vals)
            new_s = _resilience.select_tree(finite, new_s, opt_state)
            aux_updates = _resilience.select_tree(
                finite, aux_updates,
                {n: rest_env[n] for n in aux_updates})
            if stats is not None:
                return new_w, new_s, aux_updates, outs, new_streak, stats
            return new_w, new_s, aux_updates, outs, new_streak

        # donation needs a real accelerator: the CPU backend can't alias
        # donated buffers (it would only warn and copy anyway)
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        from .. import perf as _perf
        fn = _perf.wrap(jax.jit(run, donate_argnums=donate),
                        "module", key_sig, source="module")
        self._fused_cache[key_sig] = fn
        from .. import profiler as _profiler
        _profiler.counter_increment("fused_compiles")
        return fn

    def backward(self, out_grads=None):
        from ..ndarray.ndarray import NDArray, _wrap
        wrt = tuple(sorted(n for n in self.arg_dict
                           if self.grad_req.get(n, "null") != "null"))
        if not wrt:
            return
        rest_env = {n: v._data for n, v in self.aux_dict.items()}
        rest_env.update({n: v._data for n, v in self.arg_dict.items()
                         if n not in wrt})
        rest_env = self._to_exec_device(rest_env)
        wrt_vals = self._to_exec_device(
            {n: self.arg_dict[n]._data for n in wrt})
        if out_grads is not None:
            if isinstance(out_grads, (NDArray, jnp.ndarray, _np.ndarray)):
                out_grads = [out_grads]
            out_grads = [g._data if isinstance(g, NDArray)
                         else jnp.asarray(g) for g in out_grads]
        key = _random.new_eager_seed_key()
        from .. import telemetry as _telemetry
        from .. import tracing as _tracing
        with _telemetry.timer("executor.backward").time(), \
                _tracing.span("executor.backward", cat="executor"):
            _, grads = self._bwd_fn(wrt)(wrt_vals, rest_env, out_grads, key)
        for n in wrt:
            g = grads[n]
            if g.dtype == jax.dtypes.float0:
                continue
            req = self.grad_req.get(n, "write")
            g = self._repin(n, g)  # grads live beside their params
            tgt = self.grad_dict.get(n)
            if tgt is None:
                self.grad_dict[n] = _wrap(g)
            elif req == "add":
                tgt._data = self._repin(n, tgt._data + g)
            else:
                tgt._data = g

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        from ..ndarray.ndarray import NDArray
        for n, v in (arg_params or {}).items():
            if n in self.arg_dict:
                self.arg_dict[n]._data = self._repin(
                    n, v._data if isinstance(v, NDArray) else jnp.asarray(v))
            elif not allow_extra_params:
                raise ValueError("unknown argument %r" % (n,))
        for n, v in (aux_params or {}).items():
            if n in self.aux_dict:
                self.aux_dict[n]._data = self._repin(
                    n, v._data if isinstance(v, NDArray) else jnp.asarray(v))
            elif not allow_extra_params:
                raise ValueError("unknown aux state %r" % (n,))

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes (jit re-specializes per signature)."""
        from ..ndarray.ndarray import _wrap
        new_args = {}
        for n, v in self.arg_dict.items():
            if n in kwargs:
                # fresh arrays inherit the name's ctx-group placement
                new_args[n] = _wrap(self._repin(
                    n, jnp.zeros(tuple(kwargs[n]), v._data.dtype)))
            else:
                new_args[n] = v
        ex = Executor(self._symbol, self._ctx, new_args,
                      dict(self.grad_dict), self.grad_req,
                      dict(self.aux_dict))
        ex._placement = dict(self._placement)
        return ex

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor = callback
