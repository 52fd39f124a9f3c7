"""Block / HybridBlock — the Gluon module system.

Reference: ``python/mxnet/gluon/block.py`` — ``Block`` (:228) is the
define-by-run container; ``HybridBlock`` (:838) adds ``hybridize()`` (:1039)
which captures the graph into a ``CachedOp`` (:969 ``_build_cache``) for
compiled execution; deferred parameter init resolves shapes at first forward.

TPU-native re-design of CachedOp: ``hybridize()`` wraps the block's forward in
``jax.jit``.  All descendant parameters become *traced inputs* of one pure
function (so weight updates never require retrace), auxiliary-state mutations
(BatchNorm running stats) are captured during tracing and returned as extra
outputs written back after the call, and RNG is threaded as an explicit key
(see mxnet_tpu.random.trace_key_scope).  Under ``autograd.record`` the whole
cached call tapes as a *single* node whose vjp is the jit-compiled backward —
the analog of CachedOp::Backward (src/imperative/cached_op.cc:931).
jax.jit's shape-specialized trace cache replaces CachedOp's per-signature
graph cache (src/imperative/cached_op.h:156).
"""
from __future__ import annotations

import copy
import re
import threading
from collections import OrderedDict

import jax
import numpy as _np

from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray.ndarray import NDArray, _wrap
from ..ndarray import ndarray as ndarray_mod
from .. import ndarray as nd_module
from .. import autograd
from .. import _tape
from .. import random as _random
from .parameter import (Parameter, ParameterDict, DeferredInitializationError,
                        Constant)

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    """Name scope manager (reference: block.py:33)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Create prefix and params for new Block."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from ..name import NameManager
                prefix = NameManager.current.get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params

        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        from ..name import Prefix
        self._name_scope = Prefix(self._block.prefix)
        self._name_scope.__enter__()
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        self._name_scope.__exit__(ptype, value, trace)
        self._name_scope = None
        _BlockScope._current.value = self._old_scope


class Block:
    """Base class for all neural network layers and models
    (reference: gluon/block.py:228).
    """

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params,
                                                        self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(["  ({key}): {block}".format(
            key=key, block=_indent(str(block), 2))
            for key, block in self.__dict__.items()
            if isinstance(block, Block)])
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Registers parameters and children."""
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and not isinstance(
                    value, type(existing)):
                raise TypeError(
                    "Changing attribute type for {name} from {type1} to {type2}"
                    " is not allowed.".format(name=name, type1=type(existing),
                                              type2=type(value)))
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params, \
                "Overriding Parameter attribute %s is not allowed. " \
                "If you want to share parameters between blocks, please set " \
                "'params' at Block construction instead."
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _check_container_with_block(self):
        children = set(self._children.values())

        def _find_unregistered_block_in_container(data):
            if isinstance(data, (list, tuple)):
                for ele in data:
                    if _find_unregistered_block_in_container(ele):
                        return True
                return False
            if isinstance(data, dict):
                for _, v in data.items():
                    if _find_unregistered_block_in_container(v):
                        return True
                return False
            if isinstance(data, Block):
                return data not in children
            return False

        for k, v in self.__dict__.items():
            if isinstance(v, (list, tuple, dict)) and not (
                    k.startswith("__") or k == "_children"):
                if _find_unregistered_block_in_container(v):
                    import warnings
                    warnings.warn(
                        '"{name}" is an unregistered container with Blocks. '
                        "Note that Blocks inside the list, tuple or dict will "
                        "not be registered automatically. Make sure to register "
                        "them using register_child() or switching to "
                        "nn.Sequential/nn.HybridSequential instead. ".format(
                            name=self.__class__.__name__ + "." + k),
                        stacklevel=3)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Returns a name space object managing a child Block and parameter
        names (reference: block.py:375)."""
        return self._scope

    @property
    def params(self):
        """Returns this Block's parameter dictionary (does not include its
        children's parameters)."""
        return self._params

    def collect_params(self, select=None):
        """Returns a ParameterDict containing this Block's and all of its
        children's Parameters (reference: block.py:396)."""
        self._check_container_with_block()
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for cld in self._children.values():
            ret.update(cld.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """Saves parameters to file with structured names
        (reference: block.py:416)."""
        params = self._collect_params_with_prefix()
        if deduplicate:
            reverse_params = {v: k for k, v in params.items()}
            params = {v: k for k, v in reverse_params.items()}
        arg_dict = {key: val._reduce() for key, val in params.items()}
        ndarray_mod.save(filename, arg_dict)

    def save_params(self, filename):
        import warnings
        warnings.warn("save_params is deprecated. Please use save_parameters.")
        try:
            self.collect_params().save(filename, strip_prefix=self.prefix)
        except ValueError as e:
            raise ValueError("%s\nsave_params is deprecated. Using "
                             "save_parameters may resolve this error." % e.args[0])

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        """Loads parameters from file previously saved by save_parameters
        (reference: block.py:472)."""
        loaded = ndarray_mod.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return

        if not any("." in i for i in loaded.keys()):
            # legacy loading: filename was saved with collect_params().save
            loaded = None
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix,
                cast_dtype=cast_dtype, dtype_source=dtype_source)
            return

        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    "Parameter '%s' is missing in file '%s', which contains " \
                    "parameters: %s. Set allow_missing=True to ignore missing " \
                    "parameters." % (name, filename, _brief_print_list(loaded.keys()))
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    "Parameter '%s' loaded from file '%s' is not present in "
                    "ParameterDict, which contains parameters %s. Set "
                    "ignore_extra=True to ignore. " % (
                        name, filename, _brief_print_list(params.keys())))
            if name in params:
                params[name]._load_init(loaded[name], ctx,
                                        cast_dtype=cast_dtype,
                                        dtype_source=dtype_source)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        import warnings
        warnings.warn("load_params is deprecated. Please use load_parameters.")
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)

    def register_child(self, block, name=None):
        """Registers block as a child of self (reference: block.py:531)."""
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn):
        """Applies fn recursively to every child block as well as self."""
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        """Initializes Parameters of this Block and its children
        (reference: block.py:577)."""
        from .. import initializer
        if init is None:
            init = initializer.Uniform()
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        """Activates or deactivates HybridBlock children recursively."""
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        """Cast this Block to use another data type."""
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def zero_grad(self):
        for p in self.collect_params().values():
            p.zero_grad()

    def __call__(self, *args):
        """Calls forward."""
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        """Overrides to implement forward computation using NDArray."""
        raise NotImplementedError

    def summary(self, *inputs):
        """Print the summary of the model's output and parameters
        (reference: block.py:724)."""
        summary = OrderedDict()
        seen = set()
        hooks = []

        def _get_shape_str(args):
            def flatten(args):
                if not isinstance(args, (list, tuple)):
                    return [args], int(0)
                flat = []
                fmts = []
                for i in args:
                    arg, fmt = flatten(i)
                    flat.extend(arg)
                    fmts.append(fmt)
                return flat, fmts

            def regroup(args, fmt):
                if isinstance(fmt, int):
                    if fmt == 0:
                        return args[0], args[1:]
                    return args[:fmt], args[fmt:]
                ret = []
                for i in fmt:
                    res, args = regroup(args, i)
                    ret.append(res)
                return ret, args

            flat_args, fmts = flatten(args)
            flat_arg_shapes = [x.shape if isinstance(x, NDArray) else x
                               for x in flat_args]
            shapes = regroup(flat_arg_shapes, fmts)[0]
            if isinstance(shapes, list):
                shape_str = str(shapes)[1:-1]
            else:
                shape_str = str(shapes)
            return shape_str.replace("L", "")

        def _register_summary_hook(block):
            assert not isinstance(block, HybridBlock) or not block._active, \
                "\"{}\" must not be hybridized to print summary.".format(
                    block.name)

            def _summary_hook(block, _, outputs):
                class_name = block.__class__.__name__
                block_idx = len(summary) - 1
                m_key = "%s-%i" % (class_name, block_idx + 1)
                summary[m_key] = OrderedDict()
                summary[m_key]["output_shape"] = _get_shape_str(outputs)
                params = 0
                summary[m_key]["trainable"] = 0
                summary[m_key]["shared"] = 0
                for p in block.params.values():
                    params += p.data().size
                    summary[m_key]["trainable"] += (
                        0 if p.grad_req == "null" else p.data().size)
                    if p in seen:
                        summary[m_key]["shared"] += p.data().size
                    else:
                        seen.add(p)
                summary[m_key]["n_params"] = params

            from functools import partial
            hooks.append(block.register_forward_hook(_summary_hook))

        summary["Input"] = OrderedDict()
        summary["Input"]["output_shape"] = _get_shape_str(inputs)
        summary["Input"]["n_params"] = 0
        summary["Input"]["trainable"] = 0
        summary["Input"]["shared"] = 0

        try:
            self.apply(_register_summary_hook)
            self(*inputs)

            line_format = "{:>20}  {:>42} {:>15}"
            print("-" * 80)
            print(line_format.format("Layer (type)", "Output Shape", "Param #"))
            print("=" * 80)
            total_params = 0
            trainable_params = 0
            shared_params = 0
            for layer in summary:
                print(line_format.format(
                    layer, str(summary[layer]["output_shape"]),
                    summary[layer]["n_params"]))
                total_params += summary[layer]["n_params"]
                trainable_params += summary[layer]["trainable"]
                shared_params += summary[layer]["shared"]
            print("=" * 80)
            print("Parameters in forward computation graph, duplicate included")
            print("   Total params: " + str(total_params))
            print("   Trainable params: " + str(trainable_params))
            print("   Non-trainable params: " + str(total_params - trainable_params))
            print("Shared params in forward computation graph: " + str(shared_params))
            print("Unique parameters in model: " + str(total_params - shared_params))
            print("-" * 80)
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    _id = 0

    def __init__(self, hooks_dict):
        self._hooks_dict = hooks_dict
        _HookHandle._id += 1
        self.id = _HookHandle._id

    def detach(self):
        self._hooks_dict.pop(self.id, None)


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    first = lines.pop(0)
    lines = [(num_spaces * " ") + line for line in lines]
    return "\n".join([first] + lines)


def _brief_print_list(lst, limit=7):
    lst = list(lst)
    if len(lst) > limit:
        return _brief_print_list(lst[:limit // 2], limit) + ", ..., " + \
            _brief_print_list(lst[-limit // 2:], limit)
    return ", ".join(["'%s'" % str(i) for i in lst])


class _TraceGuard(threading.local):
    """True while some _CachedGraph is tracing — nested hybridized children
    must then run their eager path inline (one fused jit for the whole tree,
    like CachedOp inlining small subgraphs, cached_op.h:43 inline_limit)."""

    def __init__(self):
        self.active = False


_TRACE_GUARD = _TraceGuard()


class _CachedGraph:
    """jit-compiled executor of a hybridized block — the CachedOp analog
    (reference: src/imperative/cached_op.cc; python binding
    python/mxnet/gluon/block.py:969 _build_cache)."""

    def __init__(self, block):
        self.block = block
        self.params = None            # ordered list[Parameter]
        self._jitted = {}             # training flag -> jitted fn

    def _ensure_params(self):
        if self.params is None:
            self.params = [p for p in self.block.collect_params().values()
                           if not isinstance(p, Constant) or True]

    def _build(self, training):
        self._ensure_params()
        params = self.params
        block = self.block

        def pure(param_vals, input_vals, key):
            # swap traced values into the live Parameter handles so every
            # descendant block reads tracers; capture aux mutations.
            wrappers = [_wrap(v) for v in param_vals]
            originals = []
            for p, w in zip(params, wrappers):
                originals.append(p._data)
                p._data = w
            prev_guard = _TRACE_GUARD.active
            _TRACE_GUARD.active = True
            try:
                with autograd._RecordingStateScope(False, training):
                    with _random.trace_key_scope(key):
                        out = block._eager_forward(*[_wrap(v) for v in input_vals])
            finally:
                _TRACE_GUARD.active = prev_guard
                for p, o in zip(params, originals):
                    p._data = o
            multi = isinstance(out, (tuple, list))
            out_vals = tuple(o._data for o in out) if multi else (out._data,)
            mutated = {}
            for i, (w, v) in enumerate(zip(wrappers, param_vals)):
                if w._data is not v:
                    mutated[str(i)] = w._data
            return out_vals, multi, mutated

        def jit_target(param_vals, input_vals, key):
            out_vals, _multi, mutated = pure(param_vals, input_vals, key)
            return out_vals, mutated

        jitted = jax.jit(jit_target)
        return jitted

    def __call__(self, *args):
        from .. import config as _config
        training = autograd.is_training()
        # knob values bake in at trace: a config mutation (the epoch)
        # retraces
        key = (training, _config.epoch())
        if key not in self._jitted:
            # evict programs compiled under superseded knob epochs
            self._jitted = {k: v for k, v in self._jitted.items()
                            if k[1] == key[1]}
            from .. import perf as _perf
            # check_tracers: taped calls run inside jax.vjp — those inline
            # into the outer trace via the plain jit fn, unaccounted
            self._jitted[key] = _perf.wrap(
                self._build(training), "gluon",
                "%s/train=%s/e%d" % (self.block.name, training, key[1]),
                source="gluon", check_tracers=True)
        fn = self._jitted[key]
        self._ensure_params()
        params = self.params

        nd_inputs = []
        input_vals = []
        for a in args:
            if isinstance(a, NDArray):
                nd_inputs.append(a)
                input_vals.append(a._data)
            else:
                input_vals.append(a)
        param_vals = tuple(p.data()._data for p in params)
        key = _random.new_eager_seed_key()

        if _tape.is_recording():
            out_vals, vjp, mutated = jax.vjp(
                lambda pv, iv: fn(pv, iv, key), param_vals, tuple(input_vals),
                has_aux=True)
            outs = [_wrap(v) for v in out_vals]
            param_nds = [p._data for p in params]
            tape_inputs = param_nds + nd_inputs
            n_params = len(param_nds)
            nd_positions = [i for i, a in enumerate(args) if isinstance(a, NDArray)]

            def vjp_fn(cotangents, _vjp=vjp):
                p_cts, i_cts = _vjp(tuple(cotangents))
                from ..ops.registry import _float0_to_none
                p_out = [_float0_to_none(c) for c in p_cts]
                i_out = [_float0_to_none(i_cts[pos]) for pos in nd_positions]
                return tuple(p_out + i_out)

            _tape.record_node(tape_inputs, outs, vjp_fn,
                              name="CachedOp(%s)" % self.block.name)
        else:
            out_vals, mutated = fn(param_vals, tuple(input_vals), key)
            outs = [_wrap(v) for v in out_vals]

        # write back aux-state updates (BatchNorm running stats etc.)
        for idx_s, val in mutated.items():
            p = params[int(idx_s)]
            with autograd.pause():
                p._data._data = val

        if len(outs) == 1:
            return outs[0]
        return outs


class HybridBlock(Block):
    """A Block that can be compiled (reference: gluon/block.py:838).

    Subclasses implement ``hybrid_forward(F, x, *args, **params)`` where F is
    the ndarray (eager) or symbol (graph) namespace and registered parameters
    arrive as keyword arguments.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph_obj = None
        self._flags = {}

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, (HybridBlock, Parameter)):
            self._clear_cached_op()

    def _clear_cached_op(self):
        if getattr(self, "_cached_graph_obj", None) is not None:
            self._cached_graph_obj = None

    def register_child(self, block, name=None):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                "Children of HybridBlock must also be HybridBlock, "
                "but %s has type %s. If you are using Sequential, "
                "please try HybridSequential instead." % (
                    str(block), str(type(block))))
        super().register_child(block, name)
        self._clear_cached_op()

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Activate compiled execution via jax.jit (reference: block.py:1039;
        static_alloc/static_shape are implied by XLA and accepted for parity).
        """
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._clear_cached_op()
        for cld in self._children.values():
            cld.hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def infer_shape(self, *args):
        """Infers shapes of deferred-init Parameters from input shapes.

        Built-in layers override this; custom blocks with deferred-shape
        parameters must too (the reference infers through the symbolic graph,
        block.py:912 _infer_attrs)."""
        raise NotImplementedError(
            "infer_shape is not implemented for block %s with deferred-"
            "initialized parameters. Either give all parameters explicit "
            "shapes (in_units/in_channels/...) or override infer_shape()."
            % type(self).__name__)

    def infer_type(self, *args):
        for p in self._reg_params.values():
            if p.dtype is None:
                p._dtype = args[0].dtype

    def _deferred_infer_shape(self, *args):
        try:
            self.infer_shape(*args)
        except Exception as e:
            error_msg = "Deferred initialization failed because shape" \
                        " cannot be inferred. {}".format(e)
            raise ValueError(error_msg)

    def _get_params_nd(self, *args):
        """Resolve registered params to NDArrays, finishing deferred init."""
        try:
            return {name: p.data() for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer_shape(*args)
            for p in self._reg_params.values():
                p._finish_deferred_init()
            return {name: p.data() for name, p in self._reg_params.items()}

    def _eager_forward(self, *args):
        params = self._get_params_nd(*args)
        return self.hybrid_forward(nd_module, *args, **params)

    def forward(self, x, *args):
        """Defines the forward computation: dispatches to symbolic trace,
        cached (jit), or eager execution (reference: block.py:1146)."""
        from ..symbol import Symbol as _Symbol
        if isinstance(x, _Symbol):
            # symbolic trace (export path): parameters enter the graph as
            # named free Variables so the saved JSON's input names match
            # the param-file keys (reference block.py:1077 export contract)
            from .. import symbol as sym_module
            kwargs = {name: sym_module.var(p.name)
                      for name, p in self._reg_params.items()}
            return self.hybrid_forward(sym_module, x, *args, **kwargs)
        if self._active and not _TRACE_GUARD.active:
            if self._cached_graph_obj is None:
                # first call runs eagerly to resolve all deferred shapes,
                # then subsequent calls hit the jit cache
                out = self._eager_forward(x, *args)
                self._cached_graph_obj = _CachedGraph(self)
                return out
            return self._cached_graph_obj(x, *args)
        if _TRACE_GUARD.active:
            # inlined into a hybridized / functional program being traced:
            # the device operations carry the block's own name (a profile
            # then tells the model's stages apart); not in eager calls
            with jax.named_scope(self.name):
                return self._eager_forward(x, *args)
        return self._eager_forward(x, *args)

    def export(self, path, epoch=0, remove_amp_cast=True,
               input_names=("data",), fmt="native"):
        """Export graph JSON + params for deployment
        (reference: block.py:1077) — see mxnet_tpu.symbol for the format.
        Multi-input blocks name their inputs via ``input_names``;
        ``fmt="mxnet"`` writes the reference wire formats so the pair
        deploys on real Apache-MXNet infrastructure."""
        from ..symbol import _export_hybrid_block
        return _export_hybrid_block(self, path, epoch,
                                    input_names=input_names, fmt=fmt)

    def optimize_for(self, x, *args, backend=None, **kwargs):
        """Partial parity: on TPU the backend compiler is always XLA; this
        hybridizes and warms the cache (reference: block.py:1190)."""
        self.hybridize(True)
        self(x, *args)

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Overrides to construct computation graph."""
        raise NotImplementedError


class SymbolBlock(HybridBlock):
    """Construct block from symbol (reference: gluon/block.py:1190).

    Runs a loaded/composed Symbol graph as a block; used by
    ``SymbolBlock.imports`` to reload ``HybridBlock.export``-ed models
    (block.py:1223).
    """

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from ..symbol import load as sym_load
        sym = sym_load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        if param_file is None:
            inputs = [_sym_var(i) for i in input_names]
        else:
            inputs = [_sym_var(i) for i in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            ret.collect_params().load(param_file, ctx=ctx, cast_dtype=True,
                                      dtype_source="saved",
                                      allow_missing=True, ignore_extra=True)
        return ret

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=None)
        from ..symbol import Symbol, Group
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if isinstance(outputs, (list, tuple)):
            outputs = Group(outputs)
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        self._output_sym = outputs
        self._input_syms = list(inputs)
        self._input_names = [i.name for i in self._input_syms]

        # register every non-input free variable as a parameter; moving
        # stats are aux (non-trainable), classified like the symbol layer
        from ..symbol.symbol import _is_aux_name
        arg_names = outputs.list_inputs()
        existing = dict(params.items()) if params is not None else {}
        for name in arg_names:
            if name in self._input_names:
                continue
            if name in existing:
                self.params._params[name] = existing[name]
            else:
                self.params._params[name] = Parameter(
                    name, shape=None, allow_deferred_init=True,
                    grad_req="null" if _is_aux_name(name) else "write")
        self._executor = None

    def forward(self, x, *args):
        from ..symbol.symbol import _is_aux_name
        inputs = dict(zip(self._input_names, (x,) + args))
        arg_vals, aux_vals = {}, {}
        for name, p in self.params.items():
            if name in self._input_names:
                continue
            (aux_vals if _is_aux_name(name) else arg_vals)[name] = p.data()
        if autograd.is_recording():
            # An imported model must stay trainable: the executor path runs
            # its jitted program outside the tape (grad_req="null"), which
            # would silently zero all gradients.  Record the whole graph as
            # one tape node instead, like _CachedGraph does for CachedOp.
            return self._taped_forward(inputs, arg_vals, aux_vals)
        if self._executor is None:
            # ONE bound executor for the block's lifetime: its internal
            # (training, config-epoch)-keyed jit cache makes repeat calls
            # cached dispatch instead of a retrace per call
            bindings = dict(inputs)
            bindings.update(arg_vals)
            self._executor = self._output_sym.bind(
                None, args=bindings, aux_states=aux_vals, grad_req="null")
        ex = self._executor
        # refresh aux values (args/inputs refresh through forward(**kwargs))
        for name, v in aux_vals.items():
            if name in ex.aux_dict:
                ex.aux_dict[name]._data = v._data
        training = autograd.is_training()
        kwargs = dict(inputs)
        kwargs.update(arg_vals)
        out = ex.forward(is_train=training, **kwargs)
        if training:
            # training mode computes moving-stat updates (executor aux
            # rules); write them back into the Parameters so exports and
            # later inference see them
            for name, v in ex.aux_dict.items():
                if name in self.params._params:
                    self.params._params[name].data()._data = v._data
        if isinstance(out, (list, tuple)) and len(out) == 1:
            return out[0]
        return out

    def _taped_forward(self, inputs, arg_vals, aux_vals):
        """Run the symbol graph under the autograd tape.

        One node for the whole graph, vjp = jax.vjp through the jitted
        symbol evaluation (the CachedOp-backward analog,
        src/imperative/cached_op.cc) — gradients flow both into this
        block's Parameters and through the inputs to upstream recorded ops.
        """
        from .. import config as _config
        from .. import random as _random
        from ..symbol.symbol import _eval_symbol
        training = autograd.is_training()
        names = list(inputs.keys()) + list(arg_vals.keys())
        nds = list(inputs.values()) + list(arg_vals.values())
        # knobs bake in at trace (see _CachedGraph)
        cache_key = (training, _config.epoch())
        if getattr(self, "_taped_cache", None) is None:
            self._taped_cache = {}
        if cache_key not in self._taped_cache:
            self._taped_cache = {k: v for k, v in self._taped_cache.items()
                                 if k[1] == cache_key[1]}
            sym = self._output_sym

            def pure(vals, aux_env, key, _names=tuple(names)):
                env = dict(zip(_names, vals))
                env.update(aux_env)
                aux_updates = {}
                with _random.trace_key_scope(key):
                    outs = _eval_symbol(sym, env, training, aux_updates)
                return tuple(outs), aux_updates

            self._taped_cache[cache_key] = jax.jit(pure)
        jitted = self._taped_cache[cache_key]
        aux_env = {n: v._data for n, v in aux_vals.items()}
        key = _random.new_eager_seed_key()
        out_vals, vjp, aux_updates = jax.vjp(
            lambda vals: jitted(vals, aux_env, key),
            tuple(v._data for v in nds), has_aux=True)
        outs = [_wrap(v) for v in out_vals]

        def vjp_fn(cotangents, _vjp=vjp):
            from ..ops.registry import _float0_to_none
            (cts,) = _vjp(tuple(cotangents))
            return tuple(_float0_to_none(c) for c in cts)

        _tape.record_node(nds, outs, vjp_fn,
                          name="SymbolBlock(%s)" % self.name)
        if training:
            with autograd.pause():
                for n, v in aux_updates.items():
                    if n in self.params._params:
                        self.params._params[n].data()._data = v
        if len(outs) == 1:
            return outs[0]
        return outs

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError


def _sym_var(name):
    from ..symbol import var
    return var(name)
