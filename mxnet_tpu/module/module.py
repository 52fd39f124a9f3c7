"""Module — symbol + one jit-specialized executor.

Reference: python/mxnet/module/module.py:40 (`Module`), whose bind creates a
`DataParallelExecutorGroup` slicing the batch over contexts
(executor_group.py:144) and whose update pushes gradients through KVStore
(module.py:646).

TPU-native: a single Executor (jit per shape signature) carries the whole
batch; scale-out is mesh sharding via mxnet_tpu.parallel, not executor
replicas, so update() applies the optimizer directly (the
update_on_kvstore=False path of the reference).
"""
from __future__ import annotations

import logging

import numpy as _np
import jax.numpy as jnp

from .base_module import BaseModule
from ..ndarray.ndarray import NDArray, _wrap
from ..initializer import InitDesc
from .. import optimizer as opt_mod

__all__ = ["Module"]


class Module(BaseModule):
    """Symbolic Module (reference: python/mxnet/module/module.py:40).

    PERFORMANCE NOTE — the train step is FUSED by default.  When the bound
    optimizer is jit-traceable (``Optimizer.jit_safe``), ``fit`` /
    ``forward_backward``+``update`` dispatch ONE jitted XLA program per
    (shape signature) carrying forward + backward + the optimizer update —
    the CachedOp ``static_alloc=True`` analog — with parameters and
    optimizer state donated on accelerator backends so the update happens
    in place in HBM.  ``forward_backward`` defers the batch and ``update``
    launches the fused program; lr/wd are evaluated eagerly each step and
    fed as device arrays, so lr schedulers keep working instead of
    constant-folding into the compiled step.  The fused program bakes in
    the kernel-tier routing at trace time (Executor.fused_step_fn keys on
    the config epoch, so a knob flip retraces exactly once).

    The stage-at-a-time eager path (forward, backward, then a per-parameter
    updater loop outside jit — the reference's per-batch structure) remains
    and is selected automatically when fusion cannot apply: NaiveEngine,
    ``config.set("module.fused_step", "off")``, a non-jit-safe optimizer
    (LBSGD, Nadam), ``inputs_need_grad``, grad_req "add", ctx-group
    placement, an installed monitor, or a Module subclass that inspects
    intermediate state (SVRGModule).  Explicit ``forward()``/``backward()``
    calls are always eager, so gradient-inspection workflows keep
    reference semantics; the fused path does not materialize
    ``grad_dict``.  Numerical equivalence is tested both ways
    (tests/test_module.py::test_module_fused_vs_eager_equivalence,
    tests/test_parallel.py::test_module_vs_spmd_trainer_equivalence).
    ``mx.parallel.SPMDTrainer`` remains the hot path for sharded multi-chip
    training; fused Module.fit closes the single-chip gap
    (docs/PERF_NOTES.md).
    """

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        self._symbol = symbol
        self._data_names = list(data_names)
        self._label_names = list(label_names or [])
        self._context = context
        self._fixed_param_names = set(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        self._param_names = [n for n in arg_names
                             if n not in self._data_names
                             and n not in self._label_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._exec = None
        self._optimizer = None
        self._updater = None
        self._data_shapes = None
        self._label_shapes = None
        # fused-train-step state: forward_backward defers the batch here and
        # update() consumes it in one jitted dispatch (see class docstring)
        self._pending_batch = None
        # optimizer state for the fused path, keyed by param NAME so
        # BucketingModule can share one dict across bucket modules
        self._fused_shared = {"state": None, "t": 0, "hyper": {}}
        # False until the first fused step after init_params/set_params:
        # those share buffers with caller-owned NDArrays, which a donated
        # program would invalidate — the first step copies, then owns
        self._fused_owns_params = False
        # one-time notice when an installed Monitor rides the fused path
        self._warned_monitor_fused = False

    # ------------------------------------------------------------- binding
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            return
        self._data_shapes = _norm_shapes(data_shapes, self._data_names)
        self._label_shapes = _norm_shapes(label_shapes, self._label_names) \
            if label_shapes else []
        shapes = {}
        for name, shape in self._data_shapes + self._label_shapes:
            shapes[name] = shape
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shapes)
        args = {}
        arg_names = self._symbol.list_arguments()
        dtypes = {d.name: d.dtype for d in list(data_shapes or [])
                  + list(label_shapes or []) if hasattr(d, "dtype")}
        for name, shp in zip(arg_names, arg_shapes):
            if shp is None:
                raise ValueError(
                    "cannot infer shape of %r from data shapes %s"
                    % (name, shapes))
            args[name] = _wrap(jnp.zeros(shp, dtypes.get(name, _np.float32)))
        aux = {}
        for name, shp in zip(self._aux_names, aux_shapes):
            if shp is None:
                raise ValueError("cannot infer shape of aux %r" % (name,))
            aux[name] = _wrap(jnp.zeros(shp, _np.float32))
        req = {}
        for n in arg_names:
            if n in self._data_names:
                req[n] = "write" if inputs_need_grad else "null"
            elif n in self._label_names or n in self._fixed_param_names:
                req[n] = "null"
            else:
                req[n] = grad_req if for_training else "null"
        grads = {n: _wrap(jnp.zeros_like(args[n]._data))
                 for n, r in req.items() if r != "null"}
        from ..symbol.symbol import Executor
        self._exec = Executor(self._symbol, self._context, args, grads, req,
                              aux)
        self.binded = True
        self.for_training = for_training
        self._inputs_need_grad = inputs_need_grad
        self._pending_batch = None
        self._fused_owns_params = False

    # -------------------------------------------------------------- params
    def init_params(self, initializer="default", arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False,
                    allow_extra=False):
        assert self.binded
        if self.params_initialized and not force_init:
            return
        if initializer == "default":
            # reference default: base_module.py:640 Uniform(0.01); an
            # explicit None still means "values must come from
            # arg_params/aux_params"
            from ..initializer import Uniform
            initializer = Uniform(0.01)
        attr_map = self._symbol.attr_dict()
        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params and name in arg_params:
                src = arg_params[name]
                arr._data = src._data if isinstance(src, NDArray) \
                    else jnp.asarray(src)
            elif initializer is not None:
                desc = InitDesc(name, attr_map.get(name, {}))
                initializer(desc, arr)
            elif not allow_missing:
                raise RuntimeError("no initializer and no value for %r"
                                   % (name,))
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params and name in aux_params:
                src = aux_params[name]
                arr._data = src._data if isinstance(src, NDArray) \
                    else jnp.asarray(src)
            elif initializer is not None:
                desc = InitDesc(name, attr_map.get(name, {}))
                initializer(desc, arr)
        self.params_initialized = True
        # buffers may now be shared with caller NDArrays (arr._data is
        # src._data above) — the next fused step must copy before donating
        self._fused_owns_params = False

    def get_params(self):
        assert self.binded and self.params_initialized
        self._flush_pending()
        arg = {n: self._exec.arg_dict[n].copy() for n in self._param_names}
        aux = {n: v.copy() for n, v in self._exec.aux_dict.items()}
        return arg, aux

    # ----------------------------------------------------------- optimizer
    #: kvstore modes a single-process Module can honor.  Gradient reduction
    #: is XLA's job inside the (sharded) step, so these all collapse to the
    #: update_on_kvstore=False local-update path of the reference.
    _LOCAL_KVSTORE_TYPES = ("local", "device", "nccl",
                            "local_allreduce_cpu", "local_allreduce_device")

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        # the reference silently routed dist_* through a parameter server;
        # here there is none — accepting it would train single-process while
        # the script believes it is distributed, so it must be an error
        kv_type = kvstore if isinstance(kvstore, str) or kvstore is None \
            else getattr(kvstore, "type", None)
        if kv_type is not None:
            if kv_type.startswith("dist"):
                raise ValueError(
                    "kvstore=%r: Module has no parameter-server path; "
                    "distributed training runs through "
                    "mx.parallel.SPMDTrainer (jax.distributed + mesh "
                    "sharding, see docs/MIGRATION.md)" % (kv_type,))
            if kv_type not in self._LOCAL_KVSTORE_TYPES:
                raise ValueError(
                    "kvstore=%r is not a recognized mode; expected one of "
                    "%s or None" % (kv_type, list(self._LOCAL_KVSTORE_TYPES)))
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **dict(optimizer_params))
        self._optimizer = optimizer
        idx2name = {i: n for i, n in enumerate(self._param_names)}
        optimizer.param_idx2name = idx2name
        self._updater = opt_mod.get_updater(optimizer)
        # a (re)initialized optimizer starts fresh fused state too
        self._fused_shared = {"state": None, "t": 0, "hyper": {}}
        self.optimizer_initialized = True

    # ------------------------------------------------------ fused train step
    def _fused_active(self):
        """Whether the NEXT forward_backward+update pair may run as one
        fused jitted program (class docstring lists every condition)."""
        if not (self.binded and self.optimizer_initialized
                and self.for_training):
            return False
        if type(self) is not Module:
            # subclasses (SVRGModule) inspect grad_dict between stages
            return False
        if self._inputs_need_grad or self._exec._placement:
            return False
        cb = self._exec._monitor
        if cb is not None:
            from ..monitor import Monitor
            if not isinstance(getattr(cb, "__self__", None), Monitor):
                # a RAW monitor callback wants every intermediate eagerly
                # — only the stage-at-a-time executor materializes those
                return False
            # an mx.monitor.Monitor keeps working fused: outputs fire
            # through its callback after the dispatch and toc() reads the
            # written-back arg_dict; per-op intermediates come from the
            # numerics capture knob instead of forcing the eager path
            # (the pre-numerics behavior silently dropped 10-100x fused
            # throughput the moment a monitor was installed)
            if not self._warned_monitor_fused:
                self._warned_monitor_fused = True
                self.logger.warning(
                    "Monitor installed on a FUSED module step: interval "
                    "param/output stats keep working, but per-op "
                    "intermediates are not materialized on this path — "
                    "set numerics.capture=step:N (MXNET_TPU_NUMERICS) "
                    "for in-program per-site statistics, or "
                    "config.set('module.fused_step', 'off') for the "
                    "reference eager monitor.")
        if not getattr(self._optimizer, "jit_safe", False):
            return False
        req = self._exec.grad_req
        wrt = [n for n, r in req.items() if r != "null"]
        if not wrt or any(req[n] != "write" for n in wrt):
            return False
        from .. import engine as _engine
        from .. import config as _config
        return _engine.fused_step_allowed() \
            and _config.get("module.fused_step") != "off"

    def _flush_pending(self):
        """Replay a deferred batch through the EAGER forward+backward —
        called when outputs/grads/aux are observed before update(), so
        consumers see exactly the reference's stage-at-a-time state."""
        batch = self._pending_batch
        if batch is None:
            return
        self._pending_batch = None
        # an observed deferral costs a full eager fwd+bwd replay — a rising
        # count means something inspects state between fused steps
        from .. import telemetry as _telemetry
        from .. import tracing as _tracing
        _telemetry.counter("module.eager_replays").inc()
        with _tracing.span("module.eager_replay", cat="module"):
            BaseModule.forward_backward(self, batch)

    def _run_fused(self, data_batch):
        """One donated jit dispatch: forward + backward + optimizer update
        (Executor.fused_step_fn).  Mirrors SPMDTrainer.step for the
        symbolic path."""
        from .. import random as _random
        from .. import resilience as _resilience
        from ..parallel.trainer import (_opt_hyper_arrays, _state_to_jax)
        from .. import profiler as _profiler
        import jax
        exec_ = self._exec
        optimizer = self._optimizer
        # ensure_staged: device-resident feeds (NDArray or DevicePrefetcher
        # output) pass through with zero copies; host numpy goes straight to
        # device_put and is counted as a synchronous caller-thread transfer
        # (io.h2d_sync.module — flat in steady state with device prefetch on)
        from .. import io as _io
        feeds = {}
        for (name, _), arr in zip(self._data_shapes, data_batch.data):
            feeds[name] = arr._data if isinstance(arr, NDArray) \
                else _io.ensure_staged(arr, source="module")
        if self._label_shapes and data_batch.label:
            for (name, _), arr in zip(self._label_shapes, data_batch.label):
                feeds[name] = arr._data if isinstance(arr, NDArray) \
                    else _io.ensure_staged(arr, source="module")
        exec_._feed_inputs(feeds)  # arg_dict state matches the eager path
        req = exec_.grad_req
        wrt = tuple(sorted(n for n in exec_.arg_dict
                           if req.get(n, "null") != "null"))
        feed_sig = tuple((n, tuple(v.shape), str(v.dtype))
                         for n, v in sorted(feeds.items()))
        from .. import numerics as _numerics
        # cadence decision per step: the instrumented program is a
        # SEPARATE cache entry, so off-steps replay the plain program
        # unchanged and toggling the knob never recompiles
        cap = _numerics.should_capture("module")
        fn = exec_.fused_step_fn(wrt, optimizer, feed_sig, instrument=cap)
        idxs = tuple(self._param_names.index(n) for n in wrt)
        # lazily materialize per-name optimizer state (create_state wants
        # the live weight for shape/dtype)
        shared = self._fused_shared
        if shared["state"] is None:
            shared["state"] = {}
        state = shared["state"]
        for n, i in zip(wrt, idxs):
            if n not in state:
                state[n] = _state_to_jax(
                    optimizer.create_state(i, exec_.arg_dict[n]))
        # step count first — the lr scheduler reads num_update, and the
        # eager Updater's per-index counts must agree after a fused run;
        # continue from eager steps taken before fusion kicked in
        shared["t"] = max(shared["t"], optimizer.num_update)
        shared["t"] += 1
        t = shared["t"]
        optimizer.num_update = max(optimizer.num_update, t)
        for i in idxs:
            optimizer._index_update_count[i] = t
        lrs, wds = _opt_hyper_arrays(optimizer, len(idxs), shared["hyper"],
                                     indices=idxs)
        donating = jax.default_backend() != "cpu"
        if donating and not self._fused_owns_params:
            # params may share buffers with caller NDArrays; copy once so
            # donation can't invalidate what the caller still holds
            wrt_vals = {n: jnp.array(exec_.arg_dict[n]._data) for n in wrt}
        else:
            wrt_vals = {n: exec_.arg_dict[n]._data for n in wrt}
        opt_state = {n: state[n] for n in wrt}
        rest_env = {n: v for n, v in exec_._env().items()
                    if n not in opt_state and n not in feeds}
        key = _random.new_eager_seed_key()
        guard = _resilience.nanguard_mode()
        stats = None
        if guard:
            streak = shared.get("nan_streak")
            if streak is None:
                streak = jnp.zeros((), jnp.int32)
            res = fn(wrt_vals, opt_state, rest_env, feeds, key,
                     jnp.asarray(t, jnp.int32), lrs, wds, streak)
            if cap:
                new_w, new_s, aux_updates, outs, \
                    shared["nan_streak"], stats = res
            else:
                new_w, new_s, aux_updates, outs, shared["nan_streak"] = res
            # no-sync host inspection of completed steps' streaks
            _resilience.watch_streak("module", shared["nan_streak"])

            def _replay():
                # nanguard forensics (mx.numerics): re-run THIS batch once
                # through the instrumented variant.  Params/opt state are
                # read live (last-good after select_tree) and COPIED so
                # the replay's donation cannot invalidate the buffers the
                # abort path still checkpoints; feeds/key/t/lrs/wds are
                # the failing step's own.
                import jax as _jax
                fi = exec_.fused_step_fn(wrt, optimizer, feed_sig,
                                         instrument=True)
                wv = _jax.tree_util.tree_map(
                    jnp.array, {n: exec_.arg_dict[n]._data for n in wrt})
                st = _jax.tree_util.tree_map(
                    jnp.array, {n: state[n] for n in wrt})
                rest = {n: v for n, v in exec_._env().items()
                        if n not in st and n not in feeds}
                res = fi(wv, st, rest, feeds, key,
                         jnp.asarray(t, jnp.int32), lrs, wds,
                         jnp.zeros((), jnp.int32))
                return res[-1]

            _numerics.hold_replay("module", _replay)
        else:
            res = fn(wrt_vals, opt_state, rest_env, feeds, key,
                     jnp.asarray(t, jnp.int32), lrs, wds)
            if cap:
                new_w, new_s, aux_updates, outs, stats = res
            else:
                new_w, new_s, aux_updates, outs = res
        if stats is not None:
            # device stats land in the pending queue; the is-ready poll
            # drains them later — no host sync on this thread
            _numerics.publish("module", t, stats)
        for n in wrt:
            exec_.arg_dict[n]._data = new_w[n]
            state[n] = new_s[n]
        for n, v in aux_updates.items():
            if n in exec_.aux_dict:
                exec_.aux_dict[n]._data = v
        exec_.outputs = [_wrap(o) for o in outs]
        if exec_._monitor is not None:
            # the fused path's Monitor contract (satellite of PR 18):
            # outputs fire through the installed callback exactly like
            # the eager executor's forward does
            for name, arr in zip(self._symbol.list_outputs(),
                                 exec_.outputs):
                exec_._monitor(name, arr)
        self._fused_owns_params = True
        _profiler.counter_increment("fused_steps")

    # ------------------------------------------------------------- running
    def forward_backward(self, data_batch):
        if self._fused_active():
            # two deferrals without an update(): the first batch's
            # outputs/aux side effects must land in order — replay it
            self._flush_pending()
            self._pending_batch = data_batch
            return
        super().forward_backward(data_batch)

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._flush_pending()
        if is_train is None:
            is_train = self.for_training
        feeds = {}
        for (name, _), arr in zip(self._data_shapes, data_batch.data):
            feeds[name] = arr
        if self._label_shapes and data_batch.label:
            for (name, _), arr in zip(self._label_shapes, data_batch.label):
                feeds[name] = arr
        self._exec.forward(is_train=is_train, **feeds)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._flush_pending()
        self._exec.backward(out_grads=out_grads)

    def update(self):
        """Apply optimizer to parameters (reference module.py:646; the
        kvstore push/pull collapses — gradient reduction is XLA's job on a
        sharded step, a no-op on one chip).  A batch deferred by
        forward_backward is consumed here as ONE fused jit dispatch."""
        assert self.optimizer_initialized
        from .. import tracing as _tracing
        batch = self._pending_batch
        if batch is not None:
            self._pending_batch = None
            # one donated jit program: fwd + bwd + optimizer update
            with _tracing.span("module.fused_dispatch", cat="module"):
                self._run_fused(batch)
            return
        from .. import profiler as _profiler
        _profiler.counter_increment("eager_steps")
        from .. import resilience as _resilience
        if _resilience.nanguard_mode():
            # eager path has no fused program to fold the check into; one
            # host sync per step is the cost of running unfused
            import numpy as _np
            finite = all(
                bool(_np.all(_np.isfinite(_np.asarray(g._data))))
                for g in self._exec.grad_dict.values() if g is not None)
            if not finite:
                _resilience.report_nonfinite("module")
                return
            _resilience.note_finite("module")
        with _tracing.span("module.opt_update", cat="module"):
            for i, name in enumerate(self._param_names):
                g = self._exec.grad_dict.get(name)
                if g is None:
                    continue
                self._updater(i, g, self._exec.arg_dict[name])

    def get_outputs(self, merge_multi_context=True):
        self._flush_pending()
        return list(self._exec.outputs)

    def get_input_grads(self, merge_multi_context=True):
        assert self._inputs_need_grad
        self._flush_pending()
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        self._flush_pending()
        eval_metric.update_dict(
            {n: l for (n, _), l in zip(self._label_shapes, labels)}
            if self._label_shapes else {},
            dict(zip(self._symbol.list_outputs(), self._exec.outputs)))

    @property
    def data_names(self):
        return self._data_names

    @property
    def output_names(self):
        return self._symbol.list_outputs()

    @property
    def data_shapes(self):
        return self._data_shapes

    @property
    def label_shapes(self):
        return self._label_shapes

    @property
    def output_shapes(self):
        return [(n, tuple(o.shape)) for n, o in
                zip(self._symbol.list_outputs(), self._exec.outputs)]

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        from ..model import save_checkpoint
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg_params, aux_params)


def _norm_shapes(shapes, names):
    if shapes is None:
        return []
    out = []
    for i, s in enumerate(shapes):
        if hasattr(s, "name"):  # DataDesc
            out.append((s.name, tuple(s.shape)))
        elif isinstance(s, tuple) and len(s) == 2 and isinstance(s[0], str):
            out.append((s[0], tuple(s[1])))
        else:
            out.append((names[i], tuple(s)))
    return out


