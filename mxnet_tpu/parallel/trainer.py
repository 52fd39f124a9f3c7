"""SPMD training step — the TPU-native replacement for the reference's
Module.fit hot loop + KVStore gradient sync.

Reference path (SURVEY.md §3.3-3.4): per batch, DataParallelExecutorGroup
slices data over contexts (python/mxnet/module/executor_group.py:144), the
GraphExecutor pushes bulked engine ops (src/executor/graph_executor.cc:1384),
then KVStore reduces gradients across devices (src/kvstore/comm.h:451) and an
Updater applies the optimizer.  Four subsystems, all asynchrony hand-managed.

Here the ENTIRE iteration — forward, backward, gradient allreduce, optimizer
update — is ONE jitted function over a named mesh.  Batch dims are sharded on
'dp', parameters replicated (or sharded for tensor-parallel models), and XLA
inserts the psum/all-gather collectives and overlaps them with compute; the
engine/kvstore/bulking machinery has no residual role on the hot path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .functional import functionalize
from .mesh import data_parallel_mesh

__all__ = ["SPMDTrainer", "build_train_step"]


def _opt_hyper_arrays(optimizer, num_params, cache=None, indices=None):
    """Evaluate per-parameter lr/wd EAGERLY for the current num_update.

    These are fed into the jitted step as traced arguments so an
    ``lr_scheduler`` (reference: python/mxnet/lr_scheduler.py) keeps working —
    evaluating them at trace time would constant-fold the schedule into the
    compiled program and silently freeze it at the first step's value.

    ``cache`` (a 1-slot dict) skips the two host->device uploads when the
    schedule produced the same values as last step — constant-lr training
    would otherwise pay two per step for identical bytes.

    ``indices`` overrides the parameter indices the per-param multipliers
    are looked up under (Module's fused step trains a subset of
    ``_param_names``, whose updater indices are not contiguous).
    """
    idxs = tuple(indices) if indices is not None \
        else tuple(range(num_params))
    lr_host = tuple(optimizer._get_lr(i) for i in idxs)
    wd_host = tuple(optimizer._get_wd(i) for i in idxs)
    if cache is not None and cache.get("host") == (lr_host, wd_host):
        return cache["dev"]
    from .. import profiler as _profiler
    _profiler.counter_increment("host_syncs", 2)  # lr + wd uploads
    dev = (jnp.asarray(lr_host, jnp.float32),
           jnp.asarray(wd_host, jnp.float32))
    if cache is not None:
        cache["host"] = (lr_host, wd_host)
        cache["dev"] = dev
    return dev


def _conv_weight_names(block):
    """Names of 2-D convolution weight parameters in a Block tree — the
    exact set the HWIO weight layout applies to."""
    from ..gluon import nn as _gnn
    names, seen = set(), set()

    def walk(b):
        if id(b) in seen:
            return
        seen.add(id(b))
        if isinstance(b, _gnn.Conv2D):
            names.add(b.weight.name)
        for c in getattr(b, "_children", {}).values():
            walk(c)

    walk(block)
    return names


class SPMDTrainer:
    """Fused-step trainer for a Gluon block on a device mesh.

    Usage::

        trainer = SPMDTrainer(net, loss_fn, 'sgd',
                              {'learning_rate': 0.1, 'momentum': 0.9},
                              mesh=mesh)
        for data, label in loader:
            loss = trainer.step(data, label)
        trainer.sync()           # write weights back into the Block

    loss_fn(pred, label) must return a per-example or scalar loss NDArray-free
    (it is called on raw jax arrays via the functionalized block — gluon.loss
    objects work because they are HybridBlocks; plain callables on jnp arrays
    work too).
    """

    def __init__(self, block, loss_fn, optimizer, optimizer_params=None,
                 mesh=None, batch_axis="dp", param_specs=None,
                 donate=True, dtype=None):
        from .. import optimizer as opt_mod
        self.fn = functionalize(block)
        self.block = block
        self.loss_fn = loss_fn
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer, **(optimizer_params or {}))
        self.optimizer = optimizer
        # Mixed-precision compute policy (reference analog: mx.amp bf16 —
        # python/mxnet/contrib/amp/).  dtype='bfloat16' keeps f32 MASTER
        # weights and optimizer state, but runs forward+backward in bf16 so
        # matmuls/convs hit the MXU at its native rate.  The cast is part of
        # the jitted step, so grads flow through it back to f32 masters
        # (the standard multi-precision recipe; no loss scaling needed —
        # bf16 shares f32's exponent range).
        self.compute_dtype = (jnp.bfloat16 if str(dtype) in
                              ("bfloat16", "bf16") else None) \
            if dtype is not None else None
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        self.batch_axis = batch_axis if batch_axis in self.mesh.axis_names \
            else self.mesh.axis_names[0]
        self._param_specs = param_specs or {}

        self.params = None
        self.opt_state = None
        self._step_num = 0
        self._jitted = {}   # masked(bool) -> jitted program (one guard mode)
        self._donate = donate
        # resilience (docs/RESILIENCE.md): optional CheckpointManager for
        # periodic save / preemption save / auto-resume, plus the nanguard
        # bad-step streak carried as a device scalar so the fused step
        # never syncs the host on finite steps
        self._ckpt_manager = None
        self._guard_mode = ""
        self._nan_streak = None
        # channels-last weights end-to-end (conv.weights_layout=HWIO,
        # docs/PERF_NOTES.md): conv weights + grads + optimizer state live
        # HWIO inside the trainer; boundaries (sync, single-file
        # checkpoints) convert to/from the reference OIHW layout
        from .. import config as _cfg
        self._hwio = _cfg.get("conv.weights_layout") == "HWIO"
        self._hwio_names = _conv_weight_names(block) if self._hwio else set()
        # sparse-grad embedding tables (gluon.nn.Embedding(sparse_grad=True))
        # route through the mesh-sharded deduplicated row-sparse path
        # (parallel/embedding.py) when embedding.sharded is on: the table is
        # sharded on the vocab axis, lookups dedup ids per batch, and the
        # update touches only the gathered rows via Optimizer.step_rows —
        # all inside the same donated program as the dense step
        from . import embedding as _pemb
        self._sparse_embed = _pemb.sparse_embedding_params(
            self.fn, self.mesh, self.batch_axis)
        # compressed DCN sync (kvstore.grad_compress=2bit): per-param
        # error-feedback residuals, sharded P('dcn') and donated through
        # the step like optimizer state; None until the first compressed
        # step materializes them (or a checkpoint restores them)
        self._dcn_residuals = None

    # ------------------------------------------------- compressed DCN sync
    def _dcn_compress_active(self, pad=0):
        """True when this trainer's fused step should quantize the DCN
        gradient hop: the 2-bit knob is on AND the mesh declares a 'dcn'
        axis.  Pad-masked steps run uncompressed (the tail mask reduces
        over the global batch; under shard_map it would be shard-local),
        as do sparse-embedding models (row-sparse updates never cross
        DCN whole)."""
        from .. import config as _cfg
        if _cfg.get("kvstore.grad_compress") != "2bit":
            return False
        if "dcn" not in self.mesh.axis_names:
            return False
        if pad:
            return False
        if any(n in self._sparse_embed for n in self.fn.trainable):
            return False
        return True

    def _dcn_check(self):
        """Refuse configurations where the compressed path would silently
        compute the wrong thing instead of a smaller wire."""
        extra = [a for a in self.mesh.axis_names
                 if a not in ("dcn", self.batch_axis)]
        if extra:
            raise NotImplementedError(
                "kvstore.grad_compress=2bit supports data-parallel meshes "
                "('dcn' + the batch axis); this mesh also has axes %s"
                % (extra,))
        bad = [n for n in list(self.fn.trainable) + list(self.fn.aux)
               if len(self._spec_for(n)) > 0]
        if bad:
            raise NotImplementedError(
                "compressed DCN sync needs replicated parameters (each "
                "gradient is quantized whole); sharded specs on %s"
                % bad[:4])

    def _materialize(self, data):
        """Snapshot the Block's parameters into device-placed jax arrays.

        Deferred-shape parameters (Gluon semantics: shape inference happens on
        the first forward, python/mxnet/gluon/block.py:979-1036) are resolved
        by one eager forward on the first batch.  Values are COPIED: the
        jitted step donates its inputs, and donating buffers still referenced
        by the live Parameters would delete them under the Block.
        """
        from ..gluon.parameter import DeferredInitializationError
        from ..ndarray.ndarray import _wrap
        try:
            vals = self.fn.init_values()
        except DeferredInitializationError:
            self.block(_wrap(jnp.asarray(data)))
            self.fn = functionalize(self.block)
            vals = self.fn.init_values()
            from . import embedding as _pemb
            self._sparse_embed = _pemb.sparse_embedding_params(
                self.fn, self.mesh, self.batch_axis)
        if self._hwio:
            # the HWIO flag flips the interpretation of EVERY traced conv
            # weight, but only nn.Conv2D weights were converted: a custom
            # block with its own 4-D conv weight would silently compute
            # wrong math (square kernel, C_in == C_out) — refuse loudly
            unknown = [n for n in self.fn.trainable
                       if getattr(vals.get(n), "ndim", 0) == 4
                       and n not in self._hwio_names]
            if unknown:
                raise NotImplementedError(
                    "conv.weights_layout=HWIO supports models whose conv "
                    "weights belong to gluon nn.Conv2D blocks; found 4-D "
                    "trainable params it cannot classify: %s — use the "
                    "default 'ref' layout for this model" % unknown)
        self.params = {n: jnp.array(v) for n, v in vals.items()}
        self.params = self._layout_internal(self.params)
        self.opt_state = {}
        for i, name in enumerate(self.fn.trainable):
            st = self.optimizer.create_state(i, _wrap(self.params[name]))
            self.opt_state[name] = _state_to_jax(st)
        self._place()

    # -------------------------------------------------------- weight layout
    def _layout_internal(self, params):
        """OIHW -> HWIO for the conv weights this trainer owns (no-op when
        the knob is off or a name is not a 4-D conv weight)."""
        if not self._hwio_names:
            return params
        out = dict(params)
        for n in self._hwio_names:
            if n in out and getattr(out[n], "ndim", 0) == 4:
                out[n] = jnp.transpose(out[n], (2, 3, 1, 0))
        return out

    def _layout_ref(self, params):
        """HWIO -> OIHW (the reference/checkpoint layout) at boundaries."""
        if not self._hwio_names:
            return params
        out = dict(params)
        for n in self._hwio_names:
            if n in out and getattr(out[n], "ndim", 0) == 4:
                out[n] = jnp.transpose(out[n], (3, 2, 0, 1))
        return out

    def _layout_state(self, state, to_internal):
        """Apply the weight-layout transpose to optimizer-state leaves
        (momentum etc. shard and transpose with their weights)."""
        if not self._hwio_names:
            return state
        perm = (2, 3, 1, 0) if to_internal else (3, 2, 0, 1)
        out = dict(state)
        for n in self._hwio_names:
            if n in out and out[n] is not None:
                out[n] = jax.tree_util.tree_map(
                    lambda x: jnp.transpose(x, perm)
                    if getattr(x, "ndim", 0) == 4 else x, out[n])
        return out

    # ------------------------------------------------------------ placement
    def _spec_for(self, name):
        se = self._sparse_embed.get(name)
        if se is not None and se["axis"] is not None \
                and name not in self._param_specs:
            # embedding table sharded on the VOCAB axis: each device holds
            # rows [k*rows_per_shard, (k+1)*rows_per_shard) and its slice
            # of the optimizer state — no replica of the full table exists
            return P(se["axis"])
        spec = self._param_specs.get(name, P())  # default: replicated
        if name in self._hwio_names and len(spec) > 0:
            # user specs are written against the OIHW axis order; permute
            # them with the weight so the same logical axis stays sharded
            axes = tuple(spec) + (None,) * (4 - len(spec))
            spec = P(*(axes[i] for i in (2, 3, 1, 0)))
        return spec

    def _place(self):
        mesh = self.mesh
        for n in list(self.params.keys()):
            sh = NamedSharding(mesh, self._spec_for(n))
            self.params[n] = jax.device_put(self.params[n], sh)
            if n in self.opt_state and self.opt_state[n] is not None:
                self.opt_state[n] = jax.tree_util.tree_map(
                    lambda x: jax.device_put(x, sh), self.opt_state[n])

    @property
    def batch_sharding(self):
        """The ``NamedSharding`` the fused step expects batches under (rows
        split along the batch axis).  Available BEFORE the first compile —
        hand it (or ``lambda: trainer.batch_sharding``) to
        ``io.DevicePrefetcher`` so batches arrive pre-placed and ``step``
        never issues a synchronous ``device_put``."""
        sh = getattr(self, "_batch_sharding", None)
        if sh is None:
            if "dcn" in self.mesh.axis_names and self.batch_axis != "dcn":
                # the global batch also splits over the slow axis: each
                # dcn slice computes grads for its own rows and the dcn
                # hop (full psum, or 2-bit codes under grad_compress)
                # merges them — without this, every slice would redo the
                # whole batch
                spec = P((self.batch_axis, "dcn"))
            else:
                spec = P(self.batch_axis)
            sh = NamedSharding(self.mesh, spec)
            self._batch_sharding = sh
        return sh

    # ------------------------------------------------------------ step build
    def _build(self, pad=0, instrument=False):
        sparse_meta = {n: m for n, m in self._sparse_embed.items()
                       if n in self.fn.trainable}
        if sparse_meta:
            return self._build_sparse(pad, sparse_meta, instrument)
        from .. import numerics as _numerics
        masked = pad > 0
        fn = self.fn
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        trainable = fn.trainable
        mesh = self.mesh
        batch_sh = self.batch_sharding
        param_sh = {n: NamedSharding(mesh, self._spec_for(n))
                    for n in fn.params}

        cdt = self.compute_dtype
        hwio = bool(self._hwio_names)

        # device names (docs/OBSERVABILITY.md): the forward and loss trace
        # under mx.forward (the backward then reads
        # transpose(jvp(mx.forward))), the update under mx.opt_update
        @jax.named_scope("mx.forward")
        def loss_of(train_params, aux_params, data, label, key):
            from ..ops import nn as _nn_ops
            param_map = dict(aux_params)  # aux (BN stats) stay f32
            if cdt is not None:
                param_map.update(
                    {n: v.astype(cdt) if v.dtype == jnp.float32 else v
                     for n, v in train_params.items()})
                if data.dtype == jnp.float32:  # int inputs (token ids) keep
                    data = data.astype(cdt)    # their dtype
            else:
                param_map.update(train_params)
            prev = _nn_ops.set_hwio_weights(hwio)
            try:
                if instrument:
                    # numerics variant: model-level tap sites (the scan-
                    # carried transformer/BERT layer stats among them)
                    # fill the collector at trace time and ride out
                    # through the loss aux
                    with _numerics.collect() as sink:
                        (out,), new_aux = fn.apply(param_map, (data,), key,
                                                   training=True)
                    fstats = dict(sink)
                else:
                    (out,), new_aux = fn.apply(param_map, (data,), key,
                                               training=True)
            finally:
                _nn_ops.set_hwio_weights(prev)
            if cdt is not None:
                out = out.astype(jnp.float32)
            if masked:
                loss = _as_masked_scalar_loss(loss_fn, out, label, pad)
            else:
                loss = _as_scalar_loss(loss_fn, out, label)
            if instrument:
                _numerics.record(fstats, "out", out)
                _numerics.record(fstats, "loss", loss)
                return loss, (new_aux, out, fstats)
            return loss, (new_aux, out)

        guard = self._guard_mode

        # compressed DCN gradient sync (docs/RESILIENCE.md "Multi-host
        # elasticity"): grads crossing the 'dcn' mesh axis ride as packed
        # 2-bit codes with per-param error-feedback residuals carried as
        # donated step state; ICI axes keep the full-precision psum.  The
        # numerics-instrumented variant always runs uncompressed so
        # forensics sees the raw math.
        compress = (not instrument) and self._dcn_compress_active(pad)
        grad_fn = None
        if compress:
            self._dcn_check()
            import math as _math
            from .. import config as _cfg2
            from . import compression as _comp
            thr = float(_cfg2.get("kvstore.grad_compression_threshold"))
            n_dcn = int(mesh.shape["dcn"])
            n_shards = int(_math.prod(mesh.devices.shape))
            ici_axes = tuple(a for a in mesh.axis_names if a != "dcn")
            all_axes = tuple(mesh.axis_names)

            def sync_grads(train_params, aux_params, residuals, data,
                           label, key):
                # per-shard: grads of the LOCAL rows' mean loss
                (loss, aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_params, aux_params, data,
                                           label, key)
                new_aux = aux[0]
                out_g, new_res = {}, {}
                for n in trainable:
                    g = grads[n]
                    if ici_axes:
                        # ICI stays full precision: compiler-scheduled
                        # psum at torus bandwidth beats recompression
                        with jax.named_scope("mx.allreduce"):
                            g = jax.lax.psum(g, ici_axes)
                    # this dcn slice's share of the GLOBAL mean gradient
                    # (the dcn-psum of v is the uncompressed global grad)
                    v = g / n_shards
                    codes, r = _comp.two_bit_compress(v, residuals[n][0],
                                                      thr)
                    packed = _comp.pack_2bit(codes)
                    # the DCN hop moves 4 codes/byte — 1/16 of the f32
                    # bytes; each shard unpacks the peers' rows and sums
                    with jax.named_scope("mx.allreduce"):
                        rows = jax.lax.all_gather(packed, "dcn")
                    tot = jnp.zeros((int(v.size),), jnp.int32)
                    for w in range(n_dcn):
                        tot = tot + _comp.unpack_2bit(rows[w], int(v.size))
                    out_g[n] = (tot.astype(v.dtype) * thr).reshape(v.shape)
                    new_res[n] = r[None]
                loss = jax.lax.pmean(loss, all_axes)
                new_aux = jax.tree_util.tree_map(
                    lambda a: jax.lax.pmean(a, all_axes)
                    if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)
                    else a, new_aux)
                return loss, new_aux, out_g, new_res

            bspec = batch_sh.spec
            # sync_grads is written shard-locally: value_and_grad over the
            # LOCAL rows gives unreduced grads, and every reduction is a
            # collective the body spells out.  Under the varying-axes
            # check jax would psum those grads by itself (the params are
            # replicated inputs) and reject the result: each shard sums
            # the SAME all-gathered rows, so the grads are equal across
            # 'dcn' by construction, but on an Auto mesh only a reducing
            # collective — a second, full-precision DCN hop — may say so
            # to the type system.  So this one map, and no other, runs
            # unchecked; tests/test_elastic.py holds it to the
            # uncompressed step's values.
            grad_fn = jax.shard_map(
                sync_grads, mesh=mesh,
                in_specs=(P(), P(), P("dcn"), bspec, bspec, P()),
                out_specs=(P(), P(), P(), P("dcn")), check_vma=False)

        def _step_body(train_params, aux_params, opt_state, residuals,
                       data, label, key, t, lrs, wds, lr_scale, streak):
            if compress:
                loss, new_aux, grads, new_res = grad_fn(
                    train_params, aux_params, residuals, data, label, key)
                stats = None
            else:
                (loss, aux), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(train_params, aux_params, data,
                                           label, key)
                if instrument:
                    new_aux, _, stats = aux
                else:
                    (new_aux, _), stats = aux, None
                new_res = None
            new_params = {}
            new_state = {}
            from .. import random as _random
            # Stochastic optimizers (SGLD noise) must draw from the step's
            # traced key, not bake a trace-time constant into the compiled
            # program — keep a trace key scope open for the update loop.
            with _random.trace_key_scope(jax.random.fold_in(key, 1)), \
                    jax.named_scope("mx.opt_update"):
                for i, n in enumerate(trainable):
                    g = _preprocess(optimizer, grads[n])
                    if stats is not None:
                        _numerics.record(stats, "grad." + n, g)
                    w, s = optimizer.step(train_params[n], g,
                                          opt_state[n], lrs[i] * lr_scale,
                                          wds[i], t)
                    new_params[n] = w.astype(train_params[n].dtype)
                    new_state[n] = s
            if stats is not None:
                # pre-guard candidate updates — on a bad step they SHOW
                # the non-finite values forensics is after
                for n in trainable:
                    _numerics.record(stats, "update." + n, new_params[n])
            aux_out = dict(aux_params)
            aux_out.update(new_aux)
            if not guard:
                outs = (new_params, aux_out, new_state) \
                    + ((new_res,) if compress else ()) + (loss,)
                if stats is not None:
                    outs += (stats,)
                return outs
            # nanguard (docs/RESILIENCE.md): all on-device — a bad step
            # keeps the pre-step params/state/aux (the update is computed
            # then deselected; XLA still fuses it into one program) and the
            # host hears about it only through the cond-gated callback, so
            # finite steps pay zero host sync
            from .. import resilience as _resilience
            finite = _resilience.all_finite(loss, grads)
            new_streak = _resilience.guarded_streak(finite, streak, "spmd")
            new_params = _resilience.select_tree(finite, new_params,
                                                 train_params)
            new_state = _resilience.select_tree(finite, new_state, opt_state)
            aux_out = _resilience.select_tree(finite, aux_out, aux_params)
            if compress:
                # a rolled-back step must also roll back its quantization
                # error, or the next step double-counts the bad residual
                new_res = _resilience.select_tree(finite, new_res, residuals)
            outs = (new_params, aux_out, new_state) \
                + ((new_res,) if compress else ()) + (loss, new_streak)
            if stats is not None:
                outs += (stats,)
            return outs

        if compress:
            def step(train_params, aux_params, opt_state, residuals, data,
                     label, key, t, lrs, wds, lr_scale, streak=None):
                return _step_body(train_params, aux_params, opt_state,
                                  residuals, data, label, key, t, lrs, wds,
                                  lr_scale, streak)
            donate = (0, 2, 3) if self._donate else ()
        else:
            def step(train_params, aux_params, opt_state, data, label, key,
                     t, lrs, wds, lr_scale, streak=None):
                return _step_body(train_params, aux_params, opt_state, None,
                                  data, label, key, t, lrs, wds, lr_scale,
                                  streak)
            donate = (0, 2) if self._donate else ()

        # Sharding is carried by the arguments themselves (params were
        # device_put with their NamedShardings in _place(); the batch is
        # sharded in step()): XLA propagates and inserts the gradient
        # allreduce — the entire KVStore push/pull of the reference
        # (src/kvstore/comm.h:451) becomes one compiler-scheduled psum.
        self._batch_sharding = batch_sh
        del param_sh
        return jax.jit(step, donate_argnums=donate)

    def _build_sparse(self, pad, sparse_meta, instrument=False):
        """Fused step for models with sparse-grad embedding tables.

        Same program shape as `_build` (one donated jit: forward, backward,
        update, optional nanguard fold) with the row-sparse embedding path
        spliced in (parallel/embedding.py):

        - tables enter the loss as NON-differentiated arguments; a zero
          ``delta`` leaf of shape ``[capacity, dim]`` is added to the
          gathered unique rows, so ``jax.grad`` w.r.t. the deltas yields the
          DEDUPLICATED per-row gradients and never a dense table cotangent;
        - the op-level routing context performs the ``jnp.unique(size=)``
          dedup + shard_map gather (ids recorded through the loss aux);
        - the update applies ``Optimizer.step_rows`` per shard, touching
          only the gathered rows of the table and its optimizer state.

        Capacity is ``data.size`` (a batch cannot reference more distinct
        ids than it has elements; ``embedding.unique_size`` caps it), so
        compiled shapes — and ``fused_compiles`` — stay flat across ragged
        index batches padded to a common bucket.
        """
        from .. import numerics as _numerics
        masked = pad > 0
        fn = self.fn
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        trainable = fn.trainable
        mesh = self.mesh
        batch_sh = self.batch_sharding
        cdt = self.compute_dtype
        hwio = bool(self._hwio_names)
        from . import embedding as _pemb
        sparse_names = [n for n in trainable if n in sparse_meta]
        if not getattr(optimizer, "lazy_update", False) \
                or not hasattr(optimizer, "step_rows"):
            raise ValueError(
                "sparse-grad embedding params %s need an optimizer with a "
                "lazy step_rows path (sgd, adam); %r has none — set config "
                "embedding.sharded=False to train them densely"
                % (sparse_names, type(optimizer).__name__))

        @jax.named_scope("mx.forward")
        def loss_of(train_params, emb_deltas, aux_params, emb_tables, data,
                    label, key):
            from ..ops import nn as _nn_ops
            from ..ops import tensor as _tensor_ops
            param_map = dict(aux_params)  # aux (BN stats) stay f32
            if cdt is not None:
                param_map.update(
                    {n: v.astype(cdt) if v.dtype == jnp.float32 else v
                     for n, v in train_params.items()})
                param_map.update(
                    {n: v.astype(cdt) if v.dtype == jnp.float32 else v
                     for n, v in emb_tables.items()})
                if data.dtype == jnp.float32:  # int inputs (token ids) keep
                    data = data.astype(cdt)    # their dtype
            else:
                param_map.update(train_params)
                param_map.update(emb_tables)
            ctx = _pemb.SparseLookupContext(mesh, sparse_meta, emb_deltas)
            prev = _nn_ops.set_hwio_weights(hwio)
            prev_ctx = _tensor_ops.set_embed_context(ctx)
            try:
                if instrument:
                    # the touched-rows tap in SparseLookupContext.lookup
                    # fires inside this collector too
                    with _numerics.collect() as sink:
                        (out,), new_aux = fn.apply(param_map, (data,), key,
                                                   training=True)
                    fstats = dict(sink)
                else:
                    (out,), new_aux = fn.apply(param_map, (data,), key,
                                               training=True)
            finally:
                _tensor_ops.set_embed_context(prev_ctx)
                _nn_ops.set_hwio_weights(prev)
            if cdt is not None:
                out = out.astype(jnp.float32)
            if masked:
                loss = _as_masked_scalar_loss(loss_fn, out, label, pad)
            else:
                loss = _as_scalar_loss(loss_fn, out, label)
            if instrument:
                _numerics.record(fstats, "out", out)
                _numerics.record(fstats, "loss", loss)
                return loss, (new_aux, out, ctx.records, fstats)
            return loss, (new_aux, out, ctx.records)

        guard = self._guard_mode

        def step(train_params, aux_params, opt_state, emb_tables, data,
                 label, key, t, lrs, wds, lr_scale, streak=None):
            cap = _pemb.unique_capacity(int(data.size))
            ddt = cdt if cdt is not None else None
            deltas = {
                n: jnp.zeros((cap, sparse_meta[n]["dim"]),
                             ddt or emb_tables[n].dtype)
                for n in sparse_names}
            (loss, aux), (grads, dgrads) = jax.value_and_grad(
                loss_of, argnums=(0, 1), has_aux=True)(
                    train_params, deltas, aux_params, emb_tables, data,
                    label, key)
            if instrument:
                new_aux, _, recs, stats = aux
            else:
                (new_aux, _, recs), stats = aux, None
            new_params = {}
            new_state = {}
            from .. import random as _random
            with _random.trace_key_scope(jax.random.fold_in(key, 1)), \
                    jax.named_scope("mx.opt_update"):
                for i, n in enumerate(trainable):
                    if n in sparse_meta:
                        uniq = recs.get(n)
                        if uniq is None:
                            # table never looked up this forward: no rows
                            # to touch (the lazy-update contract)
                            new_params[n] = emb_tables[n]
                            new_state[n] = opt_state[n]
                            continue
                        gv = _preprocess(
                            optimizer,
                            dgrads[n].astype(emb_tables[n].dtype))
                        if stats is not None:
                            _numerics.record(stats, "grad." + n, gv)
                        w, s = _pemb.update_unique(
                            optimizer, emb_tables[n], opt_state[n], uniq,
                            gv, lrs[i] * lr_scale, wds[i], t,
                            mesh if sparse_meta[n]["axis"] else None,
                            sparse_meta[n]["axis"])
                        new_params[n] = w.astype(emb_tables[n].dtype)
                        new_state[n] = s
                        continue
                    g = _preprocess(optimizer, grads[n])
                    if stats is not None:
                        _numerics.record(stats, "grad." + n, g)
                    w, s = optimizer.step(train_params[n], g,
                                          opt_state[n], lrs[i] * lr_scale,
                                          wds[i], t)
                    new_params[n] = w.astype(train_params[n].dtype)
                    new_state[n] = s
            if stats is not None:
                for n in trainable:
                    _numerics.record(stats, "update." + n, new_params[n])
            aux_out = dict(aux_params)
            aux_out.update(new_aux)
            if not guard:
                if stats is not None:
                    return new_params, aux_out, new_state, loss, stats
                return new_params, aux_out, new_state, loss
            from .. import resilience as _resilience
            finite = _resilience.all_finite(loss, grads, dgrads)
            new_streak = _resilience.guarded_streak(finite, streak, "spmd")
            old_params = dict(train_params)
            old_params.update(emb_tables)
            new_params = _resilience.select_tree(finite, new_params,
                                                 old_params)
            new_state = _resilience.select_tree(finite, new_state, opt_state)
            aux_out = _resilience.select_tree(finite, aux_out, aux_params)
            if stats is not None:
                return (new_params, aux_out, new_state, loss, new_streak,
                        stats)
            return new_params, aux_out, new_state, loss, new_streak

        self._batch_sharding = batch_sh
        donate = (0, 2, 3) if self._donate else ()
        return jax.jit(step, donate_argnums=donate)

    def _program(self, pad, instrument=False):
        """Fetch-or-build the fused step program for ``(pad, variant)``.
        The program cache is keyed by pad count — the pad-masked loss
        uses a STATIC slice so its reduction is structurally identical
        to the unpadded program's (bitwise-equal losses) — each distinct
        tail size costs one compile, bounded by the bucket policy.  The
        numerics-instrumented variant is a separate entry: both coexist,
        so cadenced capture never evicts the plain program."""
        from .. import numerics as _numerics
        from .. import tracing as _tracing
        ntok = _numerics.capture_token(instrument)
        jitted = self._jitted.get((pad, ntok))
        if jitted is not None:
            return jitted
        from .. import perf as _perf
        # kernels=on earns its own program key; the OFF key is
        # unchanged from earlier rounds so perf artifacts stay
        # comparable across releases
        pkey = "pad=%d/guard=%s" % (pad, self._guard_mode)
        if self._kernel_mode:
            pkey += "/kernels=on"
        if instrument:
            pkey += "/numerics"
        elif self._dcn_compress_active(pad):
            pkey += "/dcn2bit"
        with _tracing.span("spmd.compile", cat="spmd"):
            jitted = self._jitted[(pad, ntok)] = _perf.wrap(
                self._build(pad, instrument=instrument), "spmd", pkey,
                source="spmd")
        from .. import profiler as _profiler
        _profiler.counter_increment("fused_compiles")
        return jitted

    # ------------------------------------------------------------ public
    def step(self, data, label, lr_scale=1.0, pad=0):
        """Run one fused train step; returns the (device-resident) loss.

        ``pad`` is the number of trailing fill rows in the batch
        (``DataBatch.pad`` from bucketed padding, docs/PERF_NOTES.md): when
        non-zero the step runs a pad-MASKED program whose loss/gradients
        average over the first ``rows - pad`` samples only, so wrap-padded
        rows contribute exactly nothing.  Requires ``loss_fn`` to return
        per-sample (batch-unreduced) losses.

        Feeds the ``spmd.step`` telemetry timer every call; with the JSONL
        step log enabled each step also emits one record carrying the
        collective mesh shape, compile/host-sync deltas, and throughput
        (docs/OBSERVABILITY.md).  Wall time is host-side dispatch time —
        async device work overlaps the next step by design."""
        from ..ndarray.ndarray import NDArray
        from .. import resilience as _resilience
        from .. import telemetry as _telemetry
        from .. import tracing as _tracing
        if isinstance(data, NDArray):
            data = data._data
        if isinstance(label, NDArray):
            label = label._data
        pad = int(pad or 0)
        # spmd.step holds the whole call; its children shard_batch /
        # prepare / dispatch / post name the parts, and what is left of it
        # is this wrapper's own (the checks below, step_scope, the program
        # lookup)
        with _tracing.span("spmd.step", cat="spmd",
                           step=self._step_num + 1):
            # nanguard escalation check: a dict lookup per step; raises
            # NonFiniteStepError (after flight-recorder dump + checkpoint)
            # once the device reported K consecutive bad steps
            _resilience.maybe_abort_nonfinite("spmd",
                                              save_fn=self._preempt_save)
            if _resilience.faults_active("nan") \
                    and _resilience.should_inject(
                        "nan", step=self._step_num + 1):
                data = _resilience.poison_batch(data)
            with _telemetry.step_scope(
                    "spmd", samples=int(data.shape[0]) - pad if
                    getattr(data, "ndim", 0) else None,
                    shape=tuple(getattr(data, "shape", ())) or None,
                    mesh={n: int(s) for n, s in zip(
                        self.mesh.axis_names, self.mesh.devices.shape)},
                    default_path="fused"):
                loss = self._step_impl(data, label, lr_scale, pad)
            with _tracing.span("spmd.post", cat="spmd"):
                self._after_step()
        return loss

    def _after_step(self):
        """The hooks that run between steps: periodic checkpoint, the
        multi-host preemption agreement, the preemption exit."""
        from .. import resilience as _resilience
        if self._ckpt_manager is not None:
            self._ckpt_manager.maybe_save(self._step_num,
                                          self.save_checkpoint)
        from .. import elastic as _elastic
        if _elastic.active():
            # multi-host lockstep: a SIGTERM on ANY rank (or an injected
            # peer_preempt) makes EVERY rank adopt the request at this
            # same step boundary, so the coordinated checkpoint below
            # snapshots one consistent world
            _elastic.maybe_cluster_preempt(self._step_num)
        if _resilience.preempt_requested():
            # the in-flight step is done (save gathers to host, which
            # syncs); checkpoint, flush sinks, exit 0
            _resilience.exit_on_preempt(save_fn=self._preempt_save)

    def _step_impl(self, data, label, lr_scale, pad=0):
        from .. import io as _io
        from .. import resilience as _resilience
        from .. import tracing as _tracing
        if self.params is None:
            self._materialize(data)
        guard = _resilience.nanguard_mode()
        from .. import config as _config
        from .. import kernels as _kernels
        kmode = _kernels.enabled()
        # the traced step bodies bake in config-derived constants beyond
        # the guard/kernels knobs (the sparse path sizes its dedup
        # buffers from embedding.unique_size), so any config mutation —
        # tracked by the epoch counter — invalidates the program cache
        epoch = _config.epoch()
        if self._jitted and (guard != self._guard_mode or
                             kmode != getattr(self, "_kernel_mode", kmode)
                             or epoch != getattr(self, "_config_epoch",
                                                 epoch)):
            self._jitted.clear()  # knob flip: rebuild with/without the guard
        self._guard_mode = guard
        self._kernel_mode = kmode
        self._config_epoch = epoch
        # numerics cadence (mx.numerics): on a capture step the program
        # cache serves the instrumented VARIANT — its own (pad, token)
        # entry, so off-cadence steps replay the plain program unchanged
        # and a capture-knob toggle never clears this cache (the knob is
        # epoch-neutral in config.py)
        from .. import numerics as _numerics
        cap = _numerics.should_capture("spmd")
        compressed = (not cap) and self._dcn_compress_active(pad)
        if self._dcn_residuals is not None \
                and not self._dcn_compress_active(0):
            # knob turned off: stale error feedback must not leak into a
            # later re-enable (mirrors set_gradient_compression's reset)
            self._dcn_residuals = None
        jitted = self._program(pad, instrument=cap)
        # the batch shard_put is the host->mesh boundary; the gradient
        # allreduce itself is a compiler-scheduled psum INSIDE the jitted
        # step (visible on the device plane of a merged trace, not here).
        # ensure_staged feeds host numpy STRAIGHT to the sharded device_put
        # (no intermediate default-device commit) and is a NO-OP for batches
        # a DevicePrefetcher already placed — steady-state steps then do
        # zero synchronous H2D here (io.h2d_sync.spmd stays flat).
        with _tracing.span("spmd.shard_batch", cat="spmd"):
            data = _io.ensure_staged(data, self._batch_sharding,
                                     source="spmd")
            label = _io.ensure_staged(label, self._batch_sharding,
                                      source="spmd")
        with _tracing.span("spmd.prepare", cat="spmd"):
            self._step_num += 1
            self.optimizer.num_update = self._step_num
            if not hasattr(self, "_hyper_cache"):
                self._hyper_cache = {}
            lrs, wds = _opt_hyper_arrays(
                self.optimizer, len(self.fn.trainable), self._hyper_cache)
            from .. import random as _random
            key = _random.new_eager_seed_key()
            sparse = {n for n in self._sparse_embed if n in self.fn.trainable}
            train = {n: self.params[n] for n in self.fn.trainable
                     if n not in sparse}
            tables = {n: self.params[n] for n in sparse}
            aux = {n: self.params[n] for n in self.fn.aux}
            scales = self._hyper_cache.setdefault("scales", {})
            # cache only plain-number scales (arrays are unhashable and a
            # dynamic loss-scale would grow the cache unboundedly)
            cacheable = isinstance(lr_scale, (int, float))
            sarr = scales.get(lr_scale) if cacheable else None
            if sarr is None:
                sarr = jnp.asarray(lr_scale, jnp.float32)
                if cacheable and len(scales) < 16:
                    scales[lr_scale] = sarr
            t_arr = jnp.asarray(self._step_num, jnp.int32)
            if compressed and self._dcn_residuals is None:
                n_dcn = int(self.mesh.shape["dcn"])
                rsh = NamedSharding(self.mesh, P("dcn"))
                self._dcn_residuals = {
                    n: jax.device_put(
                        jnp.zeros((n_dcn,) + tuple(train[n].shape),
                                  train[n].dtype if jnp.issubdtype(
                                      train[n].dtype, jnp.inexact)
                                  else jnp.float32), rsh)
                    for n in train}
            args = (train, aux, self.opt_state) + \
                ((self._dcn_residuals,) if compressed else ()) + \
                ((tables,) if sparse else ()) + (data, label, key, t_arr, lrs,
                                                 wds, sarr)
        stats = None
        if self._guard_mode:
            if self._nan_streak is None:
                self._nan_streak = jnp.zeros((), jnp.int32)
            with _tracing.span("spmd.dispatch", cat="spmd"):
                res = jitted(*args, self._nan_streak)
            if cap:
                (new_train, new_aux, self.opt_state, loss,
                 self._nan_streak, stats) = res
            elif compressed:
                (new_train, new_aux, self.opt_state, self._dcn_residuals,
                 loss, self._nan_streak) = res
            else:
                new_train, new_aux, self.opt_state, loss, \
                    self._nan_streak = res
            # no-sync host inspection of completed steps' streaks
            _resilience.watch_streak("spmd", self._nan_streak)

            def _replay(data=data, label=label, key=key, t_arr=t_arr,
                        lrs=lrs, wds=wds, sarr=sarr, pad=pad):
                # nanguard forensics (mx.numerics): re-run THIS batch
                # once through the instrumented variant.  Params and opt
                # state are read live (last-good after select_tree) and
                # COPIED because the replay donates them like any step;
                # the abort path still checkpoints the originals after.
                import jax as _jax
                fi = self._program(pad, instrument=True)
                spn = {n for n in self._sparse_embed
                       if n in self.fn.trainable}
                train = _jax.tree_util.tree_map(
                    jnp.array,
                    {n: self.params[n] for n in self.fn.trainable
                     if n not in spn})
                tables = _jax.tree_util.tree_map(
                    jnp.array, {n: self.params[n] for n in spn})
                aux = {n: self.params[n] for n in self.fn.aux}
                ost = _jax.tree_util.tree_map(jnp.array, self.opt_state)
                a = (train, aux, ost) + ((tables,) if spn else ()) + \
                    (data, label, key, t_arr, lrs, wds, sarr)
                return fi(*a, jnp.zeros((), jnp.int32))[-1]

            _numerics.hold_replay("spmd", _replay)
        else:
            with _tracing.span("spmd.dispatch", cat="spmd"):
                res = jitted(*args)
            if cap:
                new_train, new_aux, self.opt_state, loss, stats = res
            elif compressed:
                (new_train, new_aux, self.opt_state, self._dcn_residuals,
                 loss) = res
            else:
                new_train, new_aux, self.opt_state, loss = res
        if compressed:
            # static wire accounting (no device sync): each step's DCN hop
            # carries the packed codes — 4 per byte vs 4 bytes per f32
            wire = getattr(self, "_dcn_wire", None)
            if wire is None:
                packed = sum((int(v.size) + 3) // 4
                             for v in new_train.values())
                raw_b = sum(int(v.size) * 4 for v in new_train.values())
                wire = self._dcn_wire = (packed, raw_b)
            from .. import telemetry as _telemetry
            _telemetry.counter("kvstore.compressed_bytes").inc(wire[0])
            _telemetry.counter("kvstore.compressed_raw_bytes").inc(wire[1])
            comp = _telemetry.counter("kvstore.compressed_bytes").value
            raw = _telemetry.counter("kvstore.compressed_raw_bytes").value
            if comp:
                _telemetry.gauge("kvstore.compression_ratio").set(
                    raw / comp)
        if stats is not None:
            # device stats enter the pending queue; drained by the
            # is-ready poll later — zero host sync on this thread
            _numerics.publish("spmd", self._step_num, stats)
        from .. import profiler as _profiler
        _profiler.counter_increment("fused_steps")
        if sparse:
            # static per-step accounting (no device sync): each routed table
            # gathers/touches at most `capacity` unique rows this step; the
            # data-dependent unique_ratio gauge is fed by the eager
            # ShardedEmbedding API and the bench/check tools
            from . import embedding as _pemb
            from .. import telemetry as _telemetry
            cap = _pemb.unique_capacity(int(data.size)) * len(tables)
            _telemetry.counter("embedding.gathered_rows").inc(cap)
            _telemetry.counter("embedding.rows_touched").inc(cap)
        self.params = {}
        self.params.update(new_train)
        self.params.update(new_aux)
        return loss

    def sync(self):
        """Write device params back into the Block's Parameters (always in
        the reference OIHW layout, whatever the internal layout is)."""
        self.fn.write_back(self._layout_ref(self.params))

    # ---------------------------------------------------------- checkpoint
    def attach_checkpoint_manager(self, manager, auto_resume=True):
        """Wire a ``resilience.CheckpointManager`` into the step loop:
        ``maybe_save`` fires on its every-N cadence after each step, a
        preemption signal checkpoints through it before exiting, and the
        nanguard abort path writes a last-good checkpoint.  With
        ``auto_resume`` (default) the newest GOOD checkpoint is restored
        immediately — a corrupt/truncated newest file is skipped for the
        last good one.  Returns the resumed step, or None on cold start.

        In a multi-process world a plain CheckpointManager is upgraded to
        the coordinated protocol (``elastic.CoordinatedCheckpointManager``:
        rank 0 writes + world-stamped manifest + all-ranks barrier) — an
        uncoordinated save from N ranks into one directory would race."""
        if jax.process_count() > 1:
            from .. import elastic as _elastic
            manager = _elastic.coordinate(manager, mesh=self.mesh)
        self._ckpt_manager = manager
        if auto_resume:
            return manager.restore(self.load_checkpoint)
        return None

    def _preempt_save(self):
        """Best-effort checkpoint for preemption/nanguard-abort exits."""
        if self._ckpt_manager is not None and self.params is not None:
            self._ckpt_manager.save(self._step_num, self.save_checkpoint)

    def _ckpt_meta(self):
        """Shared guard + metadata for both checkpoint formats."""
        from .. import random as _random
        if self.params is None:
            raise ValueError("nothing to checkpoint: trainer has no "
                             "materialized params (run a step first)")
        return self._step_num, _random._global_key()

    def save_checkpoint_sharded(self, path):
        """Sharded checkpoint via orbax: every host writes ONLY its own
        shards (no gather), the layout that scales to multi-host models too
        big to fit one host's RAM — the TPU-native answer to the
        reference's single-file NDArray serializer (SURVEY §5.4;
        src/ndarray/ndarray.cc Save).  `save_checkpoint` remains the
        single-host portable-file path."""
        import os
        import orbax.checkpoint as ocp
        step_num, rng_key = self._ckpt_meta()
        tree = {
            "params": dict(self.params),
            "opt_state": self.opt_state,
            "meta": {"step_num": jnp.asarray(step_num, jnp.int32),
                     "rng_key": rng_key},
        }
        path = os.path.abspath(path)
        if os.path.isdir(path) and os.listdir(path) and not os.path.exists(
                os.path.join(path, "_CHECKPOINT_METADATA")):
            # force=True rmtree's the target; only a PRIOR CHECKPOINT may
            # be overwritten — never an unrelated user directory
            raise ValueError(
                "%s exists and is not an orbax checkpoint; refusing to "
                "delete it" % path)
        ckptr = ocp.StandardCheckpointer()
        ckptr.save(path, tree, force=True)
        ckptr.wait_until_finished()

    def load_checkpoint_sharded(self, path):
        """Restore an orbax checkpoint directly into this trainer's
        shardings: the restore target carries NamedShardings built from
        the checkpoint metadata + this trainer's param specs, so each host
        reads ONLY the shards it owns (a target-less restore would
        materialize every array in full on every process)."""
        import os
        import orbax.checkpoint as ocp
        from .. import random as _random

        path = os.path.abspath(path)
        from .. import resilience as _resilience
        if not os.path.isdir(path) or not os.path.exists(
                os.path.join(path, "_CHECKPOINT_METADATA")):
            raise _resilience.CheckpointCorruptError(
                "%s is not an orbax checkpoint (missing "
                "_CHECKPOINT_METADATA — interrupted save or wrong path)"
                % path)
        ckptr = ocp.StandardCheckpointer()
        try:
            md = ckptr.metadata(path)
            if hasattr(md, "item_metadata"):
                # newer orbax wraps the tree in a StepMetadata-style object;
                # 0.7.x StandardCheckpointer returns the tree dict directly
                md = md.item_metadata.tree
        except Exception as exc:  # noqa: BLE001 — orbax raises many types
            raise _resilience.CheckpointCorruptError(
                "orbax metadata for %s is unreadable (%s: %s)"
                % (path, type(exc).__name__, exc)) from exc
        if not isinstance(md, dict) or not {
                "params", "opt_state", "meta"} <= set(md):
            raise _resilience.CheckpointCorruptError(
                "orbax checkpoint %s carries no usable tree metadata "
                "(got %s)" % (path, type(md).__name__))
        mesh = self.mesh

        def abstract(meta, spec):
            return jax.ShapeDtypeStruct(
                tuple(meta.shape), meta.dtype,
                sharding=NamedSharding(mesh, spec))

        target = {
            "params": {n: abstract(m, self._spec_for(n))
                       for n, m in md["params"].items()},
            "opt_state": {
                n: jax.tree_util.tree_map(
                    lambda m, s=self._spec_for(n): abstract(m, s), sub)
                for n, sub in md["opt_state"].items()},
            "meta": jax.tree_util.tree_map(lambda m: abstract(m, P()),
                                           md["meta"]),
        }
        restored = ckptr.restore(path, target)
        self._step_num = int(restored["meta"]["step_num"])
        self.optimizer.num_update = self._step_num
        self.params = dict(restored["params"])
        # orbax may hand tuples back as lists; the jitted step was traced
        # with tuple-typed optimizer states, so normalize the structure
        self.opt_state = {n: _state_to_jax(v)
                          for n, v in restored["opt_state"].items()}
        _random._STATE.key = jnp.asarray(restored["meta"]["rng_key"])

    def save_checkpoint(self, path):
        """Save params + optimizer state + step count to ``path``.

        The SPMD analog of Module checkpointing (reference:
        python/mxnet/model.py:394-442 save_checkpoint) plus Trainer optimizer
        state (python/mxnet/gluon/trainer.py:436 save_states) in ONE file:
        there is no symbol/params split because the program is the jitted
        step, and optimizer state lives beside the weights it shards with.
        Arrays are gathered to host; `load_checkpoint` re-places them with
        the trainer's own shardings, so the mesh shape may differ between
        save and restore (e.g. checkpoint on 8 chips, resume on 16).
        """
        import numpy as np
        import pickle
        from .. import resilience as _resilience
        step_num, rng_key = self._ckpt_meta()
        # single-file checkpoints always carry the reference OIHW layout so
        # they stay interchangeable across conv.weights_layout settings
        ref_params = self._layout_ref(self.params)
        ref_state = self._layout_state(self.opt_state, to_internal=False)
        host = {
            "schema": _resilience.CKPT_SCHEMA,
            "format": "mxnet_tpu-spmd-ckpt",
            "step_num": step_num,
            "params": {n: _to_host(v) for n, v in ref_params.items()},
            "opt_state": jax.tree_util.tree_map(_to_host, ref_state),
            # The eager PRNG stream position: models that draw per step
            # (dropout, SGLD) must resume on the same key sequence for the
            # bitwise-continue guarantee to hold.
            "rng_key": np.asarray(rng_key),
        }
        if self._dcn_residuals is not None:
            # compressed-DCN error feedback rides along so a resumed run
            # continues the quantized trajectory bitwise
            host["dcn_residuals"] = {n: _to_host(v) for n, v in
                                     self._dcn_residuals.items()}
        # atomic publish: a crash mid-write leaves the previous checkpoint
        # under `path`, never a truncated pickle (docs/RESILIENCE.md)
        with _resilience.atomic_write(path, "wb") as f:
            pickle.dump(host, f)

    def load_checkpoint(self, path):
        """Restore a `save_checkpoint` file; training continues bitwise
        where it left off (same data ⇒ same loss curve).

        Truncated/unpicklable files and newer-schema checkpoints raise
        ``resilience.CheckpointCorruptError`` up front — never a deep
        ``EOFError``/``KeyError`` from half-restored state — so
        ``CheckpointManager.restore`` can fall back to the previous one."""
        import pickle
        from .. import random as _random
        from .. import resilience as _resilience
        try:
            with open(path, "rb") as f:
                host = pickle.load(f)
        except (OSError, EOFError, pickle.UnpicklingError, AttributeError,
                ImportError, IndexError, ValueError) as exc:
            raise _resilience.CheckpointCorruptError(
                "checkpoint %s is unreadable (%s: %s)"
                % (path, type(exc).__name__, exc)) from exc
        if not isinstance(host, dict) or not (
                {"step_num", "params", "opt_state"} <= set(host)):
            raise _resilience.CheckpointCorruptError(
                "checkpoint %s is not an SPMDTrainer checkpoint (missing "
                "step_num/params/opt_state)" % path)
        if int(host.get("schema", 1)) > _resilience.CKPT_SCHEMA:
            raise _resilience.CheckpointCorruptError(
                "checkpoint %s was written by a newer schema (%s > %s); "
                "upgrade this framework to load it"
                % (path, host.get("schema"), _resilience.CKPT_SCHEMA))
        self._step_num = host["step_num"]
        self.optimizer.num_update = self._step_num
        self.params = self._layout_internal(
            {n: jnp.asarray(v) for n, v in host["params"].items()})
        self.opt_state = self._layout_state(host["opt_state"],
                                            to_internal=True)
        self._place()
        if "rng_key" in host:
            _random._STATE.key = jnp.asarray(host["rng_key"])
        self._nan_streak = None  # restored params are finite by definition
        self._dcn_residuals = None
        dres = host.get("dcn_residuals")
        if dres and "dcn" in self.mesh.axis_names:
            n_dcn = int(self.mesh.shape["dcn"])
            if all(v.shape[0] == n_dcn for v in dres.values()):
                rsh = NamedSharding(self.mesh, P("dcn"))
                self._dcn_residuals = {
                    n: jax.device_put(jnp.asarray(v), rsh)
                    for n, v in dres.items()}
            # a re-formed world with a different dcn extent restarts the
            # error feedback from zero (first compressed step re-inits)
        return self._step_num


def _to_host(x):
    """Gather a (possibly multi-host-sharded) array to a host numpy array."""
    import numpy as np
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.experimental import multihost_utils
        x = multihost_utils.process_allgather(x, tiled=True)
    return np.asarray(x)


def _state_to_jax(st):
    from ..ndarray.ndarray import NDArray
    if st is None:
        return None
    if isinstance(st, NDArray):
        return st._data
    if isinstance(st, (tuple, list)):
        return tuple(_state_to_jax(s) for s in st)
    return st


def _preprocess(optimizer, grad):
    g = grad * optimizer.rescale_grad
    if optimizer.clip_gradient is not None:
        g = jnp.clip(g, -optimizer.clip_gradient, optimizer.clip_gradient)
    return g


def _raw_loss(loss_fn, out, label):
    from ..ndarray.ndarray import NDArray, _wrap
    try:
        loss = loss_fn(_wrap(out), _wrap(label))
        loss = loss._data if isinstance(loss, NDArray) else loss
    except (TypeError, AttributeError):
        loss = loss_fn(out, label)
        loss = loss._data if isinstance(loss, NDArray) else loss
    return loss.astype(jnp.float32)


def _as_scalar_loss(loss_fn, out, label):
    return jnp.mean(_raw_loss(loss_fn, out, label))


def _as_masked_scalar_loss(loss_fn, out, label, pad):
    """Mean loss over all but the last ``pad`` rows: trailing fill rows
    (bucketed padding, ``DataBatch.pad``) contribute nothing to loss OR
    gradients.  ``pad`` is STATIC — the slice makes the reduction
    structurally identical to the unpadded program's ``jnp.mean``, so the
    masked loss matches the unpadded loss bitwise (a traced mask would
    reduce over the padded length and drift in the last ulp)."""
    loss = _raw_loss(loss_fn, out, label)
    if loss.ndim == 0:
        raise ValueError(
            "pad-masked step needs per-sample losses: loss_fn reduced over "
            "the batch already — return unreduced losses or drop pad=")
    valid = int(loss.shape[0]) - int(pad)
    if valid <= 0:
        raise ValueError("pad=%d leaves no valid rows in a %d-row batch"
                         % (pad, int(loss.shape[0])))
    return jnp.mean(loss[:valid])


def build_train_step(block, loss_fn, optimizer, optimizer_params=None,
                     mesh=None, **kw):
    """Convenience: construct an SPMDTrainer and return (trainer, step_fn)."""
    tr = SPMDTrainer(block, loss_fn, optimizer, optimizer_params, mesh=mesh,
                     **kw)
    return tr, tr.step
