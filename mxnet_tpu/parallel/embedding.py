"""mx.parallel.embedding — mesh-sharded embedding tables with deduplicated
row-sparse lookup/update (docs/PERF_NOTES.md "Sharded embeddings").

The recommendation-scale workload (DLRM-style: tables of 10^5..10^9 rows,
each batch touching a few thousand of them) needs three things the dense
data-parallel step cannot give:

  1. **No full-table replication.**  The table is sharded on the VOCAB axis
     over one mesh axis (``NamedSharding(mesh, P(axis))``); every lookup and
     every optimizer update runs under ``shard_map`` so each shard answers
     only the ids it owns and the per-id results meet on ICI via ``psum``
     (owner contributes the row, everyone else contributes zeros).  A dense
     image of the table never exists on any one device.

  2. **Per-batch id deduplication with STATIC shapes.**  Real id batches are
     heavily repeated (Zipf traffic) and ragged.  ``jnp.unique`` with a
     static ``size=`` + sentinel ``fill_value`` keeps the compiled shapes
     identical across batches — one gather per unique id, results scattered
     back through the inverse map, and ``fused_compiles`` stays flat.

  3. **O(rows-touched) updates.**  The update reuses ``Optimizer.step_rows``
     (the lazy row_sparse path of optimizer.py) per shard: only the touched
     rows of the table AND its optimizer state are read/written, inside the
     same donated program as the dense step.

Padding contract: index batches padded by ``io.DevicePrefetcher`` carry a
SENTINEL id (any id >= num_rows; the prefetcher's ``pad_sentinel``).  The
lookup returns zero rows for sentinel ids and the update drops them — on
the owning-shard test ``sentinel - shard_base`` falls outside every shard's
``[0, rows_per_shard)`` range, so the scatter's out-of-bounds-drop semantics
mask them with no extra branch.

Routing: ``SPMDTrainer`` detects trainable 2-D ``grad_stype='row_sparse'``
parameters (what ``gluon.nn.Embedding(sparse_grad=True)`` declares) and,
when ``embedding.sharded`` is on, routes their op calls through
``SparseLookupContext`` below: the table enters the loss as a
NON-differentiated argument, the gathered unique rows get a zero "delta"
leaf added, and the delta's gradient IS the deduplicated row gradient —
``jax.grad`` never materializes a dense table cotangent.
"""
from __future__ import annotations

import math as _math
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["ShardedEmbedding", "dedup_ids", "lookup_unique", "update_unique",
           "unique_capacity", "sparse_embedding_params",
           "SparseLookupContext"]


def unique_capacity(n_ids):
    """Static unique-id capacity for a batch of ``n_ids`` indices.

    Default (``embedding.unique_size`` = 0) is ``n_ids`` — always safe,
    since a batch cannot contain more distinct ids than elements.  A
    positive knob value caps the capacity (smaller compiled buffers when
    the per-batch unique count is known to be bounded); ids beyond the cap
    would be silently dropped, so the knob is a user contract.
    """
    from .. import config as _cfg
    cap = int(_cfg.get("embedding.unique_size") or 0)
    n = int(n_ids)
    return n if cap <= 0 else min(cap, n)


def dedup_ids(ids, size, sentinel):
    """Deduplicate a batch of ids with STATIC output shapes.

    Returns ``(uniq, inv)``: ``uniq`` is ``[size]`` int32, sorted ascending,
    padded with ``sentinel`` (which sorts last when ``sentinel >= num_rows``);
    ``inv`` maps every flattened input position to its row in ``uniq``.
    Compiled shapes depend only on ``ids.size`` and ``size`` — ragged batches
    that pad to the same bucket reuse the same program.
    """
    flat = jnp.ravel(jnp.asarray(ids)).astype(jnp.int32)
    uniq, inv = jnp.unique(flat, return_inverse=True, size=int(size),
                           fill_value=jnp.int32(sentinel))
    return uniq, jnp.ravel(inv)


def lookup_unique(table, uniq, mesh=None, axis=None):
    """Gather ``table[uniq]`` — sharded when ``mesh``/``axis`` are given.

    Sharded: each shard answers only the ids it owns (local gather on its
    ``[rows_per_shard, dim]`` slice) and contributes zeros elsewhere; one
    ``psum`` over ``axis`` combines the answers on ICI.  Ids outside the
    table (the pad sentinel) come back as zero rows on every path.
    """
    num_rows = int(table.shape[0])
    if mesh is None or axis is None:
        safe = jnp.minimum(uniq, num_rows - 1)
        vals = jnp.take(table, safe, axis=0)
        return jnp.where((uniq < num_rows)[:, None], vals,
                         jnp.zeros((), table.dtype))
    rows_per = num_rows // int(mesh.shape[axis])

    def _shard(tbl, u):
        base = jax.lax.axis_index(axis) * rows_per
        local = u - base
        owned = (local >= 0) & (local < rows_per)
        vals = jnp.take(tbl, jnp.where(owned, local, 0), axis=0)
        vals = jnp.where(owned[:, None], vals, jnp.zeros((), tbl.dtype))
        return jax.lax.psum(vals, axis)

    return shard_map(_shard, mesh=mesh, in_specs=(P(axis, None), P()),
                     out_specs=P())(table, uniq)


def update_unique(optimizer, table, state, uniq, grad_rows, lr, wd, t,
                  mesh=None, axis=None):
    """Row-sparse optimizer update on deduplicated ids.

    Reuses ``optimizer.step_rows`` — only the rows named in ``uniq`` (and
    the same rows of every optimizer-state leaf) are read and written.
    Sentinel/out-of-table ids map to an out-of-range row index, which the
    ``.at[rows]`` scatters inside ``step_rows`` DROP (jax's default
    out-of-bounds scatter mode), so padded ids never touch the table.

    Sharded (``mesh``+``axis``): runs per shard under ``shard_map`` with the
    shard's local row offsets; non-owned ids fall out of the local range and
    are dropped the same way.  Returns ``(new_table, new_state)``.
    """
    num_rows = int(table.shape[0])
    if mesh is None or axis is None:
        rows = jnp.where(uniq < num_rows, uniq, num_rows)  # OOB -> dropped
        return optimizer.step_rows(table, rows, grad_rows, state, lr, wd, t)
    rows_per = num_rows // int(mesh.shape[axis])

    def _local_rows(u):
        base = jax.lax.axis_index(axis) * rows_per
        local = u - base
        owned = (local >= 0) & (local < rows_per)
        return jnp.where(owned, local, rows_per)  # OOB -> dropped

    if state is None:
        def _shard(tbl, u, g, lr_, wd_, t_):
            new_w, _ = optimizer.step_rows(tbl, _local_rows(u), g, None,
                                           lr_, wd_, t_)
            return new_w
        new_table = shard_map(
            _shard, mesh=mesh,
            in_specs=(P(axis, None), P(), P(), P(), P(), P()),
            out_specs=P(axis, None))(table, uniq, grad_rows, lr, wd, t)
        return new_table, None

    state_spec = jax.tree_util.tree_map(lambda _: P(axis, None), state)

    def _shard(tbl, st, u, g, lr_, wd_, t_):
        return optimizer.step_rows(tbl, _local_rows(u), g, st, lr_, wd_, t_)

    return shard_map(
        _shard, mesh=mesh,
        in_specs=(P(axis, None), state_spec, P(), P(), P(), P(), P()),
        out_specs=(P(axis, None), state_spec))(
            table, state, uniq, grad_rows, lr, wd, t)


def sparse_embedding_params(fn, mesh, axis):
    """Map trainable sparse-grad embedding params to their routing metadata.

    Selects 2-D trainable parameters declared ``grad_stype='row_sparse'``
    (``gluon.nn.Embedding(sparse_grad=True)``).  Each entry carries the
    table's row count, embedding dim and the mesh axis to shard the vocab
    over — ``None`` (replicated table, still deduplicated + row-sparse
    updates) when the axis has one device or the rows don't divide it.
    Empty when the ``embedding.sharded`` knob is off.
    """
    from .. import config as _cfg
    if not _cfg.get("embedding.sharded"):
        return OrderedDict()
    out = OrderedDict()
    axis_size = int(mesh.shape[axis]) if axis in mesh.axis_names else 1
    for n in fn.trainable:
        p = fn.params[n]
        if getattr(p, "_grad_stype", "default") != "row_sparse":
            continue
        shape = getattr(p, "shape", None)
        if not shape or len(shape) != 2 or not shape[0] or not shape[1]:
            continue  # deferred or non-2-D params stay on the dense path
        rows, dim = int(shape[0]), int(shape[1])
        shard_axis = axis if (axis_size > 1 and rows % axis_size == 0) \
            else None
        out[n] = {"rows": rows, "dim": dim, "axis": shard_axis}
    return out


class SparseLookupContext:
    """Routes ``Embedding(sparse_grad=True)`` op calls inside ONE fused-step
    trace through the sharded deduplicated lookup.

    The trainer passes each table into the loss as a NON-differentiated
    argument plus a zero ``delta`` leaf of shape ``[capacity, dim]``; the
    context adds the delta to the gathered unique rows, so the delta's
    gradient is exactly the deduplicated per-row gradient (summed over
    duplicates through the inverse-map scatter) and no dense table
    cotangent is ever built.  Op calls are matched to tables by weight
    shape; each table supports one lookup per forward (its single delta
    leaf carries the row gradient).
    """

    def __init__(self, mesh, meta, deltas):
        self._mesh = mesh
        self._meta = meta        # name -> {'rows', 'dim', 'axis'}
        self._deltas = deltas    # name -> [capacity, dim] zero grad leaves
        self._by_shape = {(m["rows"], m["dim"]): n for n, m in meta.items()}
        self.records = {}        # name -> uniq ids seen this forward

    def lookup(self, data, weight):
        """Sharded deduplicated gather, or None for unrouted weights."""
        shape = tuple(int(s) for s in weight.shape)
        name = self._by_shape.get(shape)
        if name is None:
            return None
        if name in self.records:
            raise NotImplementedError(
                "sparse-grad embedding %r is looked up more than once per "
                "forward (or shares its %r shape with another sparse "
                "table); the sharded row-sparse path supports one lookup "
                "per table — set config embedding.sharded=False for this "
                "model" % (name, shape))
        meta = self._meta[name]
        sentinel = meta["rows"]
        ids = jnp.asarray(data)
        uniq, inv = dedup_ids(ids, self._deltas[name].shape[0], sentinel)
        rows = lookup_unique(jax.lax.stop_gradient(weight), uniq,
                             self._mesh if meta["axis"] else None,
                             meta["axis"])
        rows = rows + self._deltas[name].astype(rows.dtype)
        from .. import numerics as _numerics
        # fused-step trace opens a numerics collector when instrumented;
        # the touched unique rows are the interesting tensor (the dense
        # take() output just repeats them)
        rows = _numerics.tap("embedding.%s.rows" % name, rows)
        self.records[name] = uniq
        return jnp.take(rows, inv, axis=0).reshape(
            tuple(ids.shape) + (shape[1],))


class ShardedEmbedding:
    """A mesh-sharded embedding table with deduplicated lookups and lazy
    row-sparse updates — the standalone counterpart of the fused-step
    routing (same ``dedup_ids``/``lookup_unique``/``update_unique``
    primitives; SPMDTrainer wires those into its donated program directly).

    Programs are cached per ids-shape, so ragged batches padded to a common
    bucket reuse one compile (``embedding.lookup_compiles`` counts cache
    misses).  Every call feeds the ``embedding.*`` telemetry:
    ``unique_ratio`` gauge, ``gathered_rows``/``rows_touched`` counters and
    the ``lookup_ms`` timer (this eager API intentionally blocks on the
    device so the timer measures real work).
    """

    def __init__(self, num_rows, dim, mesh=None, axis=None,
                 dtype=jnp.float32, optimizer=None, init_scale=0.01,
                 seed=0):
        from .mesh import data_parallel_mesh
        from .trainer import _state_to_jax
        from .. import optimizer as opt_mod
        from ..ndarray.ndarray import _wrap
        self.num_rows = int(num_rows)
        self.dim = int(dim)
        self.mesh = mesh if mesh is not None else data_parallel_mesh()
        if axis is None:
            axis = next((a for a in self.mesh.axis_names
                         if int(self.mesh.shape[a]) > 1
                         and self.num_rows % int(self.mesh.shape[a]) == 0),
                        None)
        elif self.num_rows % int(self.mesh.shape[axis]) != 0:
            raise ValueError(
                "num_rows=%d does not divide mesh axis %r (size %d)"
                % (self.num_rows, axis, int(self.mesh.shape[axis])))
        self.axis = axis
        if isinstance(optimizer, str):
            optimizer = opt_mod.create(optimizer)
        self.optimizer = optimizer if optimizer is not None \
            else opt_mod.create("sgd")
        if not getattr(self.optimizer, "lazy_update", False) \
                or not hasattr(self.optimizer, "step_rows"):
            raise ValueError(
                "ShardedEmbedding needs an optimizer with a lazy "
                "step_rows path (sgd, adam); got %r"
                % type(self.optimizer).__name__)
        key = jax.random.PRNGKey(seed)
        table = (jax.random.normal(key, (self.num_rows, self.dim),
                                   jnp.float32) * init_scale).astype(dtype)
        sh = NamedSharding(self.mesh, P(axis) if axis else P())
        self.table = jax.device_put(table, sh)
        st = _state_to_jax(self.optimizer.create_state(0, _wrap(self.table)))
        self.state = None if st is None else jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sh), st)
        self._t = 0
        self._progs = {}  # (kind, ids_shape, config-epoch) -> program

    # ------------------------------------------------------------ programs
    def _prog(self, kind, ids_shape, instrument=False):
        from .. import config as _config
        from .. import numerics as _numerics
        # the programs bake in config-derived constants (unique_capacity
        # reads embedding.unique_size), so the config epoch is part of
        # the key and superseded entries are evicted — the same
        # invalidation contract as symbol.py's key_sig.  The numerics
        # token is its own element: both variants coexist and toggling
        # capture never evicts (the knob is epoch-neutral).
        epoch = _config.epoch()
        key = (kind, ids_shape, _numerics.capture_token(instrument), epoch)
        prog = self._progs.get(key)
        if prog is not None:
            return prog
        self._progs = {k: v for k, v in self._progs.items()
                       if k[-1] == epoch}
        from .. import telemetry as _telemetry
        _telemetry.counter("embedding.lookup_compiles").inc()
        cap = unique_capacity(max(_math.prod(ids_shape), 1))
        mesh = self.mesh if self.axis else None
        sentinel = self.num_rows
        opt = self.optimizer

        if kind == "lookup":
            def run(table, ids):
                uniq, inv = dedup_ids(ids, cap, sentinel)
                rows = lookup_unique(table, uniq, mesh, self.axis)
                out = jnp.take(rows, inv, axis=0).reshape(
                    tuple(ids.shape) + (self.dim,))
                if instrument:
                    from .. import numerics as _num
                    return (out, jnp.sum(uniq < sentinel),
                            {"embedding.rows": _num.summarize(rows)})
                return out, jnp.sum(uniq < sentinel)
            prog = jax.jit(run)
        else:
            def run(table, state, ids, grad, lr, wd, t):
                uniq, inv = dedup_ids(ids, cap, sentinel)
                gsum = jnp.zeros((cap, self.dim), grad.dtype).at[inv].add(
                    grad.reshape(-1, self.dim))
                return update_unique(opt, table, state, uniq,
                                     gsum.astype(table.dtype), lr, wd, t,
                                     mesh, self.axis)
            prog = jax.jit(run, donate_argnums=(0, 1))
        from .. import perf as _perf
        # no source: embedding programs run inside the caller's step scope
        # (or eagerly) — cost registers, step MFU attribution stays with
        # the owning trainer's fused program
        prog = _perf.wrap(prog, "embedding",
                          "%s/%s%s" % (kind, ids_shape,
                                       "/numerics" if instrument else ""))
        self._progs[key] = prog
        return prog

    # -------------------------------------------------------------- public
    def lookup(self, ids):
        """Gather rows for an integer id batch: ``[*ids.shape, dim]``.

        Ids >= num_rows (the pad sentinel) return zero rows.
        """
        import time as _time
        from .. import telemetry as _telemetry
        from .. import tracing as _tracing
        from .. import numerics as _numerics
        ids = jnp.asarray(ids)
        cap_stats = _numerics.should_capture("embedding")
        with _tracing.span("embedding.lookup", cat="embedding"):
            t0 = _time.perf_counter()
            res = self._prog("lookup", tuple(ids.shape),
                             instrument=cap_stats)(self.table, ids)
            out, n_unique = res[0], res[1]
            out.block_until_ready()
            _telemetry.timer("embedding.lookup_ms").observe(
                (_time.perf_counter() - t0) * 1000.0)
        if cap_stats:
            _numerics.publish("embedding", self._t, res[2])
        n = max(int(ids.size), 1)
        _telemetry.counter("embedding.gathered_rows").inc(
            unique_capacity(n))
        _telemetry.gauge("embedding.unique_ratio").set(
            float(int(n_unique)) / n)
        return out

    def update(self, ids, grad, lr, wd=0.0):
        """Apply one lazy row-sparse optimizer step.

        ``grad`` holds one cotangent row per id (``[*ids.shape, dim]``);
        duplicate ids are summed before the update, sentinel ids are
        dropped, and only touched rows of the table + optimizer state are
        rewritten (the table/state buffers are donated).
        """
        from .. import telemetry as _telemetry
        from .. import tracing as _tracing
        ids = jnp.asarray(ids)
        grad = jnp.asarray(grad)
        self._t += 1
        with _tracing.span("embedding.update", cat="embedding"):
            self.table, self.state = self._prog("update", tuple(ids.shape))(
                self.table, self.state, ids, grad,
                jnp.asarray(lr, jnp.float32), jnp.asarray(wd, jnp.float32),
                jnp.asarray(self._t, jnp.int32))
        _telemetry.counter("embedding.rows_touched").inc(
            unique_capacity(max(int(ids.size), 1)))
        return self.table
