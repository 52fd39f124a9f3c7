"""Pipeline parallelism — GPipe-style microbatch pipelining over a mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.3: model parallelism
is manual `group2ctx` device placement, src/executor/graph_executor.cc:997 —
cross-device copies inserted between subgraphs).  The TPU-native design
instead shards the LAYER dimension over a 'pp' mesh axis: every device holds
one pipeline stage's parameters, microbatches march through the ring with one
``lax.ppermute`` hop per tick, and the whole schedule — bubbles included —
is a single ``lax.scan`` that XLA compiles and jax.grad differentiates (the
transpose of ppermute is the reverse rotation, so the backward pipeline falls
out of autodiff instead of hand-written send/recv like GPipe runtimes).

Layout contract (inside shard_map over `axis_name`):
  stage_params — THIS device's stage (leading stage axis already split off)
  x            — [n_micro, micro_batch, ...] microbatched input, replicated;
                 only stage 0 reads it
  returns      — [n_micro, micro_batch, ...] final-stage outputs, replicated
                 (broadcast off the last stage with a psum)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_sharded", "microbatch",
           "unmicrobatch", "shmap"]


def shmap(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the positional (f, mesh, in, out) order the
    parallel modules use."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def microbatch(x, n_micro):
    """[B, ...] -> [n_micro, B // n_micro, ...]."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (b, n_micro))
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def unmicrobatch(x):
    """[n_micro, mb, ...] -> [n_micro * mb, ...]."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def pipeline_apply(stage_fn, stage_params, x, axis_name="pp",
                   vary_axes=None):
    """Run the microbatched `x` through the stage ring.  Call INSIDE
    shard_map.

    stage_fn(stage_params, act) -> act — one pipeline stage.  Activations
    must keep one shape through the pipeline (the usual transformer-block
    contract); the first stage receives the raw microbatch, so embed/head
    asymmetries belong inside stage_fn gated on ``lax.axis_index``.

    vary_axes — mesh axes the activations vary over, for the scan
    carry's varying-manual-axes type.  Defaults to the input's axes plus
    `axis_name`; a stage whose body makes outputs vary over MORE axes
    (e.g. an internal expert-parallel all_to_all) must name them here.
    """
    n_micro = x.shape[0]
    n_stage = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
    ticks = n_micro + n_stage - 1

    def tick(carry, t):
        act = carry
        # stage 0 ingests microbatch t (clamped during drain ticks; those
        # outputs are never selected)
        x_t = lax.dynamic_index_in_dim(
            x, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        inp = jnp.where(idx == 0, x_t.astype(act.dtype), act)
        out = stage_fn(stage_params, inp)
        # one ICI hop: my output becomes the next stage's input
        nxt = lax.ppermute(out, axis_name, perm)
        return nxt, out

    # the carry starts replicated but turns varying after the first
    # ppermute — mark it up front
    if vary_axes is None:
        vary_axes = tuple(set(jax.typeof(x).vma) | {axis_name})
    act0 = lax.pcast(jnp.zeros(x.shape[1:], x.dtype), tuple(vary_axes),
                     to="varying")
    _, outs = lax.scan(tick, act0, jnp.arange(ticks))

    # microbatch j leaves the last stage at tick j + n_stage - 1
    y = lax.dynamic_slice_in_dim(outs, n_stage - 1, n_micro, 0)
    # broadcast the last stage's result to every stage (zeros elsewhere, so
    # the psum is a select); its transpose re-routes cotangents to the last
    # stage only, which is exactly the backward pipeline's entry point.
    return lax.psum(jnp.where(idx == n_stage - 1, y, jnp.zeros_like(y)),
                    axis_name)


def pipeline_sharded(mesh, stage_fn, stacked_params, x, n_micro,
                     axis_name="pp"):
    """shard_map wrapper: `stacked_params` leaves have a leading stage axis
    of size mesh.shape[axis_name] (sharded over it); `x` is a full [B, ...]
    batch.  Returns [B, ...] outputs.
    """
    def local(params, xm):
        # split off this device's stage (leading axis shard of size 1)
        mine = jax.tree_util.tree_map(lambda v: v[0], params)
        return pipeline_apply(stage_fn, mine, xm, axis_name=axis_name)

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    fn = shmap(local, mesh, (pspec, P()), P())
    return unmicrobatch(fn(stacked_params, microbatch(x, n_micro)))
