"""Mixture-of-Experts with expert parallelism over a mesh axis.

No reference counterpart (SURVEY.md §2.3 lists expert parallelism as absent
from the reference); built TPU-first: experts are sharded over an 'ep' mesh
axis and tokens travel to their expert's device through ONE pair of
``lax.all_to_all`` collectives (dispatch + return), the canonical
Switch/GShard layout where the routing tensors stay static-shaped — capacity
slots instead of dynamic gathers — so XLA can compile one fixed program.

Routing is top-k softmax gating with per-expert capacity; overflowing tokens
are dropped (their combine weight is zero), matching Switch Transformer
semantics.  Everything is differentiable: the all_to_all transposes are the
reverse all_to_alls, and the load-balancing auxiliary loss is returned for
the caller to add to the objective.

Layout contract (inside shard_map over `axis_name`):
  x        — [T_loc, d] this device's tokens (batch/'dp'-sharded)
  gate_w   — [d, E] replicated router weights (E = global expert count)
  w1/b1/w2/b2 — THIS device's expert shard: [E_loc, ...], E = E_loc * n_ep
  returns  — ([T_loc, d] combined outputs, scalar aux loss)

Beside the capacity layer stands the DROPLESS one a served model takes
(:func:`sigmoid_top_k` + :func:`dropless_experts`): no capacity and no
``[T, E, C]`` tensor — the (token, expert) pairs are sorted by expert and
go through two grouped matrix products (``mx.kernels.grouped_matmul``:
the Pallas kernel ``mx_grouped_matmul`` on a TPU, ``lax.ragged_dot``
elsewhere), and the layer is TOLD which experts it holds
(``expert_offset`` and the leading extent of its weights): it routes over
all of them and computes the part of the result its own experts give,
which is what one chip of an expert-parallel deployment does before the
exchange (that exchange is not here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import kernels as _kernels
from .pipeline import shmap

__all__ = ["moe_ffn", "moe_ffn_sharded", "top_k_routing",
           "sigmoid_top_k", "dropless_experts"]


def top_k_routing(logits, k, capacity):
    """Static-shape top-k routing.

    logits [T, E] -> dispatch [T, E, C] one-hot slot assignment,
    combine [T, E, C] gating weights, aux (load-balance loss).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # claimed slots per expert accumulate across the k passes so the 2nd
    # choice never collides with slots taken by 1st choices
    base = jnp.zeros((e,), jnp.int32)
    masked = probs
    for _ in range(k):
        choice = jnp.argmax(masked, axis=-1)                  # [T]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # [T, E]
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot      # slot within
        pos = pos + base[None, :] * onehot                     # expert
        keep = (pos < capacity) * onehot                       # fits?
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=jnp.float32) * keep[..., None]
        gate = jnp.sum(probs * onehot, axis=-1, keepdims=True)  # [T, 1]
        dispatch = dispatch + slot
        combine = combine + slot * gate[..., None]
        base = base + jnp.sum(keep, axis=0).astype(jnp.int32)
        masked = masked * (1.0 - onehot)  # next pass picks a new expert

    # Switch-style load balancing: E * sum_e fraction_tokens_e * mean_prob_e
    frac = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, axis=-1), e, dtype=jnp.float32),
        axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))
    return dispatch, combine, aux


def moe_ffn(gate_w, w1, b1, w2, b2, x, axis_name="ep", k=2,
            capacity_factor=2.0, activation=jax.nn.gelu):
    """Expert-parallel MoE feed-forward.  Call INSIDE shard_map.

    x [T, d]; gate_w [d, E] (replicated); w1 [E_loc, d, h], b1 [E_loc, h],
    w2 [E_loc, h, d], b2 [E_loc, d].  Returns (y [T, d], aux loss).
    """
    n_ep = lax.psum(1, axis_name)
    e_loc = w1.shape[0]
    e = e_loc * n_ep
    t, d = x.shape
    capacity = max(1, int(capacity_factor * k * t / e))

    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        gate_w.astype(jnp.float32))
    dispatch, combine, aux = top_k_routing(logits, k, capacity)

    # dispatch into per-expert capacity buffers: [E, C, d]
    buf = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    # ship every expert's buffer to the device that owns it: the global
    # expert axis becomes (n_ep groups of E_loc); after all_to_all this
    # device holds ITS E_loc experts' slots from every peer
    buf = buf.reshape(n_ep, e_loc, capacity, d)
    buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)                   # [n_ep, E_loc, C, d]
    buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, n_ep * capacity, d)

    h = activation(jnp.einsum("ecd,edh->ech", buf, w1.astype(jnp.float32))
                   + b1[:, None, :].astype(jnp.float32))
    y = jnp.einsum("ech,ehd->ecd", h, w2.astype(jnp.float32)) \
        + b2[:, None, :].astype(jnp.float32)

    # return trip: inverse reshuffle + all_to_all back to the token owners
    y = y.reshape(e_loc, n_ep, capacity, d).transpose(1, 0, 2, 3)
    y = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                       tiled=False)
    y = y.reshape(e, capacity, d)
    out = jnp.einsum("tec,ecd->td", combine, y)
    return out.astype(x.dtype), aux


def moe_ffn_sharded(mesh, gate_w, w1, b1, w2, b2, x, axis_name="ep",
                    batch_axis="dp", k=2, capacity_factor=2.0,
                    activation=jax.nn.gelu):
    """shard_map wrapper.  Tokens are sharded over BOTH the data and expert
    axes (the GShard layout: every device routes a distinct token shard, so
    the all_to_alls move distinct data); expert weights [E, ...] shard on
    `axis_name`; gate_w is replicated.  The aux loss is the mesh-wide mean.
    """
    def fn(gw, a1, c1, a2, c2, xs):
        y, aux = moe_ffn(gw, a1, c1, a2, c2, xs, axis_name=axis_name, k=k,
                         capacity_factor=capacity_factor,
                         activation=activation)
        return y, lax.pmean(aux, mesh.axis_names)

    espec = P(axis_name)
    tok = P((batch_axis, axis_name))
    shmapped = shmap(fn, mesh, (P(), espec, espec, espec, espec, tok),
                     (tok, P()))
    return shmapped(gate_w, w1, b1, w2, b2, x)


def sigmoid_top_k(x, router_w, select_bias, k, scale):
    """Sigmoid-scored top-k routing over ALL experts, in float32.

    x [T, d]; router_w [d, E]; select_bias [E] f32 joins the scores for
    the CHOICE only.  Returns ``(experts [T, k] int32, weights [T, k]
    f32)``: the k largest of ``s + select_bias`` with ``s =
    sigmoid(x . router_w)``, weighted ``scale * s_e / sum of the chosen
    s``."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), router_w.astype(jnp.float32)))
    _, experts = lax.top_k(s + select_bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


def dropless_experts(u, experts, weights, w1, w2, expert_offset=0,
                     rows_valid=None, w_gate=None):
    """The held experts' part of a top-k mixture, no token dropped.

    u [T, z] (the experts' input, already in their width); experts /
    weights [T, k] from :func:`sigmoid_top_k`; w1 [E_held, z, f] and
    w2 [E_held, f, z] are the experts this caller HOLDS, global ids
    ``expert_offset .. expert_offset + E_held``; expert e computes
    ``relu(u . w1_e)**2 . w2_e`` or, GATED (``w_gate`` [E_held, z, f], a
    third matrix an expert), ``(silu(u . w_gate_e) * (u . w1_e)) . w2_e``
    with ``w1`` the up and ``w2`` the down matrix: the first grouped
    product then multiplies the rows by both input matrices and folds the
    two in its epilogue.  A (token, expert) pair whose expert
    lives elsewhere adds nothing here; ``rows_valid`` [T] bool drops the
    pairs of rows that carry no request.  The held pairs are sorted by
    expert (stable, so the order is a pure function of the routing) and
    run as two grouped products over the experts' row groups
    (``mx.kernels.grouped_matmul``, which routes each to the Pallas
    kernel or to ``lax.ragged_dot``: float32 accumulators and results on
    both, ``h`` rounded to ``u``'s dtype between them); the weighted sum
    over each token's pairs is taken in z.

    Returns ``(y [T, z] f32, stats)`` with ``stats`` int32 scalars:
    ``pairs`` computed here, ``experts_hit`` (held experts with at least
    one row) and ``max_load`` (the most rows on one expert)."""
    t, k = experts.shape
    e_held = w1.shape[0]
    local = experts - expert_offset
    held = (local >= 0) & (local < e_held)
    if rows_valid is not None:
        held &= rows_valid[:, None]
    # pairs of absent experts sort behind every group and join none
    key = jnp.where(held, local, e_held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((e_held + 1,), jnp.int32).at[key].add(1)[:e_held]
    rows = jnp.take(u, order // k, axis=0)                  # [T*k, z]
    if w_gate is None:
        h = _kernels.grouped_matmul(
            rows, w1, sizes, epilogue=lambda a: jnp.square(jax.nn.relu(a)),
            out_dtype=u.dtype)
    else:
        h = _kernels.grouped_matmul(
            rows, w_gate, sizes, epilogue=lambda g, a: jax.nn.silu(g) * a,
            out_dtype=u.dtype, w_b=w1)
    out = _kernels.grouped_matmul(h, w2, sizes)
    # back to [T, k, z]: row i of the sorted list is pair order[i]
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    out = jnp.take(out, back, axis=0).reshape(t, k, -1)
    # (a row past the groups is whatever the product left there)
    y = jnp.sum(jnp.where(held[..., None], out * weights[..., None], 0.0),
                axis=1)
    stats = {"pairs": jnp.sum(held, dtype=jnp.int32),
             "experts_hit": jnp.sum(sizes > 0, dtype=jnp.int32),
             "max_load": jnp.max(sizes)}
    return y, stats
