"""Built-in Pallas kernels — the custom-kernel escape hatch in use.

Reference role: the hand-written CUDA kernels MXNet reaches for when
library kernels fall short (RTC, src/common/rtc.cc; fused contrib kernels).
On TPU the escape hatch is Mosaic via Pallas (pallas_guide.md); these
kernels double as the worked examples for ``mx.rtc``.

Each kernel follows the VMEM-block pattern: the grid walks row blocks, a
block lives in VMEM, and the body is VPU elementwise math with on-chip
reductions — no HBM roundtrips between the fused stages.  On CPU they run
through the Pallas interpreter (same numerics), so tests validate the
kernels without a TPU.

Kernel tier (docs/PERF_NOTES.md "Kernel tier"): flash attention is a
full training kernel — the tiled online-softmax forward saves per-row
logsumexp residuals and a Pallas backward (recompute-style, two kernels:
dq over q blocks, dk/dv over kv blocks) rides ``jax.custom_vjp``.
Routing and fallback live in ``mx.kernels``; the raw kernels here stay
policy-free.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .registry import register

__all__ = ["pallas_row_softmax", "pallas_scale_bias_relu",
           "pallas_flash_attention", "flash_attention",
           "flash_attention_tiled", "pallas_paged_attention",
           "pallas_latent_paged_attention", "pallas_grouped_matmul",
           "pallas_retention_update"]

_NEG = -1e30


_SUBLANES, _LANES = 8, 128   # the TPU's (8, 128) vreg tile


def _lane_pad(d):
    """Width a ``d``-wide minor axis occupies in VMEM: the lane axis is
    tiled by 128, so a 7-wide row still costs a full 128-lane row."""
    return -(-int(d) // _LANES) * _LANES


def _row_block(n_rows, row_bytes, budget=None, align=_SUBLANES):
    """Row-block size for a grid that walks ``n_rows`` exactly: the
    largest divisor of n_rows that is a LEGAL TPU block extent — a
    multiple of ``align`` or the whole axis — and whose block stays
    under the VMEM budget.  The Mosaic lowering refuses any other extent
    on the second-to-last block dim (the sublane axis of the (8, 128)
    tile); callers whose row axis is a leading, untiled dim pass
    ``align=1``.  When no legal divisor fits the budget the SMALLEST
    legal one is returned — the closest the tiling allows; the compiler
    then decides whether it fits fast memory.  O(sqrt(n)) divisor walk —
    this runs on the host per eager call, so no linear scans.
    ``budget`` defaults to the validated ``kernels.vmem_budget`` knob
    (MXNET_TPU_KERNELS_VMEM_BUDGET)."""
    if budget is None:
        from .. import config as _config
        budget = _config.get("kernels.vmem_budget")
    cap = max(1, budget // max(row_bytes, 1))
    best, smallest = 0, n_rows
    i = 1
    while i * i <= n_rows:
        if n_rows % i == 0:
            for j in (i, n_rows // i):
                if j % align and j != n_rows:
                    continue
                if best < j <= cap:
                    best = j
                if j < smallest:
                    smallest = j
        i += 1
    return best or smallest


def _mxu_precision(*operands):
    """Contract precision of an in-kernel MXU product.  16-bit operands
    take the MXU's native pass — Mosaic has no higher-precision matmul
    for them, so a process-wide ``jax_default_matmul_precision`` must not
    reach them; f32 operands follow the ambient setting (None)."""
    if any(o.dtype.itemsize < 4 for o in operands):  # mxlint: disable=jit.tracer-branch
        return jax.lax.Precision.DEFAULT             # (a dtype is static)
    return None


# ------------------------------------------------------------ row softmax
def _row_softmax_kernel(x_ref, o_ref, m_ref, l_ref):
    """Numerically-stable softmax over the last axis of one row block.
    max/sum reductions stay in VMEM — one HBM read, one HBM write for the
    rows plus two [rows, 1] residual columns (the saved row max/sum the
    custom-vjp backward reuses)."""
    x = x_ref[:]
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    o_ref[:] = e / s
    m_ref[:] = m
    l_ref[:] = s


def _row_softmax_bwd_kernel(x_ref, m_ref, l_ref, dy_ref, dx_ref):
    """softmax VJP from the saved row max/sum: y rebuilds as
    exp(x - m)/l on chip (no second max/sum pass), then
    dx = y * (dy - sum(dy * y))."""
    y = jnp.exp(x_ref[:] - m_ref[:]) / l_ref[:]
    dy = dy_ref[:]
    dx_ref[:] = y * (dy - jnp.sum(dy * y, axis=-1, keepdims=True))


def _softmax_fwd_call(flat):
    from jax.experimental import pallas as pl
    from ..rtc import interpret_mode
    n, d = flat.shape
    rows = _row_block(n, _lane_pad(d) * flat.dtype.itemsize)
    return pl.pallas_call(
        _row_softmax_kernel,
        out_shape=[jax.ShapeDtypeStruct(flat.shape, flat.dtype),
                   jax.ShapeDtypeStruct((n, 1), flat.dtype),
                   jax.ShapeDtypeStruct((n, 1), flat.dtype)],
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        interpret=interpret_mode(), name="mx_row_softmax")(flat)


def _softmax_bwd_call(x, m, l, dy):
    from jax.experimental import pallas as pl
    from ..rtc import interpret_mode
    n, d = x.shape
    rows = _row_block(n, _lane_pad(d) * x.dtype.itemsize)
    return pl.pallas_call(
        _row_softmax_bwd_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        interpret=interpret_mode(), name="mx_row_softmax_bwd")(x, m, l, dy)


@jax.custom_vjp
def _row_softmax(flat):
    return _softmax_fwd_call(flat)[0]


def _row_softmax_fwd(flat):
    y, m, l = _softmax_fwd_call(flat)
    return y, (flat, m, l)


def _row_softmax_bwd(res, dy):
    x, m, l = res
    return (_softmax_bwd_call(x, m, l, dy),)


_row_softmax.defvjp(_row_softmax_fwd, _row_softmax_bwd)


@register("pallas_softmax")
def pallas_row_softmax(data, **_):
    """Row softmax via the Pallas kernel (mx.nd.pallas_softmax).

    The grid walks row blocks sized to fit VMEM, so arbitrarily tall
    logits tensors stream through the kernel; one row must fit on chip
    (true for any real vocab at fp32: 32k cols = 128KB).  Differentiable:
    the forward saves the per-row max and sum and the custom-vjp backward
    kernel reuses them (no recomputed reductions)."""
    x = jnp.asarray(data)
    flat = x.reshape(-1, x.shape[-1])
    return _row_softmax(flat).reshape(x.shape)


# ------------------------------------------------------- flash attention
def _flash_fwd_kernel(scale, causal, block_q, q_ref, k_ref, v_ref,
                      o_ref, lse_ref):
    """One q block vs the full K/V of its (batch, head) slice.

    The score matrix [block_q, S] lives only in VMEM — it is never
    materialized in HBM, which is the whole point of flash attention: HBM
    traffic is O(S*D) instead of O(S^2).  Softmax accumulates in f32 on
    chip; the MXU does both matmuls.  The per-row logsumexp lands in a
    [block_q, 1] residual column (the layout the row reductions already
    have — a 1-D strip is not a legal TPU block) so the backward can
    rebuild the probabilities without a second max/sum pass.
    """
    from jax.experimental import pallas as pl
    q = q_ref[0].astype(jnp.float32)                # [bq, D]
    k = k_ref[0].astype(jnp.float32)                # [S, D]
    v = v_ref[0]                                    # [S, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        i = pl.program_id(1)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    acc = jax.lax.dot_general(e.astype(v.dtype), v,
                              (((1,), (0,)), ((), ())),
                              precision=_mxu_precision(v),
                              preferred_element_type=jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)


def _flash_bwd_dq_kernel(scale, causal, block_q, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, delta_ref, dq_ref):
    """dq for one q block: recompute the probabilities from the saved
    logsumexp (p = exp(s - lse)), then
    ds = p * (dO @ V^T - delta) * scale and dq = ds @ K — the score and
    ds matrices stay in VMEM."""
    from jax.experimental import pallas as pl
    q = q_ref[0].astype(jnp.float32)                # [bq, D]
    k = k_ref[0].astype(jnp.float32)                # [S, D]
    v = v_ref[0].astype(jnp.float32)                # [S, D]
    do = do_ref[0].astype(jnp.float32)              # [bq, D]
    lse = lse_ref[0]                                # [bq, 1]
    delta = delta_ref[0]                            # [bq, 1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        i = pl.program_id(1)
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG)
    p = jnp.exp(s - lse)                            # [bq, S]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_ref[0] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(scale, causal, block_k, q_ref, k_ref, v_ref,
                          do_ref, lse_ref, delta_ref, dk_ref, dv_ref):
    """dk/dv for one kv block against the full Q/dO of its (batch, head):
    the transposed score strip [block_k, Sq] rebuilds from the saved
    logsumexp, dv = P^T @ dO and dk = dS^T @ Q accumulate in f32 on the
    MXU."""
    from jax.experimental import pallas as pl
    q = q_ref[0].astype(jnp.float32)                # [Sq, D]
    k = k_ref[0].astype(jnp.float32)                # [bk, D]
    v = v_ref[0].astype(jnp.float32)                # [bk, D]
    do = do_ref[0].astype(jnp.float32)              # [Sq, D]
    lse = lse_ref[0]                                # [1, Sq]
    delta = delta_ref[0]                            # [1, Sq]
    st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    if causal:
        j = pl.program_id(1)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, st.shape, 0)
        q_pos = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        st = jnp.where(k_pos <= q_pos, st, _NEG)
    pt = jnp.exp(st - lse)                          # [bk, Sq]
    dv_ref[0] = jax.lax.dot_general(
        pt, do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta) * scale
    dk_ref[0] = jax.lax.dot_general(
        dst, q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _flash_forward(q, k, v, causal, scale, block_q):
    from jax.experimental import pallas as pl
    from ..rtc import interpret_mode
    B, H, S, D = q.shape
    Skv = k.shape[2]
    # largest legal divisor of S <= block_q (a multiple of 8, or S), so
    # an awkward block_q degrades to the best legal tiling
    bq = _row_block(S, 1, budget=min(block_q, S))
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, Skv, D)
    vf = v.reshape(B * H, Skv, D)
    kernel = functools.partial(_flash_fwd_kernel, scale, bool(causal), bq)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(qf.shape, q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32)],
        grid=(B * H, S // bq),
        in_specs=[pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, Skv, D), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, Skv, D), lambda b, i: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0))],
        interpret=interpret_mode(), name="mx_attention")(qf, kf, vf)
    return out.reshape(B, H, S, D), lse.reshape(B * H, S)


def _flash_backward(q, k, v, o, lse, do, causal, scale, block_q):
    from jax.experimental import pallas as pl
    from ..rtc import interpret_mode
    B, H, S, D = q.shape
    Skv = k.shape[2]
    bq = _row_block(S, 1, budget=min(block_q, S))
    bk = _row_block(Skv, 1, budget=min(block_q, Skv))
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, Skv, D)
    vf = v.reshape(B * H, Skv, D)
    dof = do.reshape(B * H, S, D)
    # delta = rowsum(dO * O) — elementwise O(S*D), cheap in plain XLA
    delta = jnp.sum(dof.astype(jnp.float32) *
                    o.reshape(B * H, S, D).astype(jnp.float32), axis=-1)
    # the [BH, S] row residuals ride into each kernel in the layout its
    # broadcast needs, with trailing block dims that are legal TPU
    # blocks: a [bq, 1] column per q block for dq, the whole [1, S] row
    # for the transposed strip of dk/dv
    lse_c, delta_c = lse[:, :, None], delta[:, :, None]
    lse_r, delta_r = lse[:, None, :], delta[:, None, :]
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale, bool(causal), bq),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid=(B * H, S // bq),
        in_specs=[pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, Skv, D), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, Skv, D), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        interpret=interpret_mode(),
        name="mx_attention_bwd_dq")(qf, kf, vf, dof, lse_c, delta_c)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale, bool(causal), bk),
        out_shape=[jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)],
        grid=(B * H, Skv // bk),
        in_specs=[pl.BlockSpec((1, S, D), lambda b, j: (b, 0, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                  pl.BlockSpec((1, S, D), lambda b, j: (b, 0, 0)),
                  pl.BlockSpec((1, 1, S), lambda b, j: (b, 0, 0)),
                  pl.BlockSpec((1, 1, S), lambda b, j: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0))],
        interpret=interpret_mode(),
        name="mx_attention_bwd_dkv")(qf, kf, vf, dof, lse_r, delta_r)
    return (dq.reshape(B, H, S, D), dk.reshape(B, H, Skv, D),
            dv.reshape(B, H, Skv, D))


@functools.lru_cache(maxsize=None)
def _flash_vjp(causal, scale, block_q):
    """custom_vjp wrapper per hashable (causal, scale, block_q) static
    config — the lru_cache keeps one stable function identity per config
    so jit caches don't churn."""

    @jax.custom_vjp
    def f(q, k, v):
        return _flash_forward(q, k, v, causal, scale, block_q)[0]

    def f_fwd(q, k, v):
        o, lse = _flash_forward(q, k, v, causal, scale, block_q)
        return o, (q, k, v, o, lse)

    def f_bwd(res, do):
        q, k, v, o, lse = res
        return _flash_backward(q, k, v, o, lse, do, causal, scale, block_q)

    f.defvjp(f_fwd, f_bwd)
    return f


def flash_attention(q, k, v, causal=False, scale=None, block_q=128):
    """Fused flash attention, forward AND backward as Pallas kernels.

    q/k/v: [B, H, S, D].  The grid walks (batch*heads, q blocks); each
    step holds one q block plus its head's full K/V in VMEM (S*D per
    operand — S=8k at D=128 bf16 is 2MB, comfortably on chip), so the
    S x S score matrix never touches HBM.  The forward additionally saves
    a per-row logsumexp strip; the ``jax.custom_vjp`` backward recomputes
    the probabilities from it in two more Pallas kernels (dq over q
    blocks; dk/dv over kv blocks), keeping backward HBM traffic O(S*D)
    too.  Sequences larger than VMEM shard S over the 'sp' mesh axis
    first (parallel.ring_attention) and run this kernel per shard.
    Routing/fallback policy lives in ``mx.kernels.attention``
    (reference analog: hand-written fused CUDA attention via RTC,
    src/common/rtc.cc).
    """
    q = jnp.asarray(q)
    k = jnp.asarray(k)
    v = jnp.asarray(v)
    B, H, S, D = q.shape
    Skv = k.shape[2]
    if causal and Skv != S:
        raise ValueError("causal flash attention needs matching q/kv "
                         "lengths, got Sq=%d Skv=%d" % (S, Skv))
    if v.shape != k.shape:
        raise ValueError("k and v shapes differ: %s vs %s"
                         % (k.shape, v.shape))
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    return _flash_vjp(bool(causal), scale, int(block_q))(q, k, v)


@register("pallas_flash_attention")
def pallas_flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                           **_):
    """Flash attention via Pallas (mx.nd.pallas_flash_attention) —
    differentiable; see ``flash_attention`` for the kernel story."""
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block_q=block_q)


# ---------------------------------------------- flash attention, K/V tiled
_TILED_BLOCK = 512           # q rows and K/V rows a step: four MXU passes


def _flash_tiled_kernel(scale, causal, bq, bk, q_ref, k_ref, v_ref, *refs):
    """One q block against one K/V block of its (batch, head) slice; the
    K/V blocks are the grid's last axis and an online softmax (``m``,
    ``l``, ``acc`` in float32 scratch) carries across them, so neither the
    score matrix nor a whole head's K/V is ever resident.  The value rows
    may be narrower or wider than the query/key rows.  Under ``causal`` a
    K/V block wholly above the diagonal is skipped (its block index is
    clamped to the last one the q block needs, so nothing is fetched for
    it either).  With a selection (``refs`` five long: an int8 ``[bq,
    bk]`` mask block first) a pair the mask leaves at 0 is masked beside
    those above the diagonal; a row none of whose pairs so far is kept
    carries ``m`` at the floor, and its first kept pair's ``alpha`` of 0
    clears what it summed."""
    from jax.experimental import pallas as pl
    mask_ref = refs[0] if len(refs) == 5 else None
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=_mxu_precision(q, k),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        keep = None
        if causal:
            q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = k_pos <= q_pos
        if mask_ref is not None:
            chosen = mask_ref[0] != 0
            keep = chosen if keep is None else keep & chosen
        if keep is not None:
            s = jnp.where(keep, s, _NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=_mxu_precision(v), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        pl.when(j * bk <= i * bq + bq - 1)(step)
    else:
        step()

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def flash_attention_tiled(q, k, v, causal=False, scale=None,
                          block=_TILED_BLOCK, mask=None):
    """Flash attention FORWARD with the keys and values tiled as well as
    the queries: q, k ``[B, H, S, Dqk]``, v ``[B, H, Skv, Dv]`` (``Dv``
    need not be ``Dqk``) -> ``[B, H, S, Dv]``.  The grid walks (batch x
    heads, q blocks, K/V blocks); a step holds one block of each
    (``block`` rows, or the largest legal divisor of the lengths below
    it), so what fits does not depend on the sequence length, where
    :func:`flash_attention` keeps a head's whole K/V resident.  A causal
    call computes the blocks on and under the diagonal only.  No backward
    of its own: ``mx.kernels.attention`` routes here and differentiates
    through the XLA lowering.

    ``mask`` (int8 ``[B, S, Skv]``, every head's): only the pairs it holds
    at non-zero are attended (named ``mx_attention_tiled_masked``; the
    query blocks a multiple of 32 rows and the K/V blocks of 128, the int8
    tile, or whole).  Every row must keep at least one pair: a row that
    keeps none reads the mean of the values its blocks visited."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..rtc import interpret_mode
    B, H, S, D = q.shape
    Skv, Dv = v.shape[2], v.shape[3]
    if k.shape != (B, H, Skv, D) or v.shape[:2] != (B, H) \
            or (causal and Skv != S) \
            or (mask is not None and mask.shape != (B, S, Skv)):
        raise ValueError("tiled flash attention takes q [B,H,S,D], k "
                         "[B,H,Skv,D], v [B,H,Skv,Dv] (Skv == S if causal)"
                         " and a mask [B,S,Skv], got %s, %s, %s, %s"
                         % (q.shape, k.shape, v.shape,
                            None if mask is None else mask.shape))
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    if mask is None:
        bq = _row_block(S, 1, budget=min(block, S))
        bk = _row_block(Skv, 1, budget=min(block, Skv))
    else:
        bq = _row_block(S, 1, budget=min(block, S), align=4 * _SUBLANES)
        bk = _row_block(Skv, 1, budget=min(block, Skv), align=_LANES)
    # (under the diagonal's clamp a skipped step names the block before it)
    last = (lambda i: (i * bq + bq - 1) // bk) if causal \
        else (lambda i: Skv // bk - 1)
    kv_at = lambda b, i, j: (b, jnp.minimum(j, last(i)), 0)   # noqa: E731
    in_specs = [pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, bk, D), kv_at),
                pl.BlockSpec((1, bk, Dv), kv_at)]
    operands = (q.reshape(B * H, S, D), k.reshape(B * H, Skv, D),
                v.reshape(B * H, Skv, Dv))
    if mask is not None:
        in_specs.append(pl.BlockSpec(
            (1, bq, bk), lambda b, i, j: (b // H, i, jnp.minimum(j, last(i)))))
        operands += (mask,)
    out = pl.pallas_call(
        functools.partial(_flash_tiled_kernel, scale, bool(causal), bq, bk),
        out_shape=jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
        grid=(B * H, S // bq, Skv // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(),
        name="mx_attention_tiled" if mask is None
        else "mx_attention_tiled_masked")(*operands)
    return out.reshape(B, H, S, Dv)


# ------------------------------------------------------- paged attention
_PAGED_TILE_TOKENS = 128     # one MXU pass wide: the tile's score row


def paged_tile_pages(psz, width, kv_itemsize, table_width):
    """Pages the paged kernel copies per step: a 128-token tile where the
    two double-buffered K and V tiles (``4 * pages * psz * width``
    elements) fit the ``kernels.vmem_budget`` knob, fewer where they do
    not or the page table is narrower, never less than one."""
    from .. import config as _config
    fit = int(_config.get("kernels.vmem_budget")) \
        // (4 * psz * width * kv_itemsize)
    return max(1, min(_PAGED_TILE_TOKENS // psz, fit, table_width))


def _paged_attn_kernel(scale, quant, tile, nh, group, scale_ids,
                       lengths_ref, table_ref, q_ref, k_hbm, v_hbm, *refs):
    """One decode row of single-query attention over its K/V pages, read
    where they lie.

    ``k_hbm``/``v_hbm`` are a whole page pool ``[P, psz, H*Dh]`` (one
    layer's, or every layer's viewed as ``L*P`` pages with the ids offset
    to the layer's) left in HBM; the row's page ids and length arrive by
    scalar prefetch.
    The row walks ``ceil(length / psz)`` pages and no more, ``tile`` pages
    per step: each page is one contiguous copy into a double-buffered
    VMEM tile (the next tile is in flight while this one is attended), and
    an online softmax (``m``, ``l``, ``acc`` in f32) carries across tiles.
    All heads of a page are attended at once: the query row becomes a
    block-diagonal ``[H, H*Dh]`` matrix (head h keeps only its own Dh
    lanes), so ``Qbd . K^T`` is every head's score row in one MXU product
    whose off-block zeros add an exact 0.0; ``P . V`` yields ``[H, H*Dh]``
    of which head h's answer is block h of row h.  Positions past the
    length inside the last page pin to the ``-1e30`` floor of
    ``parallel.ring_attention._block_attn``, so ``exp`` underflows to an
    EXACT 0.0 in both the denominator and the value sum; the V tiles are
    zeroed once, so the value a masked slot's 0.0 multiplies is always
    finite (zero, or older pool rows).  Page ids arrive clamped to the
    pool (the caller does it: the pool's size may be symbolic, and the
    kernel never asks for it); a row of length 0 reads nothing and
    answers 0.  With ``quant`` the pages are int8 and their ``[P, psz,
    H]`` f32 scale pages are fetched the same way and fold into the
    scores and the probabilities (``q.(k*s) == (q.k)*s``), so HBM traffic
    stays at the int8 byte count.  With ``scale_ids`` the scale pools are
    one layer's own beside every layer's K/V pool, and the table carries
    their ids (the same pages, not offset) behind the K/V ids.

    Grouped queries (``group`` > 1 query heads per K/V head, fewer K/V
    heads than query heads): the pool row is ``KVH*Dh`` wide, ``q_ref``
    and ``o_ref`` are ``[H, Dh]``, query head h keeps its ``Dh`` lanes in
    K/V head ``h // group``'s block, and its answer is that block of row
    h."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    if quant:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sems = refs
    else:
        o_ref, kbuf, vbuf, sems = refs
    b = pl.program_id(0)
    _, psz, width = k_hbm.shape
    W = table_ref.shape[0] // lengths_ref.shape[0] // (2 if scale_ids else 1)
    tokens = tile * psz
    length = lengths_ref[b]
    n_pages = jnp.minimum((length + psz - 1) // psz, W)
    n_tiles = (n_pages + tile - 1) // tile

    pools = [(k_hbm, kbuf), (v_hbm, vbuf)]
    if quant:
        pools += [(ks_hbm, ksbuf), (vs_hbm, vsbuf)]

    def each_page(t, slot, act):
        """``act`` on every copy of tile ``t``'s live pages into ``slot``."""
        for j in range(tile):
            i = t * tile + j

            @pl.when(i < n_pages)
            def _(i=i, j=j):
                page = table_ref[b * W + i]
                own = table_ref[table_ref.shape[0] // 2 + b * W + i] \
                    if scale_ids else page
                for n, (src, dst) in enumerate(pools):
                    act(pltpu.make_async_copy(
                        src.at[page if n < 2 else own], dst.at[slot, j],
                        sems.at[slot, n]))

    def start(t, slot):
        each_page(t, slot, lambda cp: cp.start())

    def wait(t, slot):
        each_page(t, slot, lambda cp: cp.wait())

    @pl.when(b == 0)
    def _():
        # scratch is not initialised, and a masked slot's probability of
        # 0.0 must meet a finite value (its score is replaced, not scaled)
        vbuf[...] = jnp.zeros_like(vbuf)
        if quant:
            vsbuf[...] = jnp.zeros_like(vsbuf)

    @pl.when(n_tiles > 0)
    def _():
        start(0, 0)

    kvh = nh // group
    dh = width // kvh
    cdt = q_ref.dtype if quant else kbuf.dtype      # the products' dtype
    head = jax.lax.broadcasted_iota(jnp.int32, (nh, width), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (nh, width), 1)
    # head h keeps the Dh lanes of its K/V head's block
    own = lane // dh == (head // group if group > 1 else head)
    q_rows = q_ref[...].astype(jnp.float32)
    if group > 1:
        q_rows = jnp.concatenate([q_rows] * kvh, axis=1)    # [H, width]
    # (masks have the 32-bit layout: select in f32, then narrow)
    qbd = jnp.where(own, q_rows, 0.0).astype(cdt)
    prec = _mxu_precision(qbd)
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, tokens), 1)

    def scales(buf, slot):
        """One tile's per-(token, head) scales as [H, tokens] (the pages
        arrive [psz, lanes] with the heads on the first H lanes)."""
        return buf[slot].reshape(tokens, buf.shape[-1]).T[:nh]

    def body(t, carry):
        m, l, acc = carry
        slot = t % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            start(t + 1, 1 - slot)

        wait(t, slot)
        # (widen first: a 16-row int8 page is half a packed tile)
        k = kbuf[slot].astype(cdt).reshape(tokens, width)
        v = vbuf[slot].astype(cdt).reshape(tokens, width)
        s = jax.lax.dot_general(
            qbd, k, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale     # [H, tokens]
        if quant:
            s = s * scales(ksbuf, slot)
        s = jnp.where(t * tokens + col < length, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(e, axis=-1, keepdims=True)
        p = e * scales(vsbuf, slot) if quant else e
        acc = alpha * acc + jax.lax.dot_general(
            p.astype(cdt), v, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)             # [H, H*Dh]
        return m_new, l, acc

    m0 = jnp.full((nh, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((nh, 1), jnp.float32)
    acc0 = jnp.zeros((nh, width), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_tiles, body, (m0, l0, acc0))
    out = jnp.where(own, acc / jnp.where(l == 0.0, 1.0, l), 0.0)
    if group > 1:
        # row h holds its answer in block h // group: fold the blocks
        o_ref[...] = sum(out[:, g * dh:(g + 1) * dh]
                         for g in range(kvh)).astype(o_ref.dtype)
        return
    o_ref[...] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)


def pallas_paged_attention(q, k_pages, v_pages, page_table, lengths,
                           scale=None, k_scale=None, v_scale=None,
                           layer=None):
    """Paged-attention decode kernel: one query row per sequence against
    the K/V pages its page table names, read in place.

    q [B, H, 1, Dh]; k_pages/v_pages [P, psz, H*Dh], a page pool;
    page_table [B, W] int32 (ids >= P are the sentinel and clamp to
    a real page, which the length then masks); lengths [B] int32, the
    positions each row attends over (at most W * psz).  With
    ``k_scale``/``v_scale`` ([P, psz, H] f32 per-row scale pages from
    ``mx.quantization.quantize_rows``) the pools are int8 and dequantize
    inside the kernel.  The grid walks the B rows; what a row costs
    follows from its length, not from W.  The pools may hold fewer K/V
    heads than ``q`` has heads (``[P, psz, KVH*Dh]``, H a multiple of
    KVH: query head h reads K/V head ``h // (H // KVH)``), and with
    ``layer`` (a Python int, or an int32 scalar traced in a layer scan)
    they are every layer's pool ``[L, P, psz, KVH*Dh]`` handed over
    whole (the scale pools ``[L, P, psz, H]`` too): they are VIEWED as
    ``L*P`` pages and the layer's page ids offset by ``layer * P``, so
    no K/V pool is sliced (a slice handed to a kernel is a copy) and the
    kernel sees what it sees of one layer's pool.  Routing/fallback
    policy lives in ``mx.kernels.paged_attention``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..rtc import interpret_mode
    q = jnp.asarray(q)
    B, H, Sq, D = q.shape
    if Sq != 1:
        raise ValueError("paged attention takes one query row per "
                         "sequence, got Sq=%d" % Sq)
    P, psz, width = k_pages.shape[-3:]
    kvh = width // D
    if v_pages.shape != k_pages.shape or width != kvh * D or H % kvh \
            or k_pages.ndim != (3 if layer is None else 4):
        raise ValueError("page pools must both be [%sP, psz, KVH*Dh] with "
                         "H=%d a multiple of KVH and Dh=%d, got %s and %s"
                         % ("" if layer is None else "L, ", H, D,
                            k_pages.shape, v_pages.shape))
    group = H // kvh
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(D)
    quant = k_scale is not None
    if quant and group > 1:
        raise ValueError("int8 pages take equal head counts")
    if layer is not None:
        k_pages, v_pages = (p.reshape((-1, psz, width))
                            for p in (k_pages, v_pages))
    tile = paged_tile_pages(psz, width, k_pages.dtype.itemsize,
                            page_table.shape[1])
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    # one block a decode row: all heads on the lanes at equal head counts,
    # [H, Dh] where several query heads share a K/V head
    rows, lanes_q = (1, width) if group == 1 else (H, D)
    row = pl.BlockSpec((None, rows, lanes_q), lambda b, *_: (b, 0, 0))
    operands = [q.reshape(B, rows, lanes_q), k_pages, v_pages]
    in_specs = [row, any_space, any_space]
    scratch = [pltpu.VMEM((2, tile, psz, width), k_pages.dtype),
               pltpu.VMEM((2, tile, psz, width), v_pages.dtype)]
    if quant:
        # a page of scales is copied whole, and a copy's minor axis is a
        # multiple of the 128 lanes: pad the heads up to it.  Of every
        # layer's scales only this layer's are padded (L times fewer
        # bytes): they keep their own page ids, behind the offset ones
        lanes = _lane_pad(H)
        if layer is not None:
            k_scale, v_scale = (jax.lax.dynamic_index_in_dim(
                s, layer, keepdims=False) for s in (k_scale, v_scale))
        operands += [jnp.pad(jnp.asarray(s, jnp.float32),
                             ((0, 0), (0, 0), (0, lanes - H)))
                     for s in (k_scale, v_scale)]
        in_specs += [any_space, any_space]
        scratch += [pltpu.VMEM((2, tile, psz, lanes), jnp.float32),
                    pltpu.VMEM((2, tile, psz, lanes), jnp.float32)]
    scratch.append(pltpu.SemaphoreType.DMA((2, 4 if quant else 2)))
    page_table = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, P - 1)
    ids = page_table.reshape(-1)
    if layer is not None:
        ids = (page_table + layer * P).reshape(-1)
        if quant:
            ids = jnp.concatenate([ids, page_table.reshape(-1)])
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, scale, quant, tile, H, group,
                          quant and layer is not None),
        out_shape=jax.ShapeDtypeStruct((B, rows, lanes_q), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,), in_specs=in_specs,
            out_specs=row, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(), name="mx_paged_attention")(
            jnp.asarray(lengths, jnp.int32), ids, *operands)
    return out.reshape(B, H, 1, D)


# ------------------------------------------------ latent paged attention
_LATENT_TILE_TOKENS = 512


def latent_tile_pages(psz, width, itemsize, table_width):
    """Pages the latent kernel copies per step: 512 tokens' worth where
    the double-buffered tile (``2 * pages * width * psz`` elements) fits
    the ``kernels.vmem_budget`` knob, fewer where it does not or the page
    table is narrower, never less than one."""
    from .. import config as _config
    fit = int(_config.get("kernels.vmem_budget")) \
        // (2 * psz * width * itemsize)
    return max(1, min(_LATENT_TILE_TOKENS // psz, fit, table_width))


def _latent_attn_kernel(scale, tile, dv, sparse, lengths_ref, table_ref,
                        q_ref, *refs):
    """One decode row of absorbed latent attention over its pages, read
    where they lie; with ``sparse`` over the tokens ``chosen_ref`` ``[W,
    psz]`` marks (nonzero) alone, the scores over the first ``q_ref``-width
    rows of a page (the rows below them, index keys, are copied and not
    read).

    ``pool_hbm`` is a pool of latent pages ``[P, width, psz]`` left in HBM
    (one layer's, or every layer's viewed as ``L*P`` pages with the ids
    offset): a page holds ``psz`` tokens ON THE LANES, row c of it being
    component c of their cache rows.  All ``H`` query heads read the SAME
    row of a token: ``q_ref`` is ``[H, width]`` (each head's absorbed
    query beside its rotary part), so a page's scores are ONE product
    ``q . page -> [H, psz]`` over the whole row, and its values are the
    page's first ``dv`` rows, ``p . page[:dv]^T -> [H, dv]``, from the same
    VMEM tile: a page is copied once for every head, its scores and its
    values.  As in the paged kernel the row walks ``ceil(length / psz)``
    pages, ``tile`` a step through a double-buffered tile, under a float32
    online softmax; positions past the length pin to ``-1e30`` (``exp``
    gives an exact 0.0) and the tile is zeroed once, so what a masked
    slot's 0.0 multiplies is finite; page ids arrive clamped; a row of
    length 0 reads nothing and answers 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    chosen_ref, pool_hbm, o_ref, buf, sems = refs if sparse \
        else (None,) + refs
    b = pl.program_id(0)
    _, width, psz = pool_hbm.shape
    W = table_ref.shape[0] // lengths_ref.shape[0]
    tokens = tile * psz
    length = lengths_ref[b]
    n_pages = jnp.minimum((length + psz - 1) // psz, W)
    n_tiles = (n_pages + tile - 1) // tile

    def each_page(t, slot, act):
        for j in range(tile):
            i = t * tile + j

            @pl.when(i < n_pages)
            def _(i=i, j=j):
                act(pltpu.make_async_copy(
                    pool_hbm.at[table_ref[b * W + i]], buf.at[slot, j],
                    sems.at[slot]))

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    @pl.when(n_tiles > 0)
    def _():
        each_page(0, 0, lambda cp: cp.start())

    q = q_ref[...].astype(buf.dtype)                        # [H, width]
    nh = q.shape[0]
    prec = _mxu_precision(q)
    col = jax.lax.broadcasted_iota(jnp.int32, (nh, tokens), 1)

    def body(t, carry):
        m, l, acc = carry
        slot = t % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            each_page(t + 1, 1 - slot, lambda cp: cp.start())

        each_page(t, slot, lambda cp: cp.wait())
        if chosen_ref is None:
            s = jnp.concatenate([jax.lax.dot_general(
                q, buf[slot, j], (((1,), (0,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32) for j in range(tile)],
                axis=1) * scale                             # [H, tokens]
            s = jnp.where(t * tokens + col < length, s, _NEG)
        else:
            kw = q.shape[1]
            s = jnp.concatenate([jax.lax.dot_general(
                q, buf[slot, j, :kw, :], (((1,), (0,)), ((), ())),
                precision=prec, preferred_element_type=jnp.float32)
                for j in range(tile)], axis=1) * scale
            # (a page past the table's width is masked by the length)
            chosen = jnp.concatenate([chosen_ref[pl.ds(
                jnp.minimum(t * tile + j, W - 1), 1), :]
                for j in range(tile)], axis=1)              # [1, tokens]
            s = jnp.where((t * tokens + col < length) & (chosen != 0), s,
                          _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(e, axis=-1, keepdims=True)
        p = e.astype(buf.dtype)
        pv = sum(jax.lax.dot_general(
            p[:, j * psz:(j + 1) * psz], buf[slot, j, :dv, :],
            (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) for j in range(tile))
        return m_new, l, alpha * acc + pv                   # [H, dv]

    m0 = jnp.full((nh, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((nh, 1), jnp.float32)
    acc0 = jnp.zeros((nh, dv), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_tiles, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def pallas_latent_paged_attention(q, pages, page_table, lengths, scale,
                                  value_width, layer=None):
    """Absorbed latent-attention decode kernel: one query row per
    sequence, every head reading the one cache row a token keeps.

    q [B, H, width] (head h's absorbed query beside its rotary part);
    pages [P, width, psz], a pool of latent pages with the tokens on the
    lanes, or with ``layer`` (a Python int or a traced int32 scalar) every
    layer's ``[L, P, width, psz]`` handed over whole and read as ``L*P``
    pages at the layer's ids; page_table [B, W] int32 (ids >= P clamp to a
    real page that the length masks); lengths [B] int32.  Scores run over
    a row's whole ``width``, values are its first ``value_width``
    components: returns ``[B, H, value_width]`` in q's dtype.  ``psz`` is
    a multiple of the 128 lanes, ``width`` and ``value_width`` of the
    pool dtype's sublane packing (16 rows of bf16): routing/fallback
    policy lives in ``mx.kernels.latent_paged_attention``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..rtc import interpret_mode
    q = jnp.asarray(q)
    B, H, width = q.shape
    P, wide, psz = pages.shape[-3:]
    dv = int(value_width)
    if wide != width or not 0 < dv <= width \
            or pages.ndim != (3 if layer is None else 4):
        raise ValueError("latent pages must be [%sP, width=%d, psz] with "
                         "0 < value_width <= width, got %s and %d"
                         % ("" if layer is None else "L, ", width,
                            pages.shape, dv))
    if layer is not None:
        pages = pages.reshape((-1, width, psz))
    tile = latent_tile_pages(psz, width, pages.dtype.itemsize,
                             page_table.shape[1])
    ids = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, P - 1)
    if layer is not None:
        ids = ids + layer * P
    return pl.pallas_call(
        functools.partial(_latent_attn_kernel, float(scale), tile, dv,
                          False),
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec((None, H, width), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, dv), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, tile, width, psz), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(), name="mx_latent_paged_attention")(
            jnp.asarray(lengths, jnp.int32), ids.reshape(-1), q, pages)


_INDEX_TILE_TOKENS = 2048


def index_tile_pages(psz, rows, itemsize, table_width):
    """Pages the index-score kernel copies per step: 2,048 tokens' index
    keys where the double-buffered tile fits the ``kernels.vmem_budget``
    knob, fewer where it does not or the table is narrower, at least one."""
    from .. import config as _config
    fit = int(_config.get("kernels.vmem_budget")) \
        // (2 * psz * rows * itemsize)
    return max(1, min(_INDEX_TILE_TOKENS // psz, fit, table_width))


def _index_scores_kernel(tile, first, lengths_ref, table_ref, q_ref, w_ref,
                         pool_hbm, o_ref, buf, sems):
    """One decode row's index scores over its pages, read where they lie:
    only the index-key rows ``[first, first + Di)`` of each page are copied
    (``Di`` x ``psz``, tokens on the lanes), ``tile`` pages a step through a
    double-buffered tile; a page's scores are ``sum_j w_j relu(q_j .
    key)``: one product ``q [Hi, Di] . keys [Di, psz]``, the rectified
    products weighted by ``w_ref`` ``[Hi, 1]`` (float32) and summed over
    the heads.  Row w of ``o_ref`` ``[W, psz]`` is page w's scores; the
    pages past a row's last read 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b = pl.program_id(0)
    di, psz = buf.shape[2:]
    W = table_ref.shape[0] // lengths_ref.shape[0]
    n_pages = jnp.minimum((lengths_ref[b] + psz - 1) // psz, W)
    n_tiles = (n_pages + tile - 1) // tile

    def each_page(t, slot, act):
        for j in range(tile):
            i = t * tile + j

            @pl.when(i < n_pages)
            def _(i=i, j=j):
                act(pltpu.make_async_copy(
                    pool_hbm.at[table_ref[b * W + i], pl.ds(first, di)],
                    buf.at[slot, j], sems.at[slot]))

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_tiles > 0)
    def _():
        each_page(0, 0, lambda cp: cp.start())

    q = q_ref[...].astype(buf.dtype)                        # [Hi, Di]
    w = w_ref[...]                                          # [Hi, 1]
    prec = _mxu_precision(q)

    def body(t, carry):
        slot = t % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            each_page(t + 1, 1 - slot, lambda cp: cp.start())

        each_page(t, slot, lambda cp: cp.wait())
        for j in range(tile):
            s = jax.lax.dot_general(
                q, buf[slot, j], (((1,), (0,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32)         # [Hi, psz]
            score = jnp.sum(jnp.maximum(s, 0.0) * w, axis=0, keepdims=True)

            @pl.when(t * tile + j < n_pages)
            def _(j=j, score=score):
                o_ref[pl.ds(t * tile + j, 1), :] = score
        return carry

    jax.lax.fori_loop(0, n_tiles, body, 0)


def pallas_index_scores(q, w, pages, page_table, lengths, first_row,
                        layer=None):
    """Decode-step index scores over the pages a page table names (an
    indexer's keys held in rows ``[first_row, first_row + Di)`` of latent
    pages whose tokens lie on the lanes): ``q`` ``[B, Hi, Di]`` the index
    queries, ``w`` ``[B, Hi]`` float32 their weights, ``pages`` ``[P,
    width, psz]`` (or with ``layer`` every layer's ``[L, P, width, psz]``
    handed over whole), ``page_table`` ``[B, W]``, ``lengths`` ``[B]``.
    Returns ``[B, W, psz]`` float32, ``sum_j w_j relu(q_j . k)`` of each
    token in page-table order (0 in the pages past a row's last; what its
    last page holds past the length is scored).  Routing/fallback
    policy lives in ``mx.kernels.index_scores``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..rtc import interpret_mode
    q = jnp.asarray(q)
    B, Hi, di = q.shape
    P, width, psz = pages.shape[-3:]
    W = page_table.shape[1]
    if not 0 <= first_row <= width - di or w.shape != (B, Hi) \
            or pages.ndim != (3 if layer is None else 4):
        raise ValueError("index scores take q [B, Hi, Di], w [B, Hi] and "
                         "pages [%sP, width, psz] holding the keys in rows "
                         "[%d, %d), got %s, %s and %s"
                         % ("" if layer is None else "L, ", first_row,
                            first_row + di, q.shape, w.shape, pages.shape))
    if layer is not None:
        pages = pages.reshape((-1, width, psz))
    tile = index_tile_pages(psz, di, pages.dtype.itemsize, W)
    ids = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, P - 1)
    if layer is not None:
        ids = ids + layer * P
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, tile, int(first_row)),
        out_shape=jax.ShapeDtypeStruct((B, W, psz), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec((None, Hi, di), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, Hi, 1), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, W, psz), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, tile, di, psz), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(), name="mx_index_scores")(
            jnp.asarray(lengths, jnp.int32), ids.reshape(-1), q,
            jnp.asarray(w, jnp.float32)[..., None], pages)


def pallas_sparse_latent_attention(q, pages, page_table, lengths, chosen,
                                   scale, value_width, layer=None):
    """The absorbed latent decode kernel over the tokens a selection keeps:
    :func:`pallas_latent_paged_attention`'s walk of a row's pages (whole
    pages are copied, one tile of them a step), with every token that
    ``chosen`` ``[B, W, psz]`` int32 does not mark (0) pinned to ``-1e30``
    beside those past the length.  ``q`` ``[B, H, kw]`` scores the first
    ``kw`` rows of a page (``kw`` <= its width: the rows below, an
    indexer's keys, are copied and not read); values are its first
    ``value_width`` rows.  Returns ``[B, H, value_width]`` in q's dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..rtc import interpret_mode
    q = jnp.asarray(q)
    B, H, kw = q.shape
    P, width, psz = pages.shape[-3:]
    W = page_table.shape[1]
    dv = int(value_width)
    if not 0 < dv <= kw <= width or chosen.shape != (B, W, psz) \
            or pages.ndim != (3 if layer is None else 4):
        raise ValueError("sparse latent attention takes q [B, H, kw], pages "
                         "[%sP, width >= kw, psz], chosen [B, W, psz] and 0 "
                         "< value_width <= kw, got %s, %s, %s and %d"
                         % ("" if layer is None else "L, ", q.shape,
                            pages.shape, chosen.shape, dv))
    if layer is not None:
        pages = pages.reshape((-1, width, psz))
    tile = latent_tile_pages(psz, width, pages.dtype.itemsize, W)
    ids = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, P - 1)
    if layer is not None:
        ids = ids + layer * P
    return pl.pallas_call(
        functools.partial(_latent_attn_kernel, float(scale), tile, dv,
                          True),
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[pl.BlockSpec((None, H, kw), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((None, W, psz), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, dv), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, tile, width, psz), pages.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret_mode(), name="mx_sparse_latent_attention")(
            jnp.asarray(lengths, jnp.int32), ids.reshape(-1), q,
            jnp.asarray(chosen, jnp.int32), pages)


# ------------------------------------------------------- grouped product
_GROUPED_ROW_TILE = 128          # one MXU pass tall


def grouped_row_tile(m, itemsize):
    """Rows a tile of the grouped product holds: one MXU pass (128), or
    all ``m`` rows, rounded up to the dtype's sublane packing (8 rows of
    f32, 16 of bf16), where there are fewer.  A step's cost is its
    weight tile's fetch, not its rows (the MXU loads a 128-row weight
    tile whatever it streams past it), so a tile holds as many groups as
    it can: the fewer groups lie across a tile's edge, the fewer steps
    run with no fetch in flight (on the chip, 2,816 rows over 128 groups
    of ~5: 2.33 ms an expert layer at 16 rows, 2.15 at 64 and at 128,
    2.34 at 256; PERF.md section 6, PR 30)."""
    pack = _SUBLANES * 4 // itemsize
    return min(_GROUPED_ROW_TILE, -(-m // pack) * pack)


def grouped_col_tile(k, n, itemsize):
    """Columns of one group's ``[k, n]`` matrix a step of the grouped
    product streams: the widest multiple of the 128 lanes that divides
    ``n`` and whose ``[k, tile]`` block fits ``kernels.vmem_budget`` (the
    pipeline holds two), or None where not even the narrowest does."""
    from .. import config as _config
    tile = _row_block(n, k * itemsize, align=_LANES)
    return tile if tile * k * itemsize \
        <= _config.get("kernels.vmem_budget") else None


def _grouped_work_items(sizes, row_tiles, tm):
    """The steps a grouped product walks, from the groups' sizes: one for
    every (group, row tile) pair in which the group has rows, in group
    order (``sizes`` int32).  Returns ``(group [I], tile [I], start [E],
    end [E], count [1])`` int32 with ``I = row_tiles + E - 1``, the most
    there can be
    (each group after the first adds at most the tile it shares with its
    predecessor); the ``I - count`` steps past the last pair repeat it,
    so the pipeline that walks all ``I`` fetches nothing for them."""
    e = sizes.shape[0]
    end = jnp.cumsum(sizes)
    start = end - sizes
    first = start // tm
    tiles = jnp.where(sizes > 0, (end + tm - 1) // tm - first, 0)
    item_end = jnp.cumsum(tiles)
    i = jnp.arange(row_tiles + e - 1, dtype=jnp.int32)
    i = jnp.minimum(i, jnp.maximum(item_end[-1] - 1, 0))
    # the group of step i is the first whose steps end behind it
    group = jnp.minimum(jnp.sum(item_end[None, :] <= i[:, None], axis=1,
                                dtype=jnp.int32), e - 1)
    tile = first[group] + i - (item_end - tiles)[group]
    return (group, jnp.clip(tile, 0, row_tiles - 1), start, end,
            item_end[-1:])


def _grouped_matmul_kernel(tm, epilogue, group_ref, tile_ref, start_ref,
                           end_ref, count_ref, x_ref, w_ref, *refs):
    """One (group, row tile) pair of a grouped product: the tile's ``tm``
    rows times one column tile of the group's matrix, kept for the rows
    the group owns.  Row tiles lie on multiples of ``tm`` whatever the
    groups' offsets, so a tile that several groups share is walked once a
    group (consecutive steps, the output block staying in VMEM between
    them) and each keeps its own rows; rows of no group keep what the
    buffer held.  The group and tile of a step arrive by scalar prefetch
    and pick its blocks: consecutive steps of one group name the same
    weight block, which is then fetched once, and the steps past the last
    pair (``count_ref``) name the last pair's blocks and compute nothing.
    ``epilogue`` (elementwise, on the float32 product) runs before the
    cast to the output's dtype.  With a second matrix of the group (a
    ``wb_ref`` before ``o_ref``) the same rows multiply both and
    ``epilogue`` takes the two float32 products."""
    from jax.experimental import pallas as pl
    *wb_ref, o_ref = refs
    i = pl.program_id(1)

    @pl.when(i < count_ref[0])
    def _():
        g = group_ref[i]
        x = x_ref[...]
        accs = [jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), precision=_mxu_precision(x, w),
            preferred_element_type=jnp.float32)
            for w in [r[...] for r in [w_ref] + wb_ref]]
        acc = accs[0] if epilogue is None else epilogue(*accs)
        row = tile_ref[i] * tm + jax.lax.broadcasted_iota(
            jnp.int32, acc.shape, 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        # (masks have the 32-bit layout: select in f32, then narrow)
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def pallas_grouped_matmul(rows, w, sizes, epilogue=None,
                          out_dtype=jnp.float32, w_b=None):
    """Grouped matrix product: ``rows [M, K]`` lie sorted by group, group
    ``g`` owns the next ``sizes[g]`` of them and multiplies them by ``w[g]
    [K, N]``.  Returns ``[M, N]`` of ``out_dtype``: 16-bit operands take
    the MXU's native pass, the product accumulates in float32, and
    ``epilogue`` (an elementwise function, if given) is applied to it
    before the cast.  A row behind the last group is left as it lay,
    whatever that is.  With ``w_b`` (a second matrix a group, of ``w``'s
    shape: a gated expert's two input matrices) every step multiplies its
    rows by both column tiles and ``epilogue(a, b)`` folds the two float32
    products into the one that is written.

    The row tile follows from the static shapes (:func:`grouped_row_tile`)
    and the groups' sizes decide at run time which (group, tile) pairs
    there are (:func:`_grouped_work_items`, by scalar prefetch): a group
    without rows is never read, one with rows is read once, a column tile
    at a time (:func:`grouped_col_tile`) through the pipeline's two
    buffers, and what is multiplied is a tile of rows a pair, not a tile
    a group.  The grid is the most pairs there can be; the steps past the
    last pair fetch and compute nothing (a grid cut to the pairs there
    are, which Pallas can do, cannot be traced inside the export's
    dynamic-shape lowering that the paged kernel needs).  ``K`` and ``N``
    are multiples of 128 and a ``[K, 128]`` block fits
    ``kernels.vmem_budget``: routing/fallback policy lives in
    ``mx.kernels.grouped_matmul``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..rtc import interpret_mode
    m, k = rows.shape
    e, _, n = w.shape
    tm = grouped_row_tile(m, rows.dtype.itemsize)
    pair = w_b is not None
    tn = grouped_col_tile(k * (2 if pair else 1), n, w.dtype.itemsize)
    if w.shape[1] != k or k % _LANES or n % _LANES or tn is None \
            or sizes.shape != (e,) or (pair and (
                w_b.shape != w.shape or w_b.dtype != w.dtype
                or epilogue is None)):
        raise ValueError(
            "grouped product takes rows [M, K], w [E, K, N] and sizes [E] "
            "with K and N multiples of %d and a [K, %d] block inside "
            "kernels.vmem_budget (w_b, if given, like w and folded by an "
            "epilogue of two), got %s, %s and %s"
            % (_LANES, _LANES, rows.shape, w.shape, sizes.shape))
    rows = jnp.pad(rows, ((0, -m % tm), (0, 0)))
    row_tiles = rows.shape[0] // tm
    items = _grouped_work_items(jnp.asarray(sizes, jnp.int32), row_tiles, tm)
    out = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tm, epilogue),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(n // tn, row_tiles + e - 1),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, group, tile, *_:
                             (tile[i], 0))] + [
                pl.BlockSpec((None, k, tn), lambda j, i, group, tile, *_:
                             (group[i], 0, j))] * (2 if pair else 1),
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, group, tile, *_:
                                   (tile[i], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_mode(), name="mx_grouped_matmul")(
            *items, rows, w, *((w_b,) if pair else ()))
    return out[:m]


# ------------------------------------------------------- retention update
def retention_row_tile(n, dh):
    """Rows of a K/V head's float32 ``[n, dh]`` state a step of the
    retention update streams: the most that divide ``n``, are a multiple
    of the 8 sublanes and whose ``[tile, dh]`` block fits
    ``kernels.vmem_budget`` (the pipeline holds two coming in and two
    going out), or None where no tile does."""
    from .. import config as _config
    tile = _row_block(n, dh * 4)
    return tile if tile % _SUBLANES == 0 and tile * dh * 4 \
        <= _config.get("kernels.vmem_budget") else None


def _retention_update_kernel(r, g_ref, s_ref, vec_ref, v_ref, so_ref,
                             num_ref):
    """One ``[tile, Dh]`` block of one K/V head's float32 state, through
    on-chip memory once: ``S <- g S + phi(k) v^T`` on the vector unit,
    written back, and the query heads' read-outs of the NEW rows
    accumulated over the head's tiles while the block is resident.

    ``vec_ref`` ``[rows, tile]`` holds the tile's ``phi(q)`` (rows 0..r-1)
    and ``phi(k)`` (row r) along the lanes.  The state's rows lie on the
    sublanes, so ``phi(k)`` is turned once a tile (a transpose of the
    small block) and broadcast along the lanes against ``v``'s row; the
    read-outs are ``[rows, tile] x [tile, Dh]``, which the MXU takes at
    float32 contract precision (the state is not rounded on the way: the
    sum differs from the vector unit's by its order alone)."""
    from jax.experimental import pallas as pl
    t = pl.program_id(2)
    g = g_ref[pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)]
    vecs = vec_ref[...]
    state = g * s_ref[...] + vecs.T[:, r:r + 1] * v_ref[...]
    so_ref[...] = state
    num = jax.lax.dot_general(vecs, state, (((1,), (0,)), ((), ())),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)

    @pl.when(t == 0)
    def _():
        num_ref[...] = num

    @pl.when(t > 0)
    def _():
        num_ref[...] += num


def pallas_retention_update(state, z, pk, pq, g, v):
    """One decode step of a power-retention layer's state, every row and
    K/V head: ``S <- g S + phi(k) v^T``, ``z <- g z + phi(k)`` and the
    query heads' read-outs of the new state, ``num = phi(q)^T S`` and
    ``den = phi(q) . z``, in float32.

    state [B, KVH, N, Dh] f32; z, pk [B, KVH, N] f32; pq [B, KVH, R, N]
    f32; g [B, KVH] f32; v [B, KVH, Dh].  Returns ``(state, z, num [B,
    KVH, R, Dh], den [B, KVH, R])``.  The grid walks (row, K/V head,
    state tile): a :func:`retention_row_tile` of the state crosses HBM
    once in and once out, rewritten in place (``input_output_aliases``),
    where two XLA fusions read it twice and write it once.  The
    normaliser — 1/129 of the bytes — stays the elementwise update and the
    reduction XLA makes of it: no multiple of the 128 lanes divides ``N =
    Dh (Dh + 1) / 2``, so the vectors along ``N`` reach the kernel cut
    into the state's tiles (``[B, KVH, tiles, rows, tile]``, a block its
    array's whole last two axes).  ``Dh`` is a multiple of 128 and a tile
    exists: routing/fallback policy lives in
    ``mx.kernels.retention_update``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..rtc import interpret_mode
    b, kvh, n, dh = state.shape
    r = pq.shape[2]
    tile = retention_row_tile(n, dh)
    if state.dtype != jnp.float32 or dh % _LANES or tile is None \
            or pq.shape != (b, kvh, r, n) or pk.shape != (b, kvh, n) \
            or z.shape != pk.shape or g.shape != (b, kvh) \
            or v.shape != (b, kvh, dh):
        raise ValueError(
            "retention update takes a float32 state [B, KVH, N, Dh] with "
            "Dh a multiple of %d and a row tile inside kernels.vmem_budget,"
            " z and pk [B, KVH, N], pq [B, KVH, R, N], g [B, KVH] and v "
            "[B, KVH, Dh], got %s %s, %s, %s, %s, %s and %s"
            % (_LANES, state.dtype, state.shape, z.shape, pk.shape,
               pq.shape, g.shape, v.shape))
    f32 = jnp.float32
    pk, pq, g = pk.astype(f32), pq.astype(f32), g.astype(f32)
    rows = -(-(r + 1) // _SUBLANES) * _SUBLANES
    vecs = jnp.concatenate(
        [pq, pk[:, :, None], jnp.zeros((b, kvh, rows - r - 1, n), f32)],
        axis=2)
    vecs = jnp.moveaxis(vecs.reshape(b, kvh, rows, n // tile, tile), 3, 2)

    def block(*shape):
        return pl.BlockSpec((None, None) + shape,
                            lambda i, j, t: (i, j, t, 0))

    def resident(*shape):
        return pl.BlockSpec((None, None) + shape,
                            lambda i, j, t: (i, j, 0, 0))

    state, num = pl.pallas_call(
        functools.partial(_retention_update_kernel, r),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((b, kvh, rows, dh), f32)],
        grid=(b, kvh, n // tile),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  block(tile, dh),
                  pl.BlockSpec((None, None, None, rows, tile),
                               lambda i, j, t: (i, j, t, 0, 0)),
                  resident(1, dh)],
        out_specs=[block(tile, dh), resident(rows, dh)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(), name="mx_retention_update")(
            g.reshape(-1), state, vecs, v.astype(f32)[:, :, None])
    z = g[..., None] * z + pk
    den = jnp.sum(pq * z[:, :, None], axis=-1)
    return state, z, num[:, :, :r], den


# ------------------------------------------------------- fused elementwise
def _scale_bias_relu_kernel(x_ref, scale_ref, bias_ref, o_ref):
    """Fused y = relu(x * scale + bias) — the classic post-GEMM epilogue."""
    o_ref[:] = jnp.maximum(x_ref[:] * scale_ref[:] + bias_ref[:], 0.0)


@register("pallas_scale_bias_relu", differentiable=False)
def pallas_scale_bias_relu(data, scale, bias, **_):
    """Fused per-feature epilogue y = relu(x*scale + bias)
    (mx.nd.pallas_scale_bias_relu); scale/bias broadcast over the last
    axis INSIDE the kernel, so HBM reads stay B*D + 2*D."""
    from jax.experimental import pallas as pl
    from ..rtc import interpret_mode
    x = jnp.asarray(data)
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    n = flat.shape[0]
    s = jnp.asarray(scale).reshape(1, d).astype(x.dtype)
    b = jnp.asarray(bias).reshape(1, d).astype(x.dtype)
    rows = _row_block(n, _lane_pad(d) * flat.dtype.itemsize)
    out = pl.pallas_call(
        _scale_bias_relu_kernel,
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        interpret=interpret_mode(), name="mx_scale_bias_relu")(flat, s, b)
    return out.reshape(x.shape)
