"""Bytes a decode iteration of a power-retention decoder (``models
.HybridLM`` with ``R`` and ``F`` blocks) needs from HBM, from the shapes
in the configuration's ``sizes.lm``.  Decode is bandwidth-bound: every
weight is read once an iteration, and every active row's retention state —
per ``R`` block and K/V head a ``[N, head_dim]`` float32 matrix and an
``[N]`` normaliser, ``N = head_dim (head_dim + 1) / 2`` the EXACT size of
the key's symmetric square — is read once and written once.  The count is
of what the algorithm needs, whatever implements it: a layout that pads
``N`` moves more bytes and reads as a lower share of the roofline, which
is the truth.  There are no K/V pages and no experts: ``held_tokens`` and
``experts_hit`` are accepted and play no part."""
from __future__ import annotations

BF16, F32 = 2, 4


def _count(lm, kind):
    return lm["pattern"].count(kind)


def ret_width(lm):
    """Rows of one K/V head's state: the upper triangle of the key's
    square (8,256 at a head of 128)."""
    return lm["head_dim"] * (lm["head_dim"] + 1) // 2


def block_params(lm):
    """Per kind, one block's parameters: ``R`` q, k, v, o, the gate's
    matrix and bias, two head norms and the block norm; ``F`` three
    matrices and the block norm."""
    d, dh = lm["d_model"], lm["head_dim"]
    h, kv = lm["num_heads"], lm["num_kv_heads"]
    return {"R": d * dh * (2 * h + 2 * kv) + d * kv + kv + 2 * dh + d,
            "F": 3 * d * lm["mlp_ff"] + d}


def weight_bytes(lm):
    """Every weight a decode iteration reads: the blocks, the output head
    and final norm (the embedding is a gather of a few rows and is not
    counted; the gate bias is float32)."""
    per = block_params(lm)
    d = lm["d_model"]
    return sum(per[k] * _count(lm, k) for k in per) * BF16 \
        + _count(lm, "R") * lm["num_kv_heads"] * (F32 - BF16) \
        + (lm["vocab_size"] * d + d) * BF16


def state_bytes_per_row(lm):
    """One row's float32 retention state and normaliser, all ``R``
    blocks: what ``HybridLM.kv_spec()["state"]`` lists."""
    n = ret_width(lm)
    return _count(lm, "R") * lm["num_kv_heads"] * (n * lm["head_dim"] + n) \
        * F32


def scope_bytes(lm, rows, held_tokens, experts_hit):
    """The iteration's bytes by device scope: ``rows`` active rows."""
    return {"mx.retention_update": 2 * rows * state_bytes_per_row(lm),
            "weights": weight_bytes(lm)}


def decode_iteration_bytes(lm, rows, held_tokens=0, experts_hit=0):
    return sum(scope_bytes(lm, rows, held_tokens, experts_hit).values())


def parameter_count(lm):
    per = block_params(lm)
    return sum(per[k] * _count(lm, k) for k in per) \
        + 2 * lm["vocab_size"] * lm["d_model"] + lm["d_model"]
