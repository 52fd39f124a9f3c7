"""Bytes a decode iteration of a MiniCPM-SALA stage (``models.HybridLM``
with ``B``, ``N`` and ``F`` blocks) needs from HBM, and the operations a
prefill's block-sparse attention needs, from the shapes in the
configuration's ``sizes.lm``.  Decode is bandwidth-bound: every weight is
read once an iteration; a ``B`` block reads, for every row and K/V head,
the K and V rows of the tokens its selection kept (one head's: 512 B a
token at heads of 128) and scores the row's compressed keys (both heads':
512 B a key); an ``N`` block reads and writes every active row's float32
``[heads, Dh, Dh]`` state once.  The count is of what the algorithm needs,
whatever implements it: a kernel that reads both heads of a page, or whole
pages where fewer tokens were kept, reads as a lower share of the
roofline, which is the truth."""
from __future__ import annotations

BF16, F32 = 2, 4


def _count(lm, kind):
    return lm["pattern"].count(kind)


def block_params(lm):
    """Per kind, one block's parameters: ``B`` q, k, v, o, the head-wise
    gate, two head norms and the block norm; ``N`` q, k, v, o, the
    elementwise gate, two head norms, the output norm and the block norm;
    ``F`` three matrices and the block norm."""
    d, dh = lm["d_model"], lm["head_dim"]
    h, kv = lm["num_heads"], lm["num_kv_heads"]
    return {"B": d * dh * (2 * h + 2 * kv) + d * h + 2 * dh + d,
            "N": 4 * d * h * dh + d * h * dh + 2 * dh + h * dh + d,
            "F": 3 * d * lm["mlp_ff"] + d}


def weight_bytes(lm):
    """Every weight a decode iteration reads: the blocks, the output head
    and the final norm (the embedding is a gather of a few rows and is not
    counted)."""
    per = block_params(lm)
    d = lm["d_model"]
    return (sum(per[k] * _count(lm, k) for k in per)
            + lm["vocab_size"] * d + d) * BF16


def parameter_count(lm):
    per = block_params(lm)
    return sum(per[k] * _count(lm, k) for k in per) \
        + 2 * lm["vocab_size"] * lm["d_model"] + lm["d_model"]


def state_bytes_per_row(lm):
    """One row's float32 lightning state, all ``N`` blocks."""
    return _count(lm, "N") * lm["num_heads"] * lm["head_dim"] ** 2 * F32


def cache_bytes(lm, pages, page_tokens, slots):
    """What ``HybridLM.kv_spec()`` describes at ``pages`` pages of
    ``page_tokens`` and ``slots`` decode slots: K and V pages of the ``B``
    blocks, their compressed keys (a row every ``kernel_stride``
    positions of ``max_len``) and running sums, the ``N`` blocks' state."""
    b, kv, dh = _count(lm, "B"), lm["num_kv_heads"], lm["head_dim"]
    ks = lm["kernel_size"] // lm["kernel_stride"]
    return 2 * b * pages * page_tokens * kv * dh * BF16 \
        + slots * b * kv * dh * (lm["max_len"] // lm["kernel_stride"] * BF16
                                 + ks * F32) \
        + slots * state_bytes_per_row(lm)


def scope_bytes(lm, rows, selected_tokens, compressed_keys):
    """The iteration's bytes by device scope, at what the traced
    iterations held (the ``engine.decode`` spans' means): ``rows`` active
    rows, ``selected_tokens`` the tokens a query head attended summed over
    the rows and ``B`` blocks, ``compressed_keys`` the keys scored summed
    over the rows (one ``B`` block's: all of them score as many)."""
    kv, dh = lm["num_kv_heads"], lm["head_dim"]
    return {"mx.block_sparse_attention": selected_tokens * kv * 2 * dh * BF16,
            "mx.block_select": _count(lm, "B") * compressed_keys * kv * dh
            * BF16,
            "mx.lightning_update": 2 * rows * state_bytes_per_row(lm),
            "weights": weight_bytes(lm)}


def decode_iteration_bytes(lm, rows, selected_tokens=0, compressed_keys=0):
    return sum(scope_bytes(lm, rows, selected_tokens,
                           compressed_keys).values())


def kept_tokens(lm, t):
    """Tokens a query at position t attends in a ``B`` block: every one at
    or before it under ``dense_len``, else ``block_topk`` blocks with its
    own partial block last (when there are as many)."""
    if t < lm["dense_len"]:
        return t + 1
    bs = lm["block_size"]
    return min(t // bs + 1, lm["block_topk"]) * bs - (bs - 1 - t % bs)


def prefill_pair_flops(lm, prompt_len):
    """Operations of a prefill's block-sparse attention, all ``B`` blocks:
    two products (scores and values) of ``2 head_dim`` a kept (query head,
    key) pair, summed over the prompt's queries."""
    n = int(round(prompt_len))
    dense = min(n, lm["dense_len"])
    pairs = dense * (dense + 1) // 2 + sum(
        kept_tokens(lm, t) for t in range(dense, n))
    return _count(lm, "B") * lm["num_heads"] * pairs * 4 * lm["head_dim"]
