"""Bytes a decode iteration of a sparse / windowed latent-attention,
gated-experts decoder (``models.HybridLM`` with ``S``, ``W``, ``F`` and ``G``
blocks) needs, from the shapes in the configuration's ``sizes.lm``.
Decode is bandwidth-bound, and each count is the LEAST the mathematics
reads, whatever implements it:

  weights outside the routed experts   once an iteration
  a routed expert's three matrices     once per DISTINCT held expert hit
  the selected latent rows             once per row a query attends, for
                                       the ``S`` layers: ONE row of
                                       ``kv_rank + rope_dim`` bf16 (1,152 B
                                       published), read once for all heads
  the index keys                       once per token the indexer scores:
                                       ``index_dim`` bf16 (256 B), beside
                                       the indexer's own weights
  the window's ring columns            once per column a query attends, for
                                       the ``W`` layers: ``swa_kv_rank +
                                       swa_rope_dim`` bf16 (2,176 B)

A route that reads whole pages and masks what was not selected, or that
gathers the window first, moves more and reads as a lower share: the
yardstick does not move with the route.  Token counts are what the
``engine.decode`` spans say (``selected_tokens``, ``index_tokens``,
``ring_tokens``: summed over the blocks already)."""
from __future__ import annotations

BF16, F32 = 2, 4


def _count(lm, kind):
    return lm["pattern"].count(kind)


def expert_bytes(lm):
    """One routed expert: gate and up [d, ff] and down [ff, d]."""
    return 3 * lm["d_model"] * lm["expert_ff"] * BF16


def row_bytes(lm):
    """A selected token's latent row: latent + rotary key."""
    return (lm["kv_rank"] + lm["rope_dim"]) * BF16


def index_key_bytes(lm):
    return lm["index_dim"] * BF16


def ring_row_bytes(lm):
    return (lm["swa_kv_rank"] + lm["swa_rope_dim"]) * BF16


def _latent_params(d, h, rq, rkv, dn, dr, dv):
    """An ``S`` or ``W`` block outside the indexer: the two
    down-projections, the queries' up-projection, the keys' and values'
    up-projections, the output projection, the head-wise gate, the two
    latent norms and the block norm."""
    return d * rq + rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv \
        + rkv * h * (dn + dv) + h * dv * d + d * h + d


def indexer_params(lm):
    """One ``S`` block's indexer: query and key projections, the key's
    LayerNorm (weight and bias), the head weights."""
    d, rq = lm["d_model"], lm["q_rank"]
    hi, di = lm["index_heads"], lm["index_dim"]
    return rq * hi * di + d * di + 2 * di + d * hi


def block_params(lm):
    """Per kind, one block's parameters outside the routed experts (``S``
    with its indexer; the selection bias is float32 and counted in
    ``weight_bytes``)."""
    d = lm["d_model"]
    sparse = _latent_params(d, lm["num_heads"], lm["q_rank"], lm["kv_rank"],
                            lm["nope_dim"], lm["rope_dim"], lm["v_dim"])
    return {"S": sparse + indexer_params(lm),
            "W": _latent_params(d, lm["swa_heads"], lm["swa_q_rank"],
                                lm["swa_kv_rank"], lm["swa_nope_dim"],
                                lm["swa_rope_dim"], lm["swa_v_dim"]),
            "F": 3 * d * lm["mlp_ff"] + d,
            "G": d * lm["num_experts"] + 3 * d * lm["shared_ff"] + d}


def weight_bytes(lm):
    """Every weight a decode iteration reads whatever the routing: the
    blocks outside their routed experts, the output head and final norm
    (the embedding is a gather of a few rows and is not counted)."""
    per = block_params(lm)
    d = lm["d_model"]
    return sum(per[k] * _count(lm, k) for k in per) * BF16 \
        + _count(lm, "G") * lm["num_experts"] * F32 \
        + (lm["vocab_size"] * d + d) * BF16


def scope_bytes(lm, experts_hit, selected_tokens, index_tokens,
                ring_tokens):
    """The iteration's bytes by device scope (``weights``: the rest, less
    the indexer's weights, which ``mx.dsa_indexer`` counts)."""
    indexer = _count(lm, "S") * indexer_params(lm) * BF16
    return {"mx.moe_experts": experts_hit * expert_bytes(lm),
            "mx.sparse_attention": selected_tokens * row_bytes(lm),
            "mx.dsa_indexer": index_tokens * index_key_bytes(lm) + indexer,
            "mx.window_attention": ring_tokens * ring_row_bytes(lm),
            "weights": weight_bytes(lm) - indexer}


def decode_iteration_bytes(lm, experts_hit, selected_tokens, index_tokens,
                           ring_tokens):
    return sum(scope_bytes(lm, experts_hit, selected_tokens, index_tokens,
                           ring_tokens).values())


def sparse_attention_flops(lm, selected_tokens):
    """Multiply-adds x 2 of the absorbed form over the selected rows: every
    head's score over the whole row and its value over the latent
    (``selected_tokens`` summed over the ``S`` blocks)."""
    return 2 * lm["num_heads"] * selected_tokens \
        * (2 * lm["kv_rank"] + lm["rope_dim"])


def index_score_flops(lm, index_tokens):
    """Multiply-adds x 2 of the index scores: every index head's product
    with a scored token's key, and its weight (``index_tokens`` summed over
    the ``S`` blocks)."""
    return 2 * lm["index_heads"] * index_tokens * (lm["index_dim"] + 1)


def parameter_count(lm):
    """Parameters held here (routed experts: those held)."""
    per = block_params(lm)
    held = lm.get("experts_held") or lm["num_experts"]
    return sum(per[k] * _count(lm, k) for k in per) \
        + _count(lm, "G") * (lm["num_experts"]
                             + held * expert_bytes(lm) // BF16) \
        + 2 * lm["vocab_size"] * lm["d_model"] + lm["d_model"]


def cache_bytes(lm, pages, page_tokens, slots):
    """The page pool (``S`` layers: latent row + index key a token) and the
    rings (``W`` layers: the window in whole 128-column tiles a slot)."""
    width = lm["kv_rank"] + lm["rope_dim"] + lm["index_dim"]
    ring = -(-lm["window"] // 128) * 128
    return _count(lm, "S") * pages * page_tokens * width * BF16 \
        + _count(lm, "W") * slots * ring * ring_row_bytes(lm)
