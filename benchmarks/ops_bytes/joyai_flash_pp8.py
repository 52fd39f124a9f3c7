"""Bytes (and, for the two attention kernels, operations) a decode
iteration or a prefill of a latent-attention / gated-experts decoder
(``models.HybridLM`` with ``L``, ``F`` and ``G`` blocks) needs, from the
shapes in the configuration's ``sizes.lm``.  Decode is bandwidth-bound:

  weights outside the routed experts   once an iteration
  a routed expert's three matrices     once per DISTINCT held expert hit
                                       (``expert_bytes``: 9.44 MB published)
  the cached latent rows               once per token the rows hold, for
                                       the ``L`` layers: ONE row of
                                       ``kv_rank + rope_dim`` bf16 a token a
                                       layer (1,152 B published), read once
                                       for all heads, scores and values

The count is the algorithm's, whatever implements it: a kernel that reads
a page once for its scores and again for its values, a layout that pads the
row, or a twin that gathers the whole window moves more and reads as a
lower share of the roofline, which is the truth.  The same split per device
scope is ``scope_bytes``."""
from __future__ import annotations

BF16, F32 = 2, 4


def _count(lm, kind):
    return lm["pattern"].count(kind)


def row_width(lm):
    """Components of the row a token keeps a layer: latent + rotary key."""
    return lm["kv_rank"] + lm["rope_dim"]


def expert_bytes(lm):
    """One routed expert: gate and up [d, ff] and down [ff, d]."""
    return 3 * lm["d_model"] * lm["expert_ff"] * BF16


def block_params(lm):
    """Per kind, one block's parameters outside the routed experts.  ``L``:
    the two down-projections, the queries' up-projection, the keys' and
    the values' up-projections, the output projection, the two latent
    norms and the block norm.  ``F``: three matrices and the block norm.
    ``G``: router, the shared expert's three matrices and the block norm
    (the selection bias is float32 and counted in ``weight_bytes``)."""
    d, h = lm["d_model"], lm["num_heads"]
    rq, rkv = lm["q_rank"], lm["kv_rank"]
    dn, dr, dv = lm["nope_dim"], lm["rope_dim"], lm["v_dim"]
    return {"L": d * rq + rq + rq * h * (dn + dr) + d * (rkv + dr) + rkv
            + rkv * h * (dn + dv) + h * dv * d + d,
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


def latent_bytes_per_token(lm):
    """What one cached token costs a decode iteration: its row, every
    ``L`` layer."""
    return _count(lm, "L") * row_width(lm) * BF16


def latent_attention_flops(lm, held_tokens):
    """Multiply-adds x 2 of the absorbed form over ``held_tokens`` cached
    tokens: every head's score over the whole row and its value over the
    latent, every ``L`` layer."""
    return 2 * _count(lm, "L") * lm["num_heads"] * held_tokens \
        * (row_width(lm) + lm["kv_rank"])


def prefill_attention_flops(lm, tokens):
    """Multiply-adds x 2 of the expanded causal form over a prompt of
    ``tokens``: the pairs on and under the diagonal, query/key width
    ``nope_dim + rope_dim`` and value width ``v_dim``, every ``L`` layer."""
    pairs = tokens * (tokens + 1) // 2
    return 2 * _count(lm, "L") * lm["num_heads"] * pairs \
        * (lm["nope_dim"] + lm["rope_dim"] + lm["v_dim"])


def scope_bytes(lm, held_tokens, experts_hit):
    """The iteration's bytes by device scope: the active rows hold
    ``held_tokens`` cached tokens in all, ``experts_hit`` distinct held
    experts are hit, summed over the ``G`` blocks."""
    return {"mx.moe_experts": experts_hit * expert_bytes(lm),
            "mx.latent_attention": held_tokens * latent_bytes_per_token(lm),
            "weights": weight_bytes(lm)}


def decode_iteration_bytes(lm, held_tokens, experts_hit):
    return sum(scope_bytes(lm, held_tokens, experts_hit).values())


def parameter_count(lm):
    """Parameters held here (routed experts: those held)."""
    per = block_params(lm)
    held = lm.get("experts_held") or lm["num_experts"]
    return sum(per[k] * _count(lm, k) for k in per) \
        + _count(lm, "G") * (lm["num_experts"]
                             + held * expert_bytes(lm) // BF16) \
        + 2 * lm["vocab_size"] * lm["d_model"] + lm["d_model"]
