"""Bytes a decode iteration of the hybrid decoder (``models.HybridLM``:
Mamba-2, latent mixture-of-experts and grouped-query attention blocks)
needs from HBM, from the shapes in the configuration's ``sizes.lm``.
Decode is bandwidth-bound: at 128 rows a held expert sees ~5 tokens and the
recurrent state is read and written once a row a step.

  weights outside the routed experts   once an iteration
  a routed expert's two matrices       once per DISTINCT held expert hit
                                       (``expert_bytes``: 11.0 MB published)
  the recurrent state and conv tail    read and written per active row
  cached K and V                       per token the rows hold, for the
                                       layers that attend

and the same split per device scope (``scope_bytes``)."""
from __future__ import annotations

BF16, F32 = 2, 4


def _count(lm, kind):
    return lm["pattern"].count(kind)


def expert_bytes(lm):
    """One routed expert: W1 [latent, ff] and W2 [ff, latent]."""
    return 2 * lm["moe_latent"] * lm["expert_ff"] * BF16


def block_weight_bytes(lm):
    """Per kind, one block's weights outside the routed experts."""
    d = lm["d_model"]
    inner = lm["ssm_heads"] * lm["ssm_head_dim"]
    conv = inner + 2 * lm["ssm_groups"] * lm["ssm_state"]
    attn = d * lm["head_dim"] * (2 * lm["num_heads"]
                                 + 2 * lm["num_kv_heads"]) + d
    ssm = d * (inner + conv + lm["ssm_heads"]) + inner * d + inner + d \
        + conv * (lm["conv_kernel"] + 1)
    moe = d * lm["num_experts"] + 2 * d * lm["moe_latent"] \
        + 2 * d * lm["shared_ff"] + d
    small_f32 = {"M": 3 * lm["ssm_heads"], "E": lm["num_experts"], "*": 0}
    return {k: n * BF16 + small_f32[k] * F32
            for k, n in (("*", attn), ("M", ssm), ("E", moe))}


def other_weight_bytes(lm):
    """Every weight a decode iteration reads whatever the routing: the
    blocks outside their routed experts, the output head and final norm
    (the embedding is a gather of a few rows and is not counted)."""
    per = block_weight_bytes(lm)
    d = lm["d_model"]
    return sum(per[k] * _count(lm, k) for k in per) \
        + (lm["vocab_size"] * d + d) * BF16


def ssm_state_bytes_per_row(lm):
    """One row's float32 recurrent state, all ``M`` blocks."""
    return _count(lm, "M") * lm["ssm_heads"] * lm["ssm_head_dim"] \
        * lm["ssm_state"] * F32


def conv_tail_bytes_per_row(lm):
    inner = lm["ssm_heads"] * lm["ssm_head_dim"]
    conv = inner + 2 * lm["ssm_groups"] * lm["ssm_state"]
    return _count(lm, "M") * (lm["conv_kernel"] - 1) * conv * BF16


def kv_bytes_per_token(lm):
    return 2 * _count(lm, "*") * lm["num_kv_heads"] * lm["head_dim"] * BF16


def scope_bytes(lm, rows, held_tokens, experts_hit):
    """The iteration's bytes by device scope: ``rows`` active rows holding
    ``held_tokens`` cached tokens in all, ``experts_hit`` distinct held
    experts hit, summed over the ``E`` blocks."""
    return {
        "mx.moe_experts": experts_hit * expert_bytes(lm),
        "mx.ssm_update": 2 * rows * ssm_state_bytes_per_row(lm),
        "mx.ssm_conv": 2 * rows * conv_tail_bytes_per_row(lm),
        "mx.paged_attention": held_tokens * kv_bytes_per_token(lm),
        "weights": other_weight_bytes(lm)}


def decode_iteration_bytes(lm, rows, held_tokens, experts_hit):
    return sum(scope_bytes(lm, rows, held_tokens, experts_hit).values())


def parameter_count(lm):
    """Parameters held here (routed experts: those held)."""
    per = block_weight_bytes(lm)
    return sum(per[k] // BF16 * _count(lm, k) for k in per) \
        + _count(lm, "E") * lm["experts_held"] * expert_bytes(lm) // BF16 \
        + 2 * lm["vocab_size"] * lm["d_model"] + lm["d_model"]
