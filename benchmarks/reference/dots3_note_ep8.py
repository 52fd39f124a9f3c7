"""One pipeline stage of a decoder of sparse and windowed latent attention
(``dots3_note``: full layers of latent attention with DeepSeek Sparse
Attention's indexer, sliding-window layers of a latent attention of their
own, a leading dense layer, then mixtures of gated experts), in plain
``jax.numpy``, float32, matmul precision "highest": no kernel, no cache, no
batching, no absorbed form, no ring.  It imports nothing from the program
and is handed parameter VALUES (the ``HybridLM`` pytree), which it upcasts
ONE BLOCK AT A TIME (the experts one expert at a time, the heads one head
at a time), so no float32 copy of the weights and no ``[H, S, S]`` array
is held.  The dense MLP, the gated experts, the norms, the published
rotary pairing and the float8 control are ``joyai_flash_pp8``'s reference's
own functions (the same equations).  A block's kind is read from its keys
(``w_iq``: a sparse layer; ``w_dq`` without it: a window layer; ``router``:
experts; else the dense MLP) and every width from the shapes, except what
no shape tells (``lm``: experts per token, the routing scale, the first
held expert's id, eps, the two rotary bases, the window, the tokens a query
keeps), which defaults to the published values.

Block l: ``h <- h + Mixer(RMSNorm(h; ln))``, eps 1e-5; ``logits = head .
RMSNorm(h; final_norm)``.  A published layer is two blocks.

latent attention (keys ``w_dq`` ...; arXiv:2405.04434 section 2.1)
       c_q = RMSNorm(n w_dq; q_norm); [q_nope | q_rope]_h = c_q w_uq; [c |
       k_r] = n w_dkv; c_kv = RMSNorm(c; kv_norm); q_rope, k_rope =
       RoPE(q_rope), RoPE(k_r) (the published INTERLEAVED pairing, on the
       published column order: the program stores the rotary columns
       de-interleaved, so they are put back first); k_nope_h = c_kv w_uk,
       v_h = c_kv w_uv; scores (q_nope . k_nope + q_rope . k_rope) /
       sqrt(dn + dr) over the keys ``seen`` allows; o_h = softmax v_h;
       gated o_h * sigmoid(n w_hg)_h; wo.  No bias.
sparse layer (key ``w_iq``; the DeepSeek-V3.2-Exp report section 2.1)
       index queries q^I_tj = c_q w_iq (Hi heads of Di), one index key a
       token k^I_s = LayerNorm(n w_ik; ik_w, ik_b) (eps 1e-6), RoPE on
       their first dr dims (the same pairing, base
       ``rope_theta``); w_tj = (n w_iw)_j / sqrt(Hi); I_ts = sum_j w_tj
       relu(q^I_tj . k^I_s) / sqrt(Di) for s <= t; ``seen`` = the
       ``index_topk`` largest I_ts of each row (every s <= t while there
       are fewer; ties to the lower s, as ``lax.top_k`` breaks them).
window layer   ``seen`` = t - window < s <= t; base ``swa_rope_theta``.

``routed`` TELLS the reference which experts the served program chose, as
in ``joyai_flash_pp8`` (near-ties that bf16 flips are no difference; the
told choices the reference's own scores would not have made are counted).

``degrade`` computes the forward with one part of it taken away:
``dense_selection`` (sparse layers attend every s <= t: no indexer),
``no_window`` (window layers attend every s <= t), ``fp8_experts`` (every
routed expert's matrices in float8 e4m3 under a scale an output column,
the precision below the configuration's bf16 weights).  MATCHED and
CONTROL uses as ``joyai_flash_pp8``'s."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.harness import manifest

_JOY = manifest.load_module("reference", "joyai_flash_pp8")
HI = lax.Precision.HIGHEST
F32 = jnp.float32
PUBLISHED = {"top_k": 8, "route_scale": 1.0, "expert_offset": 0,
             "eps": 1e-5, "rope_theta": 80000000.0, "swa_rope_theta": 50000.0,
             "window": 513, "index_topk": 2048}
DEGRADATIONS = ("dense_selection", "no_window", "fp8_experts")
#: the kind of block (by :func:`_kind`) a degradation touches
_DEGRADES = {"dense_selection": "S", "no_window": "W", "fp8_experts": "G"}
_INDEX_LN_EPS = 1e-6


def _kind(lp):
    if "w_iq" in lp:
        return "S"
    if "w_dq" in lp:
        return "W"
    return "G" if "router" in lp else "F"


def _block_rows(s, most):
    """Rows a block of a loop over the sequence: the largest divisor of
    ``s`` up to ``most``."""
    return max(d for d in range(1, min(s, most) + 1) if s % d == 0)


def _rotate(x, pos, theta):
    """x [S, ..., d] in the program's stored order -> rotated, published
    order (the same permutation on queries and keys: dot products are
    those of the stored order)."""
    return _JOY._rope_interleaved(_JOY._published_order(x), pos, theta)


def _selection(n, cq, lp, lm, pos):
    """[S, S] bool: row t marks the ``index_topk`` keys s <= t of largest
    index score (all of them while there are fewer), in blocks of rows."""
    s = n.shape[0]
    heads, width = lp["w_iq"].shape[1:]
    r = lp["w_dkv"].shape[1] - lp["kv_norm"].shape[0]       # rotary dims
    qi = jnp.einsum("sr,rhe->she", cq, lp["w_iq"], precision=HI)
    qi = jnp.concatenate([_rotate(qi[..., :r], pos, lm["rope_theta"]),
                          qi[..., r:]], -1)
    ki = jnp.einsum("sd,de->se", n, lp["w_ik"], precision=HI)
    mean = jnp.mean(ki, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(ki - mean), axis=-1, keepdims=True)
    ki = (ki - mean) * lax.rsqrt(var + _INDEX_LN_EPS) * lp["ik_w"] \
        + lp["ik_b"]
    ki = jnp.concatenate([_rotate(ki[:, :r], pos, lm["rope_theta"]),
                          ki[:, r:]], -1)
    w = jnp.einsum("sd,dh->sh", n, lp["w_iw"], precision=HI) \
        / math.sqrt(heads)
    rows = _block_rows(s, 64)
    keep = min(lm["index_topk"], s)
    key = jnp.arange(s)

    def block(i):
        t = i * rows + jnp.arange(rows)
        q = lax.dynamic_slice_in_dim(qi, i * rows, rows)
        wb = lax.dynamic_slice_in_dim(w, i * rows, rows)
        score = jnp.sum(jax.nn.relu(jnp.einsum(
            "qhe,se->qhs", q, ki, precision=HI)) * wb[..., None], axis=1) \
            / math.sqrt(width)
        causal = key[None, :] <= t[:, None]
        _, idx = lax.top_k(jnp.where(causal, score, -jnp.inf), keep)
        mark = jnp.zeros((rows, s), bool).at[
            jnp.arange(rows)[:, None], idx].set(True)
        return mark & causal

    return lax.map(block, jnp.arange(s // rows)).reshape(s, s)


def _attend(q, k, v, seen, window):
    """One head over the sequence, in blocks of query rows: q, k [S, w], v
    [S, dv]; ``seen`` [S, S] bool or None (causal), ``window`` or None."""
    s = q.shape[0]
    rows = _block_rows(s, 512)
    key = jnp.arange(s)

    def block(i):
        t = i * rows + jnp.arange(rows)
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows)
        ok = key[None, :] <= t[:, None]
        if seen is not None:
            ok = ok & lax.dynamic_slice_in_dim(seen, i * rows, rows)
        if window is not None:
            ok = ok & (key[None, :] > t[:, None] - window)
        score = jnp.einsum("qe,se->qs", qb, k, precision=HI) \
            / math.sqrt(q.shape[1])
        probs = jax.nn.softmax(jnp.where(ok, score, -jnp.inf), axis=-1)
        return jnp.einsum("qs,se->qe", probs, v, precision=HI)

    return lax.map(block, jnp.arange(s // rows)).reshape(s, -1)


def _latent_attention(x, lp, lm, degrade):
    sparse = _kind(lp) == "S"
    lp = _JOY._up(lp)
    s = x.shape[0]
    dn = lp["w_uk"].shape[-1]
    rank = lp["kv_norm"].shape[0]
    theta = lm["rope_theta"] if sparse else lm["swa_rope_theta"]
    pos = jnp.arange(s, dtype=F32)
    n = _JOY._rms(x, lp["ln"], lm["eps"])
    cq = _JOY._rms(jnp.einsum("sd,dr->sr", n, lp["w_dq"], precision=HI),
                   lp["q_norm"], lm["eps"])
    ckr = jnp.einsum("sd,dr->sr", n, lp["w_dkv"], precision=HI)
    c = _JOY._rms(ckr[:, :rank], lp["kv_norm"], lm["eps"])
    k_rope = _rotate(ckr[:, rank:], pos, theta)
    seen = _selection(n, cq, lp, lm, pos) \
        if sparse and degrade != "dense_selection" else None
    window = None if sparse or degrade == "no_window" else lm["window"]
    heads = lp["w_uk"].shape[1]
    gate = jax.nn.sigmoid(jnp.einsum("sd,dh->sh", n, lp["w_hg"],
                                     precision=HI)) \
        if "w_hg" in lp else jnp.ones((s, heads), F32)

    def head(acc, parts):
        w_uq, w_uk, w_uv, wo, g = parts
        q = jnp.einsum("sr,re->se", cq, w_uq, precision=HI)
        q = jnp.concatenate([q[:, :dn], _rotate(q[:, dn:], pos, theta)], -1)
        k = jnp.concatenate([jnp.einsum("sr,re->se", c, w_uk, precision=HI),
                             k_rope], -1)
        v = jnp.einsum("sr,re->se", c, w_uv, precision=HI)
        o = _attend(q, k, v, seen, window) * g[:, None]
        return acc + jnp.einsum("se,ed->sd", o, wo, precision=HI), None

    out, _ = lax.scan(head, jnp.zeros_like(x), (
        jnp.moveaxis(lp["w_uq"], 1, 0), jnp.moveaxis(lp["w_uk"], 1, 0),
        jnp.moveaxis(lp["w_uv"], 1, 0), lp["wo"], gate.T))
    return out


@functools.partial(jax.jit, static_argnames=("lm", "degrade"))
def _block(x, lp, lm, degrade, forced=None):
    """-> (x, experts used or None, forced choices missed or None)."""
    lm = dict(lm)
    kind = _kind(lp)
    if kind in "SW":
        return x + _latent_attention(x, lp, lm, degrade), None, None
    if kind == "F":
        return x + _JOY._mlp(x, lp, lm), None, None
    out, chosen, missed = _JOY._experts_routed(x, lp, lm, degrade, forced)
    return x + out, chosen, missed


def _lm(lm):
    return tuple(sorted(dict(PUBLISHED, **{
        k: v for k, v in (lm or {}).items() if k in PUBLISHED}).items()))


def _forward(params, tokens, lm, degrade, routed):
    """-> (the last block's output [S, D], the experts used [G blocks, S,
    top_k], told choices missed).  ``routed`` [G blocks, S' <= S, top_k]."""
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    forced = None
    if routed is not None:
        routed = jnp.asarray(routed, jnp.int32)   # free past S'
        forced = iter(jnp.pad(
            routed, ((0, 0), (0, x.shape[0] - routed.shape[1]), (0, 0)),
            constant_values=-1))
    used, missed = [], 0
    for name in sorted(params["layers"]):
        lp = params["layers"][name]
        kind = _kind(lp)
        # (a block the degradation leaves alone is the full one's compile)
        x, chosen, miss = _block(
            x, lp, _lm(lm), degrade if _DEGRADES.get(degrade) == kind
            else None, next(forced) if forced and kind == "G" else None)
        if chosen is not None:
            used.append(chosen)
            missed = missed + miss
    return x, used, missed


def hidden(params, tokens, lm=None, degrade=None, routed=None):
    """tokens [S] int32 -> the last block's output [S, D] float32."""
    return _forward(params, tokens, lm, degrade, routed)[0]


def logits(params, tokens, rows=None, lm=None, degrade=None, routed=None):
    """Logits [S, V] float32 (of ``rows``, a slice, if given)."""
    x = hidden(params, tokens, lm, degrade, routed)
    return _JOY._head(params, x if rows is None else x[rows],
                      dict(_lm(lm))["eps"])


def _rows(params, prompt, served, pad_to, pad_rows, lm, degrade, routed):
    """One forward over prompt + served[:-1], padded to ``pad_to`` (causal:
    what follows a position cannot reach it) -> (logits [T, V] of the
    positions that produce the served tokens, experts used [G blocks, n +
    T - 1, top_k], choices missed)."""
    n, t = len(prompt), len(served)
    buf = jnp.zeros((pad_to,), jnp.int32)
    buf = buf.at[:n].set(jnp.asarray(prompt, jnp.int32))
    buf = buf.at[n:n + t - 1].set(jnp.asarray(served[:-1], jnp.int32))
    take = jnp.minimum(n - 1 + jnp.arange(pad_rows), pad_to - 1)
    x, used, missed = _forward(params, buf, lm, degrade, routed)
    rows = _JOY._head(params, x[take], dict(_lm(lm))["eps"])[:t]
    return rows, jnp.stack(used)[:, :n + t - 1], missed


def served_token_gaps(params, prompt, served, pad_to, pad_rows, lm=None,
                      routed=None, scored=None, degrade=None):
    """As ``joyai_flash_pp8``'s: per generated position, how far the
    reference's logit of the served token sits below its best, fed the
    served prefix: ``(gaps [T], largest |logit|)``, and with ``routed`` the
    choices missed and the reference's log-probability of each served (or
    ``scored``) token."""
    rows, _, missed = _rows(params, prompt, served, pad_to, pad_rows, lm,
                            degrade, routed)
    tokens = jnp.asarray(served if scored is None else scored, jnp.int32)
    picked = _JOY._picked(rows, tokens)
    out = (rows.max(axis=1) - picked, jnp.abs(rows).max())
    if routed is None:
        return out
    return out + (missed, picked - jax.nn.logsumexp(rows, axis=1))


def simulate(params, prompt, served, pad_to, pad_rows, lm=None,
             degrade=None):
    """What a program with ``degrade``'s fault would have returned, fed the
    served prefix: ``(tokens [T], routed_experts [G blocks, n + T - 1,
    top_k], logprobs [T])``."""
    rows, used, _ = _rows(params, prompt, served, pad_to, pad_rows, lm,
                          degrade, None)
    tokens = jnp.argmax(rows, axis=1)
    return tokens, used, _JOY._picked(rows, tokens) \
        - jax.nn.logsumexp(rows, axis=1)
