"""One pipeline stage of a DeepSeek-V3-style decoder (``joyai_llm_flash``:
multi-head latent attention, a leading dense layer, then mixtures of gated
experts), in plain ``jax.numpy``, float32, matmul precision "highest": no
kernel, no cache, no batching, no absorbed form.  It imports nothing from
the program and is handed parameter VALUES (the ``HybridLM`` pytree:
``embed [V,D]``, ``head [V,D]``, ``final_norm [D]`` and ``layers``, a dict
per block in order), which it upcasts ONE BLOCK AT A TIME — the experts one
expert at a time, the heads' score matrices one head at a time — so no
float32 copy of the weights and no ``[H, S, S]`` array is held.  A block's
kind is read from its keys and every width from the shapes, except what no
shape tells (``lm``: experts per token, the routing scale, the first held
expert's id, eps, the rotary base), which defaults to the published values.

Block l: ``h <- h + Mixer(RMSNorm(h; ln))``, eps 1e-6; ``logits = head .
RMSNorm(h; final_norm)``.  A published layer is two blocks.

latent attention (keys ``w_dq`` ...; arXiv:2405.04434 section 2.1)
       c_q = RMSNorm(n w_dq; q_norm); [q_nope | q_rope]_h = c_q w_uq (H
       heads); [c | k_r] = n w_dkv; c_kv = RMSNorm(c; kv_norm); q_rope,
       k_rope = RoPE(q_rope), RoPE(k_r), k_rope ONE vector for all heads;
       k_nope_h = c_kv w_uk, v_h = c_kv w_uv; a = softmax_causal((q_nope .
       k_nope + q_rope . k_rope) / sqrt(dn + dr)); o_h = a v_h; wo.  No
       bias.  The rotary pairing is the published INTERLEAVED one
       (``rope_interleave``: dims 2i and 2i+1 turn by ``pos * theta^(-2i /
       dr)``) on the published column order; the program stores the rotary
       columns of ``w_uq`` and ``w_dkv`` de-interleaved (even dims, then
       odd dims: the fixed permutation the published code applies to q and
       k before its rotate-half), so the vectors are put back in the
       published order here first (:func:`_published_order`).
dense MLP (keys ``w_gate``, ``w_up``, ``w_down`` as matrices)
       w_down (silu(w_gate n) * (w_up n)).
gated experts (key ``router``; arXiv:2412.19437 section 2.1)
       s = sigmoid(n router) in float32 over ALL experts; the top_k largest
       of s + select_bias (the bias joins the choice only; no group limit);
       w_e = route_scale s_e / sum of the chosen s; EVERY HELD expert is
       applied to every token, E_e(n) = w_down_e (silu(w_gate_e n) * (w_up_e
       n)), with weight 0 where it was not chosen; out = sum_e w_e E_e(n) +
       v_down (silu(v_gate n) * (v_up n)), the shared expert.

``routed`` [G blocks, S', top_k] TELLS the reference which experts the
first S' tokens chose in each expert block (the serving engine says so for
a request that asks): the choice is then the served program's — a near-tie
that bf16 activations flip is no longer a difference between the two —
while scores, weights and everything else stay the reference's own
float32.  A row of -1s (and every position past S') routes freely.  The
choice is not taken on trust: the reference counts the told (token, block,
expert) triples that its OWN scores, on the same input, would not have
chosen (``missed``).

``degrade`` computes the forward with one step or precision taken away:
``no_rope`` (no rotation), ``no_absorb_scale`` (scores scaled by 1 /
sqrt(dn), what an absorbed query that forgot its rotary width would use),
``no_route_norm`` (weights ``route_scale s_e``, not divided by the chosen
scores' sum), ``fp8_rows`` (the rows a cache would keep — c_kv and k_rope —
rounded to float8 e4m3, the precision below the bf16 rows the
configuration states), ``fp8_experts`` (every routed expert's three
matrices rounded to float8 e4m3 under one scale an output column, the
precision below the bf16 weights the configuration states).  Two uses.
MATCHED: the served tokens' log-probabilities under the degraded forward, told the same choices, beside
those under the full one — a sound program's own lie nearer the full
reference's.  CONTROL (:func:`simulate`): the degraded forward, routing
freely, stands for a program with that fault; its tokens, choices and
log-probabilities are then scored exactly as a served request's are, which
is what the configuration's tolerance is set against."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
PUBLISHED = {"top_k": 8, "route_scale": 2.5, "expert_offset": 0,
             "eps": 1e-6, "rope_theta": 32000000.0}
DEGRADATIONS = ("no_rope", "no_absorb_scale", "no_route_norm", "fp8_rows",
                "fp8_experts")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _up(tree):
    return jax.tree_util.tree_map(lambda v: v.astype(F32), tree)


def _published_order(x):
    """Stored (even dims, then odd dims) -> published (interleaved)."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1) \
        .reshape(x.shape)


def _rope_interleaved(x, positions, theta):
    """x [S, ..., dr] in the published order: dims (2i, 2i+1) turn by
    ``positions * theta^(-2i/dr)``."""
    dr = x.shape[-1]
    freq = theta ** (-jnp.arange(0, dr, 2, dtype=F32) / dr)
    angle = positions.reshape((-1,) + (1,) * (x.ndim - 1)) * freq
    pair = x.reshape(x.shape[:-1] + (dr // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _fp8(x):
    return lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _fp8_columns(w):
    """Float8 e4m3 under one scale an output column (the column's largest
    magnitude at 240, the largest finite value of an IEEE-style e4m3, which
    is what ``lax.reduce_precision`` rounds to), and back."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 240.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return _fp8(w / scale) * scale


def _latent_attention(x, lp, lm, degrade):
    lp = _up(lp)
    s = x.shape[0]
    dn = lp["w_uk"].shape[-1]
    rank = lp["kv_norm"].shape[0]
    n = _rms(x, lp["ln"], lm["eps"])
    cq = _rms(jnp.einsum("sd,dr->sr", n, lp["w_dq"], precision=HI),
              lp["q_norm"], lm["eps"])
    q = jnp.einsum("sr,rhe->hse", cq, lp["w_uq"], precision=HI)
    ckr = jnp.einsum("sd,dr->sr", n, lp["w_dkv"], precision=HI)
    c = _rms(ckr[:, :rank], lp["kv_norm"], lm["eps"])
    q_nope, q_rope = q[..., :dn], _published_order(q[..., dn:])
    k_rope = _published_order(ckr[:, rank:])
    if degrade != "no_rope":
        pos = jnp.arange(s, dtype=F32)
        q_rope = jnp.swapaxes(_rope_interleaved(
            jnp.swapaxes(q_rope, 0, 1), pos, lm["rope_theta"]), 0, 1)
        k_rope = _rope_interleaved(k_rope, pos, lm["rope_theta"])
    if degrade == "fp8_rows":
        c, k_rope = _fp8(c), _fp8(k_rope)
    k_nope = jnp.einsum("sr,rhe->hse", c, lp["w_uk"], precision=HI)
    v = jnp.einsum("sr,rhe->hse", c, lp["w_uv"], precision=HI)
    width = dn if degrade == "no_absorb_scale" else dn + q_rope.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(parts):
        qn, qr, kn, vh = parts
        scores = (jnp.einsum("se,te->st", qn, kn, precision=HI)
                  + jnp.einsum("se,te->st", qr, k_rope, precision=HI)) \
            / math.sqrt(width)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("st,te->se", probs, vh, precision=HI)

    o = lax.map(head, (q_nope, q_rope, k_nope, v))           # [H, S, dv]
    return jnp.einsum("hse,hed->sd", o, lp["wo"], precision=HI)


def _gated(n, gate, up, down):
    mid = jax.nn.silu(jnp.einsum("sd,df->sf", n, gate, precision=HI)) \
        * jnp.einsum("sd,df->sf", n, up, precision=HI)
    return jnp.einsum("sf,fd->sd", mid, down, precision=HI)


def _mlp(x, lp, lm):
    lp = _up(lp)
    return _gated(_rms(x, lp["ln"], lm["eps"]), lp["w_gate"], lp["w_up"],
                  lp["w_down"])


def _experts_routed(x, lp, lm, degrade, forced):
    """-> (out [S, D], the experts used [S, top_k], how many of the
    ``forced`` ones the scores here would not have chosen)."""
    stacks = tuple(lp[k] for k in ("w_gate", "w_up", "w_down"))  # own dtype
    lp = _up({k: v for k, v in lp.items()
              if k not in ("w_gate", "w_up", "w_down")})
    held = stacks[0].shape[0]
    n = _rms(x, lp["ln"], lm["eps"])
    score = jax.nn.sigmoid(jnp.einsum("sd,de->se", n, lp["router"],
                                       precision=HI))
    _, chosen = lax.top_k(score + lp["select_bias"], lm["top_k"])
    rows = jnp.arange(x.shape[0])[:, None]
    own = jnp.zeros_like(score).at[rows, chosen].set(1.0)
    missed = jnp.zeros((), jnp.int32)
    if forced is not None:               # [S, top_k]; a row of -1s is free
        told = forced[:, :1] >= 0
        chosen = jnp.where(told, forced, chosen)
        missed = jnp.sum(told * (1.0 - jnp.take_along_axis(
            own, chosen, axis=1))).astype(jnp.int32)
    mask = jnp.zeros_like(score).at[rows, chosen].set(1.0)
    weight = lm["route_scale"] * score * mask
    if degrade != "no_route_norm":
        weight = weight / jnp.sum(score * mask, axis=-1, keepdims=True)
    weight = lax.dynamic_slice_in_dim(weight, lm["expert_offset"], held,
                                      axis=1)               # [S, held]

    def expert(acc, e):
        *mats, w = e                     # one expert's weights, upcast here
        mats = [m.astype(F32) for m in mats]
        if degrade == "fp8_experts":
            mats = [_fp8_columns(m) for m in mats]
        return acc + w[:, None] * _gated(n, *mats), None

    mixed, _ = lax.scan(expert, jnp.zeros_like(x), stacks + (weight.T,))
    out = mixed + _gated(n, lp["v_gate"], lp["v_up"], lp["v_down"])
    return out, chosen, missed


@functools.partial(jax.jit, static_argnames=("lm", "degrade"))
def _block(x, lp, lm, degrade, forced=None):
    """-> (x, experts used or None, forced choices missed or None)."""
    lm = dict(lm)
    if "w_dq" in lp:
        return x + _latent_attention(x, lp, lm, degrade), None, None
    if "router" not in lp:
        return x + _mlp(x, lp, lm), None, None
    out, chosen, missed = _experts_routed(x, lp, lm, degrade, forced)
    return x + out, chosen, missed


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, x, eps):
    x = _rms(x, params["final_norm"].astype(F32), eps)
    return jnp.einsum("sd,vd->sv", x, params["head"].astype(F32),
                      precision=HI)


def _lm(lm):
    return tuple(sorted(dict(PUBLISHED, **{
        k: v for k, v in (lm or {}).items() if k in PUBLISHED}).items()))


#: the kind of block (by a key of its own) a degradation touches
_DEGRADES = {"no_rope": "w_dq", "no_absorb_scale": "w_dq",
             "fp8_rows": "w_dq", "no_route_norm": "router",
             "fp8_experts": "router"}


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
        # (a block the degradation leaves alone is the full one's compile)
        x, chosen, miss = _block(
            x, lp, _lm(lm), degrade if _DEGRADES.get(degrade) in lp else None,
            next(forced) if forced and "router" in lp else None)
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
    return _head(params, x if rows is None else x[rows],
                 dict(_lm(lm))["eps"])


def _rows(params, prompt, served, pad_to, pad_rows, lm, degrade, routed):
    """One forward over prompt + served[:-1], padded to ``pad_to`` (causal:
    what follows a position cannot reach it; one shape, one compile) ->
    (logits [T, V] of the positions that produce the served tokens, experts
    used [G blocks, n + T - 1, top_k], choices missed)."""
    n, t = len(prompt), len(served)
    buf = jnp.zeros((pad_to,), jnp.int32)
    buf = buf.at[:n].set(jnp.asarray(prompt, jnp.int32))
    buf = buf.at[n:n + t - 1].set(jnp.asarray(served[:-1], jnp.int32))
    take = jnp.minimum(n - 1 + jnp.arange(pad_rows), pad_to - 1)
    x, used, missed = _forward(params, buf, lm, degrade, routed)
    rows = _head(params, x[take], dict(_lm(lm))["eps"])[:t]
    return rows, jnp.stack(used)[:, :n + t - 1], missed


def _picked(rows, tokens):
    return jnp.take_along_axis(rows, tokens[:, None], axis=1)[:, 0]


def served_token_gaps(params, prompt, served, pad_to, pad_rows, lm=None,
                      routed=None, scored=None, degrade=None):
    """Per generated position, how far the reference's logit of the served
    token sits below the reference's best, fed the served prefix: ``(gaps
    [T], largest |logit|)``.  With ``routed`` (the experts the served
    program chose for the tokens it was fed, module text) two more follow:
    how many of those choices the reference's own scores would not have
    made, and the reference's log-probability of each served token [T].
    ``scored`` [T]: tokens to score in the served ones' place (the prefix
    fed stays ``served``).  ``degrade``: the forward degraded (module
    text, MATCHED)."""
    rows, _, missed = _rows(params, prompt, served, pad_to, pad_rows, lm,
                            degrade, routed)
    tokens = jnp.asarray(served if scored is None else scored, jnp.int32)
    picked = _picked(rows, tokens)
    out = (rows.max(axis=1) - picked, jnp.abs(rows).max())
    if routed is None:
        return out
    return out + (missed, picked - jax.nn.logsumexp(rows, axis=1))


def simulate(params, prompt, served, pad_to, pad_rows, lm=None,
             degrade=None):
    """What a program with ``degrade``'s fault would have returned, fed the
    served prefix: ``(tokens [T], routed_experts [G blocks, n + T - 1,
    top_k], logprobs [T])`` — its best tokens, its own free choices, its
    log-probabilities of those tokens (module text, CONTROL)."""
    rows, used, _ = _rows(params, prompt, served, pad_to, pad_rows, lm,
                          degrade, None)
    tokens = jnp.argmax(rows, axis=1)
    return tokens, used, _picked(rows, tokens) \
        - jax.nn.logsumexp(rows, axis=1)
