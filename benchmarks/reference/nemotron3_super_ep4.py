"""One expert-parallel chip's share of a ``nemotron_h`` hybrid decoder, in
plain ``jax.numpy``, float32, matmul precision "highest": no kernels, no
cache, no batching, no chunked form.  It imports nothing from the program
and is handed parameter VALUES (the ``HybridLM`` pytree: ``embed [V,D]``,
``head [V,D]``, ``final_norm [D]`` and ``layers``, a dict per block in
order), which it upcasts ONE BLOCK AT A TIME — the experts one expert at a
time — so no float32 copy of the weights is held.  A block's kind is read
from its keys; every width is read from the shapes, except what no shape
tells (``lm``: B/C groups, experts per token, the routing scale, the first
held expert's id, eps), which defaults to the published values.

Block l: ``h <- h + Mixer(RMSNorm(h; ln))``, eps 1e-5, no positional term;
``logits = head . RMSNorm(h; final_norm)``.

``*``  q = wq x (H heads), k = wk x, v = wv x (KVH heads), no bias, no
       rotary; query head i reads K/V head i // (H/KVH); causal softmax of
       q.k / sqrt(Dh); wo.
``M``  [z | xBC | dt] = w_in x; xBC_t <- silu(sum_j conv_w[:, j] xBC_{t-3+j}
       + conv_b) (zeros before the sequence); X [Hm,P], B, C [G,N]; head i
       uses group i // (Hm/G); step = softplus(dt + dt_bias); A =
       -exp(a_log); S_t = exp(step A) S_{t-1} + step X_t (x) B_t from S = 0,
       A SEQUENTIAL scan over tokens; Y_t = S_t . C_t + d X_t; y =
       GroupRMSNorm(Y * silu(z); G groups) * norm; w_out.
``E``  s = sigmoid(router x) in float32 over ALL experts; the top_k largest
       of s + select_bias; w_e = scale s_e / sum of the chosen s; u =
       w_down x; EVERY HELD expert is applied to every token, E_e(u) = w2_e
       relu(w1_e u)^2, with weight 0 where it was not chosen (experts that
       live elsewhere add nothing, here as in the program); out = w_up
       sum_e w_e E_e(u) + v2 relu(v1 x)^2.

``routed`` [E blocks, S', top_k] TELLS the reference which experts the
first S' tokens chose in each ``E`` block (the serving engine says so for a
request that asks): the choice is then the served program's — a near-tie
between the 22nd and 23rd score that bf16 activations flip is no longer a
difference between the two — while scores, weights and everything else
stay the reference's own float32.  A row of -1s (and every position past
S') routes freely.  The choice is not taken on trust: the reference counts
the told (token, block, expert) triples that its OWN scores, on the same
input, would not have chosen (``missed``); near-ties give a few in a
thousand, a wrong bias or router many.

``degrade`` computes the forward with one precision or step taken away
(bf16 recurrent state, each token's heaviest held expert dropped, no
selection bias, int8 expert weights).  Two uses.  MATCHED: the served
tokens' log-probabilities under the degraded forward, told the same
choices, beside those under the full one — a sound program's own
log-probabilities lie nearer the full reference's than the degraded one's,
however small the degradation is against the program's own rounding.
CONTROL (:func:`simulate`): the degraded forward, routing freely, stands
for a program with that fault; its tokens, choices and log-probabilities
are then scored exactly as a served request's are, which is what the
configuration's tolerance is set against."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
PUBLISHED = {"ssm_groups": 8, "top_k": 22, "route_scale": 5.0,
             "expert_offset": 0, "eps": 1e-5}
DEGRADATIONS = ("bf16_state", "drop_expert", "no_bias", "int8_experts")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _up(tree):
    return jax.tree_util.tree_map(lambda v: v.astype(F32), tree)


def _attention(x, lp, lm):
    lp = _up(lp)
    s = x.shape[0]
    h = _rms(x, lp["ln"], lm["eps"])
    q = jnp.einsum("sd,dhe->hse", h, lp["wq"], precision=HI)
    k = jnp.einsum("sd,dhe->hse", h, lp["wk"], precision=HI)
    v = jnp.einsum("sd,dhe->hse", h, lp["wv"], precision=HI)
    rep = q.shape[0] // k.shape[0]
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    scores = jnp.einsum("hse,hte->hst", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,hte->hse", probs, v, precision=HI)
    return jnp.einsum("hse,hed->sd", o, lp["wo"], precision=HI)


def _mamba(x, lp, lm, degrade):
    lp = _up(lp)
    s = x.shape[0]
    heads = lp["a_log"].shape[0]
    inner = lp["norm"].shape[0]
    width, taps = lp["conv_w"].shape
    G = lm["ssm_groups"]
    N = (width - inner) // (2 * G)
    P = inner // heads
    h = _rms(x, lp["ln"], lm["eps"])
    zxd = jnp.einsum("sd,de->se", h, lp["w_in"], precision=HI)
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + width],
                  zxd[:, inner + width:])
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(lp["conv_w"][:, j] * padded[j:j + s]
                          for j in range(taps)) + lp["conv_b"])
    X = xbc[:, :inner].reshape(s, heads, P)
    Bm = jnp.repeat(xbc[:, inner:inner + G * N].reshape(s, G, N),
                    heads // G, axis=1)                     # [S, Hm, N]
    Cm = jnp.repeat(xbc[:, inner + G * N:].reshape(s, G, N),
                    heads // G, axis=1)
    step = jax.nn.softplus(dt + lp["dt_bias"])              # [S, Hm]
    decay = jnp.exp(step * -jnp.exp(lp["a_log"]))

    def token(state, t):
        x_t, b_t, c_t, step_t, decay_t = t
        state = decay_t[:, None, None] * state \
            + (step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if degrade == "bf16_state":
            # (a convert pair would be folded away on the chip)
            state = lax.reduce_precision(state, exponent_bits=8,
                                         mantissa_bits=7)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, Y = lax.scan(token, jnp.zeros((heads, P, N), F32),
                    (X, Bm, Cm, step, decay))
    Y = Y + lp["d"][:, None] * X
    y = (Y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, G, -1)
    y = y * lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True)
                      + lm["eps"])
    return jnp.einsum("si,id->sd", y.reshape(s, inner) * lp["norm"],
                      lp["w_out"], precision=HI)


def _int8_round(w):
    """Symmetric int8 per output column, and back."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.round(w / jnp.where(scale == 0, 1.0, scale)) * scale


def _experts(x, lp, lm, degrade):
    return _experts_routed(x, lp, lm, degrade, None)[0]


def _experts_routed(x, lp, lm, degrade, forced):
    """-> (out [S, D], the experts used [S, top_k], how many of the
    ``forced`` ones the scores here would not have chosen)."""
    w1, w2 = lp["w1"], lp["w2"]          # left in their dtype: see below
    lp = _up({k: v for k, v in lp.items() if k not in ("w1", "w2")})
    held = w1.shape[0]
    h = _rms(x, lp["ln"], lm["eps"])
    score = jax.nn.sigmoid(jnp.einsum("sd,de->se", h, lp["router"],
                                       precision=HI))
    pick = score if degrade == "no_bias" else score + lp["select_bias"]
    _, chosen = lax.top_k(pick, lm["top_k"])
    rows = jnp.arange(x.shape[0])[:, None]
    own = jnp.zeros_like(score).at[rows, chosen].set(1.0)
    missed = jnp.zeros((), jnp.int32)
    if forced is not None:               # [S, top_k]; a row of -1s is free
        told = forced[:, :1] >= 0
        chosen = jnp.where(told, forced, chosen)
        missed = jnp.sum(told * (1.0 - jnp.take_along_axis(
            own, chosen, axis=1))).astype(jnp.int32)
    mask = jnp.zeros_like(score).at[rows, chosen].set(1.0)
    weight = lm["route_scale"] * score * mask \
        / jnp.sum(score * mask, axis=-1, keepdims=True)
    weight = lax.dynamic_slice_in_dim(weight, lm["expert_offset"], held,
                                      axis=1)               # [S, held]
    if degrade == "drop_expert":
        top = jnp.argmax(weight, axis=-1)
        weight = weight.at[jnp.arange(x.shape[0]), top].set(0.0)
    u = jnp.einsum("sd,dz->sz", h, lp["w_down"], precision=HI)

    def expert(acc, e):
        a, b, w = e                      # one expert's weights, upcast here
        a, b = a.astype(F32), b.astype(F32)
        if degrade == "int8_experts":
            a, b = _int8_round(a), _int8_round(b)
        mid = jnp.square(jax.nn.relu(jnp.einsum("sz,zf->sf", u, a,
                                                precision=HI)))
        return acc + w[:, None] * jnp.einsum("sf,fz->sz", mid, b,
                                             precision=HI), None

    mixed, _ = lax.scan(expert, jnp.zeros_like(u), (w1, w2, weight.T))
    shared = jnp.square(jax.nn.relu(jnp.einsum("sd,df->sf", h, lp["v1"],
                                               precision=HI)))
    out = jnp.einsum("sz,zd->sd", mixed, lp["w_up"], precision=HI) \
        + jnp.einsum("sf,fd->sd", shared, lp["v2"], precision=HI)
    return out, chosen, missed


@functools.partial(jax.jit, static_argnames=("lm", "degrade"))
def _block(x, lp, lm, degrade, forced=None):
    """-> (x, experts used or None, forced choices missed or None)."""
    lm = dict(lm)
    if "wq" in lp:
        return x + _attention(x, lp, lm), None, None
    if "w_in" in lp:
        return x + _mamba(x, lp, lm, degrade), None, None
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
_DEGRADES = {"bf16_state": "w_in", "drop_expert": "router",
             "no_bias": "router", "int8_experts": "router"}


def _forward(params, tokens, lm, degrade, routed):
    """-> (the last block's output [S, D], the experts used [E blocks, S,
    top_k], told choices missed).  ``routed`` [E blocks, S' <= S, top_k]."""
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
    """One forward over prompt + served[:-1], padded to ``pad_to`` (causal
    and recurrent: what follows a position cannot reach it; one shape, one
    compile) -> (logits [T, V] of the positions that produce the served
    tokens, experts used [E blocks, n + T - 1, top_k], choices missed)."""
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
    served prefix: ``(tokens [T], routed_experts [E blocks, n + T - 1,
    top_k], logprobs [T])`` — its best tokens, its own free choices, its
    log-probabilities of those tokens (module text, CONTROL)."""
    rows, used, _ = _rows(params, prompt, served, pad_to, pad_rows, lm,
                          degrade, None)
    tokens = jnp.argmax(rows, axis=1)
    return tokens, used, _picked(rows, tokens) \
        - jax.nn.logsumexp(rows, axis=1)
