"""One pipeline stage of a ``brumby`` decoder — power-retention layers of
degree 2 (Gelada, Buckman et al., *Scaling Context Requires Rethinking
Attention*, arXiv:2507.04239) on the Qwen3-14B skeleton its ``config.json``
describes — in plain ``jax.numpy``, float32, matmul precision "highest":
no kernels, no cache, no batching, no chunked form.  It imports nothing
from the program and is handed parameter VALUES (the ``HybridLM`` pytree:
``embed [V,D]``, ``head [V,D]``, ``final_norm [D]`` and ``layers``, a dict
per block in order), upcast ONE BLOCK AT A TIME.  A block's kind is read
from its keys and every width from the shapes, except what no shape tells
(``lm``: the rotary base and eps), which defaults to the published values.

Block: ``h <- h + Mixer(RMSNorm(h; ln))``, eps 1e-6; ``logits = head .
RMSNorm(h; final_norm)``.

retention (keys ``wq wk wv wg bg qn kn wo``), n the block's normalised
input, position t from 0:

    q = wq n [H heads of Dh]   k = wk n [KVH heads]   v = wv n [KVH heads]
    q = RoPE(RMSNorm(q; qn), t)   k = RoPE(RMSNorm(k; kn), t)
        (per head over Dh; rotate-half over the whole head, base theta)
    log g = logsigmoid(wg n + bg)                  [KVH], one gate a K/V head
    query head i reads K/V head j = i // (H / KVH):
        A[t,s] = (q_i[t] . k_j[s])^2 exp(sum_{s<r<=t} log g_j[r])   s <= t
        y_i[t] = sum_s A[t,s] v_j[s] / sum_s A[t,s]
    out = wo concat_i y_i

This QUADRATIC form is the definition and what every check uses; it is
computed a block of query rows at a time so the [H, S, S] weights never
stand at once.  Beside it the RECURRENT form (``phi(u)`` = the upper
triangle of ``u u^T``, off-diagonal entries times sqrt 2, so ``phi(q) .
phi(k) = (q . k)^2``):

    S_t = g_t S_{t-1} + phi(k_t) v_t^T    z_t = g_t z_{t-1} + phi(k_t)
    y_i[t] = phi(q_i[t])^T S_t / phi(q_i[t])^T z_t

a sequential scan over tokens, used only where the state's precision is
the question (``degrade="bf16_state"``) and tested equal to the quadratic
form on the cpu.

gated MLP (keys ``w_gate w_up w_down``): ``w_down (silu(w_gate m) * (w_up
m))``, m the block's normalised input.

``degrade`` computes the forward with one precision or step taken away:
``bf16_state`` (S and z rounded to bf16 after every token), ``no_gate`` (g
= 1: nothing decays), ``no_rope`` (no rotary).  Two uses, as the hybrid
reference's.  MATCHED: the served tokens' log-probabilities under the
degraded forward beside those under the full one — a sound program lies
nearer the full one.  CONTROL (:func:`simulate`): the degraded forward
stands for a program with that fault; its tokens and log-probabilities are
scored exactly as a served request's are.  There is no routing in this
model: ``routed`` is accepted and unused, ``missed`` is 0 and the experts
returned have no entries."""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
PUBLISHED = {"rope_theta": 1e6, "eps": 1e-6}
DEGRADATIONS = ("bf16_state", "no_gate", "no_rope")
#: query rows of the quadratic form that stand at once
ROW_BLOCK = 256


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _up(tree):
    return jax.tree_util.tree_map(lambda v: v.astype(F32), tree)


def rope(x, theta):
    """x [S, heads, Dh], position = row: the pair (x[e], x[e + Dh/2]) is
    rotated by ``t theta^(-2e/Dh)``."""
    s, _, dh = x.shape
    half = dh // 2
    freq = 1.0 / theta ** (2.0 * jnp.arange(half, dtype=F32) / dh)
    angle = jnp.arange(s, dtype=F32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1)


def phi(u):
    """u [..., Dh] -> [..., Dh (Dh + 1) / 2]: ``u_a u_b`` for a <= b, a
    outermost, times sqrt 2 off the diagonal.  (The two factors are picked
    by 0/1 matrices: exact, and no gather inside the scan.)"""
    dh = u.shape[-1]
    pairs = [(a, b) for a in range(dh) for b in range(a, dh)]
    first = np.zeros((dh, len(pairs)), np.float32)
    second = np.zeros((dh, len(pairs)), np.float32)
    weight = np.ones((len(pairs),), np.float32)
    for n, (a, b) in enumerate(pairs):
        first[a, n] = second[b, n] = 1.0
        if a != b:
            weight[n] = math.sqrt(2.0)
    return jnp.einsum("...e,en->...n", u, first, precision=HI) \
        * jnp.einsum("...e,en->...n", u, second, precision=HI) * weight


def retention_quadratic(q, k, v, logg):
    """q [S,KVH,R,Dh], k and v [S,KVH,Dh], logg [S,KVH] -> y [S,KVH,R,Dh]:
    the definition, a block of query rows at a time."""
    s = q.shape[0]
    blk = max(b for b in range(1, min(s, ROW_BLOCK) + 1) if s % b == 0)
    cum = jnp.cumsum(logg, axis=0)                           # [S, KVH]
    cols = jnp.arange(s)

    def rows(start):
        qb = lax.dynamic_slice_in_dim(q, start, blk)
        cb = lax.dynamic_slice_in_dim(cum, start, blk)
        qk = jnp.einsum("ihre,jhe->hrij", qb, k, precision=HI)
        seen = cols[None, :] <= (start + jnp.arange(blk))[:, None]
        decay = jnp.exp(jnp.where(seen[None], jnp.transpose(
            cb[:, None, :] - cum[None, :, :], (2, 0, 1)), -jnp.inf))
        a = jnp.square(qk) * decay[:, None]                  # [KVH,R,i,j]
        return jnp.einsum("hrij,jhe->ihre", a, v, precision=HI) \
            / jnp.transpose(jnp.sum(a, axis=-1), (2, 0, 1))[..., None]

    y = lax.map(rows, jnp.arange(0, s, blk))
    return y.reshape((s,) + q.shape[1:])


def retention_recurrent(q, k, v, logg, bf16_state=False):
    """The same function of the same inputs, token by token through the
    state ``S [KVH, N, Dh]`` and normaliser ``z [KVH, N]``."""
    kvh, r, dh = q.shape[1:]
    n = dh * (dh + 1) // 2

    def low(x):
        # (a convert pair would be folded away on the chip)
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) \
            if bf16_state else x

    def token(carry, t):
        state, z = carry
        q_t, k_t, v_t, g_t = t
        pk = phi(k_t)                                         # [KVH, N]
        state = low(g_t[:, None, None] * state
                    + pk[:, :, None] * v_t[:, None, :])
        z = low(g_t[:, None] * z + pk)
        pq = phi(q_t)                                         # [KVH, R, N]
        num = jnp.einsum("hrn,hne->hre", pq, state, precision=HI)
        den = jnp.einsum("hrn,hn->hr", pq, z, precision=HI)
        return (state, z), num / den[..., None]

    _, y = lax.scan(token, (jnp.zeros((kvh, n, dh), F32),
                            jnp.zeros((kvh, n), F32)),
                    (q, k, v, jnp.exp(logg)))
    return y


def _retention(x, lp, lm, degrade):
    lp = _up(lp)
    h = _rms(x, lp["ln"], lm["eps"])
    q = jnp.einsum("sd,dhe->she", h, lp["wq"], precision=HI)
    k = jnp.einsum("sd,dhe->she", h, lp["wk"], precision=HI)
    v = jnp.einsum("sd,dhe->she", h, lp["wv"], precision=HI)
    q, k = _rms(q, lp["qn"], lm["eps"]), _rms(k, lp["kn"], lm["eps"])
    if degrade != "no_rope":
        q, k = rope(q, lm["rope_theta"]), rope(k, lm["rope_theta"])
    logg = jax.nn.log_sigmoid(
        jnp.einsum("sd,dh->sh", h, lp["wg"], precision=HI) + lp["bg"])
    if degrade == "no_gate":
        logg = jnp.zeros_like(logg)
    kvh = k.shape[1]
    q = q.reshape(q.shape[0], kvh, -1, q.shape[-1])
    y = retention_recurrent(q, k, v, logg, True) \
        if degrade == "bf16_state" else retention_quadratic(q, k, v, logg)
    return jnp.einsum("she,hed->sd", y.reshape(y.shape[0], -1, y.shape[-1]),
                      lp["wo"], precision=HI)


def _mlp(x, lp, lm):
    lp = _up(lp)
    h = _rms(x, lp["ln"], lm["eps"])
    mid = jax.nn.silu(jnp.einsum("sd,df->sf", h, lp["w_gate"], precision=HI)) \
        * jnp.einsum("sd,df->sf", h, lp["w_up"], precision=HI)
    return jnp.einsum("sf,fd->sd", mid, lp["w_down"], precision=HI)


@functools.partial(jax.jit, static_argnames=("lm", "degrade"))
def _block(x, lp, lm, degrade):
    lm = dict(lm)
    if "wg" in lp:
        return x + _retention(x, lp, lm, degrade)
    return x + _mlp(x, lp, lm)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(params, x, eps):
    """(An eighth of the vocabulary at a time where it divides: no float32
    copy of the whole head, 3.1 GB at the published size, is held.)"""
    x = _rms(x, params["final_norm"].astype(F32), eps)
    head = params["head"]
    parts = 8 if head.shape[0] % 8 == 0 else 1
    out = lax.map(lambda w: jnp.einsum("sd,vd->sv", x, w.astype(F32),
                                       precision=HI),
                  head.reshape(parts, -1, head.shape[1]))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)


def _lm(lm):
    return tuple(sorted(dict(PUBLISHED, **{
        k: v for k, v in (lm or {}).items() if k in PUBLISHED}).items()))


def hidden(params, tokens, lm=None, degrade=None):
    """tokens [S] int32 -> the last block's output [S, D] float32."""
    if degrade is not None and degrade not in DEGRADATIONS:
        raise ValueError("degrade %r: one of %r" % (degrade, DEGRADATIONS))
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for name in sorted(params["layers"]):
        lp = params["layers"][name]
        # (a block the degradation leaves alone is the full one's compile)
        x = _block(x, lp, _lm(lm), degrade if "wg" in lp else None)
    return x


def logits(params, tokens, rows=None, lm=None, degrade=None):
    """Logits [S, V] float32 (of ``rows``, a slice, if given)."""
    x = hidden(params, tokens, lm, degrade)
    return _head(params, x if rows is None else x[rows],
                 dict(_lm(lm))["eps"])


def _rows(params, prompt, served, pad_to, pad_rows, lm, degrade):
    """One forward over prompt + served[:-1], padded to ``pad_to`` (causal
    and recurrent: what follows a position cannot reach it; one shape, one
    compile) -> logits [T, V] of the positions that produce the served
    tokens."""
    n, t = len(prompt), len(served)
    buf = jnp.zeros((pad_to,), jnp.int32)
    buf = buf.at[:n].set(jnp.asarray(prompt, jnp.int32))
    buf = buf.at[n:n + t - 1].set(jnp.asarray(served[:-1], jnp.int32))
    take = jnp.minimum(n - 1 + jnp.arange(pad_rows), pad_to - 1)
    x = hidden(params, buf, lm, degrade)
    return _head(params, x[take], dict(_lm(lm))["eps"])[:t]


def _picked(rows, tokens):
    return jnp.take_along_axis(rows, tokens[:, None], axis=1)[:, 0]


def served_token_gaps(params, prompt, served, pad_to, pad_rows, lm=None,
                      routed=None, scored=None, degrade=None):
    """Per generated position, how far the reference's logit of the served
    token sits below the reference's best, fed the served prefix: ``(gaps
    [T], largest |logit|)``.  With ``routed`` (what a request that asked
    for its replay brings: no expert is chosen in this model, so it has no
    entries) two more follow, as the hybrid reference's: 0 choices missed,
    and the reference's log-probability of each served token [T].
    ``scored`` [T]: tokens to score in the served ones' place (the prefix
    fed stays ``served``).  ``degrade``: the forward degraded (module
    text, MATCHED)."""
    rows = _rows(params, prompt, served, pad_to, pad_rows, lm, degrade)
    tokens = jnp.asarray(served if scored is None else scored, jnp.int32)
    picked = _picked(rows, tokens)
    out = (rows.max(axis=1) - picked, jnp.abs(rows).max())
    if routed is None:
        return out
    return out + (0, picked - jax.nn.logsumexp(rows, axis=1))


def simulate(params, prompt, served, pad_to, pad_rows, lm=None,
             degrade=None):
    """What a program with ``degrade``'s fault would have returned, fed the
    served prefix: ``(tokens [T], routed_experts [0, n + T - 1, 0],
    logprobs [T])`` — its best tokens and its log-probabilities of them
    (module text, CONTROL)."""
    rows = _rows(params, prompt, served, pad_to, pad_rows, lm, degrade)
    tokens = jnp.argmax(rows, axis=1)
    return tokens, np.zeros((0, len(prompt) + len(served) - 1, 0), np.int16), \
        _picked(rows, tokens) - jax.nn.logsumexp(rows, axis=1)
