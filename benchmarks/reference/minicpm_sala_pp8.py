"""One pipeline stage of a MiniCPM-SALA decoder — block-sparse attention
chosen by compressed keys (InfLLM-v2, the MiniCPM4 report arXiv:2506.07900
section 2.1) beside lightning linear-attention layers (Qin et al.,
arXiv:2401.04658; MiniMax-01, arXiv:2501.08313 section 2.2), each followed
by a SwiGLU MLP, under the MiniCPM family's muP scalars — in plain
``jax.numpy``, float32, matmul precision "highest": no kernel, no cache, no
batching, no chunked form, no page.  It imports nothing from the program
and is handed parameter VALUES (the ``HybridLM`` pytree: ``embed [V,D]``,
``head [V,D]``, ``final_norm [D]`` and ``layers``, a dict per block in
order), upcast ONE BLOCK AT A TIME.  A block's kind is read from its keys
(``w_hg``: block-sparse attention; ``onorm``: lightning; else the MLP) and
every width from the shapes, except what no shape tells (``lm``), which
defaults to the published values.

    x = 12 embed[tokens]
    block: x <- x + 1.4 / sqrt(32) Mixer(RMSNorm(x; ln))      eps 1e-6
    logits = head . (RMSNorm(x; final_norm) / (4096 / 256))

block-sparse attention (keys ``wq wk wv qn kn w_hg wo``), n the block's
normalised input, H query heads over KVH K/V heads of Dh, query head h
reading K/V head g = h // (H / KVH), no positional encoding:

    q_h = RMSNorm(n Wq_h; qn)   k_g = RMSNorm(n Wk_g; kn)   v_g = n Wv_g
    c_gj = mean(k_g[s]) over s in [stride j, stride j + kernel)
        (every kernel that lies inside the sequence)
    r_tgj = sum_{h in g} softmax_j(q_th . c_gj / sqrt(Dh)) over the j whose
        kernel ends at or before t
    s_tgb = max r_tgj over the kernels j that overlap block b
        (positions [block b, block (b + 1)))
    kept_tg = every block b with block b <= t, while t < dense_len; else
        the first init_blocks blocks and those that overlap [t - window +
        1, t], then the rest by s_tgb (ties to the lower block) up to
        top_k blocks in all
    o_th = softmax over s <= t in a kept block of (q_th . k_gs / sqrt(Dh))
        . v_gs
    out = sum_h sigmoid(n w_hg)_h o_th Wo_h

lightning attention (keys ``wq wk wv qn kn onorm wg wo``), H heads of Dh,
published layer l of L:

    q, k, v = SiLU(n Wq), SiLU(n Wk), SiLU(n Wv)
    q = RoPE(RMSNorm(q; qn), t)   k = RoPE(RMSNorm(k; kn), t)
        (per head; the pair (x[e], x[e + Dh/2]) turned by t theta^(-2e/Dh))
    lambda_h = exp(-2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5))
    o_th = sum_{s <= t} lambda_h^(t - s) (q_th . k_sh / sqrt(Dh)) v_sh
    out = Wo (RMSNorm(concat_h o_th; onorm) * sigmoid(n Wg))

This QUADRATIC form is the definition and what every check uses, a block
of query rows at a time.  Beside it the RECURRENT form, ``S_t = lambda S_{t-1}
+ k_t^T v_t``, ``o_t = q_t S_t / sqrt(Dh)``, token by token, used only where
the state's precision is the question (``degrade="bf16_state"``).

gated MLP (keys ``w_gate w_up w_down``): ``w_down (silu(w_gate m) * (w_up
m))``.

``degrade`` computes the forward with one part of it taken away:
``dense_selection`` (block-sparse layers attend every s <= t),
``bf16_state`` (the lightning state rounded to bf16 after every token),
``no_decay`` (lambda = 1).  Two uses, as the other references'.  MATCHED:
the served tokens' log-probabilities under the degraded forward beside
those under the full one — a sound program lies nearer the full one.
CONTROL (:func:`simulate`): the degraded forward stands for a program
with that fault, scored as a served request is.  There is no routing in
this model: ``routed`` is accepted and unused."""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
PUBLISHED = {"eps": 1e-6, "rope_theta": 10000.0, "kernel": 32, "stride": 16,
             "block": 64, "top_k": 64, "init_blocks": 1, "window": 2048,
             "dense_len": 8192, "scale_emb": 12.0,
             "residual_scale": 1.4 / math.sqrt(32), "logit_scale": 256 / 4096,
             "first_layer": 0, "layers": 32}
DEGRADATIONS = ("dense_selection", "bf16_state", "no_decay")
#: query rows of the quadratic forms that stand at once
ROW_BLOCK = 128


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def _up(tree):
    return jax.tree_util.tree_map(lambda v: v.astype(F32), tree)


def _kind(lp):
    if "w_hg" in lp:
        return "B"
    return "N" if "onorm" in lp else "F"


def _rows_a_step(s):
    return max(b for b in range(1, min(s, ROW_BLOCK) + 1) if s % b == 0)


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


def decays(heads, layer, layers):
    """lambda_h [heads] of a lightning layer: the published layer ``layer``
    of ``layers``."""
    slope = 2.0 ** (-8.0 * (np.arange(heads) + 1) / heads)
    return np.exp(-slope * (1.0 - layer / (layers - 1) + 1e-5))


# ------------------------------------------------- block-sparse attention
def compressed_keys(k, lm):
    """k [S, KVH, Dh] -> c [NC, KVH, Dh]: the mean key of every kernel
    that lies inside the sequence, one every ``stride`` positions."""
    return lax.reduce_window(k, 0.0, lax.add, (lm["kernel"], 1, 1),
                             (lm["stride"], 1, 1), "VALID") / lm["kernel"]


def _overlaps(nc, blocks, lm):
    """[blocks, most] int32: the kernels j that overlap block b (kernel j
    covers [stride j, stride j + kernel), block b [block b, block (b + 1))),
    -1 past them."""
    j, b = np.arange(nc), np.arange(blocks)[:, None]
    hit = (j * lm["stride"] < (b + 1) * lm["block"]) \
        & (j * lm["stride"] + lm["kernel"] > b * lm["block"])
    rows = [list(np.flatnonzero(h)) for h in hit]
    most = max(len(r) for r in rows)
    return np.array([r + [-1] * (most - len(r)) for r in rows], np.int32)


def selection(q, c, lm, rows, s):
    """The blocks each query of ``rows`` keeps: q [T, H, Dh] (those rows'
    queries), c [NC, KVH, Dh] -> bool [T, KVH, blocks] (the module's
    rule), ``blocks`` the whole blocks of a sequence of ``s``."""
    t = rows[:, None]
    nc, kvh, dh = c.shape
    heads = q.shape[1]
    blocks = -(-s // lm["block"])
    done = jnp.arange(nc)[None, :] * lm["stride"] + lm["kernel"] - 1 <= t
    qg = q.reshape(q.shape[0], kvh, heads // kvh, dh)
    z = jnp.einsum("tgrd,jgd->tgrj", qg, c, precision=HI) / math.sqrt(dh)
    z = jnp.where(done[:, None, None, :], z, -jnp.inf)
    p = jnp.exp(z - jnp.max(jnp.where(done[:, None, None, :], z, 0.0),
                            axis=-1, keepdims=True))
    p = jnp.where(done[:, None, None, :], p, 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    r = jnp.sum(p, axis=2)                                       # [T,KVH,NC]
    js = _overlaps(nc, blocks, lm)                               # [B, most]
    got = r[:, :, np.maximum(js, 0)]                             # [T,KVH,B,m]
    ok = (js >= 0)[None, None] & done[:, None, np.maximum(js, 0)]
    score = jnp.max(jnp.where(ok, got, -jnp.inf), axis=-1)       # [T,KVH,B]
    b = jnp.arange(blocks)
    held = b[None, None, :] * lm["block"] <= t[:, :, None]
    forced = held & ((b[None, None, :] < lm["init_blocks"]) | (
        (b[None, None, :] + 1) * lm["block"] - 1
        >= t[:, :, None] - lm["window"] + 1))
    room = lm["top_k"] - jnp.sum(forced, axis=-1)                # [T, KVH]
    k = min(lm["top_k"], blocks)
    _, idx = lax.top_k(jnp.where(held & ~forced, score, -jnp.inf), k)
    good = jnp.take_along_axis(held & ~forced, idx, axis=-1) \
        & (jnp.arange(k) < room[..., None])

    def mark(i, g):
        return jnp.zeros((blocks,), bool).at[i].set(g)

    scored = jax.vmap(jax.vmap(mark))(idx, good)
    return jnp.where(t[:, :, None] < lm["dense_len"], held, forced | scored)


def _block_sparse(x, lp, lm, degrade):
    lp = _up(lp)
    s = x.shape[0]
    eps = lm["eps"]
    n = _rms(x, lp["ln"], eps)
    q = _rms(jnp.einsum("sd,dhe->she", n, lp["wq"], precision=HI),
             lp["qn"], eps)
    k = _rms(jnp.einsum("sd,dhe->she", n, lp["wk"], precision=HI),
             lp["kn"], eps)
    v = jnp.einsum("sd,dhe->she", n, lp["wv"], precision=HI)
    heads, kvh, dh = q.shape[1], k.shape[1], k.shape[2]
    c = compressed_keys(k, lm)
    rows = _rows_a_step(s)
    key = jnp.arange(s)

    def block(i):
        t = i * rows + jnp.arange(rows)
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows)
        ok = key[None, :] <= t[:, None]                          # [T, S]
        ok = jnp.broadcast_to(ok[:, None], (rows, kvh, s))
        if degrade != "dense_selection":
            kept = selection(qb, c, lm, t, s)                    # [T,KVH,B]
            ok = ok & jnp.repeat(kept, lm["block"], axis=-1)[..., :s]
        qg = qb.reshape(rows, kvh, heads // kvh, dh)
        z = jnp.einsum("tgrd,sgd->tgrs", qg, k, precision=HI) \
            / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(ok[:, :, None], z, -jnp.inf), axis=-1)
        return jnp.einsum("tgrs,sgd->tgrd", p, v,
                          precision=HI).reshape(rows, heads, dh)

    o = lax.map(block, jnp.arange(s // rows)).reshape(s, heads, dh)
    gate = jax.nn.sigmoid(jnp.einsum("sd,dh->sh", n, lp["w_hg"],
                                     precision=HI))
    return jnp.einsum("she,hed->sd", o * gate[..., None], lp["wo"],
                      precision=HI)


def block_selection(params, tokens, block_name, lm=None):
    """The kept blocks of every query of one block-sparse layer, fed the
    same tokens: bool [S, KVH, blocks] (what a test compares with the
    program's selection)."""
    lm = dict(_lm(lm))
    x = _embed(params, tokens, lm)
    for name in sorted(params["layers"]):
        lp = params["layers"][name]
        if name == block_name:
            lp = _up(lp)
            n = _rms(x, lp["ln"], lm["eps"])
            q = _rms(jnp.einsum("sd,dhe->she", n, lp["wq"], precision=HI),
                     lp["qn"], lm["eps"])
            k = _rms(jnp.einsum("sd,dhe->she", n, lp["wk"], precision=HI),
                     lp["kn"], lm["eps"])
            return selection(q, compressed_keys(k, lm), lm,
                             jnp.arange(x.shape[0]), x.shape[0])
        x = _block(x, lp, tuple(sorted(lm.items())), None,
                   _layer_of(params, name, lm))
    raise KeyError(block_name)


# ---------------------------------------------------- lightning attention
def lightning_quadratic(q, k, v, lam):
    """q, k, v [S, H, Dh], lam [H] -> o [S, H, Dh]: the definition, a
    block of query rows at a time."""
    s = q.shape[0]
    rows = _rows_a_step(s)
    key = jnp.arange(s, dtype=F32)
    loglam = jnp.log(lam)

    def block(i):
        t = (i * rows + jnp.arange(rows)).astype(F32)
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows)
        gap = t[:, None] - key[None, :]                           # [T, S]
        w = jnp.where(gap[None] >= 0, jnp.exp(
            loglam[:, None, None] * jnp.maximum(gap, 0.0)[None]), 0.0)
        a = jnp.einsum("the,she->hts", qb, k, precision=HI) * w
        return jnp.einsum("hts,she->the", a, v, precision=HI)

    o = lax.map(block, jnp.arange(s // rows))
    return o.reshape(q.shape) / math.sqrt(q.shape[-1])


def lightning_recurrent(q, k, v, lam, bf16_state=False):
    """The same function of the same inputs, token by token through the
    state ``S [H, Dh, Dh]``."""
    def low(x):
        # (a convert pair would be folded away on the chip)
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) \
            if bf16_state else x

    def token(state, t):
        q_t, k_t, v_t = t
        state = low(lam[:, None, None] * state
                    + k_t[:, :, None] * v_t[:, None, :])
        return state, jnp.einsum("he,hef->hf", q_t, state, precision=HI)

    h, dh = q.shape[1:]
    _, o = lax.scan(token, jnp.zeros((h, dh, dh), F32), (q, k, v))
    return o / math.sqrt(dh)


def _lightning(x, lp, lm, degrade, layer):
    lp = _up(lp)
    eps = lm["eps"]
    n = _rms(x, lp["ln"], eps)
    q, k, v = (jax.nn.silu(jnp.einsum("sd,dhe->she", n, lp[w], precision=HI))
               for w in ("wq", "wk", "wv"))
    q = rope(_rms(q, lp["qn"], eps), lm["rope_theta"])
    k = rope(_rms(k, lp["kn"], eps), lm["rope_theta"])
    heads = q.shape[1]
    lam = jnp.asarray(decays(heads, layer, lm["layers"]), F32)
    if degrade == "no_decay":
        lam = jnp.ones_like(lam)
    o = lightning_recurrent(q, k, v, lam, True) if degrade == "bf16_state" \
        else lightning_quadratic(q, k, v, lam)
    o = _rms(o.reshape(o.shape[0], -1), lp["onorm"], eps) * jax.nn.sigmoid(
        jnp.einsum("sd,de->se", n, lp["wg"], precision=HI))
    return jnp.einsum("she,hed->sd", o.reshape(o.shape[0], heads, -1),
                      lp["wo"], precision=HI)


def _mlp(x, lp, lm):
    """(A block of rows at a time: a whole prompt's float32 [S, 16,384]
    products would not fit beside the weights.)"""
    lp = _up(lp)
    h = _rms(x, lp["ln"], lm["eps"])
    rows = max(b for b in range(1, min(x.shape[0], 4096) + 1)
               if x.shape[0] % b == 0)

    def block(hb):
        mid = jax.nn.silu(jnp.einsum("sd,df->sf", hb, lp["w_gate"],
                                     precision=HI)) \
            * jnp.einsum("sd,df->sf", hb, lp["w_up"], precision=HI)
        return jnp.einsum("sf,fd->sd", mid, lp["w_down"], precision=HI)

    return lax.map(block, h.reshape(-1, rows, h.shape[1])).reshape(x.shape)


# ------------------------------------------------------------ the stack
@functools.partial(jax.jit, static_argnames=("lm", "degrade", "layer"))
def _block(x, lp, lm, degrade, layer):
    lm = dict(lm)
    kind = _kind(lp)
    if kind == "B":
        out = _block_sparse(x, lp, lm, degrade)
    elif kind == "N":
        out = _lightning(x, lp, lm, degrade, layer)
    else:
        out = _mlp(x, lp, lm)
    return x + lm["residual_scale"] * out


def _layer_of(params, name, lm):
    """The published layer of a block: the pattern's first one's, plus the
    mixers before it."""
    names = sorted(params["layers"])
    return lm["first_layer"] + sum(
        _kind(params["layers"][n]) != "F" for n in names[:names.index(name)])


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _head(params, x, eps, scale):
    """(An eighth of the vocabulary at a time where it divides: no float32
    copy of the whole head is held.)"""
    x = _rms(x, params["final_norm"].astype(F32), eps) * scale
    head = params["head"]
    parts = 8 if head.shape[0] % 8 == 0 else 1
    out = lax.map(lambda w: jnp.einsum("sd,vd->sv", x, w.astype(F32),
                                       precision=HI),
                  head.reshape(parts, -1, head.shape[1]))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)


def _lm(lm):
    return tuple(sorted(dict(PUBLISHED, **{
        k: v for k, v in (lm or {}).items() if k in PUBLISHED}).items()))


def _embed(params, tokens, lm):
    return params["embed"][jnp.asarray(tokens)].astype(F32) * lm["scale_emb"]


def hidden(params, tokens, lm=None, degrade=None):
    """tokens [S] int32 -> the last block's output [S, D] float32."""
    if degrade is not None and degrade not in DEGRADATIONS:
        raise ValueError("degrade %r: one of %r" % (degrade, DEGRADATIONS))
    key = _lm(lm)
    lm = dict(key)
    x = _embed(params, tokens, lm)
    touches = {"dense_selection": "B", "bf16_state": "N", "no_decay": "N"}
    for name in sorted(params["layers"]):
        lp = params["layers"][name]
        kind = _kind(lp)
        # (a block the degradation leaves alone is the full one's compile)
        x = _block(x, lp, key,
                   degrade if touches.get(degrade) == kind else None,
                   _layer_of(params, name, lm) if kind == "N" else 0)
    return x


def logits(params, tokens, rows=None, lm=None, degrade=None):
    """Logits [S, V] float32 (of ``rows``, a slice, if given)."""
    x = hidden(params, tokens, lm, degrade)
    key = dict(_lm(lm))
    return _head(params, x if rows is None else x[rows], key["eps"],
                 key["logit_scale"])


def _rows(params, prompt, served, pad_to, pad_rows, lm, degrade):
    """One forward over prompt + served[:-1], padded to ``pad_to`` (causal:
    what follows a position cannot reach it; one shape, one compile) ->
    logits [T, V] of the positions that produce the served tokens."""
    n, t = len(prompt), len(served)
    buf = jnp.zeros((pad_to,), jnp.int32)
    buf = buf.at[:n].set(jnp.asarray(prompt, jnp.int32))
    buf = buf.at[n:n + t - 1].set(jnp.asarray(served[:-1], jnp.int32))
    take = jnp.minimum(n - 1 + jnp.arange(pad_rows), pad_to - 1)
    x = hidden(params, buf, lm, degrade)
    key = dict(_lm(lm))
    return _head(params, x[take], key["eps"], key["logit_scale"])[:t]


def _picked(rows, tokens):
    return jnp.take_along_axis(rows, tokens[:, None], axis=1)[:, 0]


def served_token_gaps(params, prompt, served, pad_to, pad_rows, lm=None,
                      routed=None, scored=None, degrade=None):
    """Per generated position, how far the reference's logit of the served
    token sits below the reference's best, fed the served prefix: ``(gaps
    [T], largest |logit|)``; with ``routed`` (a replay: no expert is chosen
    in this model) two more, 0 choices missed and the reference's
    log-probability of each served (or ``scored``) token [T]."""
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
    logprobs [T])``."""
    rows = _rows(params, prompt, served, pad_to, pad_rows, lm, degrade)
    tokens = jnp.argmax(rows, axis=1)
    return tokens, np.zeros((0, len(prompt) + len(served) - 1, 0), np.int16), \
        _picked(rows, tokens) - jax.nn.logsumexp(rows, axis=1)
