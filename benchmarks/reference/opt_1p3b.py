"""The decoder-only transformer the ``opt_1p3b`` configuration runs, in plain
``jax.numpy``, float32, matmul precision "highest": no kernels, no cache, no
batching.  It imports nothing from the program and is handed parameter
VALUES (the ``TransformerLM`` pytree: ``embed [V,D]``, ``pos_embed [S,D]``,
``final_norm [D]``, ``layers`` of ``ln1 [L,D]``, ``wqkv [L,D,3,H,Dh]``,
``wo [L,H,Dh,D]``, ``ln2 [L,D]``, ``w1 [L,D,F]``, ``w2 [L,F,D]``), which it
upcasts ONE LAYER AT A TIME, so no float32 copy of the weights is held.

The block, as the configuration file's ``assumed`` lists its departures
from OPT: pre-norm RMSNorm (eps 1e-6, scale, no bias); causal softmax
attention over heads of ``D/H`` with scores scaled by ``1/sqrt(D/H)``;
GELU (tanh approximation) MLP; no biases; learned positions added to the
token embedding; the output head is the embedding, after a final RMSNorm."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-6
HI = lax.Precision.HIGHEST


def _rms(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + EPS) * scale


@jax.jit
def _layer(x, layers, i):
    """x [S, D] float32; layer ``i`` of the stacked values, upcast here."""
    lp = jax.tree_util.tree_map(
        lambda v: lax.dynamic_index_in_dim(v, i, keepdims=False).astype(
            jnp.float32), layers)
    s = x.shape[0]
    h = _rms(x, lp["ln1"])
    qkv = jnp.einsum("sd,dche->sche", h, lp["wqkv"], precision=HI)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]            # [S, H, Dh]
    scores = jnp.einsum("she,the->hst", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,the->she", probs, v, precision=HI)
    x = x + jnp.einsum("she,hed->sd", o, lp["wo"], precision=HI)
    h = _rms(x, lp["ln2"])
    u = jax.nn.gelu(jnp.einsum("sd,df->sf", h, lp["w1"], precision=HI),
                    approximate=True)
    return x + jnp.einsum("sf,fd->sd", u, lp["w2"], precision=HI)


@jax.jit
def _embed(params, tokens):
    s = tokens.shape[0]
    return (params["embed"][tokens].astype(jnp.float32)
            + params["pos_embed"][:s].astype(jnp.float32))


@jax.jit
def _head(params, x):
    x = _rms(x, params["final_norm"].astype(jnp.float32))
    return jnp.einsum("sd,vd->sv", x, params["embed"].astype(jnp.float32),
                      precision=HI)


def hidden(params, tokens):
    """tokens [S] int32 -> the last layer's output [S, D] float32."""
    x = _embed(params, tokens)
    layers = params["layers"]
    n = layers["ln1"].shape[0]
    for i in range(n):
        x = _layer(x, layers, jnp.int32(i))
    return x


def logits(params, tokens, rows=None):
    """Logits [S, V] float32 (of ``rows``, a slice, if given)."""
    x = hidden(params, tokens)
    return _head(params, x if rows is None else x[rows])


def served_token_gaps(params, prompt, served, pad_to, pad_rows):
    """One forward over prompt + served tokens, padded to ``pad_to`` (causal:
    what follows a position cannot reach it; one shape, one compile).  Per
    generated position, how far the reference's logit of the served token
    sits below the reference's best: ``(gaps [T], largest |logit|)``."""
    n, t = len(prompt), len(served)
    buf = jnp.zeros((pad_to,), jnp.int32)
    buf = buf.at[:n].set(jnp.asarray(prompt, jnp.int32))
    buf = buf.at[n:n + t - 1].set(jnp.asarray(served[:-1], jnp.int32))
    take = jnp.minimum(n - 1 + jnp.arange(pad_rows), pad_to - 1)
    rows = logits(params, buf, take)[:t]
    picked = jnp.take_along_axis(
        rows, jnp.asarray(served, jnp.int32)[:, None], axis=1)[:, 0]
    return rows.max(axis=1) - picked, jnp.abs(rows).max()
