"""``models.HybridLM``'s sparse (``S``: an indexer that keeps the top-k
earlier tokens of each query) and windowed (``W``: a ring of the latest
positions a decode slot) latent-attention blocks, with their head-wise
gate, against the plain reference (``benchmarks/reference/
dots3_note_ep8.py``: float32, the expanded form, the selection and the
window as masks over a full causal forward), at tiny sizes where every
context exceeds both the tokens a query keeps (8) and the window (9),
seeded, on the cpu backend (float32, full-precision products:
``conftest.py``).

What is held here: a padded prefill, then decode steps through pages (index
keys below the latent rows) and rings, is the full forward and the
reference's, in float32 and within a stated bound in bf16; a decode step
keeps the reference's top-k row ids; a ring wraps (positions past its
columns, past 640 at the published window); the gate; a slot
used again carries nothing of a longer request; the Pallas sparse kernel is
its XLA twin; ``kv_spec`` describes the pages and rings and the benchmark's
counts are what the model holds; a stack of such blocks goes through
``export_generation`` and the server, its counters and spans saying what
the steps read.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from benchmarks.harness import manifest
from mxnet_tpu import kernels, telemetry
from mxnet_tpu.models import HybridLM, HybridLMConfig
from mxnet_tpu.ops import pallas_kernels as pk

REF = manifest.load_module("reference", "dots3_note_ep8")
OPS = manifest.load_module("ops_bytes", "dots3_note_ep8")
PAGE = 4
SIZES = dict(vocab_size=96, pattern="SFSGWG", d_model=32, num_heads=4,
             q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
             index_heads=4, index_dim=8, index_topk=8, swa_heads=2,
             swa_q_rank=24, swa_kv_rank=24, swa_nope_dim=12, swa_rope_dim=4,
             swa_v_dim=8, swa_rope_theta=5e4, window=9, num_experts=8,
             experts_held=4,
             top_k=2, expert_ff=48, shared_ff=48, route_scale=1.0,
             mlp_ff=48, max_len=64, rope_theta=1e4, eps=1e-5,
             dtype=jnp.float32)
REF_LM = {"top_k": 2, "route_scale": 1.0, "rope_theta": 1e4, "eps": 1e-5,
          "swa_rope_theta": 5e4, "window": 9, "index_topk": 8}


def _tiny(**over):
    model = HybridLM(HybridLMConfig(**dict(SIZES, **over)))
    return model, model.init(jax.random.PRNGKey(0))


def _close(got, want, tol=1e-6):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), \
        np.abs(got - want).max()


def _through(model, params, toks, lengths, steps, bucket=16, slots=None,
             kv=None):
    """Padded prefill into ``slots`` (default: row b), then ``steps``
    teacher-forced decode steps over every slot: the logits of every
    position produced, [B, 1 + steps, V], and the cache."""
    B = toks.shape[0]
    W = -(-model.cfg.max_len // PAGE)
    table = jnp.asarray(np.arange(1, 1 + B * W).reshape(B, W), jnp.int32)
    if kv is None:
        kv = model.init_kv_pages(2 + B * W, PAGE, slots=B)
    kv, _, logits = model.prefill(
        params, kv, toks[:, :bucket], lengths, table[:, :-(-bucket // PAGE)],
        PAGE, return_logits=True, slots=slots)
    out, pos = [logits], lengths
    step = jax.jit(lambda p, c, t, n: model.decode_step(
        p, c, t, n, table, PAGE, return_logits=True))
    for _ in range(steps):
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        kv, _, logits = step(params, kv, tok, pos)
        out.append(logits)
        pos = pos + 1
    return jnp.stack(out, axis=1), kv


# ------------------------------------------------------ the two blocks
@pytest.mark.parametrize("lengths", [(13, 10), (16, 11), (9, 15)])
def test_prefill_then_decode_through_pages_and_rings_is_the_full_forward(
        lengths):
    """A prompt padded into a 16-token bucket selects and attends in the
    absorbed form and leaves its rows (and index keys) in the pages and its
    latest rows in the rings; 20 decode steps then select over the pages
    and read the rings: every position's
    logits are the cache-free forward's and the plain reference's, float32
    to 1e-6."""
    model, params = _tiny()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, 40)),
                       jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got, _ = _through(model, params, toks, lengths, 20)
    full = model.apply(params, toks)
    for b in range(2):
        n = int(lengths[b])
        _close(got[b], full[b, n - 1:n + 20])
        _close(got[b], REF.logits(params, toks[b], lm=REF_LM)[n - 1:n + 20])


def test_bf16_stays_within_eight_ulps_of_the_logits_scale():
    """The same walk with bf16 weights, activations, pages and rings
    against the float32 reference over the same (bf16) values: within 8
    bf16 ulps (2**-8 each) of the largest logit (a bf16 index score may
    trade a row at the selection's edge)."""
    model, params = _tiny(dtype=jnp.bfloat16)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 96, (2, 40)),
                       jnp.int32)
    lengths = jnp.asarray((14, 10), jnp.int32)
    got, _ = _through(model, params, toks, lengths, 12)
    for b in range(2):
        n = int(lengths[b])
        want = REF.logits(params, toks[b], lm=REF_LM)[n - 1:n + 12]
        assert np.abs(np.asarray(got[b], np.float32) - np.asarray(want)
                      ).max() <= 8 * 2.0 ** -8 * np.abs(np.asarray(want)).max()


def test_a_decode_step_keeps_the_references_top_k():
    """The first block is ``S``, so its input is the embedding: after a
    prefill of 14 tokens, the decode step's indexer over the pages keeps
    exactly the row ids the reference's float32 top-k keeps for position
    14 (8 of 15), and the reference's selection is 8 a row past the first
    8 positions, every earlier one before."""
    model, params = _tiny()
    lp = params["layers"]["00"]
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 96, (1, 24)),
                       jnp.int32)
    n = 14
    W = 6
    table = jnp.arange(1, 1 + W, dtype=jnp.int32)[None]
    kv = model.init_kv_pages(2 + W, PAGE, slots=1)
    kv, _ = model.prefill(params, kv, toks[:, :16], jnp.asarray([n]),
                          table[:, :4], PAGE)
    x = params["embed"][toks[:, n]]
    pos = jnp.asarray([n], jnp.int32)
    _, row, nrm, cq = model._latent_absorb(x, lp, pos)
    qi, ki, wi = (t[:, 0] for t in model._index_parts(
        nrm[:, None], cq[:, None], lp, pos[:, None]))
    pool = model._write_latent_column(
        kv["kv"], 0, table[:, n // PAGE][:, None],
        jnp.asarray([[n % PAGE]]), jnp.concatenate([row, ki], -1), PAGE)
    score = kernels.index_scores(qi, wi, pool, table, jnp.asarray([n + 1]),
                                 20, layer=0)[0].reshape(-1)[:n + 1]
    got = np.sort(np.asarray(jax.lax.top_k(score, 8)[1]))
    xs = params["embed"][toks[0]]
    lpf = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    nn = REF._JOY._rms(xs, lpf["ln"], 1e-5)
    cqf = REF._JOY._rms(nn @ lpf["w_dq"], lpf["q_norm"], 1e-5)
    seen = np.asarray(REF._selection(nn, cqf, lpf, dict(REF._lm(REF_LM)),
                                     jnp.arange(24, dtype=jnp.float32)))
    assert (np.flatnonzero(seen[n]) == got).all()
    assert (seen.sum(1) == np.minimum(np.arange(24) + 1, 8)).all()


@pytest.mark.parametrize("length,route", [
    pytest.param(40, "xla", id="40"), pytest.param(300, "xla", id="300"),
    pytest.param(40, "masked", id="40-masked"),
    pytest.param(300, "masked", id="300-masked"),
    pytest.param(640, "masked", id="640-masked")])
def test_apply_is_the_reference_forward(length, route):
    """The cache-free forward at 40 positions (one chunk of queries) and at
    300 (five chunks of 64 in three causal segments, each reading the keys
    before its end) is the reference's, float32 to 1e-6 — on the XLA twin
    (the default knob on this interpreted backend: each query's selected
    rows gathered) and on the masked K/V-tiled kernel (the tier on
    explicitly: one pass over the expanded form, at 640 positions in 320 x
    128 blocks)."""
    model, params = _tiny(max_len=1024)
    toks = jnp.asarray(np.random.default_rng(10).integers(0, 96, (length,)),
                       jnp.int32)
    if route == "masked":
        mx.config.set("kernels.enabled", True)
    try:
        with kernels.record_sparse_prefill_routes() as routes:
            got = model.apply(params, toks[None])[0]
    finally:
        mx.config.unset("kernels.enabled")
    assert [r["impl"] for r in routes] == [route] * 2
    _close(got, REF.logits(params, toks, lm=REF_LM))


@pytest.mark.parametrize("window,prompt,steps", [(9, 150, 6), (513, 700, 4)],
                         ids=["toy_window", "published_window"])
def test_the_ring_wraps(window, prompt, steps):
    """A ``W`` block's ring over positions past its columns: a window of 9
    in 128 columns after a 150-token prompt, the published 513 in 640
    after a 700-token prompt; the window's positions are read from where
    they lie, whatever the ring held before, as the reference's banded
    mask reads them."""
    model, params = _tiny(pattern="WF", window=window, max_len=768)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 96, (1, 768)),
                       jnp.int32)
    bucket = -(-prompt // PAGE) * PAGE
    got, kv = _through(model, params, toks, jnp.asarray([prompt]), steps,
                       bucket=bucket)
    assert kv["ring00"].shape == (1, 24 + 4, -(-window // 128) * 128)
    want = REF.logits(params, toks[0, :prompt + steps],
                      lm=dict(REF_LM, window=window))
    _close(got[0], want[prompt - 1:prompt + steps])


def test_the_gate_is_one_sigmoid_a_head():
    """``_gate`` is ``o_h * sigmoid(n w_hg)_h``, and it moves both blocks:
    the same block with the gate's weights at zero (every head halved) is
    another block."""
    model, params = _tiny(pattern="SW")
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 20, 32)),
                    jnp.float32)
    for name, run in (("00", model._dsa_sequence),
                      ("01", model._swa_sequence)):
        lp = params["layers"][name]
        half = run(x, dict(lp, w_hg=0 * lp["w_hg"]))[0]
        _close(run(x, dict(lp, w_hg=0 * lp["w_hg"],
                           wo=2 * lp["wo"]))[0], 2 * half)
        assert np.abs(np.asarray(run(x, lp)[0] - half)).max() > 1e-4
    o = jnp.asarray(np.random.default_rng(6).normal(size=(3, 4, 8)),
                    jnp.float32)
    n = jnp.asarray(np.random.default_rng(7).normal(size=(3, 32)),
                    jnp.float32)
    lp = params["layers"]["00"]
    _close(model._gate(o, n, lp),
           o * jax.nn.sigmoid(n @ lp["w_hg"])[..., None])


def test_a_slot_used_again_carries_nothing_of_a_longer_request():
    """Slot 0 serves a 16-token prompt and 20 steps (its ring and pages
    full), then a 10-token prompt is prefilled into the SAME slot and
    pages: its logits are those the same prompt gives in a fresh cache."""
    model, params = _tiny()
    rng = np.random.default_rng(5)
    long = jnp.asarray(rng.integers(0, 96, (1, 40)), jnp.int32)
    short = jnp.asarray(rng.integers(0, 96, (1, 40)), jnp.int32)
    _, kv = _through(model, params, long, jnp.asarray([16]), 20)
    assert np.asarray(kv["ring04"]).any()
    again, _ = _through(model, params, short, jnp.asarray([10]), 14, kv=kv)
    fresh, _ = _through(model, params, short, jnp.asarray([10]), 14)
    _close(again, fresh, 0.0)


# ------------------------------------------------------- the sparse kernel
@pytest.mark.parametrize("layer", [None, 1])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_sparse_kernel_is_its_twin(dtype, tol, layer):
    """Four heads over pages of 128 tokens on the lanes whose last 16 rows
    (index keys) the scores do not read, a random third of the tokens
    chosen: a row that ends inside its third page, a row of length 1, an
    empty row, a row whose FIRST tile chooses nothing; one layer's pool,
    or every layer's handed over whole."""
    rng = np.random.default_rng(8)
    B, H, kw, width, dv, psz, P = 4, 4, 48, 64, 32, 128, 7
    q = jnp.asarray(rng.normal(size=(B, H, kw)), dtype)
    pool = jnp.asarray(rng.normal(size=(2, P, width, psz)), dtype)
    table = jnp.asarray([[1, 3, 5], [2, 9, 9], [6, 0, 4], [0, 5, 2]],
                        jnp.int32)
    lengths = jnp.asarray([300, 1, 0, 384], jnp.int32)
    chosen = (rng.uniform(size=(B, 3, psz)) < 0.33).astype(np.int32)
    chosen[1, 0, 0] = 1
    chosen[3, 0] = 0
    chosen = jnp.asarray(chosen)
    pages = pool if layer is not None else pool[1]
    args = (q, pages, table, lengths, chosen, 0.2, dv)
    assert kernels.sparse_unsupported_reason(
        q, pages, table, lengths, chosen, dv, layer=layer) is None
    got = pk.pallas_sparse_latent_attention(*args, layer=layer)
    want = kernels._sparse_latent_attention_xla(*args, layer=layer)
    assert got.shape == (B, H, dv) and got.dtype == dtype
    _close(got, want, tol)
    assert not np.asarray(got[2], np.float32).any()
    # what is not chosen is not read: scrambling it moves nothing
    rows = np.array(pool, np.float32)
    rows[..., kw:, :] = 7.0
    got2 = pk.pallas_sparse_latent_attention(
        q, jnp.asarray(rows, dtype) if layer is not None
        else jnp.asarray(rows[1], dtype), table, lengths, chosen, 0.2, dv,
        layer=layer)
    _close(got2, got, 0.0)


@pytest.mark.parametrize("change,says", [
    (dict(psz=64), "multiple of 128"),
    (dict(kw=80), "wide"),
    (dict(chosen_w=3), "chosen"),
    (dict(kw=44), "packing")])
def test_the_sparse_route_says_why_it_refuses(change, says):
    """A shape the kernel cannot take routes to the twin with its reason,
    never an error."""
    psz = change.get("psz", 128)
    q = jnp.zeros((2, 4, change.get("kw", 48)), jnp.float32)
    pool = jnp.zeros((3, 64, psz), jnp.float32)
    table = jnp.zeros((2, 2), jnp.int32)
    chosen = jnp.zeros((2, change.get("chosen_w", 2), psz), jnp.int32)
    reason = kernels.sparse_unsupported_reason(q, pool, table,
                                               jnp.ones((2,), jnp.int32),
                                               chosen, 32)
    assert reason is not None and says in reason, reason


def _selections(kind, rng, B, S, K):
    """int8 [B, S, S] masks, causal: ``random`` K of each query's earlier
    positions (all of them while there are fewer), ``diagonal`` only the
    query's own position, ``causal`` every position up to it."""
    t = np.arange(S)
    if kind == "diagonal":
        mask = np.broadcast_to(np.eye(S, dtype=np.int8), (B, S, S))
    elif kind == "causal":
        mask = np.broadcast_to((t[None, :] <= t[:, None]).astype(np.int8),
                               (B, S, S))
    else:
        score = np.where(t[None, None, :] <= t[None, :, None],
                         rng.uniform(size=(B, S, S)), -1.0)
        kth = -np.sort(-score, axis=-1)[..., K - 1:K]
        mask = ((score >= kth) & (score >= 0)).astype(np.int8)
    return jnp.asarray(mask)


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("kind", ["random", "diagonal", "causal"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_the_masked_tiled_kernel_is_a_dense_masked_softmax(dtype, tol, kind,
                                                           block):
    """The K/V-tiled flash kernel handed a selection (interpreted) against a
    dense softmax over the same masked causal scores, at query/key width
    192 and value width 128 (an ``S`` block's expanded heads), 384 positions
    in 3 x 3 blocks of 128, or 2 x 3 of 192 x 128 where a block of 256
    divides neither axis: selections of 24 earlier positions a query, of
    the diagonal alone, of every earlier position."""
    rng = np.random.default_rng(11)
    B, H, S = 2, 2, 384
    q, k = (jnp.asarray(rng.normal(size=(B, H, S, 192)), dtype)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, H, S, 128)), dtype)
    mask = _selections(kind, rng, B, S, 24)
    assert kernels.sparse_prefill_unsupported_reason(q, k, v, mask) is None
    got = pk.flash_attention_tiled(q, k, v, causal=True, scale=0.1,
                                   block=block, mask=mask)
    f32 = [np.asarray(a, np.float32) for a in (q, k, v)]
    s = np.einsum("bhqd,bhkd->bhqk", f32[0], f32[1]) * 0.1
    s = np.where(np.asarray(mask)[:, None] != 0, s, -np.inf)
    e = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bhkd->bhqd", e / e.sum(-1, keepdims=True), f32[2])
    assert got.shape == (B, H, S, 128) and got.dtype == dtype
    _close(got, want, tol)


@pytest.mark.parametrize("scores", ["tied", "random", "tied_at_the_edge"])
def test_the_selection_mask_is_the_set_top_k_kept(scores):
    """``_selection_mask`` (no scatter: above the k-th value, or equal to it
    no later than the last tie the top-k kept) against the positions
    ``lax.top_k`` returns, scattered: equal scores everywhere (the earliest
    positions must win), random scores, and scores tied across the k-th
    place; -inf past each query's position, zeros past the span."""
    from mxnet_tpu.models.hybrid import _selection_mask
    rng = np.random.default_rng(12)
    B, Q, span, K, length = 2, 16, 40, 8, 48
    t = jnp.asarray(np.arange(24, 24 + Q), jnp.int32)
    if scores == "tied":
        score = np.zeros((B, Q, span), np.float32)
    elif scores == "random":
        score = rng.normal(size=(B, Q, span)).astype(np.float32)
    else:
        score = rng.integers(0, 4, (B, Q, span)).astype(np.float32)
    score = jnp.where(jnp.arange(span)[None, None, :] <= t[None, :, None],
                      jnp.asarray(score), -jnp.inf)
    top, idx = jax.lax.top_k(score, K)
    got = np.asarray(_selection_mask(score, top, idx, t, length))
    want = np.zeros((B, Q, length), np.int8)
    np.put_along_axis(want, np.asarray(idx), 1, axis=-1)
    assert got.dtype == np.int8 and (got == want).all()
    assert (got.sum(-1) == K).all()
    if scores == "tied":
        assert (np.flatnonzero(got[0, 0]) == np.arange(K)).all()


@pytest.mark.parametrize("change,says", [
    (dict(S=600), "multiple of 128"),
    (dict(mask_dtype=jnp.int32), "int8"),
    (dict(mask_S=64), "[B,S,S]"),
    (dict(v_dtype=jnp.float32), "bfloat16"),
    (dict(D=576), "head dim"),
    (dict(rank=3), "rank")])
def test_the_sparse_prefill_route_says_why_it_refuses(change, says):
    """A shape the masked kernel cannot take routes an ``S`` block's prefill
    to the XLA twin with its reason, never an error; the shapes the cell
    runs (12,288 and 16,384 positions, 128 heads of 192 / 128) qualify."""
    def shapes(S=384, D=192, v_dtype=jnp.bfloat16, mask_dtype=jnp.int8,
               mask_S=None, rank=4):
        sds = jax.ShapeDtypeStruct
        q = sds((1, 128, S, D)[4 - rank:], jnp.bfloat16)
        return (q, q, sds((1, 128, S, 128), v_dtype),
                sds((1, S, mask_S or S), mask_dtype))
    for S in (12288, 16384):
        assert kernels.sparse_prefill_unsupported_reason(*shapes(S)) is None
    reason = kernels.sparse_prefill_unsupported_reason(*shapes(**change))
    assert reason is not None and says in reason, reason


def test_the_sparse_site_counts_and_records_its_route():
    mx.config.set("kernels.enabled", True)
    try:
        q = jnp.ones((2, 4, 48), jnp.float32)
        table = jnp.zeros((2, 2), jnp.int32)
        lengths = jnp.asarray([5, 130], jnp.int32)
        before = {n: telemetry.counter("kernels." + n).value
                  for n in ("sparse_latent", "sparse_latent_fallback")}
        with kernels.record_paged_routes() as routes:
            kernels.sparse_latent_attention(
                q, jnp.ones((3, 64, 128), jnp.float32), table, lengths,
                jnp.ones((2, 2, 128), jnp.int32), 0.1, 32)
            kernels.sparse_latent_attention(
                q, jnp.ones((3, 64, 4), jnp.float32), table, lengths,
                jnp.ones((2, 2, 4), jnp.int32), 0.1, 32)
        assert [r["impl"] for r in routes] == ["sparse", "xla"]
        sds = jax.ShapeDtypeStruct
        before.update({n: telemetry.counter("kernels." + n).value
                       for n in ("sparse_prefill", "sparse_prefill_fallback")})
        with kernels.record_sparse_prefill_routes() as routes:
            for S in (512, 600):
                q = sds((1, 4, S, 192), jnp.bfloat16)
                kernels.sparse_prefill_route(
                    q, q, sds((1, 4, S, 128), jnp.bfloat16),
                    sds((1, S, S), jnp.int8))
        assert [r["impl"] for r in routes] == ["masked", "xla"]
        assert routes[0]["reason"] is None and "128" in routes[1]["reason"]
        for name in before:
            assert telemetry.counter("kernels." + name).value \
                == before[name] + 1
    finally:
        mx.config.unset("kernels.enabled")


# ------------------------------------------------------------ the cache
def test_kv_spec_describes_index_rows_and_rings():
    model, _ = _tiny()
    spec = model.kv_spec()
    assert spec["pools"] == ["kv"] and spec["page_layout"] == "lanes"
    assert spec["num_layers"] == 2 and spec["row_width"] == 16 + 4 + 8
    assert spec["value_width"] == 16 and spec["index_rows"] == [20, 28]
    assert spec["sparse"] == {"layers": 2, "top_k": 8}
    assert spec["rings"] == {"layers": 1, "window": 9, "columns": 128}
    assert spec["state"] == [{"name": "ring04", "dtype": "float32",
                              "shape": [28, 128]}]
    kv = model.init_kv_pages(9, PAGE, slots=3)
    assert {k: v.shape for k, v in kv.items()} == {
        "kv": (2, 9, 28, PAGE), "ring04": (3, 28, 128)}
    assert HybridLMConfig(**dict(SIZES, window=513)).ring == 640


@pytest.mark.parametrize("pattern", ["SL", "S*F"])
def test_sparse_pages_are_their_own_kind(pattern):
    with pytest.raises(ValueError, match="one kind of page"):
        HybridLMConfig(**dict(SIZES, pattern=pattern))


def test_the_caches_bytes_are_the_benchmarks_count():
    """``ops_bytes/dots3_note_ep8.py`` against the published sizes: the
    parameters ``HybridLM.init`` makes (4.09 G), a selected row 1,152 B,
    an index key 256 B, a ring column 2,176 B, and the cache the
    configuration's knobs make (3.59 GB)."""
    cfg = manifest.load_json("configs", "dots3_note_ep8.json")
    lm = cfg["sizes"]["lm"]
    model = HybridLM(HybridLMConfig(dtype=jnp.bfloat16, **lm))
    spec = model.kv_spec()
    assert spec["row_width"] == 704 and spec["index_rows"] == [576, 704]
    assert OPS.row_bytes(lm) == 1152 and OPS.index_key_bytes(lm) == 256
    assert OPS.ring_row_bytes(lm) == 2176
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert OPS.parameter_count(lm) == held == 4087154176
    pages = cfg["knobs"]["serving.kv_pages"]
    kv = jax.eval_shape(lambda: model.init_kv_pages(pages, 128, slots=64))
    made = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in kv.values())
    assert OPS.cache_bytes(lm, pages, 128, 64) == made == 3589275648


# ------------------------------------------------- through the artifact
@pytest.fixture
def served(tmp_path):
    """An ``SFSGWG`` stack exported as the benchmark's driver does and
    registered with a started server over TWO slots and a pool of 30
    pages of 4 tokens."""
    mx.config.set("kernels.enabled", True)
    mx.config.set("serving.kv_pages", 30)
    mx.config.set("serving.decode_slots", 2)
    model, params = _tiny()
    prefix = str(tmp_path / "lm")
    mx.deploy.export_generation(
        model, params, prefix, sampling=True, decode_batch=2,
        prompt_buckets=[16, 32], max_context=48, page_size=PAGE)
    srv = mx.serving.Server()
    engine = srv.register("lm", prefix, generate=True)
    srv.start()
    try:
        yield model, params, prefix, srv, engine
    finally:
        srv.stop()
        for knob in ("kernels.enabled", "serving.kv_pages",
                     "serving.decode_slots"):
            mx.config.unset(knob)


def test_a_sparse_stack_serves_the_oracles_tokens(served):
    """``export_generation`` -> ``Server.register(generate=True)``: six
    requests of 11-30 tokens over two slots (each slot used again after a
    longer or a shorter request) get the cache-free greedy oracle's
    tokens; the artifact describes the pages and the ring, its decode
    route is recorded (the twin: pages of 4 tokens) and counted under the
    sparse site's counters, and every page comes back."""
    model, params, prefix, srv, engine = served
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["kv"]["index_rows"] == [20, 28]
    assert meta["kv"]["rings"]["columns"] == 128
    width = str(meta["decode_widths"][-1])
    assert meta["paged"][width]["impl"] == "xla"
    # both S blocks of both prefill programs attend through the kernel
    assert meta["sparse_prefill"] == {
        "prefill-s%d" % b: {"impl": "masked", "reason": None, "sites": 2}
        for b in (16, 32)}
    assert [tuple(a.shape) for a in engine._kv] == [(2, 30, 28, PAGE),
                                                    (2, 28, 128)]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (30, 11, 17, 25, 12, 21)]
    oracle = [model.greedy_decode(params, p, 9) for p in prompts]
    fell_back = telemetry.counter("kernels.sparse_latent_fallback").value
    latent = telemetry.counter("kernels.latent_fallback").value
    prefills = {n: telemetry.counter("kernels." + n).value
                for n in ("sparse_prefill", "sparse_prefill_fallback")}
    futures = [srv.submit_generate("lm", p, 9) for p in prompts]
    for want, f in zip(oracle, futures):
        assert (f.result(timeout=300) == want).all()
    assert telemetry.counter("kernels.sparse_latent_fallback").value \
        > fell_back
    # once an S block a prefill dispatch, from the export's verdict
    assert telemetry.counter("kernels.sparse_prefill").value \
        == prefills["sparse_prefill"] + 2 * len(prompts)
    assert telemetry.counter("kernels.sparse_prefill_fallback").value \
        == prefills["sparse_prefill_fallback"]
    assert telemetry.counter("kernels.latent_fallback").value == latent
    assert engine.stats()["kv_pages_free"] == 30


def test_decode_spans_say_what_the_selection_and_the_rings_read(served):
    """``engine.decode`` carries ``index_tokens`` (rows the two S blocks'
    indexer scored), ``selected_tokens`` (rows they attended: 8 a row a
    block past the first 8 positions) and ``ring_tokens`` (window
    positions the W block read: 9 a row past the first 9), as the decode
    program counted them over the one slot of four that holds a request."""
    from mxnet_tpu import generation
    _, _, _, srv, _ = served
    seen = []
    begin = generation._begin

    class Recorded:
        """The engine's span, and every argument it is given."""

        def __init__(self, sp, args):
            self.sp, self.args = sp, dict(args)

        def set(self, **args):
            self.args.update(args)
            self.sp.set(**args)

        def __exit__(self, *exc):
            return self.sp.__exit__(*exc)

    prefills = []

    def spying(name, **args):
        sp = begin(name, **args)
        if name == "engine.prefill":
            prefills.append(args)
        if name != "engine.decode":
            return sp
        seen.append(Recorded(sp, args))
        return seen[-1]

    generation._begin = spying
    try:
        prompt = np.arange(20, dtype=np.int32) % 96
        srv.submit_generate("lm", prompt, 6).result(timeout=300)
    finally:
        generation._begin = begin
    # the prefill says how many S blocks it has and how many took the kernel
    assert [(a["sparse_layers"], a["sparse_kernel_layers"])
            for a in prefills] == [(2, 2)]
    assert seen
    for sp in seen:
        a = sp.args
        assert a["rows"] == 1
        assert a["index_tokens"] == 2 * (a["held_tokens"] + 1)
        assert a["selected_tokens"] == 2 * 8 and a["ring_tokens"] == 9


_SPARSE_SCOPES = ("mx.mla_proj", "mx.dsa_indexer", "mx.dsa_select",
                  "mx.sparse_attention", "mx.window_attention",
                  "mx.attn_gate", "mx.kv_write", "mx.moe_experts")


@pytest.mark.parametrize("program,scopes", [
    ("decode", _SPARSE_SCOPES), ("prefill", _SPARSE_SCOPES),
    ("prefill-masked", _SPARSE_SCOPES + ("mx_attention_tiled_masked",))])
def test_sparse_programs_carry_their_scopes(program, scopes):
    """The device scopes the benchmark's readers look for are in the
    lowered programs' operation names; a prefill on the masked kernel's
    route (the tier on) holds the kernel and no gather of each query's
    selected latent rows (``[B, Q, K, Rkv+dr]``: the twin's alone)."""
    model, params = _tiny()
    kv = model.init_kv_pages(16, PAGE, slots=2)
    i32 = jnp.int32
    if program == "decode":
        lowered = jax.jit(lambda p, c: model.decode_step(
            p, c, jnp.zeros((2,), i32), jnp.ones((2,), i32),
            jnp.ones((2, 4), i32), PAGE)).lower(params, kv)
    else:
        if program == "prefill-masked":
            mx.config.set("kernels.enabled", True)
        try:
            lowered = jax.jit(lambda p, c: model.prefill(
                p, c, jnp.zeros((2, 16), i32), jnp.full((2,), 13, i32),
                jnp.ones((2, 4), i32), PAGE)).lower(params, kv)
        finally:
            mx.config.unset("kernels.enabled")
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    rows = "tensor<2x16x8x20xf32>"          # [B, Q, index_topk, 16 + 4]
    assert (rows in text) == (program == "prefill"), program


@pytest.mark.parametrize("layer", [None, 1])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_index_score_kernel_is_its_twin(dtype, tol, layer):
    """Pages of 128 tokens whose rows 48-63 are index keys: the Pallas
    kernel (interpreted), copying only those rows of a row's own pages,
    against the twin's gather of every page's: the same scores wherever a
    row holds tokens (a ragged last page, a row of length 1), 0 on the
    kernel in the pages past a row's last."""
    rng = np.random.default_rng(9)
    B, Hi, di, width, psz, P = 3, 4, 16, 64, 128, 7
    q = jnp.asarray(rng.normal(size=(B, Hi, di)), dtype)
    w = jnp.asarray(rng.normal(size=(B, Hi)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(2, P, width, psz)), dtype)
    table = jnp.asarray([[1, 3, 5], [2, 9, 9], [6, 0, 4]], jnp.int32)
    lengths = jnp.asarray([300, 1, 384], jnp.int32)
    pages = pool if layer is not None else pool[1]
    args = (q, w, pages, table, lengths, 48)
    assert kernels.index_unsupported_reason(*args, layer=layer) is None
    got = np.asarray(pk.pallas_index_scores(*args, layer=layer))
    want = np.asarray(kernels._index_scores_xla(*args, layer=layer))
    held = np.arange(3 * psz)[None, :] < np.asarray(lengths)[:, None]
    _close(got.reshape(B, -1)[held], want.reshape(B, -1)[held], tol)
    pages_held = np.arange(3)[None, :] * psz < np.asarray(lengths)[:, None]
    assert not got[~pages_held].any()
    assert kernels.index_unsupported_reason(q, w, pages, table, lengths, 52,
                                            layer=layer) is not None
