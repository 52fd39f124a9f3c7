"""``models.HybridLM``'s block-sparse attention (``B``: grouped-query
attention over the blocks each query's compressed keys chose, a page a
block) and lightning attention (``N``: linear attention under a fixed
per-head decay, a float32 state a slot) with the muP scalars, against the
plain reference (``benchmarks/reference/minicpm_sala_pp8.py``: float32,
the selection as a mask over a full causal forward, the lightning layers
in their quadratic form), at tiny sizes, seeded, on the cpu backend
(float32, full-precision products: ``conftest.py``).

The sizes are InfLLM-v2's shrunk: kernels of 4 at stride 2, blocks (and
pages) of 4, a window of 8, dense below position 16, and 6 blocks kept a
query (3 where said): at 3, the first block and the window's two or three
already fill the budget and no score is ever read.

What is held here: a padded prefill, then decode steps through pages,
compressed keys and state, is the full forward and the reference's on both
sides of ``dense_len``, in float32 and within a stated bound in bf16; the
program's kept blocks are the reference's; the lightning decode update is
the chunked scan; a padded prompt leaves the state bit for bit as it was;
two slots do not see each other's state or pages; each new site's kernel
is its twin; ``kv_spec`` describes the cache and the benchmark's counts are
what the model holds; a stack of such blocks goes through
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
from mxnet_tpu.models import hybrid
from mxnet_tpu.ops import pallas_kernels as pk

REF = manifest.load_module("reference", "minicpm_sala_pp8")
OPS = manifest.load_module("ops_bytes", "minicpm_sala_pp8")
PAGE = 4
MUP = dict(scale_emb=12.0, residual_scale=1.4 / 32 ** 0.5,
           logit_scale=1 / 16.0)
SIZES = dict(vocab_size=96, pattern="BFNFNF", d_model=32, num_heads=4,
             num_kv_heads=2, head_dim=8, mlp_ff=48, max_len=64,
             kernel_size=4, kernel_stride=2, block_size=PAGE, block_topk=6,
             init_blocks=1, local_window=8, dense_len=16, chunk=4,
             rope_theta=1e4, eps=1e-6, published_layers=32,
             dtype=jnp.float32, **MUP)
REF_LM = dict(eps=1e-6, rope_theta=1e4, kernel=4, stride=2, block=PAGE,
              top_k=6, init_blocks=1, window=8, dense_len=16, first_layer=0,
              layers=32, **MUP)


@pytest.fixture
def kernel_knobs():
    """The tier's knob as the test found it, and fresh counters."""
    telemetry.reset()
    yield
    mx.config.unset("kernels.enabled")


def _tiny(**over):
    model = HybridLM(HybridLMConfig(**dict(SIZES, **over)))
    return model, model.init(jax.random.PRNGKey(0))


def _close(got, want, tol=1e-6):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * np.abs(want).max(), \
        (np.abs(got - want).max(), np.abs(want).max())


def _through(model, params, toks, lengths, steps, bucket=16, slots=None,
             kv=None, rows=None):
    """Padded prefill into ``slots`` (default: row b), then ``steps``
    teacher-forced decode steps over every slot (``rows``: the slots that
    decode, the others at position 0): the logits of every position
    produced, [B, 1 + steps, V], and the cache."""
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
    live = jnp.ones((B,), bool) if rows is None else rows
    for _ in range(steps):
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        kv, _, logits = step(params, kv, tok, jnp.where(live, pos, 0))
        out.append(logits)
        pos = pos + 1
    return jnp.stack(out, axis=1), kv


# ------------------------------------------------------ the two blocks
@pytest.mark.parametrize("lengths", [(13, 10), (16, 11), (9, 15)])
@pytest.mark.parametrize("topk", [6, 3])
def test_prefill_then_decode_through_pages_and_state_is_the_full_forward(
        lengths, topk):
    """A prompt padded into a 16-token bucket (under ``dense_len``: every
    block) leaves its K and V in the pages, its compressed keys and the
    lightning states in the slot; 30 decode steps then cross ``dense_len``
    and keep blocks by their compressed keys: every position's logits are
    the cache-free forward's and the plain reference's, float32 to 1e-6."""
    model, params = _tiny(block_topk=topk)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, 48)),
                       jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got, _ = _through(model, params, toks, lengths, 30)
    full = model.apply(params, toks)
    for b in range(2):
        n = int(lengths[b])
        _close(got[b], full[b, n - 1:n + 30])
        _close(got[b], REF.logits(params, toks[b], lm=dict(
            REF_LM, top_k=topk))[n - 1:n + 30])


def test_a_prompt_past_dense_len_selects_in_its_prefill():
    """A 32-token bucket holds prompts past ``dense_len``: the prefill's
    own queries keep blocks by their scores (the masked pass), and the
    decode steps after it agree with the reference."""
    model, params = _tiny()
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 96, (2, 48)),
                       jnp.int32)
    lengths = jnp.asarray((29, 21), jnp.int32)
    got, _ = _through(model, params, toks, lengths, 12, bucket=32)
    for b in range(2):
        n = int(lengths[b])
        _close(got[b], REF.logits(params, toks[b], lm=REF_LM)[n - 1:n + 12])


def test_bf16_stays_within_eight_ulps_of_the_logits_scale():
    """The same walk with bf16 weights, activations, pages and compressed
    keys against the float32 reference over the same (bf16) values: within
    8 bf16 ulps (2**-8 each) of the largest logit."""
    model, params = _tiny(dtype=jnp.bfloat16)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 96, (2, 40)),
                       jnp.int32)
    lengths = jnp.asarray((14, 10), jnp.int32)
    got, _ = _through(model, params, toks, lengths, 20)
    for b in range(2):
        n = int(lengths[b])
        want = REF.logits(params, toks[b], lm=REF_LM)[n - 1:n + 20]
        assert np.abs(np.asarray(got[b], np.float32) - np.asarray(want)
                      ).max() <= 8 * 2.0 ** -8 * np.abs(np.asarray(want)).max()


def test_the_kept_blocks_are_the_references():
    """Every query of a 48-token sequence, past ``dense_len`` and under it:
    the blocks the program keeps (``_block_scores`` then
    ``_block_choose``, over the compressed keys ``_compressed`` makes) are
    the reference's, K/V head by K/V head (random scores: no tie): every
    block up to the query's own under ``dense_len``, ``block_topk`` of them
    past it (fewer while fewer are held)."""
    model, params = _tiny()
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 96, (48,)),
                       jnp.int32)
    want = np.asarray(REF.block_selection(params, toks, "00", lm=REF_LM))
    lp = params["layers"]["00"]
    x = model._embed(params, toks[None])
    _, q, k, _ = model._block_qkv(x, lp)
    ck, _ = model._compressed(k, None)
    t = jnp.arange(48, dtype=jnp.int32)[None]
    score = model._block_scores(q.reshape(1, 48, 2, 2, 8), ck, t, 12)
    got = np.asarray(model._block_choose(score, t[:, None]))[0]  # [KVH,S,B]
    assert got.shape == (2, 48, 12) and want.shape == (48, 2, 12)
    assert np.array_equal(got, np.moveaxis(want, 0, 1))
    held = np.arange(48) // PAGE + 1
    assert (got.sum(-1) == np.where(np.arange(48) < 16, held,
                                    np.minimum(held, 6))).all()


def test_the_lightning_update_is_the_chunked_scan():
    """``_lightning_scan`` over chunks of 4 (a prompt off the chunk's edge,
    its tail padded with zero keys and no decay) against the decode
    recurrence, ``retention_update`` with the key and query themselves
    (its normaliser zero) token by token, and the reference's quadratic and
    recurrent forms: outputs and final state, float32."""
    rng = np.random.default_rng(4)
    B, S, H, Dh, n = 2, 16, 3, 8, 13
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
               for _ in range(3))
    lam = jnp.asarray([0.9, 0.5, 0.99], jnp.float32)
    real = (jnp.arange(S) < n)[None, :, None]
    logd = jnp.where(real, jnp.log(lam), 0.0) * jnp.ones((B, S, H))
    kp = jnp.where(real[..., None], k, 0.0)
    y, state = hybrid._lightning_scan(q, kp, v, logd, 4)
    st = jnp.zeros((B, H, Dh, Dh), jnp.float32)
    ys = []
    for t in range(S):
        st, _, num, _ = kernels._retention_update_xla(
            st, jnp.zeros((B, H, Dh)), kp[:, t], q[:, t, :, None],
            jnp.exp(logd[:, t]), v[:, t])
        ys.append(num[:, :, 0])
    _close(y[:, :n], jnp.stack(ys, 1)[:, :n], 1e-5)
    _close(state, st, 1e-5)
    for b in range(B):
        want = REF.lightning_quadratic(q[b, :n], k[b, :n], v[b, :n], lam)
        _close(y[b, :n] / Dh ** 0.5, want, 1e-5)
        _close(REF.lightning_recurrent(q[b, :n], k[b, :n], v[b, :n], lam),
               want, 1e-5)


def test_a_padded_prompt_leaves_the_state_as_it_was():
    """One 11-token prompt prefilled into slot 1 of a 16-token bucket under
    two different paddings: the lightning states, compressed keys and
    running sums of the slot are the same bit for bit (the padded positions
    neither feed nor decay them), and slot 0's rows are the zero cache's."""
    model, params = _tiny()
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 96, (11,))
    n = jnp.asarray([11], jnp.int32)
    table = jnp.arange(1, 5, dtype=jnp.int32)[None]
    caches = []
    for _ in range(2):
        toks = jnp.asarray(np.concatenate([prompt, rng.integers(0, 96, 5)]),
                           jnp.int32)[None]
        kv = model.init_kv_pages(40, PAGE, slots=2)
        kv, _ = model.prefill(params, kv, toks, n, table, PAGE,
                              slots=jnp.asarray([1], jnp.int32))
        caches.append(kv)
    names = [s["name"] for s in model.kv_spec()["state"]]
    assert {"lit02", "lit04", "ck00", "cks00"} == set(names)
    for name in names:
        a, b = (np.asarray(c[name]) for c in caches)
        assert np.array_equal(a[1], b[1]), name
        assert not a[0].any() and a[1].any(), name


def test_two_slots_see_nothing_of_each_other():
    """Slot 0 decodes the same prompt beside two different neighbours (and
    beside an idle slot): its logits are the same bit for bit."""
    model, params = _tiny()
    rng = np.random.default_rng(6)
    mine = rng.integers(0, 96, (48,))
    runs = []
    for other in (rng.integers(0, 96, (48,)), rng.integers(0, 96, (48,)),
                  None):
        toks = jnp.asarray(np.stack([mine, mine if other is None else other]),
                           jnp.int32)
        lengths = jnp.asarray((12, 1 if other is None else 15), jnp.int32)
        got, _ = _through(model, params, toks, lengths, 16, rows=jnp.asarray(
            [True, other is not None]))
        runs.append(np.asarray(got[0]))
    assert np.array_equal(runs[0], runs[1]) and np.array_equal(runs[0],
                                                               runs[2])


def test_the_mup_scalars_are_the_familys():
    """The embedding times ``scale_emb``, every block's output times
    ``residual_scale``, the final norm times ``logit_scale``: a stack of
    one MLP block is the formula; at their defaults of 1 nothing is added
    (``init`` then draws the embedding at std 1 and divides every block's
    last matrix by sqrt(depth) as before)."""
    model, params = _tiny(pattern="F")
    toks = jnp.asarray([[3, 7, 11]], jnp.int32)
    cfg = model.cfg
    x = params["embed"][toks] * 12.0
    x = x + cfg.residual_scale * model._mlp(x, params["layers"]["00"])
    want = jnp.einsum("bsd,vd->bsv", hybrid._norm(
        x, params["final_norm"], cfg.eps) / 16.0, params["head"])
    _close(model.apply(params, toks), want, 1e-6)
    plain, pp = _tiny(pattern="F", scale_emb=1.0, residual_scale=1.0,
                      logit_scale=1.0, depth=16)
    text = jax.jit(plain.apply).lower(pp, toks).as_text()
    assert "1.200000e+01" not in text and "6.250000e-02" not in text
    assert abs(float(jnp.std(pp["embed"])) - 1.0) < 0.1
    assert abs(float(jnp.std(params["embed"])) * 12 - 1.0) < 0.1
    ratio = float(jnp.std(pp["layers"]["00"]["w_down"])) \
        / float(jnp.std(params["layers"]["00"]["w_down"]))
    assert abs(ratio - 0.25) < 0.03


@pytest.mark.parametrize("degrade", ["dense_selection", "bf16_state",
                                     "no_decay"])
def test_each_control_moves_the_references_logits(degrade):
    """The reference's controls take one part of the forward away and the
    logits move (past ``dense_len``, where the selection keeps fewer tokens
    than are held): each is a different function, which the cell's
    matched comparison holds the served program away from."""
    _, params = _tiny()
    toks = jnp.asarray(np.random.default_rng(10).integers(0, 96, (48,)),
                       jnp.int32)
    full = np.asarray(REF.logits(params, toks, lm=REF_LM))
    low = np.asarray(REF.logits(params, toks, lm=REF_LM, degrade=degrade))
    gap = np.abs(full - low)[20:].max() / np.abs(full).max()
    assert gap > 1e-4, gap
    assert np.abs(full - low)[:1].max() <= 1e-6 * np.abs(full).max() \
        or degrade != "dense_selection"


def test_the_lightning_decays_are_the_published_layers():
    """Each ``N`` block's decay is ``exp(-s_h (1 - l/31 + 1e-5))`` of its
    published layer ``l`` (``first_layer`` plus the mixers before it), the
    reference's."""
    model, _ = _tiny(pattern="BFNFNFNF", first_layer=8)
    for name, layer in (("02", 9), ("04", 10), ("06", 11)):
        _close(np.exp(model._lit_log_decay[name]),
               REF.decays(4, layer, 32), 1e-6)


# ----------------------------------------------------- kernels and twins
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_the_block_sparse_decode_kernel_is_its_twin(dtype, tol):
    """``kernels.block_sparse_attention`` on the kernel's route (the paged
    kernel, interpreted, over one head a page, each row's kept pages in
    order with a partial last one) against its twin's gather of the
    table's whole width; counted ``kernels.block_sparse``."""
    rng = np.random.default_rng(7)
    R, G, D, psz, P, K = 6, 4, 128, 16, 40, 5
    q = jnp.asarray(rng.normal(size=(R, G, 1, D)), dtype)
    pools = [jnp.asarray(rng.normal(size=(2, P, psz, D)), dtype)
             for _ in range(2)]
    table = jnp.asarray(np.stack([np.sort(rng.permutation(P)[:K])
                                  for _ in range(R)]), jnp.int32)
    kept = rng.integers(1, K + 1, R)
    lengths = jnp.asarray((kept - 1) * psz + rng.integers(1, psz + 1, R),
                          jnp.int32)
    telemetry.reset()
    mx.config.set("kernels.enabled", True)
    try:
        got = kernels.block_sparse_attention(q, *pools, table, lengths,
                                             0.3, layer=1)
    finally:
        mx.config.unset("kernels.enabled")
    want = kernels._paged_attention_xla(q, *pools, table, lengths,
                                        scale=0.3, layer=1)
    assert telemetry.counter("kernels.block_sparse").value == 1
    _close(got, want, tol)


@pytest.mark.parametrize("S,nb_lanes", [(32, 8), (1024, 16)])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_the_block_masked_prefill_kernel_is_its_twin(S, nb_lanes, dtype, tol):
    """The masked K/V-tiled flash pass under a mask of blocks (query heads
    over fewer K/V heads, the mask a K/V head's; at 1,024 the K/V tiles of
    512 read their 8 blocks of 64 from a 16-lane mask) against the twin's
    token mask and one-pass softmax, and against a dense masked softmax;
    every query keeps its own block."""
    rng = np.random.default_rng(8)
    H, KV, D = 4, 2, 16
    blk = S // nb_lanes
    q = jnp.asarray(rng.normal(size=(1, H, S, D)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(1, KV, S, D)), dtype)
            for _ in range(2))
    own = np.arange(S)[:, None] // blk == np.arange(nb_lanes)[None, :]
    mask = (rng.uniform(size=(KV, S, nb_lanes)) < 0.4) | own[None]
    mask = jnp.asarray(mask, jnp.bfloat16)
    got = pk.flash_attention_tiled(q, k, v, causal=True, scale=0.25,
                                   mask=mask, mask_block=blk)
    twin = kernels._block_sparse_prefill_xla(q, k, v, mask, 0.25, blk)
    _close(got, twin, tol)
    keep = np.repeat(np.asarray(mask, bool), blk, axis=-1) \
        & np.tril(np.ones((S, S), bool))[None]
    f32 = np.float32
    s = np.einsum("hqd,hkd->hqk", np.asarray(q[0], f32),
                  np.repeat(np.asarray(k[0], f32), H // KV, 0)) * 0.25
    s = np.where(np.repeat(keep, H // KV, 0), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    dense = np.einsum("hqk,hkd->hqd", p / p.sum(-1, keepdims=True),
                      np.repeat(np.asarray(v[0], f32), H // KV, 0))
    _close(got[0], dense, tol)


@pytest.mark.parametrize("change,says", [
    (dict(mask_dtype=jnp.int8), "want bfloat16"),
    (dict(S=576), "tiles"),
    (dict(S=48, block=6), "tiles"),
    (dict(kv=3), "are not"),
    (dict(S=1536 * 8, block=64), "no multiple of 128"),
])
def test_the_block_prefill_route_says_why_it_refuses(change, says):
    """The shapes the masked pass cannot take go to the twin with a reason
    (``kernels.block_sparse_prefill_fallback``), shapes only: nothing is
    built."""
    c = dict(S=64, block=4, kv=2, mask_dtype=jnp.bfloat16)
    c.update(change)
    S, blk = c["S"], c["block"]
    sds = jax.ShapeDtypeStruct
    q = sds((1, 4, S, 16), jnp.float32)
    k = sds((1, c["kv"], S, 16), jnp.float32)
    mask = sds((2, S, S // blk), c["mask_dtype"])
    reason = kernels.block_sparse_prefill_unsupported_reason(q, k, k, mask,
                                                             blk)
    assert reason is not None and says in reason, reason
    q = sds((1, 4, 64, 16), jnp.float32)
    k = sds((1, 2, 64, 16), jnp.float32)
    assert kernels.block_sparse_prefill_unsupported_reason(
        q, k, k, sds((2, 64, 16), jnp.bfloat16), 4) is None


def test_the_lightning_update_kernel_is_its_twin(kernel_knobs):
    """The retention update's kernel (interpreted) with the key and query
    themselves at heads of 128 (``N = Dh``): the new state and the
    read-out, float32, against the twin, and a row with a zero key under a
    decay of one left as it was, bit for bit."""
    rng = np.random.default_rng(9)
    B, H, D = 2, 3, 128
    st = jnp.asarray(rng.normal(size=(B, H, D, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32).at[0].set(0.0)
    q = jnp.asarray(rng.normal(size=(B, H, 1, D)), jnp.float32)
    lam = jnp.asarray(rng.uniform(0.5, 1.0, (B, H)), jnp.float32) \
        .at[0].set(1.0)
    v = jnp.asarray(rng.normal(size=(B, H, D)), jnp.bfloat16)
    z = jnp.zeros((B, H, D), jnp.float32)
    mx.config.set("kernels.enabled", True)
    got = kernels.retention_update(st, z, k, q, lam, v)
    want = kernels._retention_update_xla(st, z, k, q, lam, v)
    assert telemetry.counter("kernels.retention_update").value == 1
    _close(got[0], want[0], 1e-6)
    _close(got[2], want[2], 1e-5)
    assert np.array_equal(got[0][0], st[0])


# ---------------------------------------------------------- the cache
def test_kv_spec_describes_pages_a_head_apart_and_the_state():
    """Pages ``[L, P, KVH, page, Dh]`` (``page_layout`` "heads"), per ``B``
    block the compressed keys (a row every stride of ``max_len``) and the
    running sums, per ``N`` block a float32 ``[H, Dh, Dh]`` state."""
    model, _ = _tiny()
    spec = model.kv_spec()
    assert spec["page_layout"] == "heads" and spec["num_layers"] == 1
    assert spec["block_sparse"] == {"layers": 1, "block": 4, "top_k": 6}
    kv = model.init_kv_pages(10, PAGE, slots=3)
    assert kv["k"].shape == kv["v"].shape == (1, 10, 2, PAGE, 8)
    assert kv["ck00"].shape == (3, 2, 32, 8)
    assert kv["cks00"].shape == (3, 2, 2, 8)
    assert kv["lit02"].shape == (3, 4, 8, 8) and kv["lit02"].dtype \
        == jnp.float32
    with pytest.raises(ValueError, match="one kind of page"):
        HybridLMConfig(pattern="B*F")
    with pytest.raises(ValueError, match="whole strides"):
        HybridLMConfig(pattern="BF", kernel_size=24, kernel_stride=16)


def test_the_caches_bytes_are_the_benchmarks_count():
    """``ops_bytes/minicpm_sala_pp8.py`` counts the weights ``init`` makes
    and the cache ``kv_spec`` describes at the cell's sizes: 1,694.5 M
    parameters (3.16 GiB in bf16) and 2.95 GiB of cache over 40,960 pages
    of 64 tokens and 64 slots."""
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "minicpm_sala_pp8.json")) as f:
        cfg = json.load(f)
    lm = cfg["sizes"]["lm"]
    model = HybridLM(HybridLMConfig(dtype=jnp.bfloat16, **lm))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert OPS.parameter_count(lm) == count == 1694483456
    pages = cfg["knobs"]["serving.kv_pages"]
    kv = jax.eval_shape(lambda: model.init_kv_pages(pages, 64, slots=64))
    made = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in kv.values())
    assert OPS.cache_bytes(lm, pages, 64, 64) == made == 3171024896


def test_the_kept_tokens_count_is_the_selections():
    """``ops_bytes``' tokens a query keeps, summed as the prefill's pairs
    are, against the program's own selection at the tiny sizes."""
    model, params = _tiny()
    lm = dict(SIZES, pattern="BFNFNF")
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 96, (48,)),
                       jnp.int32)
    kept = np.asarray(REF.block_selection(params, toks, "00", lm=REF_LM))
    for t in range(48):
        tokens = np.repeat(kept[t, 0], PAGE)[:t + 1].sum()
        assert OPS.kept_tokens(lm, t) == tokens, t


# ------------------------------------------------- through the artifact
@pytest.fixture
def served(tmp_path):
    """A ``BFNFNF`` stack exported as the benchmark's driver does and
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


def test_a_block_sparse_stack_serves_the_oracles_tokens(served):
    """``export_generation`` -> ``Server.register(generate=True)``: six
    requests of 11-30 tokens over two slots (each slot used again after a
    longer or a shorter request) get the cache-free greedy oracle's
    tokens; the artifact describes the pages a head apart and the state,
    its decode route is the paged kernel's (interpreted) and is counted
    under the block-sparse site's counters, its prefills attend through
    the masked pass, and every page comes back."""
    model, params, prefix, srv, engine = served
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["kv"]["page_layout"] == "heads"
    width = str(meta["decode_widths"][-1])
    assert meta["paged"][width]["impl"] == "block_sparse"
    assert meta["sparse_prefill"] == {
        "prefill-s%d" % b: {"impl": "masked", "reason": None, "sites": 1}
        for b in (16, 32)}
    assert [tuple(a.shape) for a in engine._kv] == [
        (1, 30, 2, PAGE, 8)] * 2 + [(2, 2, 32, 8), (2, 2, 2, 8)] \
        + [(2, 4, 8, 8)] * 2
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (30, 11, 17, 25, 12, 21)]
    oracle = [model.greedy_decode(params, p, 9) for p in prompts]
    names = ("block_sparse", "block_sparse_fallback", "block_sparse_prefill",
             "block_sparse_prefill_fallback", "sparse_prefill")
    before = {n: telemetry.counter("kernels." + n).value for n in names}
    futures = [srv.submit_generate("lm", p, 9) for p in prompts]
    for want, f in zip(oracle, futures):
        assert (f.result(timeout=300) == want).all()
    after = {n: telemetry.counter("kernels." + n).value - before[n]
             for n in names}
    assert after["block_sparse"] > 0 and after["block_sparse_fallback"] == 0
    # once a B block a prefill dispatch, from the export's verdict
    assert after["block_sparse_prefill"] == len(prompts)
    assert after["block_sparse_prefill_fallback"] == 0
    assert after["sparse_prefill"] == 0
    assert engine.stats()["kv_pages_free"] == 30


def test_decode_spans_say_what_the_selection_read(served):
    """``engine.decode`` carries ``compressed_keys`` (kernels complete at
    the row's position), ``selected_tokens`` (a query head's kept tokens:
    every one under ``dense_len``, six blocks with the partial one last
    past it: ``ops_bytes``' count), ``held_tokens`` and ``state_rows``,
    over the one slot of two that holds a request."""
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

    def spying(name, **args):
        sp = begin(name, **args)
        if name != "engine.decode":
            return sp
        seen.append(Recorded(sp, args))
        return seen[-1]

    generation._begin = spying
    try:
        prompt = np.arange(14, dtype=np.int32) % 96
        srv.submit_generate("lm", prompt, 12).result(timeout=300)
    finally:
        generation._begin = begin
    assert seen
    for sp in seen:
        a = sp.args
        t = a["held_tokens"]          # the new token's position
        assert a["rows"] == 1 and a["state_rows"] == 1
        assert a["compressed_keys"] == max(0, (t - 3) // 2 + 1)
        assert a["selected_tokens"] == OPS.kept_tokens(SIZES, t)


_BLOCK_SCOPES = ("mx.block_select", "mx.compressed_key_write", "mx.attn_gate",
                 "mx.out_gate", "mx.kv_write", "mx.qkv", "mx.attn_out")


@pytest.mark.parametrize("program,scopes", [
    ("decode", _BLOCK_SCOPES + ("mx.block_sparse_attention",
                                "mx.lightning_update")),
    ("prefill", _BLOCK_SCOPES + ("mx.block_sparse_prefill",
                                 "mx.lightning_scan",
                                 "mx_attention_tiled_masked"))])
def test_block_sparse_programs_carry_their_scopes(program, scopes):
    """The device scopes the benchmark's readers look for are in the
    lowered programs' operation names; the prefill (the tier on) holds the
    masked kernel."""
    model, params = _tiny()
    kv = model.init_kv_pages(16, PAGE, slots=2)
    i32 = jnp.int32
    mx.config.set("kernels.enabled", True)
    try:
        if program == "decode":
            lowered = jax.jit(lambda p, c: model.decode_step(
                p, c, jnp.zeros((2,), i32), jnp.ones((2,), i32),
                jnp.ones((2, 4), i32), PAGE)).lower(params, kv)
        else:
            lowered = jax.jit(lambda p, c: model.prefill(
                p, c, jnp.zeros((2, 16), i32), jnp.full((2,), 13, i32),
                jnp.ones((2, 4), i32), PAGE)).lower(params, kv)
    finally:
        mx.config.unset("kernels.enabled")
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
