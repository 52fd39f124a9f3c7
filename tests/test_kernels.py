"""mx.kernels — the Pallas kernel tier (round 12).

Covers the routing contract (one static rule for both routed sites: off
⇒ byte-identical programs, the default knob on an interpreted backend ⇒
the XLA lowering, on ⇒ the kernel for supported shapes with counted XLA
fallback; nothing timed or persisted), flash-attention fwd+bwd parity vs
the XLA lowering at f32 and bf16, the differentiable pallas_row_softmax
custom_vjp, the optimizer update being one program whatever the tier
says, the VMEM-budget row-block divisor walk + knob validation,
scan/remat stack tuning at equal loss, the program caches retracing once
per knob change, and the tools/check_kernels.py wiring.

All kernels run through the Pallas interpreter on CPU.  That checks the
math; whether Mosaic takes the block shapes is tests/test_tpu_compile.py's
question, and what the chip computes is chip_smoke.py's.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import config, kernels, profiler, telemetry
from mxnet_tpu.ops.pallas_kernels import (_row_block, flash_attention,
                                          grouped_col_tile,
                                          grouped_row_tile,
                                          pallas_grouped_matmul,
                                          pallas_paged_attention,
                                          pallas_row_softmax)
from mxnet_tpu.parallel.ring_attention import attention as xla_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VMEM_DEFAULT = 2097152


@pytest.fixture(autouse=True)
def _kernel_knobs():
    """Every test leaves the tier the way it found it: off, default
    budget, scan stack, no remat."""
    yield
    config.set("kernels.enabled", False)
    config.set("kernels.vmem_budget", VMEM_DEFAULT)
    config.set("runtime.stack_mode", "scan")
    config.set("runtime.remat", "")


def _qkv(shape=(1, 2, 32, 16), dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))


# ------------------------------------------------------------ row blocks
def test_row_block_divisor_walk():
    """Largest LEGAL divisor of n_rows (a multiple of 8, or n_rows) whose
    block fits the byte budget."""
    assert _row_block(1024, 4, budget=2048) == 512
    assert _row_block(96, 4, budget=128) == 32      # 32 | 96, 48 doesn't fit
    assert _row_block(64, 4, budget=10 ** 9) == 64  # whole array fits
    assert _row_block(200, 1, budget=128) == 40     # 100 | 200 is no tile
    # a leading (untiled) row axis takes any divisor
    assert _row_block(96, 1, budget=6, align=1) == 6


def test_row_block_edge_cases():
    # no legal divisor fits: the smallest legal extent, never an illegal one
    assert _row_block(97, 4, budget=64) == 97       # prime rows: whole axis
    assert _row_block(1024, 10 ** 9, budget=VMEM_DEFAULT) == 8  # huge rows
    assert _row_block(1, 1, budget=1) == 1
    assert _row_block(97, 4, budget=64, align=1) == 1


@pytest.mark.parametrize("n_rows", [1, 6, 7, 8, 12, 24, 96, 97, 100, 200,
                                    1000, 1001, 1024, 1344, 4096, 786432])
def test_row_block_is_a_legal_tpu_extent(n_rows):
    """The Mosaic lowering only takes a second-to-last block extent that
    is a multiple of 8 or the whole axis, and the grid must tile the
    array exactly — over a sweep of (row_bytes, budget) the pick always
    is both, and it is the budget's best whenever any legal pick fits."""
    for row_bytes in (1, 4, 168, 512, 3072, 73728, 10 ** 9):
        for budget in (1, 64, 4096, VMEM_DEFAULT, 10 ** 12):
            r = _row_block(n_rows, row_bytes, budget=budget)
            assert n_rows % r == 0, (n_rows, row_bytes, budget, r)
            assert r % 8 == 0 or r == n_rows, (n_rows, row_bytes, budget, r)
            legal = [d for d in range(1, n_rows + 1)
                     if n_rows % d == 0 and (d % 8 == 0 or d == n_rows)] \
                if n_rows <= 4096 else None
            if legal:
                fit = [d for d in legal if d * row_bytes <= budget]
                assert r == (max(fit) if fit else min(legal)), (
                    n_rows, row_bytes, budget, r)


def test_vmem_budget_knob_reject_and_revert():
    config.set("kernels.vmem_budget", 1024)
    assert config.get("kernels.vmem_budget") == 1024
    with pytest.raises(ValueError):
        config.set("kernels.vmem_budget", -1)
    # the rejected set cleared the override: back to the default
    assert config.get("kernels.vmem_budget") == VMEM_DEFAULT
    with pytest.raises(ValueError):
        config.set("kernels.vmem_budget", 0)
    assert config.get("kernels.vmem_budget") == VMEM_DEFAULT


def test_stack_knobs_reject_and_revert():
    config.set("runtime.stack_mode", "unroll")
    with pytest.raises(ValueError):
        config.set("runtime.stack_mode", "sideways")
    assert config.get("runtime.stack_mode") == "scan"
    config.set("runtime.remat", "dots")
    with pytest.raises(ValueError):
        config.set("runtime.remat", "everything")
    assert config.get("runtime.remat") == ""


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_bwd_parity_f32(causal):
    """Interpreter flash vs XLA at f32: fwd to float ulps, custom_vjp
    grads for q, k AND v."""
    q, k, v = _qkv()
    cot = jnp.asarray(np.random.RandomState(9).randn(*q.shape), jnp.float32)

    def ref(q, k, v):
        return jnp.sum(xla_attention(q, k, v, causal=causal) * cot)

    def ker(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * cot)

    o_ref = jax.jit(lambda *a: xla_attention(*a, causal=causal))(q, k, v)
    o_ker = jax.jit(lambda *a: flash_attention(*a, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-6, atol=1e-6)
    g_ref = jax.jit(jax.grad(ref, argnums=(0, 1, 2)))(q, k, v)
    g_ker = jax.jit(jax.grad(ker, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ker, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=2e-6)


def test_flash_parity_bf16():
    """bf16 runs the same f32 online-softmax accumulation in both paths;
    the documented tolerance is a few bf16 ulps (2^-8 relative) from the
    input/output casts."""
    q, k, v = _qkv(dtype=jnp.bfloat16, seed=1)
    got = flash_attention(q, k, v, causal=True)
    ref = xla_attention(q, k, v, causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_cross_attention_grads():
    """Skv != Sq (non-causal): the dkv kernel walks a different grid
    than dq — both must still match XLA."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 8, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 24, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 24, 16), jnp.float32)

    def loss(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(jnp.tanh(fn(q, k, v))),
            argnums=(0, 1, 2)))(q, k, v)

    for a, b in zip(loss(flash_attention), loss(xla_attention)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=2e-6)


def test_flash_rejects_causal_cross_and_mismatched_kv():
    q, k, v = _qkv()
    with pytest.raises(ValueError):
        flash_attention(q, k[:, :, :16], v[:, :, :16], causal=True)
    with pytest.raises(ValueError):
        flash_attention(q, k, v[:, :, :16])


# ------------------------------------------------------------- routing
def test_routing_off_is_program_byte_identical():
    """kernels.enabled=False traces the exact pre-tier program: the
    lowered module text is byte-equal to calling the XLA lowering
    directly (the acceptance gate for 'off changes nothing')."""
    q, k, v = _qkv((1, 2, 16, 8))
    config.set("kernels.enabled", False)

    def route(q, k, v):
        return kernels.attention(q, k, v, causal=True)

    off_text = jax.jit(route).lower(q, k, v).as_text()

    def route(q, k, v):  # noqa: F811 — same __name__ on purpose
        return xla_attention(q, k, v, causal=True)

    ref_text = jax.jit(route).lower(q, k, v).as_text()
    assert off_text == ref_text


def test_routing_counters_and_fallback():
    q, k, v = _qkv()
    telemetry.reset()
    config.set("kernels.enabled", True)
    out = kernels.attention(q, k, v, causal=True)
    assert telemetry.counter("kernels.flash_attention").value == 1
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(xla_attention(q, k, v, causal=True)),
        rtol=1e-6, atol=1e-6)
    # rank-3 input can never take the kernel — falls back, never errors
    q3 = q[0]
    out3 = kernels.attention(q3, k[0], v[0])
    assert telemetry.counter("kernels.fallback").value == 1
    np.testing.assert_allclose(np.asarray(out3),
                               np.asarray(xla_attention(q3, k[0], v[0])),
                               rtol=1e-6, atol=1e-6)
    # a kv slice over the VMEM budget falls back too
    config.set("kernels.vmem_budget", 64)
    kernels.attention(q, k, v, causal=True)
    assert telemetry.counter("kernels.fallback").value == 2
    assert kernels.flash_unsupported_reason(q, k, v, True) is not None
    config.set("kernels.vmem_budget", VMEM_DEFAULT)
    assert kernels.flash_unsupported_reason(q, k, v, True) is None


def test_routed_attention_runs_per_shard_on_a_mesh():
    """On a mesh that splits batch and heads the routed attention runs
    under shard_map (the compiler cannot partition a Mosaic kernel): the
    kernel is picked at the SHARD's shape, and loss and grads equal the
    tier-off global program's."""
    from jax.sharding import NamedSharding
    from mxnet_tpu.models.transformer import (TransformerLM,
                                              TransformerLMConfig)
    from mxnet_tpu.parallel import make_mesh
    mesh = make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])
    cfg = TransformerLMConfig(vocab_size=64, num_layers=2, d_model=32,
                              num_heads=4, d_ff=64, max_len=16,
                              dtype=jnp.float32)
    model = TransformerLM(cfg, mesh=mesh)
    params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        model.init(jax.random.PRNGKey(0)), model.param_specs())
    tok = jnp.asarray(np.random.RandomState(8).randint(0, 64, (4, 16)),
                      jnp.int32)
    out = {}
    for on in (False, True):
        config.set("kernels.enabled", on)
        telemetry.reset()
        out[on] = jax.jit(jax.value_and_grad(model.loss))(params, tok, tok)
        # one trace of the scanned layer body (+ its vjp re-trace)
        assert (telemetry.counter("kernels.flash_attention").value > 0) == on
    assert abs(float(out[True][0]) - float(out[False][0])) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(out[True][1]),
                    jax.tree_util.tree_leaves(out[False][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


# --------------------------------------------------- paged decode kernel
PSZ, HEADS, DH = 4, 2, 8
POOL_DTYPES = {"f32": (jnp.float32, jnp.float32, 2e-6),
               "bf16": (jnp.bfloat16, jnp.bfloat16, 2e-2),
               "int8": (jnp.int8, jnp.float32, 1e-5)}
#: one batch each; psz = 4, so 8 and 9 are k*psz and k*psz + 1
LENGTHS = {"ragged": [11, 3, 30, 18], "page_multiples": [4, 8, 9, 5],
           "one_token": [1, 1, 7, 1], "inactive_rows": [6, 0, 13, 0]}


def _pool_case(lengths, width, pool="f32", pages=48, seed=7, order="mixed"):
    """A page pool, a batch of rows that hold ``lengths`` tokens, and the
    page table that finds them.  A row's pages lie scattered through the
    pool out of order (``order="mixed"``) or in a run (``"run"``), the
    same K/V rows either way; a row of length 0 is an inactive decode
    slot: all sentinel.  Pages no row owns hold noise."""
    kv_dt, q_dt, _ = POOL_DTYPES[pool]
    rng = np.random.RandomState(seed)
    B, W = len(lengths), width
    q = jnp.asarray(rng.randn(B, HEADS, 1, DH), q_dt)
    need = [-(-n // PSZ) for n in lengths]
    ids = rng.permutation(pages) if order == "mixed" else np.arange(pages)
    table = np.full((B, W), pages, np.int32)
    rows = {}                     # the rows' own K/V, independent of order
    for b, n in enumerate(need):
        table[b, :n], ids = ids[:n], ids[n:]
        rows[b] = np.random.RandomState(100 + b).randn(
            2, max(n, 1), PSZ, HEADS * DH)
    wide = (pages, PSZ, HEADS * DH)
    if pool == "int8":
        kp = rng.randint(-127, 128, wide).astype(np.int8)
        vp = rng.randint(-127, 128, wide).astype(np.int8)
        scales = [jnp.asarray(rng.uniform(1e-3, 2e-2, wide[:2] + (HEADS,)),
                              jnp.float32) for _ in range(2)]
    else:
        kp, vp = rng.randn(*wide), rng.randn(*wide)
        scales = [None, None]
        for b, n in enumerate(need):
            kp[table[b, :n]], vp[table[b, :n]] = rows[b][0][:n], \
                rows[b][1][:n]
    return (q, jnp.asarray(kp, kv_dt), jnp.asarray(vp, kv_dt),
            jnp.asarray(table), jnp.asarray(lengths, jnp.int32), *scales)


def _both(case):
    q, kp, vp, table, lengths, ks, vs = case
    run = lambda fn: np.asarray(jax.jit(  # noqa: E731
        lambda *a: fn(*a[:5], k_scale=a[5], v_scale=a[6]))(*case),
        np.float32)
    return run(pallas_paged_attention), run(kernels._paged_attention_xla)


@pytest.mark.parametrize("pool", list(POOL_DTYPES))
@pytest.mark.parametrize("lengths", list(LENGTHS))
def test_paged_kernel_matches_twin(lengths, pool):
    """The in-place kernel agrees with the twin that gathers the whole
    window, to the rounding of the pool's precision (jit-vs-jit; not
    bitwise: an online softmax sums in another order): lengths ragged
    within one batch, lengths of k*psz and k*psz + 1, rows of one token,
    and inactive rows whose table is all sentinel."""
    served = [max(n, 1) for n in LENGTHS[lengths]]   # an idle slot: pos 0
    case = list(_pool_case(LENGTHS[lengths], 8, pool))
    case[4] = jnp.asarray(served, jnp.int32)
    got, want = _both(case)
    tol = POOL_DTYPES[pool][2]
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("budget", [VMEM_DEFAULT, 1024],
                         ids=["tile_of_32_pages", "tile_of_1_page"])
def test_paged_kernel_carries_its_softmax_across_tiles(budget):
    """Rows longer than one tile (128 tokens; one page when the VMEM
    budget allows no more) and rows that end on and just past a tile's
    edge: the running max, sum and accumulator carry over."""
    config.set("kernels.vmem_budget", budget)
    got, want = _both(_pool_case([128, 129, 150, 7], 40, pages=160))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


def test_paged_kernel_table_width_does_not_change_a_row():
    """Page tables of width 1 and 128 give the same answer for the same
    rows (to f32 rounding: the tile, and so the order of a sum, follows
    the narrower of 128 tokens and the table): what a row reads and
    computes follows from its length."""
    narrow = _pool_case([3, 4, 1, 2], 1, order="run")
    wide = _pool_case([3, 4, 1, 2], 128, order="run", pages=48)
    np.testing.assert_array_equal(np.asarray(narrow[3]),
                                  np.asarray(wide[3][:, :1]))
    got_n, want_n = _both(narrow)
    got_w, want_w = _both(wide)
    np.testing.assert_allclose(got_n, got_w, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(want_n, want_w, rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(got_w, want_w, rtol=2e-6, atol=2e-6)


def test_paged_kernel_finds_pages_scattered_out_of_order():
    """The same rows, their pages in a run or scattered through the pool
    out of order: the same bits."""
    run, _ = _both(_pool_case(LENGTHS["ragged"], 8, order="run"))
    mixed, want = _both(_pool_case(LENGTHS["ragged"], 8, order="mixed"))
    np.testing.assert_array_equal(run, mixed)
    np.testing.assert_allclose(mixed, want, rtol=2e-6, atol=2e-6)


def test_paged_row_of_length_zero_reads_nothing_and_answers_zero():
    """Length 0 walks no page (its table may hold anything, here ids far
    outside the pool) and answers 0 on both routes; its neighbours are
    untouched by it."""
    q, kp, vp, table, lengths, _, _ = _pool_case([9, 0, 5], 4)
    table = table.at[1].set(10 ** 6)
    got, want = _both((q, kp, vp, table, lengths, None, None))
    assert not got[1].any() and not want[1].any()
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("pool", ["bf16", "int8"])
@pytest.mark.parametrize("route", ["kernel", "twin"])
def test_paged_layer_of_whole_pools_traced_or_static_is_that_layers_pool(
        route, pool):
    """Every layer's pool ``[L, P, psz, W]`` (and scale pools ``[L, P,
    psz, H]``) handed over whole with ``layer`` a traced int32 scalar —
    as a layer scan hands it — or a Python int: the answer of the
    one-layer call on ``pool[layer]`` (to the rounding of a program fused
    otherwise), for every layer, sentinel ids included."""
    fn = pallas_paged_attention if route == "kernel" \
        else kernels._paged_attention_xla
    layers = [_pool_case(LENGTHS["inactive_rows"], 8, pool, seed=7 + i)
              for i in range(2)]
    q, _, _, table, lengths = layers[0][:5]
    tol = POOL_DTYPES[pool][2]
    stack = [None if layers[0][i] is None
             else jnp.stack([c[i] for c in layers]) for i in (1, 2, 5, 6)]

    def whole(layer):
        return fn(q, stack[0], stack[1], table, lengths, k_scale=stack[2],
                  v_scale=stack[3], layer=layer)

    one = jax.jit(lambda kp, vp, ks, vs: fn(
        q, kp, vp, table, lengths, k_scale=ks, v_scale=vs))
    traced = jax.jit(whole)
    for i, (_, kp, vp, _, _, ks, vs) in enumerate(layers):
        want = np.asarray(one(kp, vp, ks, vs), np.float32)
        assert np.abs(want).max() > 0
        for got in (traced(jnp.int32(i)), jax.jit(lambda: whole(i))()):
            np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                       rtol=tol, atol=tol)
    assert np.abs(want - np.asarray(traced(jnp.int32(0)), np.float32)).max() \
        > 10 * tol                            # the layers do differ


def test_paged_routing_explicit_vs_default():
    """Explicit tier-on routes decode through the Pallas kernel (counter
    + route record); the graduated default on the interpreter backend
    and the tier switched off take the twin, each with its reason — the
    same answer every way."""
    case = _pool_case(LENGTHS["ragged"], 8)[:5]
    telemetry.reset()
    outs = []
    for setting, impl, reason in ((True, "paged", None),
                                  (None, "xla", "interpreted"),
                                  (False, "xla", "tier off")):
        if setting is None:
            config.unset("kernels.enabled")       # graduated default
        else:
            config.set("kernels.enabled", setting)    # explicit source
        with kernels.record_paged_routes() as routes:
            # (a new function each time: jit would reuse a trace)
            outs.append(np.asarray(jax.jit(
                lambda *a: kernels.paged_attention(*a))(*case)))
        assert routes == [{"impl": impl, "reason": reason,
                           "quantized": False}]
        assert telemetry.counter("kernels.paged_attention").value == 1
    assert telemetry.counter("kernels.gated_fallback").value == 1
    assert telemetry.counter("kernels.paged_fallback").value == 0
    assert np.array_equal(outs[1], outs[2])
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-6, atol=2e-6)


def test_paged_unsupported_reasons():
    q, kp, vp, table, lengths, _, _ = _pool_case(LENGTHS["ragged"], 8)
    reason = kernels.paged_unsupported_reason
    assert reason(q, kp, vp, table, lengths) is None
    # multi-row query: prefill shapes never take the decode kernel
    q2 = jnp.concatenate([q, q], axis=2)
    assert "query row" in reason(q2, kp, vp, table, lengths)
    # a pool of another build's [P, psz, H, Dh] pages is refused, typed
    assert "rank" in reason(q, kp.reshape(48, PSZ, HEADS, DH), vp, table,
                            lengths)
    # (a row of fewer K/V heads is grouped queries; one that is no whole
    # number of heads is refused)
    assert reason(q, kp[..., :DH], vp[..., :DH], table, lengths) is None
    assert "row width" in reason(q, kp[..., :DH + 4], vp[..., :DH + 4],
                                 table, lengths)
    assert "batch" in reason(q, kp, vp, table[:2], lengths)
    # int8 pages without the quantized contract are refused
    assert reason(q, kp.astype(jnp.int8), vp, table, lengths) is not None
    assert reason(q, kp.astype(jnp.int8), vp.astype(jnp.int8), table,
                  lengths, quantized=True) is None
    # tier on, shape refused: the twin serves it and the reason is kept
    config.set("kernels.enabled", True)
    telemetry.reset()
    with kernels.record_paged_routes() as routes:
        kernels.paged_attention(q, kp.astype(jnp.float16), vp, table,
                                lengths)
    assert routes[0]["impl"] == "xla" and "dtype" in routes[0]["reason"]
    assert telemetry.counter("kernels.paged_fallback").value == 1


@pytest.mark.parametrize("in_trace", [False, True], ids=["eager", "traced"])
def test_paged_kernel_the_compiler_refuses_raises(monkeypatch, in_trace):
    """A kernel that cannot compile is an ERROR out of the routed call —
    from inside a jit trace too — never a quiet route to the twin."""
    class MosaicSaysNo(Exception):
        pass

    def refuse(*_a, **_k):
        raise MosaicSaysNo("block shape (6, 64) is not a legal TPU block")

    monkeypatch.setattr(kernels, "pallas_paged_attention", refuse)
    config.set("kernels.enabled", True)
    case = _pool_case(LENGTHS["ragged"], 8)[:5]
    fn = jax.jit(lambda *a: kernels.paged_attention(*a)) if in_trace \
        else kernels.paged_attention
    with kernels.record_paged_routes() as routes:
        with pytest.raises(MosaicSaysNo, match="not a legal TPU block"):
            fn(*case)
    assert [r["impl"] for r in routes] == ["paged"]


# ------------------------------------------------------- grouped product
#: rows, groups: a decode step's regime (groups of a few rows, several a
#: tile), a prefill's (groups of tens to hundreds) and fewer rows than a
#: tile
GROUPED_SHAPES = {"decode": (352, 16), "prefill": (1408, 8), "short": (40, 4)}


def _group_sizes(pattern, m, e, tm):
    """``(sizes [e], rows)`` for a named pattern at row tile ``tm``;
    ``rows`` may be off every tile (the kernel pads them)."""
    rng = np.random.RandomState(len(pattern))
    rest = [0] * (e - 4)
    if pattern == "one_group_alone":
        return [0, 0, min(tm + 3, m), 0] + rest, m
    if pattern == "one_row_groups":
        return [1] * e, m
    if pattern == "larger_than_a_tile":     # (or all of the only tile)
        return [2, max(tm + 5, m // 2) if tm < m else m - 3, 0, 1] + rest, m
    if pattern == "ends_on_a_tile_edge":    # (or with the rows)
        return ([tm, 0, tm - 3, 3] if 2 * tm <= m
                else [m - 3, 0, 3, 0]) + rest, m
    if pattern == "trailing_rows_of_no_group":
        return list(rng.randint(0, m // (4 * e) + 1, size=e)), m
    if pattern == "rows_off_every_tile":
        sizes = list(rng.randint(1, m // e, size=e))
        return sizes, sum(sizes) + 3
    if pattern == "no_group_has_a_row":
        return [0] * e, m
    assert pattern == "every_row_in_a_group"
    return list(rng.multinomial(m, np.ones(e) / e)), m


GROUP_PATTERNS = ["one_group_alone", "one_row_groups", "larger_than_a_tile",
                  "ends_on_a_tile_edge", "trailing_rows_of_no_group",
                  "rows_off_every_tile", "no_group_has_a_row",
                  "every_row_in_a_group"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", list(GROUPED_SHAPES))
@pytest.mark.parametrize("pattern", GROUP_PATTERNS)
def test_grouped_kernel_is_ragged_dot(pattern, shape, dtype):
    """The Pallas grouped product (interpreter) against ``lax.ragged_dot``
    on every row that lies in a group, over the ways groups can lie on
    the row tiles; the first product's epilogue and cast ride along."""
    m, e = GROUPED_SHAPES[shape]
    k, n = 256, 384
    tm = grouped_row_tile(m, jnp.dtype(dtype).itemsize)
    sizes, m = _group_sizes(pattern, m, e, tm)
    assert sum(sizes) <= m
    rng = np.random.RandomState(7)
    rows = jnp.asarray(rng.randn(m, k), dtype)
    w = jnp.asarray(rng.randn(e, k, n) / 16, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    want = jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=jnp.float32)
    got = jax.jit(pallas_grouped_matmul)(rows, w, sizes)
    held = int(sizes.sum())
    assert got.shape == (m, n) and got.dtype == jnp.float32
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got)[:held],
                               np.asarray(want)[:held], rtol=tol, atol=tol)
    relu2 = lambda a: jnp.square(jax.nn.relu(a))  # noqa: E731
    got = jax.jit(lambda *a: pallas_grouped_matmul(
        *a, epilogue=relu2, out_dtype=dtype))(rows, w, sizes)
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[:held],
        np.asarray(relu2(want).astype(dtype), np.float32)[:held],
        rtol=4 * tol, atol=4 * tol)


def test_grouped_tiles_follow_from_the_shapes():
    """The row tile is a function of the rows and the dtype's packing
    (one MXU pass, or every row where there are fewer), the column tile
    of the weights' shape and the VMEM budget; nothing else is asked."""
    assert grouped_row_tile(2816, 2) == 128            # 128 rows x top 22
    assert grouped_row_tile(22528, 2) == 128           # a 1,024 prefill
    assert grouped_row_tile(40, 2) == 48               # whole packed
    assert grouped_row_tile(40, 4) == 40               # sublane tiles
    assert grouped_row_tile(3, 2) == 16
    assert grouped_col_tile(1024, 2688, 2) == 896      # 1.75 of 2 MiB
    assert grouped_col_tile(2688, 1024, 2) == 256
    assert grouped_col_tile(2688, 1024, 4) == 128
    config.set("kernels.vmem_budget", 64 * 1024)
    assert grouped_col_tile(2688, 1024, 2) is None
    assert grouped_col_tile(128, 1024, 2) == 256


def _grouped_case(k=128, n=256, dtype=jnp.float32):
    rng = np.random.RandomState(5)
    sizes = jnp.asarray([3, 0, 9, 1], jnp.int32)
    return (jnp.asarray(rng.randn(40, k), dtype),
            jnp.asarray(rng.randn(4, k, n) / 8, dtype), sizes)


def test_grouped_routing_explicit_vs_default():
    """Explicit tier-on routes the grouped product through the Pallas
    kernel (counter + route record); the graduated default on the
    interpreter backend and the tier switched off take ``lax.ragged_dot``,
    each with its reason: the same answer every way, epilogue and cast
    included."""
    case = _grouped_case()
    telemetry.reset()
    outs = []
    for setting, impl, reason in ((True, "grouped", None),
                                  (None, "xla", "interpreted"),
                                  (False, "xla", "tier off")):
        if setting is None:
            config.unset("kernels.enabled")       # graduated default
        else:
            config.set("kernels.enabled", setting)    # explicit source
        with kernels.record_grouped_routes() as routes, \
                kernels.record_paged_routes() as paged:
            outs.append(np.asarray(jax.jit(
                lambda *a: kernels.grouped_matmul(
                    *a, epilogue=jnp.tanh, out_dtype=jnp.bfloat16))(*case),
                np.float32)[:13])
        assert routes == [{"impl": impl, "reason": reason}] and not paged
        assert telemetry.counter("kernels.grouped_matmul").value == 1
    assert telemetry.counter("kernels.gated_fallback").value == 1
    assert telemetry.counter("kernels.grouped_fallback").value == 0
    assert np.array_equal(outs[1], outs[2])
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-2, atol=1e-2)


def test_grouped_unsupported_reasons():
    rows, w, sizes = _grouped_case()
    reason = kernels.grouped_unsupported_reason
    assert reason(rows, w, sizes) is None
    assert reason(*_grouped_case(dtype=jnp.bfloat16)) is None
    assert "rank" in reason(rows[None], w, sizes)
    assert "groups" in reason(rows, w, sizes[:3])
    assert "groups" in reason(rows[:, :64], w, sizes)
    assert "multiples of 128" in reason(*_grouped_case(k=96))
    assert "multiples of 128" in reason(*_grouped_case(n=200))
    assert "float32" in reason(rows.astype(jnp.bfloat16), w, sizes)
    assert "float32" in reason(*_grouped_case(dtype=jnp.float16))
    config.set("kernels.vmem_budget", 1024)
    assert "vmem budget" in reason(rows, w, sizes)
    config.set("kernels.vmem_budget", VMEM_DEFAULT)
    # tier on, shape refused: ragged_dot serves it and the reason is kept
    config.set("kernels.enabled", True)
    telemetry.reset()
    with kernels.record_grouped_routes() as routes:
        got = kernels.grouped_matmul(*_grouped_case(k=96))
    assert routes[0]["impl"] == "xla" and "128" in routes[0]["reason"]
    assert telemetry.counter("kernels.grouped_fallback").value == 1
    assert telemetry.counter("kernels.grouped_matmul").value == 0
    assert got.shape == (40, 256) and got.dtype == jnp.float32


# ----------------------------------------------------------- row softmax
def test_pallas_softmax_grads_match_jnp():
    """The op is differentiable now — its custom_vjp reuses the saved
    row max/sum instead of recomputing the forward."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(32, 48), jnp.float32)
    cot = jnp.asarray(rng.randn(32, 48), jnp.float32)
    g_pal = jax.jit(jax.grad(
        lambda x: jnp.sum(pallas_row_softmax(x) * cot)))(x)
    g_ref = jax.jit(jax.grad(
        lambda x: jnp.sum(jax.nn.softmax(x, axis=-1) * cot)))(x)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)


def test_pallas_softmax_registered_differentiable():
    from mxnet_tpu.ops.registry import _REGISTRY
    assert _REGISTRY["pallas_softmax"].differentiable


# ---------------------------------------------------- one static route
def _attention_site(infeasible=False):
    q, k, v = _qkv()
    if infeasible:                      # rank 3: the kernel wants [B,H,S,D]
        q, k, v = q[0], k[0], v[0]
    return (lambda: kernels.attention(q, k, v),
            "flash_attention", ("mxnet_tpu.parallel.ring_attention",
                                "attention"),
            "kernels.flash_attention", "kernels.fallback", q)


def _paged_site(infeasible=False):
    q, kp, vp, table, lengths = _pool_case(LENGTHS["ragged"], 8)[:5]
    if infeasible:                      # two query rows per sequence
        q = jnp.concatenate([q, q], axis=2)
    return (lambda: kernels.paged_attention(q, kp, vp, table, lengths),
            "pallas_paged_attention", ("mxnet_tpu.kernels",
                                       "_paged_attention_xla"),
            "kernels.paged_attention", "kernels.paged_fallback", q)


def _grouped_site(infeasible=False):
    rows, w, sizes = _grouped_case(k=96 if infeasible else 128)
    return (lambda: kernels.grouped_matmul(rows, w, sizes),
            "pallas_grouped_matmul", ("mxnet_tpu.kernels",
                                      "_grouped_matmul_xla"),
            "kernels.grouped_matmul", "kernels.grouped_fallback", rows)


def _retention_site(infeasible=False):
    dh = 16 if infeasible else 128      # heads narrower than the lanes
    n = dh * (dh + 1) // 2
    state, z, pk, pq, g, v = (jnp.ones(shape, jnp.float32) for shape in (
        (1, 1, n, dh), (1, 1, n), (1, 1, n), (1, 1, 2, n), (1, 1),
        (1, 1, dh)))
    return (lambda: kernels.retention_update(state, z, pk, pq, g, v),
            "pallas_retention_update", ("mxnet_tpu.kernels",
                                        "_retention_update_xla"),
            "kernels.retention_update", "kernels.retention_fallback", state)


#: knob setting, backend, shape -> implementation and the counter that moves
ROUTES = {
    "default-interpreter": (None, True, False, "xla",
                            "kernels.gated_fallback"),
    "on-interpreter": ("set", True, False, "kernel", None),
    "env_on-interpreter": ("env", True, False, "kernel", None),
    "off-interpreter": (False, True, False, "xla", None),
    "default-chip": (None, False, False, "kernel", None),
    "on-infeasible": ("set", True, True, "xla", "fallback"),
    "default-chip-infeasible": (None, False, True, "xla", "fallback"),
}


@pytest.mark.parametrize("case", list(ROUTES))
@pytest.mark.parametrize("site", ["attention", "paged", "grouped",
                                  "retention"])
def test_route_is_static(site, case, monkeypatch, tmp_path):
    """Which implementation a routed site takes follows from the knob,
    whether the backend interprets Pallas, and the shape — through the
    one rule every site asks, with nothing timed, read or written."""
    import builtins
    import importlib
    from mxnet_tpu import rtc, runtime
    knob, interpreted, infeasible, impl, moved = ROUTES[case]
    call, kernel_name, (twin_mod, twin_name), kernel_ctr, fallback_ctr, q = \
        {"attention": _attention_site, "paged": _paged_site,
         "grouped": _grouped_site,
         "retention": _retention_site}[site](infeasible)
    if moved == "fallback":
        moved = fallback_ctr
    elif impl == "kernel":
        moved = kernel_ctr

    monkeypatch.delenv("MXNET_TPU_KERNELS", raising=False)
    config.unset("kernels.enabled")
    if knob == "env":
        monkeypatch.setenv("MXNET_TPU_KERNELS", "1")
    elif knob is not None:
        config.set("kernels.enabled", knob == "set")
    monkeypatch.setattr(rtc, "interpret_mode", lambda: interpreted)

    taken, asked, opened = [], [], []
    cache = str(tmp_path / "cache")
    monkeypatch.setattr(runtime, "cache_root", lambda: cache)
    real_open = builtins.open

    def spy_open(file, *a, **kw):
        if str(file).startswith(cache):
            opened.append(file)
        return real_open(file, *a, **kw)

    def stand_in(name):         # an implementation that only says it ran
        def fn(*a, **kw):
            taken.append(name)
            return jnp.zeros_like(q)
        return fn

    rule = kernels._route_reason

    def spy_rule(*a, **kw):
        asked.append(1)
        return rule(*a, **kw)

    monkeypatch.setattr(builtins, "open", spy_open)
    monkeypatch.setattr(kernels, "_route_reason", spy_rule)
    monkeypatch.setattr(kernels, kernel_name, stand_in("kernel"))
    monkeypatch.setattr(importlib.import_module(twin_mod), twin_name,
                        stand_in("xla"))
    telemetry.reset()
    try:
        call()
    finally:
        config.unset("kernels.enabled")
    assert taken == [impl]
    assert asked == [1]
    counters = {n: telemetry.counter(n).value for n in (
        "kernels.flash_attention", "kernels.paged_attention",
        "kernels.grouped_matmul", "kernels.retention_update",
        "kernels.fallback", "kernels.paged_fallback",
        "kernels.grouped_fallback", "kernels.retention_fallback",
        "kernels.gated_fallback")}
    assert counters == {n: int(n == moved) for n in counters}
    assert opened == [] and not os.path.exists(cache)


# ------------------- programs without a grouped product did not move
def _lowered_for_the_chip(which, monkeypatch):
    """The StableHLO of one program, lowered for the TPU with the kernels
    off the interpreter and the tier on: a dense transformer's decode
    step and prefill, and an ``SPMDTrainer`` step of the model zoo's
    ResNet-18 (trace and lowering only: nothing compiles or runs)."""
    from mxnet_tpu import perf, rtc
    monkeypatch.setattr(rtc, "interpret_mode", lambda: False)
    config.set("kernels.enabled", True)
    i32 = jnp.int32
    if which == "resnet_step":
        from mxnet_tpu import gluon
        from mxnet_tpu.gluon.model_zoo import vision
        from mxnet_tpu.parallel import SPMDTrainer, make_mesh

        class Lowered(Exception):
            pass

        def capture(self, args):
            raise Lowered(self.fn.trace(*args).lower(
                lowering_platforms=("tpu",)).as_text())

        monkeypatch.setattr(perf.PerfProgram, "_capture", capture)
        mx.random.seed(7)
        net = vision.resnet18_v1(classes=10)
        net.initialize(mx.init.Xavier())
        tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9},
                         mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
        with pytest.raises(Lowered) as step:
            tr.step(np.zeros((2, 3, 32, 32), np.float32),
                    np.zeros((2,), np.float32))
        return str(step.value)
    from mxnet_tpu.models.transformer import TransformerLM, \
        TransformerLMConfig
    model = TransformerLM(TransformerLMConfig(
        vocab_size=61, num_layers=2, d_model=32, num_heads=4, d_ff=64,
        max_len=16, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0))
    kv = model.init_kv_pages(8, 4)
    if which == "lm_decode":
        traced = jax.jit(lambda p, c, t, pos, tab: model.decode_step(
            p, c, t, pos, tab, 4)).trace(
                params, kv, jnp.zeros((4,), i32), jnp.zeros((4,), i32),
                jnp.zeros((4, 2), i32))
    else:
        traced = jax.jit(lambda p, c, t, n, tab: model.prefill(
            p, c, t, n, tab, 4)).trace(
                params, kv, jnp.zeros((1, 8), i32), jnp.ones((1,), i32),
                jnp.zeros((1, 2), i32))
    return traced.lower(lowering_platforms=("tpu",)).as_text()


#: sha256 of the programs' text at the commit before the grouped product
#: became a kernel (2468edf, PR 29's): no program without one moved
PROGRAMS_BEFORE_THE_GROUPED_KERNEL = {
    "lm_decode":
        "1f8717d9b861d52e2515828215a9d97d124fcaec4bd152aa61eed2440dfc60b7",
    "lm_prefill":
        "26a258ead8526e935eb9880b55bd59455bfc49a309bf8d3f17f5f251957f53a7",
    "resnet_step":
        "79d82527531aaeac0242ae610846cefa48f1c7d64522ec2e6fa98d46e168c090",
}


@pytest.mark.parametrize("which", list(PROGRAMS_BEFORE_THE_GROUPED_KERNEL))
def test_programs_without_a_grouped_product_are_the_parents(which,
                                                            monkeypatch):
    """The grouped product's route, counters and export sink reach only
    programs that hold one: a dense transformer's served programs (the
    paged kernel inside the decode step) and a ResNet training step
    lower for the TPU, locations aside, byte for byte as they did."""
    import hashlib
    from _util import without_kernel_locations
    text = without_kernel_locations(_lowered_for_the_chip(which, monkeypatch))
    assert ("mx_paged_attention" in text) == (which == "lm_decode")
    assert "mx_grouped_matmul" not in text and "ragged" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PROGRAMS_BEFORE_THE_GROUPED_KERNEL[which]


# ----------------------------- the update is one program across the tier
OPTIMIZERS = {"sgd": ("sgd", {"learning_rate": 0.1}),
              "sgd_momentum": ("sgd", {"learning_rate": 0.1,
                                       "momentum": 0.9}),
              "adam": ("adam", {"learning_rate": 1e-3})}


def _spmd_step(opt, opt_params, sparse):
    """One SPMDTrainer step of a model without attention (``sparse``: an
    Embedding with row-sparse gradients in front, the sparse builder)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    mx.random.seed(7)
    rng = np.random.RandomState(7)
    net = nn.HybridSequential(prefix="net_")
    with net.name_scope():
        if sparse:
            net.add(nn.Embedding(32, 4, sparse_grad=True), nn.Flatten())
            X = rng.randint(0, 32, (8, 3)).astype(np.int32)
        else:
            X = rng.randn(8, 6).astype(np.float32)
        net.add(nn.Dense(8, activation="relu"), nn.Dense(1))
    net.initialize(mx.init.Xavier())
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), opt, dict(opt_params),
                     mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
    tr.step(X, rng.rand(8, 1).astype(np.float32))
    (program,) = tr._jitted.values()
    return program._compiled.as_text()


def _module_step(opt, opt_params):
    """One fused Module train step of a two-layer MLP."""
    prev = config.get("module.fused_step")
    config.set("module.fused_step", "on")
    try:
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
        mod = mx.mod.Module(mx.sym.SoftmaxOutput(net, name="softmax"))
        mod.bind([("data", (8, 6))], [("softmax_label", (8,))])
        mx.random.seed(7)
        mod.init_params(mx.init.Uniform(0.1))
        mod.init_optimizer(optimizer=opt, optimizer_params=dict(opt_params))
        rng = np.random.RandomState(7)
        it = mx.io.NDArrayIter(rng.randn(8, 6).astype(np.float32),
                               rng.randint(0, 3, (8,)).astype(np.float32),
                               batch_size=8)
        mod.train_step(next(iter(it)))
        (program,) = mod._exec._fused_cache.values()
        return program._compiled.as_text()
    finally:
        config.set("module.fused_step", prev)


def _eager_multi_precision(opt, opt_params):
    """Three eager multi-precision updates (bf16 weight, f32 master): the
    bits of the weight, the master and the optimizer state."""
    o = mx.optimizer.create(opt, multi_precision=True, **opt_params)
    rng = np.random.RandomState(6)
    w = mx.nd.array(rng.randn(16, 5).astype(np.float32), dtype="bfloat16")
    state = o.create_state_multi_precision(0, w)
    for _ in range(3):
        g = mx.nd.array(rng.randn(16, 5).astype(np.float32),
                        dtype="bfloat16")
        o.update_multi_precision(0, w, g, state)
    leaves = jax.tree_util.tree_leaves(
        (w._data, jax.tree_util.tree_map(
            lambda s: getattr(s, "_data", s), state,
            is_leaf=lambda s: hasattr(s, "_data"))))
    return b"".join(np.asarray(leaf).tobytes() for leaf in leaves)


BUILDERS = {
    "spmd_dense": lambda *o: _spmd_step(*o, sparse=False),
    "spmd_sparse": lambda *o: _spmd_step(*o, sparse=True),
    "module_fused": _module_step,
    "eager_multi_precision": _eager_multi_precision,
}


@pytest.mark.parametrize("optimizer", list(OPTIMIZERS))
@pytest.mark.parametrize("builder", list(BUILDERS))
def test_update_is_one_program_across_the_tier(builder, optimizer):
    """No optimizer update is routed: for a model without attention every
    step builder compiles the same program (the eager path: computes the
    same bits) with the kernel tier explicitly on and explicitly off."""
    got = {}
    for tier in (True, False):
        config.set("kernels.enabled", tier)
        got[tier] = BUILDERS[builder](*OPTIMIZERS[optimizer])
    assert got[True] == got[False]


@pytest.mark.parametrize("value", ["off", "auto", "measure", "env"])
def test_retired_autotune_knob_is_accepted_and_read_by_nothing(
        value, monkeypatch):
    """``perf.autotune`` is still a name ``config.set`` takes (benchmark
    configs set it) and nothing reads it: a decode program lowers the same
    whatever it says.  The cache knob that went with it is gone."""
    from mxnet_tpu.models.transformer import (TransformerLM,
                                              TransformerLMConfig)
    model = TransformerLM(TransformerLMConfig(
        vocab_size=64, num_layers=2, d_model=32, num_heads=2, d_ff=64,
        max_len=64, dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0))
    kv = model.init_kv_pages(8, 4)
    args = (params, kv, jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32))
    config.set("kernels.enabled", True)

    def lowered():
        def decode(ps, kv, tok, pos, table):
            return model.decode_step(ps, kv, tok, pos, table, 4)
        return jax.jit(decode).lower(*args).as_text()

    try:
        before = lowered()
        if value == "env":
            monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "measure")
        else:
            config.set("perf.autotune", value)
            assert config.get("perf.autotune") == value
        assert lowered() == before
    finally:
        config.unset("perf.autotune")
    assert "perf.autotune_cache" not in config.knobs()
    with pytest.raises(KeyError):
        config.set("perf.autotune_cache", "/tmp/autotune.json")


# ------------------------------------- a knob change retraces, and once
def _gluon_cache():
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4)
    net.initialize()
    net.hybridize()
    x = mx.nd.random.uniform(shape=(2, 3))
    net(x)                      # the first hybrid call builds the cache
    return (lambda: net(x)), lambda: net._cached_graph_obj._jitted


def _symbol_block_taped_cache():
    from mxnet_tpu import autograd, gluon
    x = mx.sym.Variable("x")
    net = gluon.SymbolBlock(
        mx.sym.FullyConnected(x, num_hidden=3, name="fc"), x)
    for name, shape in (("fc_weight", (3, 5)), ("fc_bias", (3,))):
        net.params[name].shape = shape
    net.initialize()
    data = mx.nd.random.uniform(shape=(2, 5))

    def run():
        with autograd.record():
            net(data)

    return run, lambda: net._taped_cache


def _symbol_cache(which):
    x = mx.sym.Variable("x")
    ex = mx.sym.FullyConnected(x, num_hidden=3, name="fc").simple_bind(
        mx.cpu(), x=(2, 5))

    def run():
        ex.forward(is_train=True)
        if which == "_bwd_cache":
            ex.backward()

    return run, lambda: getattr(ex, which)


def _symbol_fused_cache():
    data = mx.sym.Variable("data")
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=3, name="fc"),
        name="softmax"))
    mod.bind([("data", (8, 6))], [("softmax_label", (8,))])
    mod.init_params(mx.init.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    batch = next(iter(mx.io.NDArrayIter(
        np.zeros((8, 6), np.float32), np.zeros((8,), np.float32),
        batch_size=8)))
    return (lambda: mod.train_step(batch)), lambda: mod._exec._fused_cache


def _spmd_cache():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    net = nn.Dense(1)
    net.initialize()
    tr = SPMDTrainer(net, gluon.loss.L2Loss(), "sgd",
                     {"learning_rate": 0.1},
                     mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
    X, Y = np.zeros((8, 6), np.float32), np.zeros((8, 1), np.float32)
    return (lambda: tr.step(X, Y)), lambda: tr._jitted


PROGRAM_CACHES = {
    "gluon_cached_graph": _gluon_cache,
    "symbol_block_taped": _symbol_block_taped_cache,
    "symbol_forward": lambda: _symbol_cache("_fwd_cache"),
    "symbol_backward": lambda: _symbol_cache("_bwd_cache"),
    "symbol_fused_step": _symbol_fused_cache,
    "spmd_trainer": _spmd_cache,
}


@pytest.mark.parametrize("cache", list(PROGRAM_CACHES))
def test_knob_toggle_retraces_once(cache):
    """Every program cache holds one program while no knob moves, swaps
    it for one new program when a knob does (the config epoch is in its
    key), and holds that one from then on.  The move here is the one the
    routing rule reads and no value shows: ``kernels.enabled`` from its
    default to the same value set explicitly."""
    prev = config.get("module.fused_step")
    config.set("module.fused_step", "on")
    config.unset("kernels.enabled")
    try:
        run, programs = PROGRAM_CACHES[cache]()

        def held():             # (the programs: an id could be reused)
            run()
            return list(programs().values())

        first = held()
        assert len(first) == 1 and held() == first
        config.set("kernels.enabled", True)
        second = held()
        assert len(second) == 1 and second != first
        assert held() == second
    finally:
        config.set("module.fused_step", prev)
        config.unset("kernels.enabled")


# ------------------------------------------------ trainer recompile guard
def test_trainer_fused_compiles_flat_across_kernel_toggle():
    """N steps reuse ONE fused program whatever the tier says; each knob
    flip invalidates the trainer cache for exactly one more compile —
    never a per-step recompile."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    rng = np.random.RandomState(7)
    X = rng.randn(8, 6).astype(np.float32)
    Y = (rng.rand(8) * 4).astype(np.float32)
    config.set("kernels.enabled", True)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net(mx.nd.array(X))
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1, "momentum": 0.9},
                     mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
    profiler.reset_counters()
    for _ in range(3):
        tr.step(X, Y)
    assert profiler.counters()["fused_compiles"] == 1
    config.set("kernels.enabled", False)   # toggle → one retrace, once
    for _ in range(2):
        tr.step(X, Y)
    assert profiler.counters()["fused_compiles"] == 2
    config.set("kernels.enabled", True)
    tr.step(X, Y)
    c = profiler.counters()
    assert c["fused_compiles"] == 3, c
    assert c["fused_steps"] == 6, c


# --------------------------------------------------- stack scan + remat
def test_scan_remat_modes_equal_loss():
    """scan vs unroll vs scan+remat('dots'/'full') all compute the same
    loss — program tuning must never change the math."""
    from mxnet_tpu.models.transformer import (TransformerLM,
                                              TransformerLMConfig)
    cfg = TransformerLMConfig(vocab_size=64, num_layers=3, d_model=32,
                              num_heads=2, d_ff=64, max_len=16,
                              dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = jnp.asarray(np.random.RandomState(8).randint(0, 64, (2, 16)),
                      jnp.int32)
    losses, grads = {}, {}
    for mode, remat in (("unroll", ""), ("scan", ""), ("scan", "dots"),
                        ("scan", "full")):
        config.set("runtime.stack_mode", mode)
        config.set("runtime.remat", remat)
        val, grad = jax.jit(jax.value_and_grad(model.loss))(
            params, tok, tok)
        losses[(mode, remat)] = float(val)
        grads[(mode, remat)] = grad
    base = losses[("scan", "")]
    for key, val in losses.items():
        assert abs(val - base) < 1e-6, (key, val, base)
    # remat recomputes the forward in the backward — grads must agree
    g0 = jax.tree_util.tree_leaves(grads[("scan", "")])
    for key in (("scan", "dots"), ("scan", "full")):
        for a, b in zip(jax.tree_util.tree_leaves(grads[key]), g0):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------- tool wiring
def test_check_kernels_smoke():
    """Subprocess wiring for tools/check_kernels.py — every tier leg
    proves out from a clean interpreter, exactly how CI runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the tool runs on the default 1-dev host
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_kernels.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["routing"]["fallback_count"] == 1, report
    assert report["flash"]["causal"]["fwd_maxdiff"] < 2e-6, report
    assert report["stack"]["scan"]["build_ms"] < \
        report["stack"]["unroll"]["build_ms"], report
