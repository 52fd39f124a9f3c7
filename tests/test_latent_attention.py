"""``models.HybridLM``'s latent-attention (``L``) and gated-experts (``G``)
blocks against the plain reference (``benchmarks/reference/
joyai_flash_pp8.py``: float32, the expanded form, every expert over every
token), at tiny sizes, seeded, on the cpu backend (float32, full-precision
products: ``conftest.py``).

What is held here: a padded prefill in the EXPANDED form, then decode steps
in the ABSORBED form through latent pages, is the full forward and the
reference's; bf16 pages stay within a stated bound; the gated experts are
the reference's, told or free; the relu-squared experts are bit for bit
what they were; the Pallas latent kernel is its XLA twin (a ragged last
page, a row of length 1, an empty row); the K/V-tiled flash kernel takes
value rows of another width; ``kv_spec`` describes ONE pool whose pages
hold their tokens on the lanes, and a model of such pages goes through
``export_generation`` and the server like any other — pages admitted,
freed and used again; the cache's bytes are the benchmark's count.
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
from mxnet_tpu.parallel import moe

REF = manifest.load_module("reference", "joyai_flash_pp8")
OPS = manifest.load_module("ops_bytes", "joyai_flash_pp8")
PAGE = 4
SIZES = dict(vocab_size=96, pattern="LFLG", d_model=32, num_heads=4,
             q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
             num_experts=8, top_k=2, expert_ff=48, shared_ff=48,
             route_scale=2.5, mlp_ff=48, max_len=64, rope_theta=1e4,
             eps=1e-6, dtype=jnp.float32)
REF_LM = {"top_k": 2, "route_scale": 2.5, "rope_theta": 1e4, "eps": 1e-6}


def _tiny(**over):
    model = HybridLM(HybridLMConfig(**dict(SIZES, **over)))
    return model, model.init(jax.random.PRNGKey(0))


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), \
        np.abs(got - want).max()


def _through_the_pages(model, params, toks, lengths, steps):
    """Padded prefill, then ``steps`` teacher-forced decode steps: the
    logits of every position produced, [B, 1 + steps, V]."""
    B = toks.shape[0]
    table = jnp.asarray(np.arange(1, 1 + B * 6).reshape(B, 6), jnp.int32)
    kv = model.init_kv_pages(2 + B * 6, PAGE, slots=B)
    kv, _, logits = model.prefill(params, kv, toks[:, :16], lengths,
                                  table[:, :4], PAGE, return_logits=True)
    out, pos = [logits], lengths
    for _ in range(steps):
        tok = jnp.take_along_axis(toks, pos[:, None], axis=1)[:, 0]
        kv, _, logits = model.decode_step(params, kv, tok, pos, table, PAGE,
                                          return_logits=True)
        out.append(logits)
        pos = pos + 1
    return jnp.stack(out, axis=1)


# ------------------------------------------------------ the two forms
@pytest.mark.parametrize("lengths", [(13, 6), (16, 1), (3, 9)])
def test_expanded_prefill_then_absorbed_decode_is_the_full_forward(lengths):
    """A prompt padded into a 16-token bucket attends in the expanded form
    and leaves its rows in the pages; decode steps then attend in the
    absorbed form over those rows: every position's logits are the
    cache-free forward's and the plain reference's, float32 to 2e-5."""
    model, params = _tiny()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, 24)),
                       jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = _through_the_pages(model, params, toks, lengths, 5)
    full = model.apply(params, toks)
    for b in range(2):
        n = int(lengths[b])
        _close(got[b], full[b, n - 1:n + 5])
        _close(got[b], REF.logits(params, toks[b], lm=REF_LM)[n - 1:n + 5])


def test_bf16_rows_stay_within_four_ulps_of_the_logits_scale():
    """The same walk with bf16 weights, activations and pages against the
    float32 reference over the same (bf16) values: within 4 bf16 ulps
    (2**-8 each) of the largest logit."""
    model, params = _tiny(dtype=jnp.bfloat16)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 96, (2, 24)),
                       jnp.int32)
    lengths = jnp.asarray((11, 7), jnp.int32)
    got = _through_the_pages(model, params, toks, lengths, 5)
    for b in range(2):
        n = int(lengths[b])
        want = REF.logits(params, toks[b], lm=REF_LM)[n - 1:n + 5]
        assert np.abs(np.asarray(got[b]) - np.asarray(want)).max() \
            <= 4 * 2.0 ** -8 * np.abs(np.asarray(want)).max()


def test_apply_is_the_reference_forward():
    model, params = _tiny(pattern="LFLGLG")
    toks = jnp.asarray(np.random.default_rng(2).integers(0, 96, (20,)),
                       jnp.int32)
    _close(model.apply(params, toks[None])[0],
           REF.logits(params, toks, lm=REF_LM))


@pytest.mark.parametrize("told", [False, True])
def test_gated_experts_are_the_references(told):
    """One ``G`` block over 12 tokens: the routed gated experts and the
    shared one are the reference's every-expert-over-every-token sum,
    routing freely or told the block's own choices (none missed)."""
    model, params = _tiny(pattern="G")
    lp = params["layers"]["00"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(12, 32)),
                    jnp.float32)
    out, stats, chosen = model._gated_moe(x, lp)
    want, used, missed = REF._experts_routed(
        x, lp, dict(REF._lm(REF_LM)), None, chosen if told else None)
    _close(out, want)
    assert int(missed) == 0 and int(stats["pairs"]) == 24
    assert (np.sort(np.asarray(used), 1) == np.sort(np.asarray(chosen),
                                                    1)).all()


@pytest.mark.parametrize("control", REF.DEGRADATIONS)
def test_every_control_of_the_reference_moves_the_logits(control):
    """A control is a forward with one step taken away: it differs from the
    full forward by far more than float32 rounding (3e-8 here), so a
    comparison can see it."""
    model, params = _tiny(pattern="LFLGLG")
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 96, (20,)),
                       jnp.int32)
    full = REF.logits(params, toks, lm=REF_LM)
    off = REF.logits(params, toks, lm=REF_LM, degrade=control)
    assert np.abs(np.asarray(full - off)).max() > 1e-6


# ----------------------------------------------------------- the experts
def _routing(t=24, e=8, k=2, seed=5):
    rng = np.random.default_rng(seed)
    experts = np.stack([rng.permutation(e)[:k] for _ in range(t)])
    return jnp.asarray(experts, jnp.int32), \
        jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)


@pytest.mark.parametrize("tier", ["twin", "kernel"])
def test_relu2_experts_are_bit_for_bit_what_they_were(tier):
    """``dropless_experts`` without a gate matrix is the two grouped
    products it was — one matrix, the relu-squared epilogue, float32 out —
    written out here as the parent had them: equal bits, on the XLA twin
    and on the Pallas kernel (interpreted)."""
    mx.config.set("kernels.enabled", tier == "kernel")
    try:
        rng = np.random.default_rng(6)
        u = jnp.asarray(rng.normal(size=(24, 128)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(8, 128, 256)) * 0.1, jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(8, 256, 128)) * 0.1, jnp.float32)
        experts, weights = _routing()
        got, _ = moe.dropless_experts(u, experts, weights, w1, w2)
        key = experts.reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((9,), jnp.int32).at[key].add(1)[:8]
        rows = jnp.take(u, order // 2, axis=0)
        h = kernels.grouped_matmul(
            rows, w1, sizes, epilogue=lambda a: jnp.square(jax.nn.relu(a)),
            out_dtype=u.dtype)
        out = kernels.grouped_matmul(h, w2, sizes)
        back = jnp.zeros((48,), jnp.int32).at[order].set(
            jnp.arange(48, dtype=jnp.int32))
        want = jnp.sum(jnp.take(out, back, axis=0).reshape(24, 2, -1)
                       * weights[..., None], axis=1)
        assert (np.asarray(got) == np.asarray(want)).all()
    finally:
        mx.config.unset("kernels.enabled")


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_gated_grouped_product_is_its_twin(dtype, tol):
    """Two matrices a group folded by ``silu(a) * b`` in the kernel's
    epilogue: the Pallas kernel (interpreted) against ``lax.ragged_dot``
    twice, a group without rows among them."""
    rng = np.random.default_rng(7)
    rows = jnp.asarray(rng.normal(size=(40, 128)), dtype)
    w = jnp.asarray(rng.normal(size=(5, 128, 256)) * 0.1, dtype)
    wb = jnp.asarray(rng.normal(size=(5, 128, 256)) * 0.1, dtype)
    sizes = jnp.asarray([3, 0, 17, 12, 8], jnp.int32)
    fold = lambda a, b: jax.nn.silu(a) * b   # noqa: E731
    got = pk.pallas_grouped_matmul(rows, w, sizes, epilogue=fold, w_b=wb)
    want = kernels._grouped_matmul_xla(rows, w, sizes, fold, jnp.float32, wb)
    _close(got, want, tol)
    assert kernels.grouped_unsupported_reason(rows, w, sizes, wb[:, :, :128]
                                              ) is not None


# ------------------------------------------------------ the latent kernel
@pytest.mark.parametrize("layer", [None, 0, 1])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_the_latent_kernel_is_its_twin(dtype, tol, layer):
    """Four heads over pages of 128 tokens on the lanes: a row that ends
    inside its third page (ragged), a row of length 1, an empty row and a
    row whose table names the sentinel; one layer's pool, or every
    layer's handed over whole."""
    rng = np.random.default_rng(8)
    B, H, width, dv, psz, P = 4, 4, 48, 32, 128, 7
    q = jnp.asarray(rng.normal(size=(B, H, width)), dtype)
    pool = jnp.asarray(rng.normal(size=(2, P, width, psz)), dtype)
    table = jnp.asarray([[1, 3, 5], [2, 9, 9], [6, 0, 4], [0, 9, 9]],
                        jnp.int32)
    lengths = jnp.asarray([300, 1, 0, 128], jnp.int32)
    pages = pool if layer is not None else pool[1]
    args = (q, pages, table, lengths, 0.2, dv)
    assert kernels.latent_unsupported_reason(*args[:4], dv,
                                             layer=layer) is None
    got = pk.pallas_latent_paged_attention(*args, layer=layer)
    want = kernels._latent_paged_attention_xla(*args, layer=layer)
    assert got.shape == (B, H, dv) and got.dtype == dtype
    _close(got, want, tol)
    assert not np.asarray(got[2], np.float32).any()


@pytest.mark.parametrize("change,says", [
    (dict(psz=64), "multiple of 128"),
    (dict(dv=20), "multiple of 128"),
    (dict(q_width=40), "wide"),
    (dict(q_dtype=jnp.bfloat16), "both"),
    (dict(batch=3), "do not match the batch")])
def test_the_latent_route_says_why_it_refuses(change, says):
    """A shape the kernel cannot take routes to the twin with its reason
    (``kernels.latent_fallback``), never an error."""
    psz, dv = change.get("psz", 128), change.get("dv", 32)
    q = jnp.zeros((2, 4, change.get("q_width", 48)),
                  change.get("q_dtype", jnp.float32))
    pool = jnp.zeros((3, 48, psz), jnp.float32)
    table = jnp.zeros((change.get("batch", 2), 2), jnp.int32)
    lengths = jnp.ones((2,), jnp.int32)
    reason = kernels.latent_unsupported_reason(q, pool, table, lengths, dv)
    assert reason is not None and says in reason, reason


def test_the_latent_site_counts_and_records_its_route():
    mx.config.set("kernels.enabled", True)
    try:
        q = jnp.ones((2, 4, 48), jnp.float32)
        table = jnp.zeros((2, 2), jnp.int32)
        lengths = jnp.asarray([5, 130], jnp.int32)
        before = {n: telemetry.counter("kernels." + n).value
                  for n in ("latent_paged", "latent_fallback")}
        with kernels.record_paged_routes() as routes:
            kernels.latent_paged_attention(
                q, jnp.ones((3, 48, 128), jnp.float32), table, lengths,
                0.1, 32)
            kernels.latent_paged_attention(
                q, jnp.ones((3, 48, 4), jnp.float32), table, lengths,
                0.1, 32)
        assert [r["impl"] for r in routes] == ["latent", "xla"]
        assert "128" in routes[1]["reason"]
        for name in before:
            assert telemetry.counter("kernels." + name).value \
                == before[name] + 1
    finally:
        mx.config.unset("kernels.enabled")


# ------------------------------------------------- the tiled flash kernel
@pytest.mark.parametrize("causal", [True, False])
def test_tiled_flash_takes_value_rows_of_another_width(causal):
    """Query/key width 24, value width 16, blocks of 16 over 64 positions:
    the K/V-tiled kernel (interpreted) against the XLA lowering, and its
    gradient (the XLA lowering's) through ``kernels.attention``."""
    from mxnet_tpu.parallel.ring_attention import attention as xla_attention
    rng = np.random.default_rng(9)
    q, k = (jnp.asarray(rng.normal(size=(1, 2, 64, 24)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 16)), jnp.float32)
    _close(pk.flash_attention_tiled(q, k, v, causal=causal, block=16),
           xla_attention(q, k, v, causal=causal))
    mx.config.set("kernels.enabled", True)
    try:
        tiled = telemetry.counter("kernels.flash_attention_tiled").value
        assert kernels.flash_unsupported_reason(q, k, v, causal) is not None
        assert kernels.tiled_unsupported_reason(q, k, v, causal) is None
        got, grads = jax.value_and_grad(
            lambda *a: kernels.attention(*a, causal=causal).sum(),
            argnums=(0, 1, 2))(q, k, v)
        want, want_grads = jax.value_and_grad(
            lambda *a: xla_attention(*a, causal=causal).sum(),
            argnums=(0, 1, 2))(q, k, v)
        assert telemetry.counter("kernels.flash_attention_tiled").value \
            > tiled
        _close(got, want)
        for g, w in zip(grads, want_grads):
            _close(g, w)
    finally:
        mx.config.unset("kernels.enabled")


# ------------------------------------------------------------ the cache
def test_kv_spec_describes_one_pool_of_latent_pages():
    model, _ = _tiny(pattern="LFLGLG")
    spec = model.kv_spec()
    assert spec["pools"] == ["kv"] and spec["page_layout"] == "lanes"
    assert spec["num_layers"] == 3 and spec["state"] == []
    assert spec["row_width"] == 16 + 4 and spec["value_width"] == 16
    kv = model.init_kv_pages(9, PAGE, slots=2)
    assert list(kv) == ["kv"] and kv["kv"].shape == (3, 9, 20, PAGE)
    specs = mx.deploy._kv_pool_specs(dict(spec, page_size=PAGE), 9, 2)
    assert [tuple(s.shape) for s in specs] == [(3, 9, 20, PAGE)]
    assert mx.deploy.kv_pool_names(spec) == ("kv",)
    # a model of K and V pages says nothing new
    old = HybridLM(HybridLMConfig(pattern="*F", **{
        k: v for k, v in SIZES.items() if k != "pattern"})).kv_spec()
    assert "pools" not in old and mx.deploy.kv_pool_names(old) == ("k", "v")


@pytest.mark.parametrize("pattern", ["L*", "*FLG"])
def test_a_pattern_may_not_mix_the_two_kinds_of_page(pattern):
    with pytest.raises(ValueError, match="one kind of page"):
        HybridLMConfig(**dict(SIZES, pattern=pattern))


def test_the_caches_bytes_are_the_benchmarks_count():
    """``ops_bytes/joyai_flash_pp8.py`` against hand counts at the
    published sizes, and against what ``kv_spec`` makes."""
    cfg = manifest.load_json("configs", "joyai_flash_pp8.json")
    lm = cfg["sizes"]["lm"]
    model = HybridLM(HybridLMConfig(dtype=jnp.bfloat16, **lm))
    spec = model.kv_spec()
    assert spec["row_width"] == OPS.row_width(lm) == 576
    assert OPS.latent_bytes_per_token(lm) == 5 * 1152 \
        == spec["num_layers"] * spec["row_width"] * 2
    assert OPS.expert_bytes(lm) == 3 * 2048 * 768 * 2
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert OPS.parameter_count(lm) == held == 5558141952


# ------------------------------------------------- through the artifact
@pytest.fixture
def served(tmp_path):
    """An ``LFLG`` stack exported as the benchmark's driver does and
    registered with a started server over TWO slots and a pool of 12
    pages of 4 tokens."""
    mx.config.set("kernels.enabled", True)
    mx.config.set("serving.kv_pages", 12)
    mx.config.set("serving.decode_slots", 2)
    model, params = _tiny()
    prefix = str(tmp_path / "lm")
    mx.deploy.export_generation(
        model, params, prefix, sampling=True, decode_batch=2,
        prompt_buckets=[8, 16], max_context=32, page_size=PAGE)
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


def test_a_latent_stack_serves_the_oracles_tokens(served):
    """``export_generation`` -> ``Server.register(generate=True)`` over
    latent pages: six requests over two slots and 12 pages (each needs up
    to 7, so pages are admitted, freed and used again) get the cache-free
    greedy oracle's tokens; the artifact describes one pool, its decode
    route is recorded (the twin, with the reason: pages of 4 tokens), the
    engine counts it under the latent site's counters, and every page
    comes back."""
    model, params, prefix, srv, engine = served
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["kv"]["pools"] == ["kv"] and meta["kv"]["num_layers"] == 2
    assert meta["kv"]["page_layout"] == "lanes"
    width = str(meta["decode_widths"][-1])
    assert meta["paged"][width]["impl"] == "xla"
    assert "128" in meta["paged"][width]["reason"]
    assert engine.predictor.paged
    assert [tuple(a.shape) for a in engine._kv] == [(2, 12, 20, PAGE)]
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 11, 3, 16, 7, 13)]
    oracle = [model.greedy_decode(params, p, 9) for p in prompts]
    fell_back = telemetry.counter("kernels.latent_fallback").value
    paged = telemetry.counter("kernels.paged_fallback").value
    futures = [srv.submit_generate("lm", p, 9) for p in prompts]
    for want, f in zip(oracle, futures):
        assert (f.result(timeout=300) == want).all()
    assert telemetry.counter("kernels.latent_fallback").value > fell_back
    assert telemetry.counter("kernels.paged_fallback").value == paged
    assert engine.stats()["kv_pages_free"] == 12
    gp = mx.deploy.load_generator(prefix)
    assert (gp.generate(prompts[1], 9) == oracle[1]).all()


def test_a_page_used_again_holds_the_new_requests_rows(served):
    """The same prompt served after other, longer requests have left
    their rows in every page gives the tokens it gave into a fresh pool:
    a prefill writes whole pages and a decode step its own column."""
    model, params, _, srv, engine = served
    rng = np.random.default_rng(2)
    probe = rng.integers(0, 96, (6,)).astype(np.int32)
    fresh = srv.submit_generate("lm", probe, 8).result(timeout=300)
    assert (fresh == model.greedy_decode(params, probe, 8)).all()
    for n in (15, 13, 16):          # dirty the pool
        srv.submit_generate("lm", rng.integers(0, 96, (n,)).astype(np.int32),
                            12).result(timeout=300)
    assert np.asarray(engine._kv[0]).any(axis=(0, 2, 3)).sum() >= 6
    again = [srv.submit_generate("lm", probe, 8) for _ in range(2)]
    for f in again:
        assert (f.result(timeout=300) == fresh).all()


@pytest.mark.parametrize("program,scopes", [
    ("decode", ("mx.mla_proj", "mx.latent_attention", "mx.kv_write",
                "mx.moe_router", "mx.moe_experts", "mx.moe_shared",
                "mx.mlp")),
    ("prefill", ("mx.mla_proj", "mx.attention", "mx.kv_write",
                 "mx.moe_experts"))])
def test_latent_programs_carry_their_scopes(program, scopes):
    """The device scopes the benchmark's readers look for are in the
    lowered programs' operation names."""
    model, params = _tiny()
    kv = model.init_kv_pages(4, PAGE, slots=2)
    i32 = jnp.int32
    if program == "decode":
        lowered = jax.jit(lambda p, c: model.decode_step(
            p, c, jnp.zeros((2,), i32), jnp.ones((2,), i32),
            jnp.ones((2, 2), i32), PAGE)).lower(params, kv)
    else:
        lowered = jax.jit(lambda p, c: model.prefill(
            p, c, jnp.zeros((2, 8), i32), jnp.full((2,), 5, i32),
            jnp.ones((2, 2), i32), PAGE)).lower(params, kv)
    text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
