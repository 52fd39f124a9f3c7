"""The kernels and programs of the main paths COMPILE for the chip.

No TPU is attached here; the TPU's compiler is, and it compiles for a chip
that is described (``v5e:2x2``) and not attached.  That shows what the
Pallas interpreter cannot: a block extent the (8, 128) tiling refuses, a
kernel that wants more fast memory than it may have, a Mosaic call the
partitioner cannot split over a mesh.  Nothing runs, so these tests say
nothing about results or times — ``chip_smoke.py`` does, on the chip.

Shapes are ``chip_smoke.py``'s: the default ``TransformerLMConfig`` (12
heads x 64) and ResNet-50's parameter shapes.  The topology is described
inside a module-scoped fixture (never at import: only one process may load
the TPU library, and every xdist worker imports this file), and every
compile happens in the test's own process.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mxnet_tpu import autotune, config, kernels, rtc
from mxnet_tpu.models.transformer import TransformerLM, TransformerLMConfig
from mxnet_tpu.ops import pallas_kernels as pk

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8
B, H, D = 8, 12, 64          # decode batch x the default config's heads


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """``compile_for_chip(fn, *specs)`` -> optimized HLO text.  Steers the
    kernels off the interpreter from here, not through an option of the
    program: ``interpret_mode`` asks the backend, which is the cpu."""
    monkeypatch.setattr(rtc, "interpret_mode", lambda: False)

    def run(fn, *specs):
        return jax.jit(fn).lower(*specs).compile().as_text()

    def spec(shape, dtype, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    run.spec = spec
    return run


def _kernel_count(text):
    return text.count('custom_call_target="tpu_custom_call"')


# ------------------------------------------------------------ attention
FLASH_SHAPES = [(8, 12, 1024, 64),    # the train width
                (2, 12, 200, 64)]     # S not a multiple of 128


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_forward_compiles(compile_for_chip, shape):
    q = compile_for_chip.spec(shape, BF16)
    text = compile_for_chip(
        lambda q, k, v: pk.flash_attention(q, k, v, causal=True), q, q, q)
    assert _kernel_count(text) == 1


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
def test_flash_backward_compiles(compile_for_chip, shape):
    q = compile_for_chip.spec(shape, BF16)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True).astype(F32).sum()

    text = compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert _kernel_count(text) == 3   # forward, dq, dk/dv


@pytest.mark.parametrize("S", [1024, 2048])
def test_every_flash_candidate_of_the_search_compiles(compile_for_chip, S):
    """A candidate that fails to compile is an error out of the autotune
    search, so the search may only propose blocks the chip can hold."""
    q = compile_for_chip.spec((1, 12, S, 64), BF16)
    cands = autotune._attention_candidates(S, S)
    assert len(cands) >= 2
    for bq in cands:
        text = compile_for_chip(functools.partial(
            pk.flash_attention, causal=True, block_q=bq), q, q, q)
        assert _kernel_count(text) == 1, bq


# --------------------------------------------------------- paged decode
def _paged_specs(spec, K, kv_dtype, q_dtype):
    specs = [spec((B, H, 1, D), q_dtype), spec((B, H, K, D), kv_dtype),
             spec((B, H, K, D), kv_dtype), spec((B, K), jnp.bool_)]
    if kv_dtype == I8:
        specs += [spec((B, H, K), F32)] * 2
    return specs


def _paged(block_bh=None):
    def fn(q, k, v, valid, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return pk.pallas_paged_attention(q, k, v, valid, block_bh=block_bh,
                                         **kw)
    return fn


@pytest.mark.parametrize("K", [1024, 2048])
@pytest.mark.parametrize("kv_dtype, q_dtype",
                         [(F32, F32), (BF16, BF16), (I8, BF16)],
                         ids=["f32", "bf16", "int8"])
def test_paged_attention_compiles(compile_for_chip, K, kv_dtype, q_dtype):
    text = compile_for_chip(
        _paged(), *_paged_specs(compile_for_chip.spec, K, kv_dtype, q_dtype))
    assert _kernel_count(text) == 1


@pytest.mark.parametrize("kv_dtype", [BF16, I8], ids=["bf16", "int8"])
def test_every_paged_candidate_of_the_search_compiles(compile_for_chip,
                                                      kv_dtype):
    K = 2048
    cands = autotune._paged_candidates(B * H, K, D,
                                       jnp.dtype(kv_dtype).itemsize,
                                       kv_dtype == I8)
    assert cands[0] == 1
    for bb in cands:
        text = compile_for_chip(_paged(bb), *_paged_specs(
            compile_for_chip.spec, K, kv_dtype, BF16))
        assert _kernel_count(text) == 1, bb


# -------------------------------------------------- optimizer epilogues
EPILOGUE_SHAPES = [(768, 3072),        # an MLP weight of the LM
                   (512, 512, 3, 3),   # ResNet-50: a 3-wide minor axis
                   (2048, 512, 1, 1),  # ... a 1-wide one
                   (1000, 2048), (64,), (1000,)]


@pytest.mark.parametrize("shape", EPILOGUE_SHAPES, ids=str)
def test_fused_sgd_step_compiles(compile_for_chip, shape):
    w = compile_for_chip.spec(shape, F32)
    text = compile_for_chip(
        lambda w, g, m: pk.fused_sgd_step(w, g, m, 0.1, 1e-4, 0.9,
                                          out_dtype=BF16), w, w, w)
    assert _kernel_count(text) == 1


@pytest.mark.parametrize("shape", [(768, 3072), (12, 768, 3, 12, 64),
                                   (768,)], ids=str)
def test_fused_adam_step_compiles(compile_for_chip, shape):
    w = compile_for_chip.spec(shape, F32)
    text = compile_for_chip(
        lambda w, g, m, v: pk.fused_adam_step(
            w, g, m, v, 1e-3, 0.01, 0.9, 0.999, 1e-8, out_dtype=BF16),
        w, w, w, w)
    assert _kernel_count(text) == 1


# ------------------------------------------------------------ row kernels
@pytest.mark.parametrize("shape", [(4096, 1024), (100, 1000)], ids=str)
def test_row_softmax_forward_and_backward_compile(compile_for_chip, shape):
    x = compile_for_chip.spec(shape, F32)
    assert _kernel_count(compile_for_chip(pk.pallas_row_softmax, x)) == 1
    text = compile_for_chip(
        jax.grad(lambda x: (pk.pallas_row_softmax(x) ** 2).sum()), x)
    assert _kernel_count(text) == 2


@pytest.mark.parametrize("shape", [(4096, 768), (128, 56, 56, 256)], ids=str)
def test_scale_bias_relu_compiles(compile_for_chip, shape):
    x = compile_for_chip.spec(shape, F32)
    s = compile_for_chip.spec(shape[-1:], F32)
    assert _kernel_count(
        compile_for_chip(pk.pallas_scale_bias_relu, x, s, s)) == 1


# ---------------------------------------------------------- whole programs
@pytest.fixture
def kernel_tier_on():
    """The explicit knob: kernels wherever feasible, no measured gate (a
    search would have to run them, and nothing runs here)."""
    config.set("kernels.enabled", True)
    config.set("perf.autotune", "off")
    yield
    config.unset("kernels.enabled")
    config.unset("perf.autotune")


def _default_lm(mesh=None):
    model = TransformerLM(TransformerLMConfig(), mesh=mesh)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_step_program_of_the_default_config_compiles(
        compile_for_chip, kernel_tier_on, quantized):
    """One whole decode iteration as the server exports it: 12 scanned
    layers, page-table gather, the paged kernel baked in."""
    spec = compile_for_chip.spec
    model, shapes = _default_lm()
    cfg = model.cfg
    psz, pool, width = 16, 512, 128       # width 128: a 2048-slot window
    page = (cfg.num_layers, pool, psz, cfg.num_heads, cfg.head_dim)
    kv = {n: spec(page, I8 if quantized else cfg.dtype) for n in "kv"}
    if quantized:
        kv.update({n: spec(page[:-1], F32) for n in ("k_scale", "v_scale")})
    params = jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), shapes)
    with kernels.record_paged_routes() as routes:
        text = compile_for_chip(
            lambda ps, kv, tok, pos, table: model.decode_step(
                ps, kv, tok, pos, table, psz),
            params, kv, spec((B,), jnp.int32), spec((B,), jnp.int32),
            spec((B, width), jnp.int32))
    assert routes and routes[0]["impl"] == "paged", routes
    assert _kernel_count(text) == 1       # the scan body holds it once


def test_routed_attention_compiles_per_shard_on_a_2x2_mesh(
        topo, compile_for_chip, kernel_tier_on):
    """The compiler refuses to partition a Mosaic kernel over a mesh; the
    model runs the routed attention per (dp, tp) shard, so a dp2 x tp2
    loss+grad step compiles with the flash kernels inside — and no
    gather of q/k/v to get there."""
    mesh = Mesh(np.asarray(topo.devices, dtype=object).reshape(2, 2),
                ("dp", "tp"))
    model, shapes = _default_lm(mesh)
    params = jax.tree_util.tree_map(
        lambda a, s: compile_for_chip.spec(a.shape, a.dtype,
                                           NamedSharding(mesh, s)),
        shapes, model.param_specs())
    tok = compile_for_chip.spec((4, 1024), jnp.int32,
                                NamedSharding(mesh, P("dp", None)))
    text = compile_for_chip(jax.value_and_grad(model.loss), params, tok, tok)
    assert _kernel_count(text) == 3
    assert "all-gather(" not in text
