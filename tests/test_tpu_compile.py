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
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from mxnet_tpu import config, kernels, rtc
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

    def program(fn, *specs, donate_argnums=()):
        return jax.jit(fn, donate_argnums=donate_argnums).lower(
            *specs).compile()

    def run(fn, *specs):
        return program(fn, *specs).as_text()

    def spec(shape, dtype, sharding=one_chip):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    run.spec = spec
    run.program = program
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
def test_flash_compiles_at_the_block_the_router_passes(compile_for_chip, S):
    """``kernels.attention`` hands the flash kernel one constant query
    block: the chip has to hold it at the widths the serving cells
    prefill at."""
    q = compile_for_chip.spec((1, 12, S, 64), BF16)
    config.set("kernels.enabled", True)
    try:
        text = compile_for_chip(functools.partial(
            kernels.attention, causal=True), q, q, q)
    finally:
        config.unset("kernels.enabled")
    assert _kernel_count(text) == 1


# --------------------------------------------------------- paged decode
# the serving cell's shapes (benchmarks/configs/opt_1p3b.json): 32 rows,
# 1,536 pages of 16 tokens, 32 heads x 64
CELL = dict(B=32, P=1536, psz=16, H=32, D=64)


def _paged_specs(spec, W, kv_dtype, q_dtype, B, P, psz, H, D):
    specs = [spec((B, H, 1, D), q_dtype), spec((P, psz, H * D), kv_dtype),
             spec((P, psz, H * D), kv_dtype), spec((B, W), jnp.int32),
             spec((B,), jnp.int32)]
    if kv_dtype == I8:
        specs += [spec((P, psz, H), F32)] * 2
    return specs


def _paged(q, k, v, table, lengths, *scales):
    return pk.pallas_paged_attention(
        q, k, v, table, lengths, **dict(zip(("k_scale", "v_scale"), scales)))


@pytest.mark.parametrize("W", [128, 8])
@pytest.mark.parametrize("kv_dtype, q_dtype",
                         [(F32, F32), (BF16, BF16), (I8, BF16)],
                         ids=["f32", "bf16", "int8"])
def test_paged_attention_compiles_at_the_cells_shapes(
        compile_for_chip, W, kv_dtype, q_dtype):
    """The in-place kernel: pools left in HBM, table and lengths by scalar
    prefetch, 64 KiB page copies into double-buffered VMEM tiles.  The
    pools reach it as they are: no copy of a pool-sized array."""
    text = compile_for_chip(_paged, *_paged_specs(
        compile_for_chip.spec, W, kv_dtype, q_dtype, **CELL))
    assert _kernel_count(text) == 1
    pool = "[%d,%d,%d]" % (CELL["P"], CELL["psz"], CELL["H"] * CELL["D"])
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and pool in ln.split(" copy(")[0]]


def test_paged_attention_compiles_for_the_default_config(compile_for_chip):
    """12 heads x 64: a 768-lane row, six lane tiles."""
    text = compile_for_chip(_paged, *_paged_specs(
        compile_for_chip.spec, 128, BF16, BF16, B=B, P=512, psz=16, H=H,
        D=D))
    assert _kernel_count(text) == 1


# ------------------------------------------------------ grouped product
# the hybrid cell's shapes (benchmarks/configs/nemotron3_super_ep4.json):
# 128 held experts of 2,688 in a 1,024-wide latent, top 22 of 128 decode
# rows or of a 1,024-token prompt
@pytest.mark.parametrize("dtype", [BF16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("k, n", [(1024, 2688), (2688, 1024)],
                         ids=["first", "second"])
@pytest.mark.parametrize("rows", [128 * 22, 1024 * 22, 100],
                         ids=["decode", "prefill1024", "off_tile"])
def test_grouped_matmul_compiles_at_the_cells_shapes(compile_for_chip, rows,
                                                     k, n, dtype):
    """Mosaic takes the grouped product's blocks at the cell's widths:
    the first product with its ``relu(.)**2`` epilogue and the cast to
    the activations' dtype, the second in float32, at a decode step's
    rows, a long prefill's and a row count off every tile."""
    spec = compile_for_chip.spec
    first = n > k
    text = compile_for_chip(
        lambda x, w, sizes: pk.pallas_grouped_matmul(
            x, w, sizes,
            epilogue=(lambda a: jnp.square(jax.nn.relu(a))) if first
            else None, out_dtype=dtype if first else F32),
        spec((rows, k), dtype), spec((128, k, n), dtype),
        spec((128,), jnp.int32))
    assert _kernel_count(text) == 1 and "mx_grouped_matmul" in text


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
    """The explicit knob: kernels wherever feasible."""
    config.set("kernels.enabled", True)
    yield
    config.unset("kernels.enabled")


def _default_lm(mesh=None):
    model = TransformerLM(TransformerLMConfig(), mesh=mesh)
    return model, jax.eval_shape(model.init, jax.random.PRNGKey(0))


def _assert_pools_stay_where_they_are(program, pools):
    """``pools``: the specs of the donated cache of a compiled serving
    ``program``, the K and V page pools ``[L, P, psz, W]`` the largest.
    All of it comes back in the buffers it came in (aliased), the program's
    temporaries are smaller than one pool, and no instruction makes an
    array the size of the whole K or V pool or of one layer's share of it
    — a copy, a slice out of the stack, an update back into it — but the
    write of the new rows, which is in place (a scatter whose fusion
    aliases its operand).  (An int8 pool's scale pools, a thirty-second
    of the bytes, the compiler may move to faster memory for the loop.)"""
    import math
    import re
    pools = sorted(pools, reverse=True,
                   key=lambda p: math.prod(p.shape) * p.dtype.itemsize)
    mem = program.memory_analysis()
    sizes = [math.prod(p.shape) * p.dtype.itemsize for p in pools]
    assert mem.alias_size_in_bytes >= sum(sizes)
    assert mem.temp_size_in_bytes < sizes[0], mem.temp_size_in_bytes
    pools = pools[:2]
    dtypes = {"bf16": BF16, "f32": F32, "s8": I8}
    watched = {(p.dtype, math.prod(p.shape) // whole)
               for p in pools for whole in (1, p.shape[0])}
    made = re.compile(r"\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")
    for line in program.as_text().splitlines():
        m = made.match(line)
        if not m or m.group(1) not in dtypes:
            continue
        dtype, dims, opcode = m.groups()
        size = math.prod(int(d) for d in dims.split(",") if d)
        if (dtypes[dtype], size) not in watched or opcode in (
                "parameter", "get-tuple-element", "bitcast", "scatter"):
            continue
        assert opcode == "fusion" and '"aliasing_operands":{"lists":[{' \
            in line, line[:300]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_step_program_of_the_default_config_compiles(
        compile_for_chip, kernel_tier_on, quantized, program):
    """One whole decode iteration: 12 scanned layers that carry the whole
    pool, the paged kernel baked in once on the pool and the layer's
    index, no gather, and the pool written where it lies; the prefill of
    a 512-token prompt beside it writes it there too."""
    spec = compile_for_chip.spec
    model, shapes = _default_lm()
    psz, pool, width = 16, 512, 128       # width 128: a 2048-slot window
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: spec(a.shape, a.dtype))
    kv = on_chip(jax.eval_shape(
        lambda: model.init_kv_pages(pool, psz, quantized=quantized)))
    params = on_chip(shapes)
    if program == "prefill":
        compiled = compile_for_chip.program(
            lambda ps, kv, tok, n, table: model.prefill(
                ps, kv, tok, n, table, psz),
            params, kv, spec((1, 512), jnp.int32), spec((1,), jnp.int32),
            spec((1, 512 // psz), jnp.int32), donate_argnums=(1,))
        _assert_pools_stay_where_they_are(compiled, kv.values())
        return
    with kernels.record_paged_routes() as routes:
        compiled = compile_for_chip.program(
            lambda ps, kv, tok, pos, table: model.decode_step(
                ps, kv, tok, pos, table, psz),
            params, kv, spec((B,), jnp.int32), spec((B,), jnp.int32),
            spec((B, width), jnp.int32), donate_argnums=(1,))
    text = compiled.as_text()
    assert routes and routes[0]["impl"] == "paged", routes
    assert _kernel_count(text) == 1       # the scan body holds it once
    assert "mx.kv_gather" not in text and "mx.paged_attention" in text
    _assert_pools_stay_where_they_are(compiled, kv.values())


def test_decode_programs_exported_as_the_benchmark_does_take_the_kernel(
        compile_for_chip, kernel_tier_on, monkeypatch, tmp_path):
    """``export_generation(sampling=True, decode_batch=32)`` as
    ``benchmarks/drivers/serve_lm.py`` calls it, lowered for the TPU: the
    pool's page count is symbolic in the artifact, and every decode width
    still records route "paged"; reloaded and compiled for the described
    chip at the cell's pool of 1,536 pages, donated as the server donates
    it, widths 128 and 8 hold the kernel and no gather, and they and the
    prefill bucket leave the pool where it lies.  (Two layers and a small
    vocabulary: the kernel's shapes are the cell's.)"""
    import json
    from jax import export as jexport
    from mxnet_tpu import deploy
    monkeypatch.setattr(jexport, "export", functools.partial(
        jexport.export, platforms=["tpu"]))
    spec = compile_for_chip.spec
    model = TransformerLM(TransformerLMConfig(
        dtype=BF16, vocab_size=512, num_layers=2, d_model=2048,
        num_heads=32, d_ff=256, max_len=2048))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    prefix = str(tmp_path / "lm")
    deploy.export_generation(
        model, params, prefix, sampling=True, decode_batch=32,
        prompt_buckets=[128], max_context=2048, page_size=16,
        include_params=False)
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert sorted(meta["paged"], key=int) == [
        "1", "2", "4", "8", "16", "32", "64", "128"]
    assert all(r["impl"] == "paged" for r in meta["paged"].values()), \
        meta["paged"]
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: spec(a.shape, a.dtype))
    pools = on_chip(deploy._kv_pool_specs(meta["kv"], CELL["P"]))

    def compiled(program, rows, *specs):
        with open("%s-%s.stablehlo" % (prefix, program), "rb") as f:
            exp = jexport.deserialize(f.read())
        return compile_for_chip.program(
            exp.call, on_chip(params), pools, *specs, spec((rows,), F32),
            spec((rows,), jnp.int32), spec((rows,), F32),
            spec((rows, 2), jnp.uint32), donate_argnums=(1,))

    rows = 32
    for width in (128, 8):
        decode = compiled(
            "decode-w%d" % width, rows, spec((rows,), jnp.int32),
            spec((rows,), jnp.int32), spec((rows, width), jnp.int32))
        text = decode.as_text()
        assert _kernel_count(text) == 1, width
        assert "mx.kv_gather" not in text, width
        _assert_pools_stay_where_they_are(decode, pools)
    _assert_pools_stay_where_they_are(compiled(
        "prefill-s128", 1, spec((1, 128), jnp.int32), spec((1,), jnp.int32),
        spec((1, 128 // 16), jnp.int32)), pools)


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


# ------------------------------------------------ the hybrid decoder's cell
def test_hybrid_programs_at_the_cells_sizes_compile_into_the_chip(
        compile_for_chip, kernel_tier_on, monkeypatch, tmp_path):
    """``benchmarks/configs/nemotron3_super_ep4.json`` as its driver
    exports it — published widths, 128 of 512 experts, 128 decode slots,
    K/V pages beside per-slot state — lowered for the TPU, reloaded and
    compiled for the described chip: the one decode program holds the
    paged kernel at 16 queries a K/V head and no gather, rewrites cache
    and state in place (all of it aliased), and weights, cache and the
    largest program's temporaries fit one chip's 15.75 GiB.  (Weights are
    shapes only: nothing is made.)"""
    import json
    import os
    from jax import export as jexport
    from mxnet_tpu import deploy
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    monkeypatch.setattr(jexport, "export", functools.partial(
        jexport.export, platforms=["tpu"]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "nemotron3_super_ep4.json")) as f:
        cell = json.load(f)
    sz = cell["sizes"]
    model = HybridLM(HybridLMConfig(dtype=BF16, **sz["lm"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefix = str(tmp_path / "lm")
    # (the suite's full-precision products are the cpu's: the chip's
    # grouped product takes bf16 operands at the default precision)
    with jax.default_matmul_precision(None):
        deploy.export_generation(
            model, shapes, prefix, sampling=True,
            decode_batch=sz["decode_batch"], prompt_buckets=[128, 1024],
            max_context=2048, page_size=sz["page_tokens"],
            decode_widths=sz["decode_widths"], include_params=False,
            replay=sz["replay"])
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["replay"] == {"layers": 5, "top_k": 22}
    width = sz["decode_widths"][-1]
    assert meta["paged"] == {str(width): {
        "impl": "paged", "reason": None, "quantized": False}}
    # every program's ten grouped products (two an E block) are the
    # program's own kernel
    assert meta["grouped"] == {program: {
        "impl": "grouped", "reason": None, "sites": 10}
        for program in ("prefill-s128", "prefill-s1024",
                        "decode-w%d" % width)}
    spec = compile_for_chip.spec
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: spec(a.shape, a.dtype))
    rows, pages = sz["decode_batch"], cell["knobs"]["serving.kv_pages"]
    cache = on_chip(deploy._kv_pool_specs(meta["kv"], pages, rows))

    def sample(b):
        return (spec((b,), F32), spec((b,), jnp.int32), spec((b,), F32),
                spec((b, 2), jnp.uint32))

    def compiled(path, *specs):
        with open(path, "rb") as f:
            exp = jexport.deserialize(f.read())
        return jax.jit(exp.call, donate_argnums=(1,)).lower(
            on_chip(shapes), cache, *specs).compile()

    decode = compiled("%s-decode-w%d.stablehlo" % (prefix, width),
                      spec((rows,), jnp.int32), spec((rows,), jnp.int32),
                      spec((rows, width), jnp.int32), *sample(rows))
    # tokens, three counts, log-probabilities, experts [5, rows, 22]
    assert decode.out_info[1].shape == (2 * rows + 3 + 5 * rows * 22,)
    text = decode.as_text()
    assert text.count("mx_paged_attention") >= 1
    assert "mx.kv_gather" not in text
    prefill = compiled(
        "%s-prefill-s1024.stablehlo" % prefix,
        spec((1, 1024), jnp.int32), spec((1,), jnp.int32),
        spec((1, -(-1024 // sz["page_tokens"])), jnp.int32),
        spec((1,), jnp.int32), *sample(1))
    for program in (text, prefill.as_text()):
        # the experts' products are the program's kernel, under the scope
        # that names their device time; XLA's grouped product is gone
        assert program.count("mx_grouped_matmul") >= 10
        assert "mx.moe_experts" in program
        assert "ragged" not in program
    need = []
    for program in (decode, prefill):
        mem = program.memory_analysis()
        cache_bytes = sum(np.prod(c.shape) * c.dtype.itemsize for c in cache)
        assert mem.alias_size_in_bytes >= cache_bytes       # in place
        need.append(mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    assert max(need) < 15.75 * 2 ** 30, need
    assert min(need) > 0.6 * 15.75 * 2 ** 30    # and the chip is filled


# ------------------------------------------- the retention decoder's cell
def test_retention_programs_at_the_cells_sizes_compile_into_the_chip(
        compile_for_chip, kernel_tier_on, monkeypatch, tmp_path):
    """``benchmarks/configs/brumby_14b_pp5.json`` as its driver exports it
    — published widths, eight ``RF`` layers, the whole vocabulary, 16
    decode slots of 272.6 MB of float32 state each and NO K/V page —
    lowered for the TPU, reloaded and compiled for the described chip: one
    decode program at a one-column table with no paged site, every state
    array rewritten in place (all 4.06 GiB aliased), and weights, state and
    the largest prefill bucket's temporaries inside one chip's 15.75 GiB.
    (Weights are shapes only: nothing is made.)"""
    import json
    import os
    from jax import export as jexport
    from mxnet_tpu import deploy
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    monkeypatch.setattr(jexport, "export", functools.partial(
        jexport.export, platforms=["tpu"]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "brumby_14b_pp5.json")) as f:
        cell = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "offline_reasoning_s16.json")) as f:
        traffic = json.load(f)
    sz = cell["sizes"]
    bucket = traffic["prompt_buckets"][-1]
    model = HybridLM(HybridLMConfig(dtype=BF16, **sz["lm"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefix = str(tmp_path / "lm")
    with jax.default_matmul_precision(None):
        deploy.export_generation(
            model, shapes, prefix, sampling=True,
            decode_batch=sz["decode_batch"], prompt_buckets=[bucket],
            max_context=traffic["max_context"],
            page_size=sz["page_tokens"], include_params=False,
            replay=sz["replay"])
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["decode_widths"] == [1] and meta["paged"] == {}
    assert meta["kv"]["num_layers"] == 0 and meta["grouped"] == {}
    assert meta["replay"] == {"layers": 0, "top_k": 0}
    spec = compile_for_chip.spec
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: spec(a.shape, a.dtype))
    rows = sz["decode_batch"]
    cache = on_chip(deploy._kv_pool_specs(
        meta["kv"], cell["knobs"]["serving.kv_pages"], rows))

    def sample(b):
        return (spec((b,), F32), spec((b,), jnp.int32), spec((b,), F32),
                spec((b, 2), jnp.uint32))

    def compiled(path, *specs):
        with open(path, "rb") as f:
            exp = jexport.deserialize(f.read())
        return jax.jit(exp.call, donate_argnums=(1,)).lower(
            on_chip(shapes), cache, *specs).compile()

    decode = compiled("%s-decode-w1.stablehlo" % prefix,
                      spec((rows,), jnp.int32), spec((rows,), jnp.int32),
                      spec((rows, 1), jnp.int32), *sample(rows))
    # tokens, three counts (all zero: no expert), log-probabilities
    assert decode.out_info[1].shape == (2 * rows + 3,)
    text = decode.as_text()
    assert "mx.retention_update" in text and "mx.rope" in text
    assert "mx_paged_attention" not in text
    # the update is the kernel, one an ``R`` block, under the scope the
    # benchmark reads; and nothing makes a second copy of a layer's state:
    # its only results of that size are the kernels' own, written over
    # their operand
    assert meta["retention"] == {"decode-w1": {
        "impl": "retention", "reason": None, "sites": 8}}
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "mx_retention_update" in line]
    assert len(calls) == 8
    assert all("mx.retention_update" in line for line in calls)
    state = "f32[%d,%d,8256,128]" % (rows, sz["lm"]["num_kv_heads"])
    made = [line for line in text.splitlines()
            if re.match(r"\s*(?:ROOT )?%\S+ = \(?" + re.escape(state), line)
            and " parameter(" not in line and " get-tuple-element(" not in line
            and " bitcast(" not in line]
    assert made and all(
        "mx_retention_update" in line
        and "output_to_operand_aliasing={{0}: (1, {})}" in line
        for line in made), [line[:200] for line in made]
    prefill = compiled(
        "%s-prefill-s%d.stablehlo" % (prefix, bucket),
        spec((1, bucket), jnp.int32), spec((1,), jnp.int32),
        spec((1, -(-bucket // sz["page_tokens"])), jnp.int32),
        spec((1,), jnp.int32), *sample(1))
    assert "mx.retention_scan" in prefill.as_text()
    cache_bytes = sum(np.prod(c.shape) * c.dtype.itemsize for c in cache)
    assert round(cache_bytes / 2 ** 30, 2) == 4.06
    need = []
    for program in (decode, prefill):
        mem = program.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes       # in place
        need.append(mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    assert max(need) < 15.75 * 2 ** 30, need
    assert min(need) > 0.7 * 15.75 * 2 ** 30    # and the chip is filled


# --------------------------------------- the latent-attention decoder's cell
def test_latent_programs_at_the_cells_sizes_compile_into_the_chip(
        compile_for_chip, kernel_tier_on, monkeypatch, tmp_path):
    """``benchmarks/configs/joyai_flash_pp8.json`` as its driver exports it
    — published widths, layers 0-4 (``LF`` + ``LG`` x 4), all 256 gated
    experts and the whole vocabulary, 64 decode slots over 4,864 latent
    pages of 128 tokens — lowered for the TPU, reloaded and compiled for
    the described chip: the one decode program holds the latent kernel at
    every ``L`` layer and no gather, the gated grouped products are the
    program's kernel, the prefill at the largest bucket attends through
    the K/V-tiled flash kernel, the ONE pool is rewritten in place where it
    lies (its pages' tokens on the lanes, no pool-sized copy or turn), and
    weights, pool and the largest prefill's temporaries fit one chip's
    15.75 GiB.  (Weights are shapes only: nothing is made.)"""
    import json
    import os
    from jax import export as jexport
    from mxnet_tpu import deploy
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    monkeypatch.setattr(jexport, "export", functools.partial(
        jexport.export, platforms=["tpu"]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "joyai_flash_pp8.json")) as f:
        cell = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "offline_docreason_s64.json")) as f:
        traffic = json.load(f)
    sz = cell["sizes"]
    bucket = traffic["prompt_buckets"][-1]
    model = HybridLM(HybridLMConfig(dtype=BF16, **sz["lm"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefix = str(tmp_path / "lm")
    with jax.default_matmul_precision(None):
        deploy.export_generation(
            model, shapes, prefix, sampling=True,
            decode_batch=sz["decode_batch"], prompt_buckets=[bucket],
            max_context=traffic["max_context"],
            page_size=sz["page_tokens"], decode_widths=sz["decode_widths"],
            include_params=False, replay=sz["replay"])
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    width = sz["decode_widths"][-1]
    assert meta["decode_widths"] == [width] == [76]
    assert meta["paged"] == {str(width): {
        "impl": "latent", "reason": None, "quantized": False}}
    assert meta["grouped"] == {program: {
        "impl": "grouped", "reason": None, "sites": 8}
        for program in ("prefill-s%d" % bucket, "decode-w%d" % width)}
    assert meta["replay"] == {"layers": 4, "top_k": 8}
    assert meta["kv"]["pools"] == ["kv"] and meta["kv"]["row_width"] == 576
    spec = compile_for_chip.spec
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: spec(a.shape, a.dtype))
    rows, pages = sz["decode_batch"], cell["knobs"]["serving.kv_pages"]
    cache = on_chip(deploy._kv_pool_specs(meta["kv"], pages, rows))
    assert [c.shape for c in cache] == [(5, 4864, 576, 128)]

    def sample(b):
        return (spec((b,), F32), spec((b,), jnp.int32), spec((b,), F32),
                spec((b, 2), jnp.uint32))

    def compiled(path, *specs):
        with open(path, "rb") as f:
            exp = jexport.deserialize(f.read())
        return jax.jit(exp.call, donate_argnums=(1,)).lower(
            on_chip(shapes), cache, *specs).compile()

    decode = compiled("%s-decode-w%d.stablehlo" % (prefix, width),
                      spec((rows,), jnp.int32), spec((rows,), jnp.int32),
                      spec((rows, width), jnp.int32), *sample(rows))
    # tokens, three counts, log-probabilities, experts [4, rows, 8]
    assert decode.out_info[1].shape == (2 * rows + 3 + 4 * rows * 8,)
    text = decode.as_text()
    assert text.count("mx_latent_paged_attention") >= 5
    assert "mx.kv_gather" not in text and "mx_paged_attention" not in text
    prefill = compiled(
        "%s-prefill-s%d.stablehlo" % (prefix, bucket),
        spec((1, bucket), jnp.int32), spec((1,), jnp.int32),
        spec((1, -(-bucket // sz["page_tokens"])), jnp.int32), *sample(1))
    assert prefill.as_text().count("mx_attention_tiled") >= 5
    pool = "bf16[5,4864,576,128]"
    for program in (text, prefill.as_text()):
        # two grouped products a G block, under the scope that names them
        assert program.count("mx_grouped_matmul") >= 8
        assert "mx.moe_experts" in program and "ragged" not in program
        # the pool keeps the layout it is handed in: no copy turns it
        assert pool + "{3,2,1,0" in program
        assert not [ln for ln in program.splitlines()
                    if pool in ln.split("=")[0] and " copy(" in ln]
        assert pool + "{2,3,1,0" not in program
    cache_bytes = sum(np.prod(c.shape) * c.dtype.itemsize for c in cache)
    assert round(cache_bytes / 2 ** 30, 2) == 3.34
    need = []
    for program in (decode, prefill):
        mem = program.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes       # in place
        need.append(mem.argument_size_in_bytes + mem.temp_size_in_bytes)
    assert max(need) < 15.5 * 2 ** 30, need     # 15.75 less what is reserved
    assert min(need) > 0.85 * 15.75 * 2 ** 30   # and the chip is filled


# ------------------------------------ the sparse / windowed decoder's cell
def test_sparse_programs_at_the_cells_sizes_compile_into_the_chip(
        compile_for_chip, kernel_tier_on, monkeypatch, tmp_path):
    """``benchmarks/configs/dots3_note_ep8.json`` as its driver exports it
    — published widths, layers 0-4 (``SF`` + ``SG`` + ``WG`` x 3), 32 of
    256 gated experts, 64 decode slots over 9,216 pages of 128 tokens (a
    latent row and an index key a token) and a 640-column ring a slot for
    each window layer — lowered for the TPU, reloaded and compiled for the
    described chip: the decode program holds the sparse kernel at both
    ``S`` layers and no gather of whole pages, the prefill at the largest
    bucket attends through the masked K/V-tiled kernel at both and gathers
    no query's selected rows, the pool and the rings are rewritten in
    place, and weights, cache and the temporaries of a decode step and of
    the largest bucket's prefill (16,384: its query chunks' selections,
    the expanded heads) fit one chip's 15.75 GiB.  (Weights are shapes
    only.)"""
    import json
    import os
    from jax import export as jexport
    from mxnet_tpu import deploy
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    monkeypatch.setattr(jexport, "export", functools.partial(
        jexport.export, platforms=["tpu"]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "dots3_note_ep8.json")) as f:
        cell = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "offline_sparsedoc_s64.json")) as f:
        traffic = json.load(f)
    sz = cell["sizes"]
    bucket = traffic["prompt_buckets"][-1]
    model = HybridLM(HybridLMConfig(dtype=BF16, **sz["lm"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefix = str(tmp_path / "lm")
    with jax.default_matmul_precision(None):
        deploy.export_generation(
            model, shapes, prefix, sampling=True,
            decode_batch=sz["decode_batch"], prompt_buckets=[bucket],
            max_context=traffic["max_context"],
            page_size=sz["page_tokens"], decode_widths=sz["decode_widths"],
            include_params=False, replay=sz["replay"])
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    width = sz["decode_widths"][-1]
    assert meta["decode_widths"] == [width] == [144]
    assert meta["paged"] == {str(width): {
        "impl": "sparse", "reason": None, "quantized": False}}
    assert meta["kv"]["row_width"] == 704
    assert meta["sparse_prefill"] == {"prefill-s%d" % bucket: {
        "impl": "masked", "reason": None, "sites": 2}}
    spec = compile_for_chip.spec
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: spec(a.shape, a.dtype))
    rows, pages = sz["decode_batch"], cell["knobs"]["serving.kv_pages"]
    cache = on_chip(deploy._kv_pool_specs(meta["kv"], pages, rows))
    assert [c.shape for c in cache] == [(2, 9216, 704, 128)] \
        + [(64, 1088, 640)] * 3

    def sample(b):
        return (spec((b,), F32), spec((b,), jnp.int32), spec((b,), F32),
                spec((b, 2), jnp.uint32))

    def compiled(path, *specs):
        with open(path, "rb") as f:
            exp = jexport.deserialize(f.read())
        return jax.jit(exp.call, donate_argnums=(1,)).lower(
            on_chip(shapes), cache, *specs).compile()

    decode = compiled("%s-decode-w%d.stablehlo" % (prefix, width),
                      spec((rows,), jnp.int32), spec((rows,), jnp.int32),
                      spec((rows, width), jnp.int32), *sample(rows))
    text = decode.as_text()
    assert text.count("mx_sparse_latent_attention") >= 2
    assert text.count("mx_index_scores") >= 2
    assert "mx.kv_gather" not in text
    # the index keys are read where they lie: no copy of the pool turns it
    assert not [ln for ln in text.splitlines() if " copy(" in ln and (
        "[2,9216,704,128]" in ln or "[18432,704,128]" in ln)]
    prefill = compiled(
        "%s-prefill-s%d.stablehlo" % (prefix, bucket),
        spec((1, bucket), jnp.int32), spec((1,), jnp.int32),
        spec((1, -(-bucket // sz["page_tokens"])), jnp.int32),
        spec((1,), jnp.int32), *sample(1))
    assert prefill.as_text().count("mx_attention_tiled_masked") >= 2
    # no query chunk's gathered rows [1, 64, 2,048, 576] (the twin's)
    assert "[1,64,2048,576]" not in prefill.as_text()
    pool = "bf16[2,9216,704,128]"
    for program in (text, prefill.as_text()):
        assert pool + "{3,2,1,0" in program
        assert not [ln for ln in program.splitlines()
                    if pool in ln.split("=")[0] and " copy(" in ln]
    cache_bytes = sum(np.prod(c.shape) * c.dtype.itemsize for c in cache)
    assert round(cache_bytes / 2 ** 30, 2) == 3.34
    need = []
    for program in (decode, prefill):
        mem = program.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes       # in place
        need.append((mem.argument_size_in_bytes, mem.temp_size_in_bytes))
    print("sizes_analysis", [(round(a / 2 ** 30, 3), round(t / 2 ** 30, 3))
                             for a, t in need])
    assert max(a + t for a, t in need) < 15.5 * 2 ** 30, need
    assert min(a for a, _ in need) > 0.6 * 15.75 * 2 ** 30


def test_block_sparse_programs_at_the_cells_sizes_compile_into_the_chip(
        compile_for_chip, kernel_tier_on, monkeypatch, tmp_path):
    """``benchmarks/configs/minicpm_sala_pp8.json`` as its driver exports it
    — published widths, layers 0-3 (``BF`` + ``NF`` x 3), 64 decode slots
    over 40,960 pages of 64 tokens (K and V of two heads, a head apart), a
    compressed-key row every 16 positions and a float32 ``[32, 128, 128]``
    lightning state a slot for each ``N`` layer — lowered for the TPU,
    reloaded and compiled for the described chip: the decode program
    attends through the paged kernel over the kept pages and updates the
    three lightning states through the retention kernel, the prefill at
    its one bucket (32,768) attends through the masked K/V-tiled kernel
    under a mask of blocks, the pools and the states are rewritten in
    place, and weights, cache and the temporaries of a decode step and of
    the prefill fit one chip's 15.75 GiB.  (Weights are shapes only.)"""
    import json
    import os
    from jax import export as jexport
    from mxnet_tpu import deploy
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    monkeypatch.setattr(jexport, "export", functools.partial(
        jexport.export, platforms=["tpu"]))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "minicpm_sala_pp8.json")) as f:
        cell = json.load(f)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "offline_longdoc_s64.json")) as f:
        traffic = json.load(f)
    sz = cell["sizes"]
    bucket = traffic["prompt_buckets"][-1]
    model = HybridLM(HybridLMConfig(dtype=BF16, **sz["lm"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    prefix = str(tmp_path / "lm")
    with jax.default_matmul_precision(None):
        deploy.export_generation(
            model, shapes, prefix, sampling=True,
            decode_batch=sz["decode_batch"], prompt_buckets=[bucket],
            max_context=traffic["max_context"],
            page_size=sz["page_tokens"], decode_widths=sz["decode_widths"],
            include_params=False, replay=sz["replay"])
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    width = sz["decode_widths"][-1]
    assert meta["decode_widths"] == [width] == [640]
    assert meta["paged"] == {str(width): {
        "impl": "block_sparse", "reason": None, "quantized": False}}
    assert meta["retention"]["decode-w%d" % width] == {
        "impl": "retention", "reason": None, "sites": 3}
    assert meta["sparse_prefill"] == {"prefill-s%d" % bucket: {
        "impl": "masked", "reason": None, "sites": 1}}
    spec = compile_for_chip.spec
    on_chip = functools.partial(jax.tree_util.tree_map,
                                lambda a: spec(a.shape, a.dtype))
    rows, pages = sz["decode_batch"], cell["knobs"]["serving.kv_pages"]
    cache = on_chip(deploy._kv_pool_specs(meta["kv"], pages, rows))
    assert [c.shape for c in cache] == [(1, 40960, 2, 64, 128)] * 2 + [
        (64, 2, 2560, 128), (64, 2, 2, 128)] + [(64, 32, 128, 128)] * 3

    def sample(b):
        return (spec((b,), F32), spec((b,), jnp.int32), spec((b,), F32),
                spec((b, 2), jnp.uint32))

    def compiled(path, *specs):
        with open(path, "rb") as f:
            exp = jexport.deserialize(f.read())
        return jax.jit(exp.call, donate_argnums=(1,)).lower(
            on_chip(shapes), cache, *specs).compile()

    decode = compiled("%s-decode-w%d.stablehlo" % (prefix, width),
                      spec((rows,), jnp.int32), spec((rows,), jnp.int32),
                      spec((rows, width), jnp.int32), *sample(rows))
    text = decode.as_text()
    assert text.count("mx_paged_attention") >= 1
    assert text.count("mx_retention_update") >= 3
    assert "mx.kv_gather" not in text
    prefill = compiled(
        "%s-prefill-s%d.stablehlo" % (prefix, bucket),
        spec((1, bucket), jnp.int32), spec((1,), jnp.int32),
        spec((1, -(-bucket // sz["page_tokens"])), jnp.int32),
        spec((1,), jnp.int32), *sample(1))
    assert prefill.as_text().count("mx_attention_tiled_masked") >= 1
    pool = "bf16[1,40960,2,64,128]"
    for program in (text, prefill.as_text()):
        assert not [ln for ln in program.splitlines()
                    if pool in ln.split("=")[0] and " copy(" in ln]
    cache_bytes = sum(np.prod(c.shape) * c.dtype.itemsize for c in cache)
    assert round(cache_bytes / 2 ** 30, 2) == 2.95
    need = []
    for program in (decode, prefill):
        mem = program.memory_analysis()
        assert mem.alias_size_in_bytes >= cache_bytes       # in place
        need.append((mem.argument_size_in_bytes, mem.temp_size_in_bytes))
    print("sizes_analysis", [(round(a / 2 ** 30, 3), round(t / 2 ** 30, 3))
                             for a, t in need])
    assert max(a + t for a, t in need) < 15.5 * 2 ** 30, need
    assert min(a for a, _ in need) > 0.35 * 15.75 * 2 ** 30
