"""``models.HybridLM``'s power-retention (``R``) and gated-MLP (``F``)
blocks against the plain reference (``benchmarks/reference/
brumby_14b_pp5.py``: float32, the quadratic form that defines the layer,
and a token-by-token recurrence beside it), at tiny sizes, seeded, on the
cpu backend (float32, full-precision products: ``conftest.py``).

What is held here: the state's layout gives ``phi(q) . phi(k) = (q . k)^2``;
the chunked form, the recurrence and the quadratic form are one function;
the query heads of a group read one K/V head's state; a padded prefill
then decode steps through the state, rotary at the decode positions, is
the full forward; a stack with NO attending layer — no K/V page at all —
goes through ``export_generation`` and the server like any other, slots
reused from a zero state; a pattern that mixes the new kinds with the old
still serves; the cache's bytes are the benchmark's count.
"""
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from benchmarks.harness import manifest
from mxnet_tpu import telemetry
from mxnet_tpu.models import HybridLM, HybridLMConfig
from mxnet_tpu.models import hybrid

REF = manifest.load_module("reference", "brumby_14b_pp5")
OPS = manifest.load_module("ops_bytes", "brumby_14b_pp5")
PAGE = 4
SIZES = dict(vocab_size=96, pattern="RFRF", d_model=32, num_heads=4,
             num_kv_heads=2, head_dim=8, mlp_ff=48, chunk=4, max_len=64,
             rope_theta=1e4, eps=1e-6, dtype=jnp.float32)
REF_LM = {"rope_theta": 1e4, "eps": 1e-6}
# the old kinds' sizes, for a pattern that mixes them in
MIXED = dict(ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
             conv_kernel=4)


def _tiny(**over):
    model = HybridLM(HybridLMConfig(**dict(SIZES, **over)))
    return model, model.init(jax.random.PRNGKey(0))


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), \
        np.abs(got - want).max()


# ------------------------------------------------------------- the layout
@pytest.mark.parametrize("dh", [2, 8, 16])
def test_the_states_layout_squares_the_inner_product(dh):
    """``phi`` is the exact upper triangle, ``dh (dh + 1) / 2`` wide, and
    ``phi(q) . phi(k) = (q . k)^2``; the reference's own ``phi`` (built
    pair by pair) has the same entries in the same order."""
    rng = np.random.default_rng(dh)
    q = rng.normal(size=(5, dh)).astype(np.float32)
    k = rng.normal(size=(5, dh)).astype(np.float32)
    pq, pk = hybrid._phi(jnp.asarray(q)), hybrid._phi(jnp.asarray(k))
    assert pq.shape == (5, dh * (dh + 1) // 2) and pq.dtype == jnp.float32
    _close((pq * pk).sum(-1), np.square((q * k).sum(-1)), 1e-6)
    _close(pq, REF.phi(jnp.asarray(q)), 1e-6)


# --------------------------------------------------------- the recurrence
def _retention_inputs(length, B=2, KV=2, R=3, Dh=4, Q=4):
    rng = np.random.default_rng(length)
    S = -(-length // Q) * Q
    # (entries of one sign: ``q . k`` then stays away from zero, where
    # ``phi(q) . phi(k)`` cancels in float32 and ``(q . k)^2`` does not)
    q = np.abs(rng.normal(size=(B, S, KV, R, Dh))).astype(np.float32)
    k = np.abs(rng.normal(size=(B, S, KV, Dh))).astype(np.float32)
    v = rng.normal(size=(B, S, KV, Dh)).astype(np.float32)
    logg = np.log(rng.uniform(0.5, 0.999, size=(B, S, KV))
                  ).astype(np.float32)
    k[:, length:] = 0.0          # what a padded position is handed
    logg[:, length:] = 0.0
    return q, k, v, logg


@pytest.mark.parametrize("length", [1, 3, 4, 5, 11, 13, 16])
def test_chunked_retention_is_the_recurrence_and_the_quadratic_form(length):
    """``_retention_scan`` over chunks of 4 (the sequence padded to the
    boundary with zero keys and gates of one) = the reference's
    token-by-token recurrence = its quadratic form, and the state it
    leaves is the recurrence's at ``length``: lengths on and off the
    chunk's edge."""
    q, k, v, logg = _retention_inputs(length)
    Y, state, z = hybrid._retention_scan(
        *map(jnp.asarray, (q, k, v, logg)), 4)
    for b in range(q.shape[0]):
        part = [jnp.asarray(a[b, :length]) for a in (q, k, v, logg)]
        quad = REF.retention_quadratic(*part)
        _close(REF.retention_recurrent(*part), quad)
        _close(Y[b, :length], quad)
    s = np.zeros(state.shape, np.float32)
    n = np.zeros(z.shape, np.float32)
    for t in range(length):
        g = np.exp(logg[:, t])
        pk = np.asarray(hybrid._phi(jnp.asarray(k[:, t])))
        s = g[..., None, None] * s + pk[..., None] * v[:, t, :, None, :]
        n = g[..., None] * n + pk
    _close(state, s)
    _close(z, n)


def test_every_query_head_of_a_group_reads_its_k_v_heads_state():
    """Five query heads over one K/V head: each head's read-out is what a
    model with that query head alone computes from the same keys, values
    and gates — one state serves the group."""
    q, k, v, logg = _retention_inputs(9, B=1, KV=2, R=5)
    Y, state, _ = hybrid._retention_scan(
        *map(jnp.asarray, (q, k, v, logg)), 4)
    for r in range(5):
        alone, same, _ = hybrid._retention_scan(
            *map(jnp.asarray, (q[:, :, :, r:r + 1], k, v, logg)), 4)
        _close(alone[:, :, :, 0], Y[:, :, :, r])
        _close(same, state)


# ------------------------------------------- prefill, decode, the forward
@pytest.mark.parametrize("lengths", [(13, 6), (16, 1), (3, 9)])
def test_padded_prefill_then_decode_is_the_full_forward(lengths):
    """Prompts padded to a bucket of 16, prefilled into chosen state slots
    of a cache WITHOUT pages, then five decode steps, rotary at each
    row's own position: every step's logits are the reference's one full
    forward over the tokens so far (logits, not tokens)."""
    model, params = _tiny()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32) for n in lengths]
    buf = np.full((2, 16), 7, np.int32)          # padding is not zeros
    for b, p in enumerate(prompts):
        buf[b, :len(p)] = p
    kv = model.init_kv_pages(1, PAGE, slots=4)
    assert kv["k"].shape[0] == 0                 # no layer attends
    table = np.full((2, 4), 1, np.int32)         # names no page
    slots = np.array([2, 0], np.int32)
    kv, ids, logits = model.prefill(
        params, kv, jnp.asarray(buf), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(table), PAGE, return_logits=True,
        slots=jnp.asarray(slots))
    seqs = {int(s): list(p) for s, p in zip(slots, prompts)}
    for b, s in enumerate(slots):
        _close(logits[b], REF.logits(params, prompts[b], lm=REF_LM)[-1])
    nxt = {int(s): int(ids[b]) for b, s in enumerate(slots)}
    tab = np.full((4, 1), 1, np.int32)
    for _ in range(5):
        tok, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
        for s, seq in seqs.items():
            tok[s], pos[s] = nxt[s], len(seq)
            seq.append(nxt[s])
        kv, ids, logits = model.decode_step(
            params, kv, jnp.asarray(tok), jnp.asarray(pos),
            jnp.asarray(tab), PAGE, return_logits=True)
        for s, seq in seqs.items():
            _close(logits[s], REF.logits(
                params, np.asarray(seq, np.int32), lm=REF_LM)[-1])
            nxt[s] = int(ids[s])


def test_apply_is_the_reference_forward():
    model, params = _tiny(pattern="RFRFRF")
    toks = np.random.default_rng(1).integers(0, 96, (2, 13)).astype(np.int32)
    got = model.apply(params, jnp.asarray(toks))
    for b in range(2):
        _close(got[b], REF.logits(params, toks[b], lm=REF_LM))


def test_init_draws_the_gates_where_trained_ones_lie():
    """Gate biases put ``g`` in [0.9, 0.999], head norms are one, and
    each block's last matrix is drawn over the square root of the whole
    stack's depth."""
    model, params = _tiny(depth=80)
    shallow = _tiny(depth=None)[1]
    for name, kind in zip(model.names, model.kinds):
        lp = params["layers"][name]
        if kind == "R":
            g = np.asarray(jax.nn.sigmoid(lp["bg"]))
            assert lp["bg"].dtype == jnp.float32
            assert ((g >= 0.9) & (g <= 0.999)).all(), g
            assert (np.asarray(lp["qn"]) == 1).all() \
                and (np.asarray(lp["kn"]) == 1).all()
        last = "wo" if kind == "R" else "w_down"
        _close(np.asarray(lp[last]) * np.sqrt(80.0 / 4.0),
               shallow["layers"][name][last], 1e-6)


def test_the_caches_bytes_are_the_benchmarks_count():
    """``kv_spec()``'s state region, a row a slot, is what ``ops_bytes``
    counts a row at (the exact width of the key's square), at the
    published cut: 272.6 MB a slot, 34.08 MB = 32.5 MiB a block."""
    with open(manifest.BENCH_DIR + "/configs/brumby_14b_pp5.json") as f:
        lm = json.load(f)["sizes"]["lm"]
    spec = HybridLM(HybridLMConfig(**lm)).kv_spec()
    assert spec["num_layers"] == 0
    names = [s["name"] for s in spec["state"]]
    assert names[:2] == ["ret00", "retz00"] and len(names) == 16
    assert spec["state"][0]["shape"] == [8, 8256, 128]
    assert spec["state"][1]["shape"] == [8, 8256]
    row = sum(int(np.prod(s["shape"])) * np.dtype(s["dtype"]).itemsize
              for s in spec["state"])
    assert row == OPS.state_bytes_per_row(lm) == 8 * 34080768
    assert OPS.scope_bytes(lm, 16, 0, 0)["mx.retention_update"] == 32 * row
    assert round(OPS.parameter_count(lm) * 2 / 2 ** 30, 2) == 7.82


# ------------------------------------------------- through the artifact
def _serve(tmp_path, pattern, slots=2, **over):
    mx.config.set("kernels.enabled", True)
    mx.config.set("serving.kv_pages", 1 if "*" not in pattern else 64)
    mx.config.set("serving.decode_slots", slots)
    model, params = _tiny(pattern=pattern, **over)
    prefix = str(tmp_path / "lm")
    mx.deploy.export_generation(
        model, params, prefix, sampling=True, decode_batch=slots,
        prompt_buckets=[8, 16], max_context=64, page_size=PAGE)
    srv = mx.serving.Server()
    engine = srv.register("lm", prefix, generate=True)
    srv.start()
    return model, params, prefix, srv, engine


@pytest.fixture
def served(tmp_path):
    """An ``RFRF`` stack — no layer attends — exported as the benchmark's
    driver does and registered with a started server over TWO slots and a
    pool of one page."""
    out = _serve(tmp_path, "RFRF")
    try:
        yield out
    finally:
        out[3].stop()
        for knob in ("kernels.enabled", "serving.kv_pages",
                     "serving.decode_slots"):
            mx.config.unset(knob)


def test_a_stack_without_pages_serves_the_oracles_tokens(served):
    """``export_generation`` -> ``Server.register(generate=True)`` with
    zero attending layers: five requests interleaved over two slots each
    get the cache-free greedy oracle's tokens; the artifact has one decode
    program at a one-column table and no paged route, no request waits
    for a page or is counted as a paged fallback, and the engine says how
    many bytes of state its slots hold."""
    model, params, prefix, srv, engine = served
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["kv"]["num_layers"] == 0 and meta["paged"] == {}
    assert meta["decode_widths"] == [1]
    assert [s["name"] for s in meta["kv"]["state"]] == [
        "ret00", "retz00", "ret02", "retz02"]
    assert not engine.predictor.paged
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 11, 3, 16, 7)]
    oracle = [model.greedy_decode(params, p, 9) for p in prompts]
    waits = telemetry.counter("serving.kv_pool_exhausted").value
    fell_back = telemetry.counter("kernels.paged_fallback").value
    futures = [srv.submit_generate("lm", p, 9) for p in prompts]
    for want, f in zip(oracle, futures):
        assert (f.result(timeout=300) == want).all()
    assert telemetry.counter("serving.kv_pool_exhausted").value == waits
    assert telemetry.counter("kernels.paged_fallback").value == fell_back
    assert engine.stats()["kv_pages_free"] == 1      # none was ever taken
    row = sum(int(np.prod(s["shape"])) * 4 for s in meta["kv"]["state"])
    assert telemetry.gauge("serving.state_bytes").value == 2 * row
    gp = mx.deploy.load_generator(prefix)
    assert (gp.generate(prompts[1], 9) == oracle[1]).all()


def test_a_long_request_needs_no_page(served):
    """A request as long as the artifact's context is admitted over a
    pool of one page: decode slots alone bound admission."""
    model, params, _, srv, engine = served
    prompt = np.arange(16, dtype=np.int32)
    got = srv.submit_generate("lm", prompt, 48).result(timeout=300)
    assert (got == model.greedy_decode(params, prompt, 48)).all()


def test_a_slot_used_again_starts_from_a_zero_state(served):
    """The same prompt served into a slot that a DIFFERENT, longer request
    just left gives the tokens it gave into a fresh cache: the prefill
    rewrites the slot's state and normaliser whole."""
    model, params, _, srv, engine = served
    rng = np.random.default_rng(2)
    probe = rng.integers(0, 96, (6,)).astype(np.int32)
    fresh = srv.submit_generate("lm", probe, 8).result(timeout=300)
    assert (fresh == model.greedy_decode(params, probe, 8)).all()
    for n in (15, 13):              # dirty both slots
        srv.submit_generate("lm", rng.integers(0, 96, (n,)).astype(np.int32),
                            12).result(timeout=300)
    assert all(np.asarray(a).any(axis=tuple(range(1, a.ndim))).all()
               for a in engine._kv[2:])
    again = [srv.submit_generate("lm", probe, 8) for _ in range(2)]
    for f in again:
        assert (f.result(timeout=300) == fresh).all()


def test_a_pattern_that_mixes_the_kinds_still_serves(tmp_path):
    """``R`` and ``F`` beside Mamba-2 and an attending layer: pages, a
    recurrent state and a retention state in one cache, through the
    server, the oracle's tokens."""
    model, params, prefix, srv, engine = _serve(
        tmp_path, "RFM*RF", slots=3, **MIXED)
    try:
        with open(prefix + "-meta.json") as f:
            meta = json.load(f)
        assert meta["kv"]["num_layers"] == 1
        assert [s["name"] for s in meta["kv"]["state"]] == [
            "ret00", "retz00", "ssm02", "conv02", "ret04", "retz04"]
        assert list(meta["paged"]) == [str(w) for w in meta["decode_widths"]]
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
                   for n in (5, 12, 16, 9)]
        oracle = [model.greedy_decode(params, p, 7) for p in prompts]
        futures = [srv.submit_generate("lm", p, 7) for p in prompts]
        for want, f in zip(oracle, futures):
            assert (f.result(timeout=300) == want).all()
    finally:
        srv.stop()
        for knob in ("kernels.enabled", "serving.kv_pages",
                     "serving.decode_slots"):
            mx.config.unset(knob)


@pytest.mark.parametrize("program,scopes", [
    ("decode", ("mx.retention_update", "mx.rope", "mx.mlp", "mx.qkv")),
    ("prefill", ("mx.retention_scan", "mx.rope", "mx.mlp", "mx.qkv")),
    ("decode-kernel", ("mx.retention_update", "mx_retention_update",
                       "mx.rope", "mx.mlp", "mx.qkv"))])
def test_retention_programs_carry_their_scopes(program, scopes, monkeypatch,
                                               kernel_knobs):
    """The device scopes the benchmark's readers look for are in the
    lowered programs' operation names: on the twin's route (tiny heads),
    and on the kernel's (heads of 128, lowered for the TPU with the tier
    on), where the custom call lies under the update's scope."""
    i32 = jnp.int32
    if program == "decode-kernel":
        from mxnet_tpu import rtc
        monkeypatch.setattr(rtc, "interpret_mode", lambda: False)
        mx.config.set("kernels.enabled", True)
        model, params = _tiny(pattern="RF", num_heads=2, num_kv_heads=1,
                              head_dim=128)
        kv = model.init_kv_pages(1, PAGE, slots=2)
        text = jax.jit(lambda p, c: model.decode_step(
            p, c, jnp.zeros((2,), i32), jnp.ones((2,), i32),
            jnp.ones((2, 1), i32), PAGE)).trace(params, kv).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
        (call,) = [line for line in text.splitlines()
                   if "@tpu_custom_call" in line]
        where = call[call.rindex("loc(") + 4:].rstrip(")")
        (named,) = [line for line in text.splitlines()
                    if line.startswith(where + " = ")]
        assert "mx.retention_update" in named \
            and "mx_retention_update" in named, named
        assert telemetry.counter("kernels.retention_update").value == 1
    else:
        model, params = _tiny()
        kv = model.init_kv_pages(1, PAGE, slots=2)
        if program == "decode":
            lowered = jax.jit(lambda p, c: model.decode_step(
                p, c, jnp.zeros((2,), i32), jnp.ones((2,), i32),
                jnp.ones((2, 1), i32), PAGE)).lower(params, kv)
        else:
            lowered = jax.jit(lambda p, c: model.prefill(
                p, c, jnp.zeros((2, 8), i32), jnp.full((2,), 5, i32),
                jnp.ones((2, 2), i32), PAGE)).lower(params, kv)
        text = lowered.as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope


# ------------------------------------------- the update's kernel and twin
@pytest.fixture
def kernel_knobs():
    """The tier's knobs as the test found them, and fresh counters."""
    telemetry.reset()
    yield
    mx.config.unset("kernels.enabled")
    mx.config.unset("kernels.vmem_budget")


def _update_case(dh, r, b, kvh, tokens=3, seed=0):
    """q [b,S,kvh,r,dh], k, v [b,S,kvh,dh], log-gates [b,S,kvh]: entries
    of one sign (``_retention_inputs``), and row 0's last token a padded
    position, a zero key under a gate of one."""
    rng = np.random.default_rng(seed)
    q = np.abs(rng.normal(size=(b, tokens, kvh, r, dh))) / np.sqrt(dh)
    k = np.abs(rng.normal(size=(b, tokens, kvh, dh))) / np.sqrt(dh)
    v = rng.normal(size=(b, tokens, kvh, dh))
    logg = np.log(rng.uniform(0.5, 0.999, size=(b, tokens, kvh)))
    k[0, -1] = 0.0
    logg[0, -1] = 0.0
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, logg)]


#: head width, query heads a K/V head, rows, K/V heads, state rows a step
UPDATE_CASES = {
    "one-query-head-3-tiles": (128, 1, 1, 1, 2752),
    "five-heads-8-tiles-2x2": (128, 5, 2, 2, 1032),
    "five-heads-2-tiles": (128, 5, 1, 1, 4128),
    "three-heads-1-tile": (128, 3, 1, 2, 8256),
    "nine-heads-of-256-4-tiles": (256, 9, 1, 1, 8224),
}


@pytest.mark.parametrize("case", list(UPDATE_CASES))
def test_the_update_kernel_is_its_twin_and_the_reference(case, kernel_knobs):
    """``pallas_retention_update`` (interpreted, under an explicit
    ``kernels.enabled``) against ``_retention_update_xla`` token by token
    from the zero state — state, normaliser and read-outs to float32
    reassociation error — and its ``num / den`` against the reference's
    recurrence; a padded position (zero key, gate one) leaves state and
    normaliser as they were bit for bit.  The tile follows from the shapes
    and ``kernels.vmem_budget`` alone."""
    from mxnet_tpu import kernels
    from mxnet_tpu.ops.pallas_kernels import retention_row_tile
    dh, r, b, kvh, tile = UPDATE_CASES[case]
    n = dh * (dh + 1) // 2
    mx.config.set("kernels.enabled", True)
    mx.config.set("kernels.vmem_budget", tile * dh * 4)
    assert retention_row_tile(n, dh) == tile and n % tile == 0
    q, k, v, logg = _update_case(dh, r, b, kvh)
    state = jnp.zeros((b, kvh, n, dh), jnp.float32)
    z = jnp.zeros((b, kvh, n), jnp.float32)
    site, twin = jax.jit(kernels.retention_update), \
        jax.jit(kernels._retention_update_xla)
    ys = []
    for t in range(q.shape[1]):
        args = (state, z, hybrid._phi(k[:, t]), hybrid._phi(q[:, t]),
                jnp.exp(logg[:, t]), v[:, t])
        got, want = site(*args), twin(*args)
        for a, w in zip(got, want):
            assert a.shape == w.shape and a.dtype == jnp.float32
            _close(a, w, 2e-6)
        ys.append(got[2] / got[3][..., None])
        if t == q.shape[1] - 1:
            assert np.array_equal(got[0][0], state[0])
            assert np.array_equal(got[1][0], z[0])
            if b > 1:               # and the other rows moved
                assert not np.array_equal(got[0][-1], state[-1])
        state, z = got[:2]
    assert telemetry.counter("kernels.retention_update").value == 1
    assert telemetry.counter("kernels.retention_fallback").value == 0
    y = jnp.stack(ys, axis=1)
    for row in range(b):
        _close(y[row], REF.retention_recurrent(
            q[row], k[row], v[row], logg[row]), 2e-5)


def test_the_update_routes_by_knob_backend_and_shape(kernel_knobs):
    """Tier off -> the twin; the default knob on the cpu -> the twin and
    ``kernels.gated_fallback``; explicit on -> the kernel and
    ``kernels.retention_update``; a shape the check refuses (a bfloat16
    state, heads of 16, a symbolic dim) -> the twin and
    ``kernels.retention_fallback``, never an error."""
    from jax import export as jexport
    from mxnet_tpu import kernels

    def case(dh=128, dtype=jnp.float32, b=1):
        n = dh * (dh + 1) // 2
        shapes = ((b, 1, n, dh), (b, 1, n), (b, 1, n), (b, 1, 2, n),
                  (b, 1), (b, 1, dh))
        return [jax.ShapeDtypeStruct(s, dtype if i == 0 else jnp.float32)
                for i, s in enumerate(shapes)]

    def count():
        return {c: telemetry.counter("kernels." + c).value for c in (
            "retention_update", "retention_fallback", "gated_fallback")}

    def routed(*specs):
        with kernels.record_retention_routes() as routes:
            # (a fresh function: a cached trace would not ask again)
            out = jax.eval_shape(
                lambda *a: kernels.retention_update(*a), *specs)
        assert [o.shape for o in out] == [
            specs[0].shape, specs[1].shape,
            specs[3].shape[:3] + specs[5].shape[2:], specs[3].shape[:3]]
        return routes

    mx.config.set("kernels.enabled", False)
    assert routed(*case()) == [{"impl": "xla", "reason": "tier off"}]
    mx.config.unset("kernels.enabled")
    assert routed(*case()) == [{"impl": "xla", "reason": "interpreted"}]
    assert count() == dict(retention_update=0, retention_fallback=0,
                           gated_fallback=1)
    mx.config.set("kernels.enabled", True)
    assert routed(*case()) == [{"impl": "retention", "reason": None}]
    assert count() == dict(retention_update=1, retention_fallback=0,
                           gated_fallback=1)
    for refused, why in ((case(dtype=jnp.bfloat16), "float32"),
                         (case(dh=16), "multiple of 128")):
        (route,) = routed(*refused)
        assert route["impl"] == "xla" and why in route["reason"]
    mx.config.set("kernels.vmem_budget", 1024)
    (route,) = routed(*case())
    assert route["impl"] == "xla" and "vmem budget" in route["reason"]
    mx.config.unset("kernels.vmem_budget")
    (rows,) = jexport.symbolic_shape("rows")
    with kernels.record_retention_routes() as routes:
        jexport.export(jax.jit(kernels.retention_update))(*case(b=rows))
    assert routes[0]["impl"] == "xla" and "symbolic" in routes[0]["reason"]
    assert count() == dict(retention_update=1, retention_fallback=4,
                           gated_fallback=1)
