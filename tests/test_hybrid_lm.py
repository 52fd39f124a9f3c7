"""``models.HybridLM`` — Mamba-2, latent mixture-of-experts and grouped-query
attention blocks in one pattern-built stack — against the plain reference
(``benchmarks/reference/nemotron3_super_ep4.py``: float32, a sequential
recurrence, every held expert applied densely), at tiny sizes, seeded, on
the cpu backend (float32, full-precision products: ``conftest.py``).

What is held here: the block form of the recurrence is the recurrence; a
padded prefill then decode steps through pages and state is the full
forward; the router and the held experts are the reference's; four shares
of an expert layer add up to the whole; a slot that is used again carries
nothing over; the artifact round-trips through the server.
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
from mxnet_tpu.parallel import moe

REF = manifest.load_module("reference", "nemotron3_super_ep4")
PAGE = 4
SIZES = dict(vocab_size=96, pattern="MEM*E", d_model=32, num_heads=4,
             num_kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=8,
             ssm_groups=2, ssm_state=16, conv_kernel=4, chunk=4,
             num_experts=16, top_k=3, moe_latent=16, expert_ff=24,
             shared_ff=40, route_scale=2.5, experts_held=8, expert_offset=4,
             max_len=64, dtype=jnp.float32)
REF_LM = {"ssm_groups": 2, "top_k": 3, "route_scale": 2.5,
          "expert_offset": 4}


def _tiny(**over):
    model = HybridLM(HybridLMConfig(**dict(SIZES, **over)))
    return model, model.init(jax.random.PRNGKey(0))


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), \
        np.abs(got - want).max()


# --------------------------------------------------------- the recurrence
@pytest.mark.parametrize("length", [1, 3, 4, 5, 11, 13, 16])
def test_chunked_recurrence_is_the_sequential_one(length):
    """``_ssd`` over chunks of 4 (the sequence padded to the boundary with
    zero steps) = the recurrence token by token, outputs and final state,
    at lengths on and off the chunk boundary."""
    B, G, R, P, N, Q = 2, 2, 3, 5, 7, 4
    rng = np.random.default_rng(length)
    S = -(-length // Q) * Q
    X = rng.normal(size=(B, S, G, R, P)).astype(np.float32)
    Bm = rng.normal(size=(B, S, G, N)).astype(np.float32)
    Cm = rng.normal(size=(B, S, G, N)).astype(np.float32)
    step = rng.uniform(0.01, 0.5, size=(B, S, G, R)).astype(np.float32)
    step[:, length:] = 0.0
    rate = -rng.uniform(0.5, 4.0, size=(G, R)).astype(np.float32)
    Y, state = hybrid._ssd(*map(jnp.asarray, (X, Bm, Cm, step, rate)), Q)
    s = np.zeros((B, G, R, P, N), np.float32)
    for t in range(length):
        s = np.exp(step[:, t] * rate)[..., None, None] * s \
            + (step[:, t, ..., None] * X[:, t])[..., None] \
            * Bm[:, t, :, None, None, :]
        _close(Y[:, t], (s * Cm[:, t, :, None, None, :]).sum(-1))
    _close(state, s)


@pytest.mark.parametrize("lengths", [(13, 6), (16, 1), (3, 9)])
def test_padded_prefill_then_decode_is_the_full_forward(lengths):
    """Prompts padded to a bucket of 16, prefilled into pages and into
    chosen state slots, then five decode steps through pages and state:
    every step's logits are the reference's one full forward over the
    tokens so far (logits, not tokens)."""
    model, params = _tiny()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32) for n in lengths]
    buf = np.zeros((2, 16), np.int32)
    for b, p in enumerate(prompts):
        buf[b, :len(p)] = p
        buf[b, len(p):] = 7                      # padding is not zeros
    kv = model.init_kv_pages(32, PAGE, slots=4)
    table = np.arange(16, dtype=np.int32).reshape(2, 8)
    slots = np.array([2, 0], np.int32)
    kv, ids, logits = model.prefill(
        params, kv, jnp.asarray(buf), jnp.asarray(lengths, jnp.int32),
        jnp.asarray(table), PAGE, return_logits=True,
        slots=jnp.asarray(slots))
    seqs = {int(s): list(p) for s, p in zip(slots, prompts)}
    for b, s in enumerate(slots):
        _close(logits[b], REF.logits(params, prompts[b], lm=REF_LM)[-1])
    nxt = {int(s): int(ids[b]) for b, s in enumerate(slots)}
    tab = np.full((4, 8), 32, np.int32)
    tab[2], tab[0] = table[0], table[1]
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
    model, params = _tiny(pattern="MEM*EME*")
    toks = np.random.default_rng(1).integers(0, 96, (2, 13)).astype(np.int32)
    got = model.apply(params, jnp.asarray(toks))
    for b in range(2):
        _close(got[b], REF.logits(params, toks[b], lm=REF_LM))


# ------------------------------------------------------ router and experts
def _expert_layer(seed=0, T=9, D=32, E=16, Z=16, F=24, Fs=40):
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda k, *s: jax.random.normal(k, s, jnp.float32) * 0.3  # noqa
    lp = {"ln": 1.0 + n(keys[0], D), "router": n(keys[1], D, E),
          "select_bias": n(keys[2], E), "w_down": n(keys[3], D, Z),
          "w_up": n(keys[4], Z, D), "w1": n(keys[5], E, Z, F),
          "w2": n(keys[6], E, F, Z), "v1": n(keys[7], D, Fs),
          "v2": n(keys[8], Fs, D)}
    return lp, jax.random.normal(jax.random.PRNGKey(seed + 100), (T, D))


@pytest.mark.parametrize("k", [2, 3, 5])
def test_router_and_held_experts_are_the_references(k):
    """Sigmoid scores, the selection bias in the choice only, normalised
    weights x scale, experts 4..11 of 16 held: the layer's output is the
    reference's (which applies every held expert to every token with
    weight 0 where it was not chosen), and the bias moves the choice."""
    lp, x = _expert_layer()
    held = dict(lp, w1=lp["w1"][4:12], w2=lp["w2"][4:12])
    model, _ = _tiny(top_k=k, experts_held=8, expert_offset=4)
    got, stats, chosen = model._moe(x, held)
    lm = dict(REF.PUBLISHED, top_k=k, route_scale=2.5, expert_offset=4)
    _close(got, REF._experts(x, held, lm, None))
    h = hybrid._norm(x, lp["ln"], 1e-5)
    experts, weights = moe.sigmoid_top_k(h, lp["router"],
                                         lp["select_bias"], k, 2.5)
    assert (np.asarray(chosen) == np.asarray(experts)).all()
    unbiased, _ = moe.sigmoid_top_k(h, lp["router"],
                                    jnp.zeros_like(lp["select_bias"]), k, 2.5)
    assert (np.sort(experts, 1) != np.sort(unbiased, 1)).any()
    _close(weights.sum(-1), np.full(9, 2.5))
    on_held = (np.asarray(experts) >= 4) & (np.asarray(experts) < 12)
    assert int(stats["pairs"]) == on_held.sum()
    assert int(stats["experts_hit"]) == len(
        set(np.asarray(experts)[on_held].tolist()))
    assert int(stats["max_load"]) == np.bincount(
        np.asarray(experts)[on_held]).max()


@pytest.mark.parametrize("kind", ["E", "G"])
def test_four_shares_of_an_expert_layer_add_up_to_the_whole(kind):
    """One expert layer cut four ways (4 of 16 experts a share, the router
    whole): the shares' routed parts — an ``E`` layer's summed in the
    latent, through ``w_up`` ONCE; a ``G`` layer's at full width — plus
    the shared expert ONCE, equal the uncut reference; and each share
    alone is the reference given that share."""
    if kind == "G":
        _gated_shares_add_up()
        return
    lp, x = _expert_layer(seed=3)
    model, _ = _tiny()
    h = hybrid._norm(x, lp["ln"], 1e-5)
    experts, weights = moe.sigmoid_top_k(h, lp["router"],
                                         lp["select_bias"], 3, 2.5)
    u = h @ lp["w_down"]
    latent = 0.0
    for share in range(4):
        lo = 4 * share
        y, stats = moe.dropless_experts(
            u, experts, weights, lp["w1"][lo:lo + 4], lp["w2"][lo:lo + 4],
            expert_offset=lo)
        latent = latent + y
        part = dict(lp, w1=lp["w1"][lo:lo + 4], w2=lp["w2"][lo:lo + 4])
        lm = dict(REF.PUBLISHED, top_k=3, route_scale=2.5, expert_offset=lo)
        shared = jnp.square(jax.nn.relu(h @ lp["v1"])) @ lp["v2"]
        _close(y @ lp["w_up"] + shared, REF._experts(x, part, lm, None))
    whole = dict(REF.PUBLISHED, top_k=3, route_scale=2.5, expert_offset=0)
    shared = jnp.square(jax.nn.relu(h @ lp["v1"])) @ lp["v2"]
    _close(latent @ lp["w_up"] + shared, REF._experts(x, lp, whole, None))


def _gated_shares_add_up():
    joy = manifest.load_module("reference", "joyai_flash_pp8")
    _, params = _tiny(pattern="G", experts_held=16, expert_offset=0)
    lp = params["layers"]["00"]
    x = jax.random.normal(jax.random.PRNGKey(103), (9, 32))
    h = hybrid._norm(x, lp["ln"], 1e-5)
    shared = (jax.nn.silu(h @ lp["v_gate"]) * (h @ lp["v_up"])) \
        @ lp["v_down"]
    lm = dict(joy.PUBLISHED, top_k=3, route_scale=2.5, eps=1e-5)
    routed = 0.0
    for share in range(4):
        lo = 4 * share
        part = dict(lp, **{k: lp[k][lo:lo + 4]
                           for k in ("w_gate", "w_up", "w_down")})
        model, _ = _tiny(pattern="G", experts_held=4, expert_offset=lo)
        y, _, _ = model._gated_moe(x, part)
        routed = routed + (y - shared)
        _close(y, joy._experts_routed(x, part, dict(lm, expert_offset=lo),
                                      None, None)[0])
    _close(routed + shared, joy._experts_routed(
        x, lp, dict(lm, expert_offset=0), None, None)[0])


def test_rows_without_a_request_route_to_no_expert():
    lp, x = _expert_layer()
    h = hybrid._norm(x, lp["ln"], 1e-5)
    experts, weights = moe.sigmoid_top_k(h, lp["router"],
                                         lp["select_bias"], 3, 2.5)
    valid = jnp.arange(9) % 2 == 0
    y, stats = moe.dropless_experts(h @ lp["w_down"], experts, weights,
                                    lp["w1"], lp["w2"], rows_valid=valid)
    full, _ = moe.dropless_experts(h @ lp["w_down"], experts, weights,
                                   lp["w1"], lp["w2"])
    assert int(stats["pairs"]) == 5 * 3
    _close(y[::2], full[::2])
    assert not np.asarray(y[1::2]).any()


@pytest.mark.parametrize("holes", [False, True], ids=["all_rows", "holes"])
@pytest.mark.parametrize("tokens", [8, 40], ids=["decode", "prefill"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_held_experts_are_equal_on_both_routes(dtype, tokens, holes):
    """``dropless_experts`` at widths the Pallas grouped product takes
    (interpreter, explicit knob) against the ``lax.ragged_dot`` route
    (tier off): 6 of 16 experts held, so most pairs trail behind the
    groups, at a decode step's handful of rows a group and at a
    prefill's, with and without rows that carry no request; the counters
    say which route ran."""
    lp, x = _expert_layer(seed=5, T=tokens, D=32, E=16, Z=128, F=256)
    u = (x @ lp["w_down"]).astype(dtype)
    experts, weights = moe.sigmoid_top_k(
        hybrid._norm(x, lp["ln"], 1e-5), lp["router"], lp["select_bias"],
        5, 2.5)
    valid = jnp.arange(tokens) % 3 != 1 if holes else None
    w1, w2 = (lp[n][3:9].astype(dtype) for n in ("w1", "w2"))

    def run():
        return jax.jit(lambda: moe.dropless_experts(
            u, experts, weights, w1, w2, expert_offset=3,
            rows_valid=valid))()

    names = ("kernels.grouped_matmul", "kernels.grouped_fallback",
             "kernels.gated_fallback")
    outs = {}
    try:
        for route, setting, moved in (("kernel", True, names[0]),
                                      ("xla", False, None),
                                      ("gated", None, names[2])):
            if setting is None:
                mx.config.unset("kernels.enabled")
            else:
                mx.config.set("kernels.enabled", setting)
            telemetry.reset()
            outs[route] = run()
            assert {n: telemetry.counter(n).value for n in names} \
                == {n: 2 * (n == moved) for n in names}, route
    finally:
        mx.config.unset("kernels.enabled")
    (y, stats), (want, want_stats) = outs["kernel"], outs["xla"]
    assert {k: int(v) for k, v in stats.items()} \
        == {k: int(v) for k, v in want_stats.items()}
    assert 0 < int(stats["pairs"]) < tokens * 5
    assert np.abs(np.asarray(want)).max() > 0
    _close(y, want, tol=2e-5 if dtype == jnp.float32 else 1e-2)
    assert np.array_equal(np.asarray(outs["gated"][0]), np.asarray(want))
    if holes:
        assert not np.asarray(y)[1::3].any()


def test_widths_the_kernel_cannot_take_fall_back_counted():
    """The tiny model's experts (16 and 24 wide) are no multiples of 128:
    tier on, every grouped product of a decode step takes
    ``lax.ragged_dot`` and ``kernels.grouped_fallback`` says so."""
    model, params = _tiny()
    kv = model.init_kv_pages(8, PAGE, slots=2)
    mx.config.set("kernels.enabled", True)
    telemetry.reset()
    try:
        with mx.kernels.record_grouped_routes() as routes:
            jax.jit(lambda: model.decode_step(
                params, kv, jnp.zeros((2,), jnp.int32),
                jnp.ones((2,), jnp.int32), jnp.zeros((2, 2), jnp.int32),
                PAGE))()
    finally:
        mx.config.unset("kernels.enabled")
    blocks = model.kinds.count("E")
    assert [r["impl"] for r in routes] == ["xla"] * 2 * blocks
    assert all("multiples of 128" in r["reason"] for r in routes)
    assert telemetry.counter("kernels.grouped_fallback").value == 2 * blocks
    assert telemetry.counter("kernels.grouped_matmul").value == 0


# -------------------------------------------------------- the paged kernel
@pytest.mark.parametrize("heads,kv_heads,whole_pool", [
    (32, 2, True), (32, 2, False), (8, 4, True), (4, 4, False),
    (4, 4, True)])
def test_grouped_query_paged_kernel_is_the_xla_twin(heads, kv_heads,
                                                    whole_pool):
    """The Pallas paged kernel (interpreter) at 16 queries a K/V head (32
    over 2), at 2, and unchanged at equal head counts, on one layer's
    pool and on the whole pool with a layer index, against the XLA twin
    and against plain attention over the gathered rows."""
    from mxnet_tpu import kernels
    from mxnet_tpu.ops import pallas_kernels as pk
    B, D, psz, P, W, L = 3, 16, 4, 12, 4, 2
    rng = np.random.default_rng(heads + kv_heads)
    q = jnp.asarray(rng.normal(size=(B, heads, 1, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(L, P, psz, kv_heads * D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, P, psz, kv_heads * D)), jnp.float32)
    table = jnp.asarray(rng.permutation(P).reshape(B, W), jnp.int32)
    lengths = jnp.asarray([5, 16, 0], jnp.int32)
    layer = 1
    pools = (k, v) if whole_pool else (k[layer], v[layer])
    kw = {"layer": layer} if whole_pool else {}
    got = pk.pallas_paged_attention(q, *pools, table, lengths, **kw)
    twin = kernels._paged_attention_xla(q, *pools, table, lengths, **kw)
    _close(got, twin)
    group = heads // kv_heads
    for b, n in enumerate([5, 16]):
        rows_k = k[layer][table[b]].reshape(W * psz, kv_heads, D)[:n]
        rows_v = v[layer][table[b]].reshape(W * psz, kv_heads, D)[:n]
        for h in range(heads):
            s = rows_k[:, h // group] @ q[b, h, 0] / np.sqrt(D)
            _close(got[b, h, 0], jax.nn.softmax(s) @ rows_v[:, h // group])
    assert not np.asarray(got[2]).any()
    assert kernels.paged_unsupported_reason(
        q, *pools, table, lengths, **kw) is None


# ------------------------------------------------- through the artifact
@pytest.fixture
def served(tmp_path):
    """A tiny hybrid model exported as the benchmark's driver does and
    registered with a started server (kernel tier on: the paged kernel
    runs in the interpreter)."""
    mx.config.set("kernels.enabled", True)
    mx.config.set("serving.kv_pages", 64)
    mx.config.set("serving.decode_slots", 4)
    model, params = _tiny(pattern="MEM*EM*E")
    prefix = str(tmp_path / "lm")
    mx.deploy.export_generation(
        model, params, prefix, sampling=True, decode_batch=4,
        prompt_buckets=[8, 16], max_context=64, page_size=PAGE,
        decode_widths=[16])
    refused = telemetry.counter("serving.prefix_share_refused").value
    srv = mx.serving.Server()
    engine = srv.register("lm", prefix, generate=True)
    srv.start()
    try:
        yield model, params, prefix, srv, engine, refused
    finally:
        srv.stop()
        for knob in ("kernels.enabled", "serving.kv_pages",
                     "serving.decode_slots"):
            mx.config.unset(knob)


def test_export_round_trip_serves_the_oracles_tokens(served):
    """``export_generation`` -> ``Server.register(generate=True)``: seven
    requests over four slots (so slots are used again) each get the
    cache-free greedy oracle's tokens; the artifact names its state
    region, its one decode width on the kernel's route and the counts a
    step brings back; the offline loop of the reloaded artifact agrees."""
    model, params, prefix, srv, engine, _ = served
    with open(prefix + "-meta.json") as f:
        meta = json.load(f)
    assert meta["decode_widths"] == [16]
    assert meta["paged"]["16"]["impl"] == "paged"
    assert meta["decode_stats"] == list(model.decode_stats)
    assert [s["name"] for s in meta["kv"]["state"]] == [
        "ssm00", "conv00", "ssm02", "conv02", "ssm05", "conv05"]
    assert meta["kv"]["num_layers"] == 2 and meta["kv"]["row_width"] == 16
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 11, 3, 16, 7, 9, 12)]
    before = {n: telemetry.counter("serving." + n).value
              for n in model.decode_stats}
    # (the oracle first: tracing it counts its own routes)
    oracle = [model.greedy_decode(params, p, 9) for p in prompts]
    dispatches = lambda: sum(  # noqa: E731
        telemetry.timer("serving." + t).count
        for t in ("prefill_ms", "decode_step_ms"))
    fell_back = telemetry.counter("kernels.grouped_fallback").value \
        - dispatches()
    by_kernel = telemetry.counter("kernels.grouped_matmul").value
    futures = [srv.submit_generate("lm", p, 9) for p in prompts]
    for want, f in zip(oracle, futures):
        assert (f.result(timeout=300) == want).all()
    for n in model.decode_stats:
        assert telemetry.counter("serving." + n).value > before[n]
    # the experts are too narrow for the grouped kernel: every program
    # says so, and every dispatch is counted as a fallback
    assert meta["grouped"] == {p: {
        "impl": "xla", "sites": 2 * model.kinds.count("E"),
        "reason": "K=16 and N=24 must be multiples of 128"}
        for p in ("prefill-s8", "prefill-s16", "decode-w16")}
    assert telemetry.counter("kernels.grouped_matmul").value == by_kernel
    assert telemetry.counter("kernels.grouped_fallback").value \
        == fell_back + dispatches() > fell_back
    gp = mx.deploy.load_generator(prefix)
    assert (gp.generate(prompts[1], 9)
            == model.greedy_decode(params, prompts[1], 9)).all()


def test_a_slot_used_again_carries_nothing_of_the_first_request(served):
    """The same prompt served into a slot that a DIFFERENT, longer request
    just left gives the tokens it gave into a fresh cache: the prefill
    rewrites the slot's state whole."""
    model, params, _, srv, engine, _ = served
    rng = np.random.default_rng(2)
    probe = rng.integers(0, 96, (6,)).astype(np.int32)
    fresh = srv.submit_generate("lm", probe, 8).result(timeout=300)
    assert (fresh == model.greedy_decode(params, probe, 8)).all()
    for n in (15, 13, 16, 14):      # dirty every slot
        srv.submit_generate("lm", rng.integers(0, 96, (n,)).astype(np.int32),
                            12).result(timeout=300)
    dirty = [np.asarray(a).any() for a in engine._kv[2:]]
    assert all(dirty)
    again = [srv.submit_generate("lm", probe, 8) for _ in range(4)]
    for f in again:
        assert (f.result(timeout=300) == fresh).all()


def test_prefix_sharing_is_refused_for_a_state_model(served, caplog):
    """``serving.shared_prefix`` (on by default) is refused for a model
    whose cache has a state region — counted, logged once, not silent —
    and two requests with a common prefix share no page."""
    model, params, _, srv, engine, refused = served
    assert mx.config.get("serving.shared_prefix")
    assert not engine._share and not engine.stats()["shared_prefix"]
    assert telemetry.counter("serving.prefix_share_refused").value \
        == refused + 1
    hits = telemetry.counter("serving.prefix_hits").value
    prompt = np.arange(12, dtype=np.int32)
    a = srv.submit_generate("lm", prompt, 6)
    b = srv.submit_generate("lm", prompt, 6)
    assert (a.result(timeout=300) == b.result(timeout=300)).all()
    assert telemetry.counter("serving.prefix_hits").value == hits


def test_a_rebuilt_cache_zeroes_the_state_too(served):
    model, params, _, srv, engine, _ = served
    srv.submit_generate("lm", np.arange(9, dtype=np.int32),
                        6).result(timeout=300)
    srv.stop()
    assert any(np.asarray(a).any() for a in engine._kv[2:])
    engine._fail_active(RuntimeError("rebuild"))
    assert len(engine._kv) == 2 + len(engine.predictor.state)
    assert not any(np.asarray(a).any() for a in engine._kv)


def test_a_request_that_asks_gets_what_replaying_it_needs(tmp_path):
    """``export_generation(replay=True, include_params=False)`` ->
    ``register(params=...)`` (the arrays on the device are served; no
    params file exists): a request that asks gets ``(ids, replay)`` — a
    log-probability per token, and per ``E`` block the experts chosen for
    the prompt and every generated token but the last.  Told the choices,
    the reference would itself have made every one (float32: no near-tie
    flips), scores the served tokens as its best and gives them the same
    log-probabilities; a request that does not ask gets the ids alone."""
    mx.config.set("serving.kv_pages", 64)
    mx.config.set("serving.decode_slots", 4)
    model, params = _tiny(pattern="MEM*E")
    prefix = str(tmp_path / "lm")
    paths = mx.deploy.export_generation(
        model, params, prefix, sampling=True, decode_batch=4,
        prompt_buckets=[8, 16], max_context=64, page_size=PAGE,
        decode_widths=[16], include_params=False, replay=True)
    assert not any(p.endswith(".npz") for p in paths)
    with open(prefix + "-meta.json") as f:
        assert json.load(f)["replay"] == {"layers": 2, "top_k": 3}
    srv = mx.serving.Server()
    with pytest.raises(Exception, match="include_params=False"):
        srv.register("lm", prefix, generate=True)
    with pytest.raises(ValueError, match="not the ones"):
        srv.register("lm", prefix, generate=True,
                     params=dict(params, extra=params["embed"]))
    srv.register("lm", prefix, generate=True, params=params)
    srv.start()
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
                   for n in (11, 5, 16, 7, 9)]
        plain = srv.submit_generate("lm", prompts[0], 7)
        asked = [srv.submit_generate("lm", p, 9, return_replay=True)
                 for p in prompts]
        assert (plain.result(timeout=300)
                == model.greedy_decode(params, prompts[0], 7)).all()
        for p, f in zip(prompts, asked):
            ids, replay = f.result(timeout=300)
            assert (ids == model.greedy_decode(params, p, 9)).all()
            routed = replay["routed_experts"]
            assert routed.shape == (2, len(p) + 8, 3)
            assert routed.dtype == np.int16
            assert replay["logprobs"].shape == (9,)
            gaps, _, missed, logprobs = REF.served_token_gaps(
                params, p, ids, 32, 9, lm=REF_LM, routed=routed)
            assert int(missed) == 0 and float(gaps.max()) == 0.0
            _close(replay["logprobs"], logprobs)
    finally:
        srv.stop()
        for knob in ("serving.kv_pages", "serving.decode_slots"):
            mx.config.unset(knob)


def test_asking_for_a_replay_needs_an_artifact_that_returns_it(served):
    _, _, _, srv, _, _ = served
    with pytest.raises(ValueError, match="replay=True"):
        srv.submit_generate("lm", np.arange(5, dtype=np.int32), 4,
                            return_replay=True)
    with pytest.raises(ValueError, match="what a replay needs"):
        from mxnet_tpu.models import TransformerLM, TransformerLMConfig
        lm = TransformerLM(TransformerLMConfig(
            vocab_size=32, d_model=16, num_heads=2, num_layers=1, d_ff=32,
            max_len=32))
        mx.deploy.export_generation(lm, lm.init(jax.random.PRNGKey(0)),
                                    "/nonexistent/lm", replay=True)


@pytest.mark.parametrize("depth,want", [(None, 5), (80, 80)])
def test_init_scales_every_blocks_last_matrix_by_the_stacks_depth(depth,
                                                                   want):
    """The family's ``rescale_prenorm_residual``: ``depth`` (default: the
    pattern's own length; more where the pattern is one stage of a deeper
    stack) divides each block's last matrix by its square root, beside a
    unit-variance embedding; nothing else moves."""
    over = {} if depth is None else {"depth": depth}
    model, params = _tiny(d_model=64, shared_ff=256, **over)
    assert model.cfg.depth == want
    base, _ = _tiny(d_model=64, shared_ff=256, depth=1)
    ref = base.init(jax.random.PRNGKey(0))
    for name, last in (("00", "w_out"), ("01", "w_up"), ("01", "v2"),
                       ("03", "wo")):
        got, one = params["layers"][name][last], ref["layers"][name][last]
        _close(got, one / np.sqrt(want), 1e-6)
    for name, same in (("00", "w_in"), ("01", "w1"), ("03", "wq")):
        _close(params["layers"][name][same], ref["layers"][name][same], 0)
    assert abs(float(jnp.std(params["embed"])) - 1.0) < 0.05


# ------------------------------------------------------- scopes, compile
@pytest.mark.parametrize("program,scopes", [
    ("decode", ("mx.ssm", "mx.ssm_conv", "mx.ssm_update", "mx.moe",
                "mx.moe_router", "mx.moe_experts", "mx.moe_shared",
                "mx.qkv", "mx.kv_write", "mx.paged_attention",
                "mx.attn_out", "mx.lm_head", "mx.sample")),
    ("prefill", ("mx.ssm", "mx.ssm_conv", "mx.ssm_scan", "mx.moe",
                 "mx.moe_router", "mx.moe_experts", "mx.moe_shared",
                 "mx.qkv", "mx.kv_write", "mx.attention", "mx.attn_out",
                 "mx.lm_head", "mx.sample")),
])
def test_hybrid_programs_carry_scopes_as_metadata_only(program, scopes,
                                                       monkeypatch):
    from _util import lowered_with_and_without_scopes
    mx.config.set("kernels.enabled", True)
    model, params = _tiny()
    kv = model.init_kv_pages(8, PAGE, slots=4)
    i32 = jnp.int32

    def lower():
        if program == "decode":
            return jax.jit(lambda p, c, t, pos, tab: model.decode_step(
                p, c, t, pos, tab, PAGE, return_stats=True)).lower(
                    params, kv, jnp.zeros((4,), i32), jnp.ones((4,), i32),
                    jnp.zeros((4, 2), i32))
        return jax.jit(lambda p, c, t, n, tab, s: model.prefill(
            p, c, t, n, tab, PAGE, slots=s)).lower(
                params, kv, jnp.zeros((1, 8), i32), jnp.ones((1,), i32),
                jnp.zeros((1, 2), i32), jnp.zeros((1,), i32))

    try:
        text = lowered_with_and_without_scopes(lower, monkeypatch)
    finally:
        mx.config.unset("kernels.enabled")
    for scope in scopes:
        assert scope + "/" in text or scope + '"' in text, scope
    if program == "decode":
        assert "mx.kv_gather" not in text and "mx_paged_attention" in text


#: sha256 of the programs' text at the commit that gave the held experts
#: a grouped product of the program's own (PR 30; until then the hashes
#: were 17977ce's, the commit before the pool left ``TransformerLM``'s
#: layer scan).  At these widths (16 and 24) the products take the
#: ``lax.ragged_dot`` twin, which now slices each product back to its
#: rows: the paged kernel's call and everything else lower as they did
PARENT_PROGRAMS = {
    ("decode", "chip"):
        "cce4daf4add2508a188559de30bfe5f5ad0e3267c900c59610f7191188df71f0",
    ("decode", "interpreter"):
        "32d5f5b27b224791b731153b5568f2925d3a1783dd06d0d2601282bb790ef326",
    ("prefill", "chip"):
        "0e423326dc42450523b624e77bc736b3f807b31f55fe99698f36e26e7dd98ff6",
    ("prefill", "interpreter"):
        "4189b73884b8383098fe0cc6d981e46e6aeb400e816fc8e344bc1e6b9437b2e7",
}


@pytest.mark.parametrize("program,lowered_for", list(PARENT_PROGRAMS))
def test_hybrid_programs_are_the_parents(program, lowered_for, monkeypatch):
    """``HybridLM`` walks its blocks in Python and hands the paged kernel
    a static ``layer``: its decode and prefill programs on the kernel's
    route — lowered for the TPU as the cell's artifact is (the Mosaic
    kernel's body compared without its locations), and for the
    interpreter — are, locations aside, byte for byte the pinned ones
    (a change that means to move them pins them again and says why)."""
    import hashlib
    from mxnet_tpu import rtc
    mx.config.set("kernels.enabled", True)
    if lowered_for == "chip":
        monkeypatch.setattr(rtc, "interpret_mode", lambda: False)
    platforms = {"chip": ("tpu",), "interpreter": None}[lowered_for]
    model, params = _tiny()
    kv = model.init_kv_pages(8, PAGE, slots=4)
    i32 = jnp.int32
    try:
        if program == "decode":
            traced = jax.jit(lambda p, c, t, pos, tab: model.decode_step(
                p, c, t, pos, tab, PAGE, return_stats=True)).trace(
                    params, kv, jnp.zeros((4,), i32), jnp.ones((4,), i32),
                    jnp.zeros((4, 2), i32))
        else:
            traced = jax.jit(lambda p, c, t, n, tab, s: model.prefill(
                p, c, t, n, tab, PAGE, slots=s)).trace(
                    params, kv, jnp.zeros((1, 8), i32), jnp.ones((1,), i32),
                    jnp.zeros((1, 2), i32), jnp.zeros((1,), i32))
        text = traced.lower(lowering_platforms=platforms).as_text()
    finally:
        mx.config.unset("kernels.enabled")
    if program == "decode":
        assert ("tpu_custom_call" in text) == (lowered_for == "chip")
    from _util import without_kernel_locations
    text = without_kernel_locations(text)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_PROGRAMS[program, lowered_for]
