"""mx.perf.autotune — measured config search + persisted winners (round 16).

Covers the tuning-cache contract (cross-process round-trip with ZERO
re-measurement on the warm leg, asserted via telemetry counters), the
``kernels.vmem_budget`` fingerprint regression (a budget change
invalidates persisted block picks), corrupt/stale cache tolerance, the
default-on kernel-tier graduation (default-source CPU programs stay
byte-identical to the pre-tier lowering; explicit on/off bypasses the
gate), tuned block_q flowing through ``kernels.attention``, generation
bumps retracing cached programs, the stack_mode × remat sweep with
knob-source restoration, the ``config.source``/``config.unset``
primitives underneath it all, and the tools/check_autotune.py wiring.
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
from mxnet_tpu import autotune, config, kernels, perf, runtime, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VMEM_DEFAULT = 2097152


@pytest.fixture(autouse=True)
def _autotune_knobs(tmp_path):
    """Every test gets a private tuning cache and leaves the knobs the
    way it found them; in-memory tuning state resets on both sides."""
    config.set("perf.autotune_cache", str(tmp_path / "autotune.json"))
    telemetry.reset_counters()
    autotune.reset()
    yield
    for name in ("perf.autotune", "perf.autotune_cache", "kernels.enabled",
                 "kernels.vmem_budget", "runtime.stack_mode",
                 "runtime.remat"):
        config.unset(name)
    telemetry.reset_counters()
    autotune.reset()


def _qkv(shape=(1, 2, 32, 16), dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*shape), dtype) for _ in range(3))


def _count(name):
    return telemetry.counter(name).value


# ------------------------------------------- a broken kernel is an error
class _MosaicSaysNo(RuntimeError):
    pass


def _refuse(*_a, **_k):
    raise _MosaicSaysNo("block shape (6, 64) is not a legal TPU block")


def _pick_attention():
    return autotune.attention_pick((1, 2, 32, 16), (1, 2, 32, 16),
                                   "float32", True)


def _pick_fused():
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9)
    opt.step_fused = _refuse
    return autotune.fused_step_pick(opt)


@pytest.mark.parametrize("in_trace", [False, True], ids=["eager", "traced"])
@pytest.mark.parametrize("kernel, pick", [
    ("flash_attention", _pick_attention),
    (None, _pick_fused),
], ids=["attention", "fused"])
def test_candidate_that_cannot_compile_raises_and_is_never_recorded(
        monkeypatch, kernel, pick, in_trace):
    """On a compiled (non-interpreted) backend a cache miss searches; a
    candidate the compiler refuses is an ERROR out of the pick — from
    inside a jit trace too — never an "xla won" verdict: nothing lands
    in memory or on disk, so the next run meets the same error."""
    from mxnet_tpu.ops import pallas_kernels
    monkeypatch.setattr(autotune, "_interpreted", lambda: False)
    if kernel is not None:
        monkeypatch.setattr(pallas_kernels, kernel, _refuse)
    path = config.get("perf.autotune_cache")

    def run():
        if in_trace:
            return jax.jit(lambda x: (pick(), x)[1])(jnp.zeros(()))
        return pick()

    for _ in range(2):   # the failure is not memoized as a verdict either
        with pytest.raises(_MosaicSaysNo, match="not a legal TPU block"):
            run()
    assert not os.path.exists(path)
    state = autotune.export_entries()
    assert state["entries"] == {} and state["applied"] == {}
    assert _count("autotune.search") == 0


def test_search_from_inside_a_trace_measures_concrete_arrays():
    """A trace-time pick that misses the cache runs its search outside
    the ambient trace: it measures and reads back real arrays (under the
    old catch-all, the tracer error here became a silent "xla" verdict
    for every site a jitted model routed)."""
    config.set("perf.autotune", "measure")
    q, k, v = _qkv()
    out = jax.jit(lambda q, k, v: kernels.attention(q, k, v, causal=True))(
        q, k, v)
    assert out.shape == q.shape
    assert _count("autotune.search") == 1 and _count("autotune.measure") > 1
    (entry,) = autotune.export_entries()["entries"].values()
    assert "reason" not in entry or "failed" not in entry["reason"], entry
    assert entry["candidates"], entry


def test_default_cache_file_lives_in_the_checkout(monkeypatch):
    """No state from outside the tree decides a route: the default path
    is <checkout>/.jax_cache/autotune.json, not ~/.mxnet."""
    config.unset("perf.autotune_cache")
    monkeypatch.setenv("HOME", "/nonexistent-home")
    assert autotune.cache_path() == os.path.join(ROOT, ".jax_cache",
                                                 "autotune.json")


# --------------------------------------------------- config primitives
def test_config_source_tracks_override_env_default(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_REMAT", raising=False)
    config.unset("runtime.remat")
    assert config.source("runtime.remat") == "default"
    monkeypatch.setenv("MXNET_TPU_REMAT", "dots")
    assert config.source("runtime.remat") == "env"
    assert config.get("runtime.remat") == "dots"
    config.set("runtime.remat", "full")
    assert config.source("runtime.remat") == "override"
    config.unset("runtime.remat")
    assert config.source("runtime.remat") == "env"


def test_config_unset_restores_default_and_bumps_epoch():
    config.unset("runtime.stack_mode")
    e0 = config.epoch()
    config.unset("runtime.stack_mode")      # no override: no-op
    assert config.epoch() == e0
    config.set("runtime.stack_mode", "unroll")
    config.unset("runtime.stack_mode")
    assert config.get("runtime.stack_mode") == "scan"
    assert config.source("runtime.stack_mode") == "default"
    assert config.epoch() > e0
    with pytest.raises(KeyError):
        config.unset("no.such.knob")


def test_autotune_mode_knob_reject_and_revert():
    config.set("perf.autotune", "measure")
    with pytest.raises(ValueError):
        config.set("perf.autotune", "bogus")
    assert config.get("perf.autotune") == "auto"   # rejected set reverts
    assert autotune.mode() == "auto"
    config.set("perf.autotune", "off")
    assert not autotune.enabled()


# ------------------------------------------------- default-on graduation
def test_default_on_cpu_is_byte_identical_to_pre_tier():
    """The graduated default routes interpreted backends to XLA via a
    static verdict — the lowered program is byte-for-byte the pre-tier
    program, so flipping the default moved nothing on CPU."""
    assert config.source("kernels.enabled") == "default"
    q, k, v = _qkv()

    def f(q, k, v):
        return kernels.attention(q, k, v, causal=True)

    tuned = jax.jit(f).lower(q, k, v).as_text()
    config.set("kernels.enabled", False)
    off = jax.jit(f).lower(q, k, v).as_text()
    assert tuned == off
    assert _count("autotune.measure") == 0
    assert _count("kernels.gated_fallback") >= 1


def test_explicit_enable_bypasses_gate_with_zero_measurement():
    config.set("kernels.enabled", True)
    q, k, v = _qkv()
    out = kernels.attention(q, k, v, causal=True)
    jax.block_until_ready(out)
    assert _count("kernels.flash_attention") == 1
    assert _count("autotune.measure") == 0
    assert _count("autotune.search") == 0


def test_tuned_block_q_flows_through_routing(monkeypatch):
    """A persisted flash winner's block_q reaches flash_attention."""
    q, k, v = _qkv()
    site = autotune._attention_site(tuple(q.shape), tuple(k.shape), True)
    autotune.record("attention", site, "float32",
                    {"impl": "flash", "block_q": 16, "speedup": 1.2,
                     "parity": "tolerance"})
    seen = {}

    def spy(q, k, v, causal=False, scale=None, block_q=128):
        seen["block_q"] = block_q
        from mxnet_tpu.parallel.ring_attention import attention
        return attention(q, k, v, causal=causal, scale=scale)

    monkeypatch.setattr(kernels, "flash_attention", spy)
    kernels.attention(q, k, v, causal=True)
    assert seen == {"block_q": 16}
    assert _count("kernels.flash_attention") == 1
    assert _count("autotune.measure") == 0


# --------------------------------------------------- tuning-cache keying
def test_vmem_budget_change_invalidates_persisted_picks():
    """Regression: block picks derived under one VMEM budget must not
    survive a budget change — the budget is part of the cache
    fingerprint, so old winners simply stop matching."""
    fp0 = autotune.config_fingerprint()
    autotune.record("attention", "attn/site", "float32",
                    {"impl": "flash", "block_q": 256})
    assert autotune.lookup("attention", "attn/site", "float32") is not None

    config.set("kernels.vmem_budget", 4096)
    assert autotune.config_fingerprint() != fp0
    assert autotune.lookup("attention", "attn/site", "float32") is None
    assert _count("autotune.cache_miss") >= 1

    config.set("kernels.vmem_budget", VMEM_DEFAULT)
    assert autotune.lookup("attention", "attn/site", "float32") is not None


def test_lookup_memoizes_within_epoch_and_refreshes_on_epoch_move():
    autotune.record("stack", "memo", "-", {"impl": "x", "knobs": {}})
    autotune.reset()            # drop the pick memo; the disk file stays
    telemetry.reset_counters()
    for _ in range(3):
        assert autotune.lookup("stack", "memo", "-") is not None
    assert _count("autotune.cache_hit") == 1   # memoized after first
    config.set("runtime.remat", "dots")        # epoch moves, memo drops
    assert autotune.lookup("stack", "memo", "-") is not None
    assert _count("autotune.cache_hit") == 2


def test_generation_bumps_only_on_recorded_winners():
    g0 = autotune.generation()
    autotune.lookup("attention", "nope", "float32")
    assert autotune.generation() == g0
    autotune.record("attention", "yes", "float32", {"impl": "xla"})
    assert autotune.generation() == g0 + 1


def test_corrupt_and_stale_caches_fall_back_to_defaults():
    path = config.get("perf.autotune_cache")
    with open(path, "w") as f:
        f.write("{ not json")
    assert autotune.lookup("attention", "s", "float32") is None
    assert _count("autotune.cache_invalid") == 1
    autotune.reset()
    with open(path, "w") as f:
        json.dump({"version": 999, "entries": {}}, f)
    assert autotune.lookup("attention", "s", "float32") is None
    assert _count("autotune.cache_invalid") == 2
    # a fresh record overwrites the bad file with a valid one
    autotune.record("attention", "s", "float32", {"impl": "xla"})
    with open(path) as f:
        blob = json.load(f)
    assert blob["version"] == autotune.CACHE_VERSION
    assert len(blob["entries"]) == 1


def test_perf_export_carries_autotune_evidence():
    autotune.record("attention", "exp", "float32",
                    {"impl": "flash", "block_q": 64, "speedup": 1.1})
    snap = perf.export()
    at = snap["autotune"]
    assert at["generation"] >= 1
    assert at["mode"] == "auto"
    assert any(k.startswith("attention|exp|") for k in at["entries"])


# -------------------------------------------------- cross-process contract
_ROUNDTRIP = """
import json, os
import numpy as np, jax, jax.numpy as jnp
from mxnet_tpu import config, kernels, telemetry
rng = np.random.RandomState(0)
q, k, v = (jnp.asarray(rng.randn(1, 2, 32, 16), jnp.float32)
           for _ in range(3))
c = lambda n: telemetry.counter(n).value
def leg():
    jax.block_until_ready(kernels.attention(q, k, v, causal=True))
    print(json.dumps({"search": c("autotune.search"),
                      "measure": c("autotune.measure"),
                      "hit": c("autotune.cache_hit"),
                      "flash": c("kernels.flash_attention")}))
leg()
if os.environ.get("MXNET_TPU_TEST_REBUDGET"):
    telemetry.reset()
    config.set("kernels.vmem_budget", 65536)
    leg()
"""


def _run_leg(cache, extra_env=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_AUTOTUNE="measure",
               MXNET_TPU_AUTOTUNE_CACHE=cache, **dict(extra_env))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _ROUNDTRIP],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=ROOT)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    return [json.loads(line)
            for line in proc.stdout.strip().splitlines()
            if line.startswith("{")]


def test_cross_process_roundtrip_and_vmem_invalidation(tmp_path):
    """The acceptance contract end-to-end: process A searches and
    persists; process B (same config epoch zero / same knob values)
    applies the cached winner with ZERO measurement calls, then flips
    its VMEM budget and re-searches because the cache-key fingerprint
    moved — process A's persisted winner must not apply."""
    cache = str(tmp_path / "autotune.json")
    cold, = _run_leg(cache)
    assert cold["search"] >= 1 and cold["measure"] >= 2, cold
    assert os.path.exists(cache)

    warm, rebudget = _run_leg(cache, [("MXNET_TPU_TEST_REBUDGET", "1")])
    assert warm["measure"] == 0, warm    # the zero-re-measurement clause
    assert warm["search"] == 0, warm
    assert warm["hit"] >= 1, warm

    assert rebudget["search"] >= 1, rebudget  # old winner didn't match
    assert rebudget["measure"] >= 2, rebudget


# ------------------------------------------------- step-level search space
def test_search_stack_persists_winner_and_restores_knob_sources():
    config.set("perf.autotune", "measure")
    rng = np.random.RandomState(0)
    Ws = jnp.asarray(rng.randn(2, 8, 8) * 0.1, jnp.float32)
    x0 = jnp.asarray(rng.randn(2, 8), jnp.float32)

    def make_step():
        def loss(ws, x):
            def body(carry, w):
                return jnp.tanh(carry @ w), None
            h, _ = runtime.scan_stack(body, x, ws)
            return jnp.sum(h * h)
        return jax.value_and_grad(loss)

    entry = autotune.search_stack(make_step, (Ws, x0))
    assert set(entry["candidates"]) == {
        "remat=/stack_mode=scan", "remat=dots/stack_mode=scan",
        "remat=full/stack_mode=scan", "remat=/stack_mode=unroll"}
    assert config.source("runtime.stack_mode") == "default"
    assert config.source("runtime.remat") == "default"

    # the persisted winner now steers stack_tuning() at default knobs...
    m, r = entry["knobs"]["runtime.stack_mode"], entry["knobs"]["runtime.remat"]
    assert runtime.stack_tuning() == (m, r)
    # ...but an explicit knob always wins over the tuned pick
    config.set("runtime.stack_mode", "unroll" if m == "scan" else "scan")
    assert runtime.stack_tuning()[0] != m


def test_search_step_restores_explicit_overrides():
    config.set("perf.autotune", "measure")
    config.set("runtime.remat", "dots")    # operator's explicit choice

    def make_fn():
        return jax.jit(lambda x: x * 2.0)

    autotune.search_step("restore", make_fn, (jnp.ones((4,)),),
                         [{"runtime.remat": ""}, {"runtime.remat": "full"}])
    assert config.source("runtime.remat") == "override"
    assert config.get("runtime.remat") == "dots"


def test_generation_bump_retraces_hybridized_program():
    from mxnet_tpu.gluon import nn
    net = nn.Dense(4)
    net.initialize()
    x = mx.nd.random.uniform(shape=(2, 3))
    net.hybridize()
    net(x)                      # first hybrid call builds the cache...
    net(x)                      # ...second runs the jitted program
    cg = net._cached_graph_obj
    (key0,) = cg._jitted.keys()
    net(x)
    assert set(cg._jitted.keys()) == {key0}   # stable while nothing moves
    autotune.record("attention", "retrace", "float32", {"impl": "xla"})
    net(x)
    (key1,) = cg._jitted.keys()               # superseded program evicted
    assert key1 != key0
    assert key1[1][1] == key0[1][1] + 1       # the generation slot moved


# ------------------------------------------------------------- tool wiring
def test_perf_report_autotune_delta_table():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import perf_report
    finally:
        sys.path.pop(0)
    autotune.record("attention", "attn/x", "float32",
                    {"impl": "flash", "site": "attn/x", "baseline_ms": 0.2,
                     "best_ms": 0.1, "block_q": 64, "parity": "bitwise",
                     "speedup": 2.0})
    autotune.record("stack", "default", "-",
                    {"impl": "remat=/stack_mode=unroll", "site": "default",
                     "best_ms": 0.07,
                     "knobs": {"runtime.stack_mode": "unroll",
                               "runtime.remat": ""},
                     "candidates": {"remat=/stack_mode=scan": 0.14,
                                    "remat=/stack_mode=unroll": 0.07}})
    rows = perf_report.autotune_table(perf.export()["autotune"])
    by_family = {r["family"]: r for r in rows}
    assert by_family["attention"]["speedup"] == 2.0
    assert by_family["attention"]["verdict"] == "graduated"
    # step-space entries derive the default from the default-knob combo
    assert by_family["stack"]["default_ms"] == 0.14
    assert by_family["stack"]["speedup"] == 2.0
    assert perf_report.autotune_table(None) == []  # pre-round-16 dumps
    text = perf_report.render(perf_report.summarize(
        [], [], autotune=perf.export()["autotune"]))
    assert "tuned_ms" in text and "attn/x" in text


def test_check_autotune_smoke():
    """Subprocess wiring for tools/check_autotune.py — search, persist,
    zero-measure reload, exactly how CI runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    for var in ("MXNET_TPU_AUTOTUNE", "MXNET_TPU_AUTOTUNE_CACHE",
                "MXNET_TPU_KERNELS"):
        env.pop(var, None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_autotune.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["attention"]["impl"] in ("flash", "xla"), report
    assert report["attention"]["parity"] in ("bitwise", "tolerance"), report
    assert report["reload"]["measure"] == 0, report
    assert report["reload"]["cache_hit"] >= 2, report
