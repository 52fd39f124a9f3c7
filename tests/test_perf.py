"""mx.perf — compiled-program cost attribution.

Covers the registry record schema (cost_analysis / memory_analysis /
phase breakdown / HLO op-class table), the roofline classifier and peak
tables, the PerfProgram wrapper's
bitwise no-op + fallback semantics, step-record flops/mfu schema, the
MXNET_TPU_PROFILE knob validation, and the perf_report / check_perf
tool wiring.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401 — registers the lazy perf entry
from mxnet_tpu import config, perf, telemetry

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registry():
    perf.reset()
    yield
    perf.reset()


def _mlp_fn():
    def fn(w1, w2, x):
        return jnp.tanh(x @ w1) @ w2
    return jax.jit(fn)


def _mlp_args(b=8, i=16, h=32, o=4, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(i, h), jnp.float32),
            jnp.asarray(rng.randn(h, o), jnp.float32),
            jnp.asarray(rng.randn(b, i), jnp.float32))


# ---------------------------------------------------------------- registry
@pytest.fixture
def as_v5e(monkeypatch):
    """Steer the peak lookup from the test: the arithmetic of MFU and
    roofline is checked against the v5e row on this CPU mesh."""
    monkeypatch.setattr(perf, "_KIND_CACHE", ["TPU v5 lite"])


def test_register_compiled_record_schema(as_v5e):
    fn = _mlp_fn()
    args = _mlp_args()
    compiled = fn.trace(*args).lower().compile()
    rec = perf.register_compiled("module", "schema", compiled,
                                 phases_ms={"trace_ms": 1.0,
                                            "lower_ms": 2.0,
                                            "compile_ms": 3.0},
                                 dtype="float32")
    assert rec is not None
    assert rec["flops"] > 0
    assert rec["bytes_accessed"] > 0
    # tanh costs transcendentals; XLA reports them separately
    assert rec["transcendentals"] > 0
    mem = rec["memory"]
    for field in ("argument_bytes", "output_bytes", "temp_bytes",
                  "generated_code_bytes"):
        assert field in mem, mem
    assert mem["argument_bytes"] > 0
    assert rec["phases_ms"] == {"trace_ms": 1.0, "lower_ms": 2.0,
                                "compile_ms": 3.0}
    ops = rec["op_classes"]
    assert ops.get("matmul", 0) >= 2, ops
    assert rec["roofline"]["bound"] in ("compute", "bandwidth")
    # accessors round-trip, private accounting fields stripped
    got = perf.program("module", "schema")
    assert got["flops"] == rec["flops"]
    assert not any(k.startswith("_") for k in got)
    assert perf.programs("module") and not perf.programs("serving")


def test_phase_timers_observed():
    telemetry.reset()
    fn = _mlp_fn()
    args = _mlp_args()
    compiled = fn.trace(*args).lower().compile()
    perf.register_compiled("module", "timers", compiled,
                           phases_ms={"trace_ms": 1.5, "lower_ms": 2.5,
                                      "compile_ms": 10.0})
    snap = telemetry.snapshot()
    for name in ("perf.trace_ms", "perf.lower_ms", "perf.compile_ms"):
        assert snap["timers"][name]["count"] >= 1, (name, snap["timers"])
    assert snap["counters"]["perf.programs"] >= 1


def test_export_strips_private_and_writes(tmp_path):
    fn = _mlp_fn()
    args = _mlp_args()
    perf.register_compiled("module", "exp",
                           fn.trace(*args).lower().compile())
    path = tmp_path / "programs.json"
    dump = perf.export(str(path))
    assert dump["event"] == "perf_programs"
    on_disk = json.loads(path.read_text())
    assert on_disk["programs"][0]["key"] == "exp"
    assert "_flops_over_peak" not in on_disk["programs"][0]


# ----------------------------------------------------- roofline and peaks
def test_roofline_classification():
    # device intensity for the default table: 197e12 / 819e9 ~ 240 (bf16)
    hi = perf.roofline(1e12, 1e9, kind="TPU v5 lite", dtype="bfloat16")
    assert hi["bound"] == "compute"
    lo = perf.roofline(1e9, 1e9, kind="TPU v5 lite", dtype="bfloat16")
    assert lo["bound"] == "bandwidth"
    assert lo["arithmetic_intensity"] == 1.0
    assert hi["device_intensity"] == lo["device_intensity"] > 0
    # zero bytes: intensity unknowable, classified compute (no evidence
    # of a bandwidth ceiling)
    z = perf.roofline(1e9, 0, kind="TPU v5 lite")
    assert z["arithmetic_intensity"] is None and z["bound"] == "compute"


def test_peak_tables_dtype_aware():
    assert perf.peak_flops("TPU v5 lite", "bfloat16") == 197.0e12
    assert perf.peak_flops("TPU v5 lite", "float32") == 197.0e12 * 0.5
    assert perf.peak_flops("TPU v5 lite", "int8") == 197.0e12 * 2.0
    assert perf.peak_bandwidth("TPU v5 lite") == 819.0e9
    assert perf.peak_bandwidth("TPU v4") == 1228.0e9


@pytest.mark.parametrize("call", [
    lambda: perf.peak_flops("no-such-device"),
    lambda: perf.peak_bandwidth("no-such-device"),
    lambda: perf.roofline(1e9, 1e9, kind="no-such-device"),
    lambda: perf.peak_flops(),       # the CPU test mesh is not in the table
    lambda: perf.roofline(1e9, 1e9),
], ids=["flops", "bandwidth", "roofline", "local-flops", "local-roofline"])
def test_unknown_device_kind_has_no_peak(call):
    """The single peak table raises on a device it does not list: an MFU
    or a roofline share is never computed against another chip's peak."""
    assert not perf.known_device("no-such-device")
    assert not perf.known_device()
    with pytest.raises(perf.UnknownDeviceError, match="no published peaks"):
        call()


def test_unknown_device_records_counts_but_no_peak_numbers():
    """A program compiled on a device without published peaks (this CPU
    mesh) keeps its counts; what needs a peak is None, not a v5e number."""
    fn = _mlp_fn()
    args = _mlp_args()
    rec = perf.register_compiled("module", "nopeak",
                                 fn.trace(*args).lower().compile())
    assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
    assert rec["roofline"] is None and rec["peak_tflops"] is None
    telemetry.reset()
    w = perf.wrap(_mlp_fn(), "module", "nopeak2", source="module")
    w(*args)
    fields = perf._on_step("module", 1, 0.01)
    assert fields == {"flops": pytest.approx(
        perf.program("module", "nopeak2")["flops"])}
    assert "perf.mfu" not in telemetry.snapshot()["gauges"]


# ------------------------------------------------------------ op classes
def test_classify_op():
    assert perf.classify_op("dot.1") == "matmul"
    assert perf.classify_op("%convolution.42") == "conv"
    assert perf.classify_op("add.7") == "elementwise"
    assert perf.classify_op("tanh") == "elementwise"
    # collectives win over the "reduce" substring they contain
    assert perf.classify_op("all-reduce.3") == "collective"
    assert perf.classify_op("reduce-scatter.1") == "collective"
    assert perf.classify_op("reduce.5") == "reduction"
    assert perf.classify_op("transpose.2") == "copy"
    assert perf.classify_op("fusion.10") == "other"
    assert perf.classify_op("custom-call") == "other"


def test_hlo_op_classes_skips_wrappers():
    text = """
HloModule m
fused_computation {
  p0 = f32[8,4]{1,0} parameter(0)
  c = f32[8,4]{1,0} constant(0)
  ROOT add.1 = f32[8,4]{1,0} add(p0, c)
}
ENTRY main {
  %p = f32[8,4]{1,0} parameter(0)
  %fusion.1 = f32[8,4]{1,0} fusion(%p), kind=kLoop
  ROOT %dot.2 = f32[8,8]{1,0} dot(%fusion.1, %fusion.1)
}
"""
    counts = perf.hlo_op_classes(text)
    # fusion wrapper skipped; its body's add counted; dot counted
    assert counts == {"elementwise": 1, "matmul": 1}, counts


def test_hlo_op_names_joins_instructions_to_name_paths():
    """What a reader of a device trace joins event names with: every
    instruction that runs as an operation of its own, with the name path
    jax recorded for it.  Fusion bodies are left out; a fusion jax named
    nothing takes its root's name; what nobody named is absent."""
    text = """HloModule jit_step, is_scheduled=true

%fused_computation.3 (param_0: f32[8,4]) -> f32[8,4] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  ROOT %mul.7 = f32[8,4]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/mx.opt_update/mul" stack_frame_id=2}
}

%body.1 (arg: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %arg = (s32[], f32[8,4]{1,0}) parameter(0)
  %mx_paged_attention.4 = f32[8,4]{1,0} custom-call(f32[8,4]{1,0} %x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/mx.layers/while/body/mx.paged_attention/mx_paged_attention/pallas_call"}
  ROOT %tuple.2 = (s32[], f32[8,4]{1,0}) tuple(%i, %mx_paged_attention.4)
}

ENTRY %main.9 (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0} parameter(0), metadata={op_name="x"}
  %while.2 = (s32[], f32[8,4]{1,0}) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/mx.layers/while"}
  %copy.5 = f32[8,4]{0,1} copy(f32[8,4]{1,0} %p)
  ROOT %fusion.11 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %p), kind=kLoop, calls=%fused_computation.3
}
"""
    table = perf.hlo_op_names(text)
    assert table["module"] == "jit_step"
    assert table["ops"] == {
        "mx_paged_attention.4": [
            "f32[8,4]{1,0}",
            "jit(step)/mx.layers/while/body/mx.paged_attention/"
            "mx_paged_attention/pallas_call"],
        "p": ["f32[8,4]{1,0}", "x"],
        "while.2": ["(s32[], f32[8,4]{1,0})", "jit(step)/mx.layers/while"],
        "fusion.11": ["f32[8,4]{1,0}", "jit(step)/mx.opt_update/mul"]}
    assert perf.hlo_op_names("") == {"module": None, "ops": {}}


def test_op_names_of_registered_programs():
    """``mx.perf.op_names()``: per registered program its table with
    family, key and HLO module name — and the ``jax.named_scope`` names of
    the program in it; kept out of ``programs()`` and ``export()``."""
    def step(w, x):
        with jax.named_scope("mx.forward"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("mx.opt_update"):
            return w - 0.1 * y.sum()

    args = (jnp.ones((4, 4), jnp.float32), jnp.ones((2, 4), jnp.float32))
    perf.register_compiled("spmd", "named",
                           jax.jit(step).trace(*args).lower().compile())
    (table,) = perf.op_names("spmd")
    assert (table["family"], table["key"]) == ("spmd", "named")
    assert table["module"] == "jit_step"
    paths = " ".join(path for _, path in table["ops"].values())
    assert "mx.forward/" in paths and "mx.opt_update/" in paths
    assert perf.op_names("serving") == []
    assert "_op_names" not in perf.program("spmd", "named")
    assert "_op_names" not in json.dumps(perf.export())


# ------------------------------------------------------- wrapper semantics
def test_wrap_bitwise_noop():
    """Wrapped dispatch must be byte-identical to the plain jit path —
    same lowering, so wrapping is pure observation."""
    fn = _mlp_fn()
    args = _mlp_args()
    plain = np.asarray(fn(*args))
    w = perf.wrap(_mlp_fn(), "module", "noop")
    first = np.asarray(w(*args))
    steady = np.asarray(w(*args))
    assert plain.tobytes() == first.tobytes() == steady.tobytes()
    assert perf.program("module", "noop")["calls"] == 2


def test_wrap_fallback_on_signature_change():
    telemetry.reset()
    w = perf.wrap(_mlp_fn(), "module", "fb")
    args = _mlp_args(b=8)
    w(*args)
    before = telemetry.counter("perf.aot_fallback").value
    drifted = _mlp_args(b=4)
    out = np.asarray(w(*drifted))
    want = np.asarray(_mlp_fn()(*drifted))
    assert out.tobytes() == want.tobytes()
    assert telemetry.counter("perf.aot_fallback").value == before + 1
    # the fallback is permanent: later calls go straight to plain jit
    # without re-capturing (counter stays flat)
    w(*args)
    assert telemetry.counter("perf.aot_fallback").value == before + 1


def test_wrap_tracer_check_falls_through():
    """A wrapped program invoked with tracers (gluon under jax.vjp) must
    inline via the plain fn — the Compiled can't take tracers."""
    w = perf.wrap(jax.jit(lambda x: x * 2.0), "gluon", "tr",
                  check_tracers=True)
    x = jnp.arange(4.0)
    w(x)  # concrete call: AOT captures
    calls_before = perf.program("gluon", "tr")["calls"]
    out, vjp = jax.vjp(lambda v: w(v).sum(), x)
    (g,) = vjp(jnp.ones_like(out))
    assert np.allclose(np.asarray(g), 2.0)
    # tracer call neither dispatched the Compiled nor accounted
    assert perf.program("gluon", "tr")["calls"] == calls_before


def test_step_hook_accounts_and_clears(as_v5e):
    telemetry.reset()
    w = perf.wrap(_mlp_fn(), "module", "hook", source="module")
    args = _mlp_args()
    w(*args)
    fields = perf._on_step("module", 1, 0.01)
    assert fields is not None
    rec = perf.program("module", "hook")
    assert fields["flops"] == pytest.approx(rec["flops"])
    pk = perf.peak_flops(dtype=rec["dtype"])
    assert fields["mfu"] == pytest.approx(rec["flops"] / (0.01 * pk),
                                          rel=1e-3)
    assert telemetry.gauge("perf.mfu").value == fields["mfu"]
    assert telemetry.gauge("perf.mfu.module").value == fields["mfu"]
    # accumulator popped: a step with no dispatches attributes nothing
    assert perf._on_step("module", 2, 0.01) is None
    # no-dispatch sources never see fields
    assert perf._on_step("spmd", 1, 0.01) is None


def test_step_record_schema_accepts_flops_mfu():
    rec = {"event": "step", "ts": 1.0, "source": "module", "step": 1,
           "path": "fused", "wall_ms": 5.0, "compiles": 0,
           "host_syncs": 0, "flops": 123456.0, "mfu": 0.0123}
    telemetry.validate_step_record(rec)
    rec["mfu"] = "high"
    with pytest.raises(ValueError, match="mfu"):
        telemetry.validate_step_record(rec)


# ------------------------------------------------------------ profile knob
def test_profile_knob_validation():
    config.set("perf.profile", "step:5")
    assert perf._PROFILE["every"] == 5
    config.set("perf.profile", "")
    assert perf._PROFILE["every"] == 0
    with pytest.raises(ValueError):
        config.set("perf.profile", "bogus")
    # the bad spec did not linger as an override (the nanguard pattern)
    assert config.get("perf.profile") == ""
    assert perf._PROFILE["every"] == 0


# ----------------------------------------------------------------- reports
def test_perf_report_summarize_and_anomalies():
    import perf_report
    progs = [
        {"family": "module", "key": "a", "flops": 9e9,
         "bytes_accessed": 1e9,
         "roofline": {"bound": "bandwidth", "arithmetic_intensity": 9.0,
                      "device_intensity": 240.0},
         "phases_ms": {"trace_ms": 1, "lower_ms": 2, "compile_ms": 100},
         "op_classes": {"matmul": 3}, "calls": 5},
        {"family": "module", "key": "b", "flops": 1e9,
         "bytes_accessed": 1e6,
         "roofline": {"bound": "compute", "arithmetic_intensity": 1000.0,
                      "device_intensity": 240.0},
         "phases_ms": {"trace_ms": 1, "lower_ms": 2, "compile_ms": 900},
         "op_classes": {}, "calls": 5},
    ]
    # mfu series: 2 good windows then a collapsed final window
    records = [{"event": "step", "source": "module", "step": i + 1,
                "wall_ms": 1.0, "mfu": 0.3 if i < 16 else 0.05,
                "compiles": 0}
               for i in range(24)]
    s = perf_report.summarize(progs, records)
    kinds = {a["kind"] for a in s["anomalies"]}
    assert "bandwidth_bound_hotspot" in kinds, s["anomalies"]
    assert "mfu_regression" in kinds, s["anomalies"]
    # compile blowup needs > 5x the median AND the 250ms floor: 900 vs
    # median 100 trips it
    assert "compile_phase_blowup" in kinds, s["anomalies"]
    assert s["mfu"]["module"]["steps"] == 24
    text = perf_report.render(s)
    assert "module" in text and "ANOMALIES" in text


def test_telemetry_report_mfu_column_and_collapse():
    import telemetry_report
    base = {"event": "step", "source": "spmd", "path": "fused",
            "compiles": 0, "host_syncs": 0}
    records = [dict(base, step=i + 1, wall_ms=1.0,
                    mfu=0.4 if i < 15 else 0.1)
               for i in range(20)]
    s = telemetry_report.summarize(records)
    assert s["sources"]["spmd"]["mfu_mean"] == pytest.approx(0.325)
    kinds = {a["kind"] for a in s["anomalies"]}
    assert "mfu_collapse" in kinds, s["anomalies"]
    assert "mfu" in telemetry_report.render(s)


def test_telemetry_report_serving_cost_columns():
    import telemetry_report
    records = [{"event": "serving", "model": "m", "requests": 2, "rows": 4,
                "bucket": 4, "fill": 1.0, "queue_delay_ms": 1.0,
                "wall_ms": 2.0, "flops": 4000.0, "bytes": 8000.0}
               for _ in range(3)]
    s = telemetry_report.summarize(records)
    t = s["serving"]["m"]
    assert t["flops_per_request"] == pytest.approx(1000.0)
    assert t["bytes_per_request"] == pytest.approx(2000.0)
    assert "flops/req" in telemetry_report.render(s)


# ------------------------------------------------------------- tool wiring
def test_check_perf_smoke():
    """Subprocess wiring for tools/check_perf.py — all five compile-site
    families register from a clean interpreter, exactly how CI runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the tool runs on the default 1-dev host
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_perf.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["families"] == ["embedding", "gluon", "module",
                                  "serving", "spmd"], report
    assert report["module"]["gap_pct"] < 10.0, report
