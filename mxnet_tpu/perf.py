"""mx.perf — compiled-program cost attribution (docs/OBSERVABILITY.md).

The reference framework answered "what does this program COST" with the
engine profiler's per-op FLOP/memory tables (src/profiler/profiler.h,
the OPPERF artifacts).  On TPU the whole train step is ONE XLA
executable, so the attribution seam moves to the compile boundary: this
module keeps a registry of every fused program the framework compiles —
Module ``fused_step_fn``, ``SPMDTrainer``'s dense/sparse step programs,
gluon ``_CachedGraph``, serving's per-(model, bucket) AOT programs and
``ShardedEmbedding``'s lookup/update programs — and captures, ONCE per
compile:

* ``Compiled.cost_analysis()``   — flops, bytes accessed, transcendentals;
* ``Compiled.memory_analysis()`` — argument/output/temp/generated-code
  bytes (the XLA memory plan the reference's GPU pooled allocator stats
  approximated);
* a trace/lower/compile wall-time phase breakdown per cache key (fed to
  the ``perf.trace_ms``/``perf.lower_ms``/``perf.compile_ms`` timers);
* an HLO op-class instruction table (matmul/conv/elementwise/reduction/
  collective/copy) parsed from the optimized module text — the OPPERF
  analog, reproducible in-tree;
* a roofline classification: program arithmetic intensity (flops/byte)
  against the device's (peak FLOPs / peak HBM bandwidth) — compute- vs
  bandwidth-bound.

From the registry the per-step *achieved* FLOPs are derived live: each
registered program dispatch adds its (compile-time-known) FLOPs to a
per-source accumulator, and ``telemetry.step_scope`` pops it on step
exit into the ``perf.mfu`` / ``perf.mfu.<source>`` gauges and the
``flops``/``mfu`` JSONL step-record fields.  The off-path contract: all
analysis happens at compile time; the per-dispatch cost is one dict add
and the per-step cost is one dict pop + one divide — nothing touches
the device.

Capture mechanics: the registry wraps each jitted step fn in a
:class:`PerfProgram` that AOT-compiles (``fn.trace(*args).lower()
.compile()``) on its first concrete call — the same single XLA compile
the lazy ``jit`` path would have done, now with the phase breakdown and
the ``Compiled`` handle in hand — then dispatches that Compiled
directly.  Two calls the Compiled can't take go to the plain jitted
callable instead: tracer arguments from an outer ``jax.vjp``, and a
shape-signature drift under a cached wrapper (``perf.aot_fallback``
counts those, permanently).  A failure to trace, lower or compile is NOT
one of them — it propagates, exactly as it would from the lazy ``jit``.

``MXNET_TPU_PROFILE=step:N`` adds periodic evidence capture: every N
steps the next full step runs under a ``jax.profiler`` device trace
(written to ``MXNET_TPU_PROFILE_DIR``), folded with the chrome span
sink through tools/trace_merge.py into a two-plane timeline when
``tracing.sink`` is active.  ``tools/perf_report.py`` merges a
``perf.export()`` registry dump with the telemetry JSONL into the
MFU/roofline report with anomaly flags.
"""
from __future__ import annotations

import json
import os
import re
import threading
import time

__all__ = [
    "DEVICE_PEAKS", "UnknownDeviceError", "known_device",
    "OP_CLASSES", "classify_op", "hlo_op_classes", "device_kind",
    "peak_flops", "peak_bandwidth", "roofline", "register_compiled",
    "programs", "program", "reset", "export", "wrap", "PerfProgram",
    "configure_profile", "cost_analysis", "hlo_op_names",
    "op_names",
]

# ------------------------------------------------------------ peak table
# THE peak table: published per-chip peaks keyed by jax's ``device_kind``,
# as (bf16 MXU TFLOP/s, HBM GB/s).  Source: Google Cloud TPU
# documentation, the per-generation system-architecture pages ("TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s; "TPU v5p": 459, 2765; "TPU v4": 275, 1228;
# "TPU v6e": 918, 1640).  chip_smoke.py and the tools read this table
# and keep no copy.  A device that is not here has no peak: every
# path that would print an MFU or a roofline share for it raises
# :class:`UnknownDeviceError` instead of assuming some other chip's
# numbers, and cost records for it carry ``None`` in their place.
DEVICE_PEAKS = {
    "TPU v5 lite": (197.0, 819.0),   # v5e
    "TPU v5": (459.0, 2765.0),       # v5p
    "TPU v4": (275.0, 1228.0),
    "TPU v6 lite": (918.0, 1640.0),  # v6e / Trillium
}

# peak scaling per compute dtype: bf16 is the MXU native rate; f32 has no
# MXU path and runs at roughly half; int8 doubles on chips with int MXU
# modes.  The basis is recorded next to every MFU number so denominators
# stay auditable.
_DTYPE_PEAK_SCALE = {
    "bfloat16": 1.0, "float16": 1.0, "int8": 2.0,
    "float32": 0.5, "float64": 0.25,
}


class UnknownDeviceError(LookupError):
    """The device kind has no entry in :data:`DEVICE_PEAKS`."""


def device_kind():
    """The local device's ``device_kind`` string, cached (the device set
    is fixed per process)."""
    kind = _KIND_CACHE[0]
    if kind is None:
        import jax
        kind = _KIND_CACHE[0] = str(jax.local_devices()[0].device_kind)
    return kind


_KIND_CACHE = [None]


def known_device(kind=None):
    """True when ``kind`` (default: the local device) has published
    peaks — i.e. an MFU or a roofline share can be stated for it."""
    return (device_kind() if kind is None else kind) in DEVICE_PEAKS


def _peaks(kind):
    if kind is None:
        kind = device_kind()
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise UnknownDeviceError(
            "no published peaks for device kind %r (known: %s); an MFU or "
            "roofline share against another chip's peak would be a made-up "
            "number — add the device to mxnet_tpu.perf.DEVICE_PEAKS with "
            "its source" % (kind, ", ".join(sorted(DEVICE_PEAKS)))) from None


def peak_flops(kind=None, dtype="bfloat16"):
    """Peak FLOP/s for a device kind at a compute dtype (dtype-aware:
    bf16 MXU basis scaled by ``_DTYPE_PEAK_SCALE``).  Raises
    :class:`UnknownDeviceError` for a kind without published peaks."""
    return _peaks(kind)[0] * _DTYPE_PEAK_SCALE.get(str(dtype), 1.0) * 1e12


def peak_bandwidth(kind=None):
    """Peak HBM bandwidth in bytes/s for a device kind; raises
    :class:`UnknownDeviceError` for a kind without published peaks."""
    return _peaks(kind)[1] * 1e9


# --------------------------------------------------------- op-class map
# Shared by the registry's HLO instruction table and
# tools/perf_report.py --trace's device-trace bucketing, so the two cost
# reports cannot drift.  Input is either a bare HLO opcode ("dot") or a
# device-trace op name ("%fusion.42", "convolution.7").
OP_CLASSES = ("matmul", "conv", "elementwise", "reduction", "collective",
              "copy", "other")

_ELEMENTWISE_OPS = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "sqrt", "rsqrt", "cbrt", "power", "negate", "abs", "sign", "floor",
    "ceil", "round-nearest-afz", "round-nearest-even", "compare", "select",
    "and", "or", "not", "xor", "convert", "clamp", "sine", "cosine", "tan",
    "atan2", "logistic", "remainder", "is-finite", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "popcnt",
    "count-leading-zeros", "erf", "real", "imag", "complex", "map",
))

# ordered substring rules for compound/trace names; first hit wins
# (collectives before reductions: "all-reduce" contains "reduce").
_CLASS_SUBSTRINGS = (
    ("collective", ("all-reduce", "allreduce", "all-gather", "allgather",
                    "reduce-scatter", "all-to-all", "collective-permute",
                    "collective", "psum", "ppermute")),
    ("conv", ("conv",)),
    ("matmul", ("dot", "einsum", "matmul", "gemm")),
    ("reduction", ("reduce", "batchnorm", "variance", "argmax", "argmin",
                   "sort", "top-k", "topk", "cumsum", "norm",
                   "select-and-scatter")),
    ("copy", ("transpose", "copy", "reshape", "bitcast", "slice",
              "concatenate", "pad", "broadcast", "gather", "scatter",
              "iota", "reverse", "dynamic-update")),
)


def classify_op(name):
    """Map an HLO opcode or device-trace op name to one of
    :data:`OP_CLASSES`.  Fusion wrappers land in "other" — a trace name
    like ``fusion.42`` says nothing about its body (the registry's
    instruction table counts the fused bodies themselves instead)."""
    n = str(name).lower().lstrip("%")
    base = re.split(r"[.(\s]", n, 1)[0]
    if base in _ELEMENTWISE_OPS:
        return "elementwise"
    for cls, keys in _CLASS_SUBSTRINGS:
        if any(k in n for k in keys):
            return cls
    return "other"


# instruction lines in HLO text: "  %name = f32[8,4]{1,0} opcode(...)".
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([a-z][a-z0-9\-]*)\(",
    re.M)
# bookkeeping opcodes and region wrappers: fusion/call/while bodies are
# listed as their own computations in the module text, so counting the
# wrapper too would double-book them.
_HLO_SKIP_OPS = frozenset(("parameter", "constant", "tuple",
                           "get-tuple-element", "fusion", "call", "while",
                           "conditional", "after-all", "bitcast-convert"))


def hlo_op_classes(hlo_text):
    """Instruction counts per op class from an (optimized) HLO module
    text — fused-computation bodies included, wrappers skipped."""
    counts = {}
    for m in _HLO_INSTR_RE.finditer(hlo_text or ""):
        op = m.group(1)
        if op in _HLO_SKIP_OPS:
            continue
        cls = classify_op(op)
        counts[cls] = counts.get(cls, 0) + 1
    return counts


# -------------------------------------------------------------- roofline
def roofline(flops, bytes_accessed, kind=None, dtype="bfloat16"):
    """Classify a program as compute- vs bandwidth-bound: its arithmetic
    intensity (flops per HBM byte) against the device's ridge point
    (peak FLOPs / peak bandwidth).  A program whose intensity sits left
    of the ridge cannot reach compute peak no matter how good the
    kernels are — the roofline model's one actionable sentence."""
    pf = peak_flops(kind, dtype)
    bw = peak_bandwidth(kind)
    device_ai = pf / bw
    ai = (float(flops) / float(bytes_accessed)) if bytes_accessed else None
    bound = "compute" if (ai is None or ai >= device_ai) else "bandwidth"
    return {
        "arithmetic_intensity": round(ai, 3) if ai is not None else None,
        "device_intensity": round(device_ai, 3),
        "bound": bound,
    }


# -------------------------------------------------------------- registry
_REG_LOCK = threading.Lock()
# an executed instruction of optimized HLO text with what follows its
# "=": '  [ROOT ]%fusion.11 = bf16[8,4]{1,0} fusion(...), ..., metadata={...}'
_HLO_NAMED_RE = re.compile(r"^\s+(ROOT\s+)?%([\w.\-]+) = (.*)$")
_HLO_SHAPE_RE = re.compile(r"^(.*?) [a-z][\w\-]*\(")
_HLO_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS_RE = re.compile(r"kind=k\w+, calls=%([\w.\-]+)")
_HLO_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) \(.*\{$")
_HLO_MODULE_RE = re.compile(r"^HloModule ([\w.\-]+)")


def hlo_op_names(hlo_text):
    """``{"module": name, "ops": {instruction: [result shape, op_name]}}``
    from optimized HLO text: for every instruction the device executes as
    an operation of its own (fusion bodies left out), the name-stack path
    jax recorded for it — ``jit(step)/mx.opt_update/mul``.  A fusion
    without a name of its own takes its root's.  A device profile taken
    without HLO protos names an event by its instruction alone
    (``%fusion.11 = ...``); this table is what joins it to the
    ``jax.named_scope`` / ``pallas_call(name=)`` names of the program."""
    module = None
    ops, roots, fused, calls = {}, {}, set(), {}
    comp = None
    for line in (hlo_text or "").splitlines():
        m = _HLO_NAMED_RE.match(line)
        if m is None:
            c = _HLO_COMPUTATION_RE.match(line)
            if c is not None:
                comp = c.group(1)
            elif module is None:
                h = _HLO_MODULE_RE.match(line)
                if h is not None:
                    module = h.group(1)
            continue
        root, name, rest = m.groups()
        n = _HLO_OP_NAME_RE.search(rest)
        op_name = n.group(1) if n is not None else ""
        if root:
            roots[comp] = op_name
        k = _HLO_CALLS_RE.search(rest)
        if k is not None:
            fused.add(k.group(1))
            calls[name] = k.group(1)
        sh = _HLO_SHAPE_RE.match(rest)
        ops[name] = [comp, sh.group(1) if sh is not None else "", op_name]
    out = {}
    for name, (comp, shape, op_name) in ops.items():
        if comp in fused:
            continue
        if not op_name and name in calls:
            op_name = roots.get(calls[name], "")
        if op_name:     # an instruction jax named nothing is in no scope
            out[name] = [shape, op_name]
    return {"module": module, "ops": out}


_PROGRAMS = {}  # guarded-by[writes]: _REG_LOCK — (family, key) -> record

FAMILIES = ("module", "spmd", "gluon", "serving", "embedding")

#: flops dispatched through registered programs since the last step pop,
#: per step-log source: source -> [flops, flops/peak_flops].
_PENDING_LOCK = threading.Lock()
_PENDING = {}  # guarded-by: _PENDING_LOCK


def _dominant_dtype(args):
    """The compute dtype an MFU denominator should assume: bf16/f16 if
    any argument leaf carries it, else f32."""
    try:
        import jax
        for leaf in jax.tree_util.tree_leaves(args):
            d = str(getattr(leaf, "dtype", ""))
            if d in ("bfloat16", "float16"):
                return d
    except Exception:  # noqa: BLE001 — dtype guess only
        pass
    return "float32"


def register_compiled(family, key, compiled, phases_ms=None, dtype=None):
    """Capture one compiled program's cost/memory/op-class/roofline
    analysis into the registry (idempotent per (family, key): a
    recompile under a new knob epoch overwrites).  Returns the record,
    or None when the runtime exposes no cost analysis at all."""
    from . import telemetry as _telemetry
    cost = {}
    try:
        c = compiled.cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0] if c else {}
        cost = dict(c or {})
    except Exception:  # noqa: BLE001 — backend without cost analysis
        cost = {}
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    memory = {}
    try:
        m = compiled.memory_analysis()
        for attr, out in (("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          ("temp_size_in_bytes", "temp_bytes"),
                          ("alias_size_in_bytes", "alias_bytes"),
                          ("generated_code_size_in_bytes",
                           "generated_code_bytes")):
            v = getattr(m, attr, None)
            if v is not None:
                memory[out] = int(v)
    except Exception:  # noqa: BLE001 — backend without memory analysis
        pass
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 — opaque executable
        text = ""
    if not cost and not memory:
        return None
    dtype = dtype or "float32"
    kind = device_kind()
    # a device without published peaks (the CPU test mesh) still gets its
    # counts; what needs a peak is None, never another chip's number
    known = known_device(kind)
    phases = {k: round(float(v), 3)
              for k, v in (phases_ms or {}).items()}
    # the compile-phase breakdown as live timer histograms
    if "trace_ms" in phases:
        _telemetry.timer("perf.trace_ms").observe(phases["trace_ms"])
    if "lower_ms" in phases:
        _telemetry.timer("perf.lower_ms").observe(phases["lower_ms"])
    if "compile_ms" in phases:
        _telemetry.timer("perf.compile_ms").observe(phases["compile_ms"])
    rec = {
        "family": str(family),
        "key": str(key),
        "ts": round(time.time(), 3),
        "device_kind": kind,
        "dtype": dtype,
        "flops": flops,
        "bytes_accessed": nbytes,
        "transcendentals": float(cost.get("transcendentals", 0.0) or 0.0),
        "memory": memory,
        "phases_ms": phases,
        "op_classes": hlo_op_classes(text),
        # private (large): instruction -> name-stack path, for op_names()
        "_op_names": hlo_op_names(text),
        "roofline": roofline(flops, nbytes, kind, dtype) if known else None,
        "peak_tflops": (round(peak_flops(kind, dtype) / 1e12, 3)
                        if known else None),
        "calls": 0,
        # private: per-dispatch accumulation precomputes flops/peak so
        # the step-exit MFU is one divide (stripped from snapshots)
        "_flops_over_peak": (flops / peak_flops(kind, dtype)
                             if known else None),
    }
    _telemetry.counter("perf.programs").inc()
    with _REG_LOCK:
        _PROGRAMS[(rec["family"], rec["key"])] = rec
    return rec


def cost_analysis(fn, *args):
    """Compiler cost analysis for ``fn(*args)`` without running it:
    ``{"flops", "bytes_accessed", "transcendentals"}`` floats, or None
    when the backend exposes no analysis.  ``fn`` may be plain or
    already jitted — either way this only lowers and compiles (AOT);
    tools/opperf.py uses it for per-op achieved-GFLOPs columns."""
    import jax
    try:
        if not hasattr(fn, "lower"):
            fn = jax.jit(fn)
        c = fn.lower(*args).compile().cost_analysis()
        if isinstance(c, (list, tuple)):
            c = c[0] if c else {}
        c = dict(c or {})
    except Exception:  # noqa: BLE001 — backend without cost analysis
        return None
    if not c:
        return None
    return {"flops": float(c.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(c.get("bytes accessed", 0.0) or 0.0),
            "transcendentals": float(c.get("transcendentals", 0.0) or 0.0)}


def _public(rec):
    return {k: v for k, v in rec.items() if not k.startswith("_")}


def programs(family=None):
    """Snapshot of registered program records (dict copies, private
    accounting fields stripped), newest last."""
    with _REG_LOCK:
        recs = list(_PROGRAMS.values())
    recs.sort(key=lambda r: r["ts"])
    return [_public(r) for r in recs
            if family is None or r["family"] == family]


def program(family, key):
    """One registered record by (family, key), or None."""
    with _REG_LOCK:
        rec = _PROGRAMS.get((str(family), str(key)))
    return _public(rec) if rec is not None else None


def op_names(family=None):
    """Per registered program, the instruction -> ``[result shape,
    op_name]`` table of :func:`hlo_op_names` with the program's
    ``family``, ``key`` and HLO ``module`` name — what a reader of a
    device trace joins event names with to find the ``mx.*`` scope (or the
    Gluon block) an operation ran under."""
    with _REG_LOCK:
        recs = list(_PROGRAMS.values())
    return [dict(rec["_op_names"], family=rec["family"], key=rec["key"])
            for rec in recs if family is None or rec["family"] == family]


def reset():
    """Forget every registered program and pending step attribution
    (tests; the instruments themselves reset via telemetry.reset)."""
    with _REG_LOCK:
        _PROGRAMS.clear()
    with _PENDING_LOCK:
        _PENDING.clear()


def export(path=None):
    """The registry as one JSON-serializable dict (written to ``path``
    when given) — the program-side input of tools/perf_report.py."""
    out = {
        "event": "perf_programs",
        "ts": round(time.time(), 3),
        "device_kind": device_kind(),
        "device_peaks": DEVICE_PEAKS.get(device_kind()),
        "programs": programs(),
    }
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return out


# -------------------------------------------------------- program wrapper
def _has_tracers(args):
    import jax
    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(args))


class PerfProgram:
    """Registry-instrumented dispatch of one cached jitted program.

    First concrete call AOT-compiles (trace -> lower -> compile, each
    phase timed) and registers the analysis; every later call goes to
    the Compiled directly and adds the program's FLOPs to its source's
    step accumulator.  Tracer arguments (a gluon program invoked inside
    an outer ``jax.vjp`` trace) are passed to the plain jitted fn so it
    inlines into the outer program, exactly as unwrapped; a signature
    drift under the cached wrapper (the Compiled rejects the args)
    permanently falls back to plain jit and counts
    ``perf.aot_fallback``.  Trace, lower and compile errors propagate."""

    __slots__ = ("fn", "family", "key", "source", "check_tracers",
                 "_compiled", "_record", "_fellback")

    def __init__(self, fn, family, key, source=None, check_tracers=False):
        self.fn = fn
        self.family = family
        self.key = key
        self.source = source
        self.check_tracers = check_tracers
        self._compiled = None
        self._record = None
        self._fellback = False

    def _account(self):
        rec = self._record
        if rec is None:
            return
        rec["calls"] += 1
        src = self.source
        if src is None:
            return
        over_peak = rec["_flops_over_peak"]
        with _PENDING_LOCK:
            cur = _PENDING.get(src)
            if cur is None:
                _PENDING[src] = [rec["flops"], over_peak]
            else:
                cur[0] += rec["flops"]
                if over_peak is not None:  # one device kind per process:
                    cur[1] += over_peak    # every record has it, or none

    def _fallback(self, *args):
        from . import telemetry as _telemetry
        self._compiled = None
        self._fellback = True
        _telemetry.counter("perf.aot_fallback").inc()
        return self.fn(*args)

    def _capture(self, args):
        t0 = time.perf_counter()
        traced = self.fn.trace(*args)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        compiled = lowered.compile()
        t3 = time.perf_counter()
        self._record = register_compiled(
            self.family, self.key, compiled,
            phases_ms={"trace_ms": (t1 - t0) * 1e3,
                       "lower_ms": (t2 - t1) * 1e3,
                       "compile_ms": (t3 - t2) * 1e3},
            dtype=_dominant_dtype(args))
        return compiled

    def __call__(self, *args):
        if self.check_tracers and _has_tracers(args):
            # inside an outer trace (gluon autograd vjp): the plain jit
            # fn inlines; the Compiled could not accept tracers
            return self.fn(*args)
        if self._fellback:
            self._account()
            return self.fn(*args)
        compiled = self._compiled
        if compiled is None:
            compiled = self._compiled = self._capture(args)
        self._account()
        try:
            return compiled(*args)
        except (TypeError, ValueError):
            # signature drift under the cached wrapper: the Compiled
            # checks its arguments BEFORE it dispatches and rejects a
            # changed shape/dtype/weak-type/pytree (TypeError) or
            # sharding (ValueError).  Re-dispatch through plain jit,
            # which retraces per signature like the unwrapped path did.
            # What the device raises is a RuntimeError and propagates.
            return self._fallback(*args)


def wrap(fn, family, key, source=None, check_tracers=False):
    """Instrument one cached jitted callable for the program registry.
    ``source`` names the telemetry step-log source whose MFU this
    program's dispatches feed (module/spmd/gluon); None (serving,
    embedding) registers cost without step attribution."""
    return PerfProgram(fn, family, key, source=source,
                       check_tracers=check_tracers)


# ------------------------------------------------------------- step hook
def _on_step(source, step_idx, wall_s):
    """telemetry.step_scope exit hook: pop the source's dispatched-FLOPs
    accumulator into the live MFU gauges and the step record's
    ``flops``/``mfu`` fields (``mfu`` only on a device with published
    peaks).  Cost: one dict pop; one divide and two gauge sets when a
    registered program ran this step."""
    with _PENDING_LOCK:
        acc = _PENDING.pop(source, None)
    extra = None
    if acc is not None and wall_s > 0:
        extra = {"flops": round(acc[0], 1)}
        if acc[1] is not None:  # None: this device has no published peak
            from . import telemetry as _telemetry
            # 6 significant digits, not decimals: a small program's MFU
            # must survive the JSONL round-trip
            mfu = float("%.6g" % (acc[1] / wall_s))
            _telemetry.gauge("perf.mfu").set(mfu)
            _telemetry.gauge("perf.mfu.%s" % source).set(mfu)
            extra["mfu"] = mfu
    if _PROFILE["every"] > 0:
        _maybe_profile(source, step_idx)
    return extra


# --------------------------------------------- periodic device capture
# guarded-by: _PROFILE_LOCK — the lock-free ``every`` read on the step
# path tolerates staleness by one step during reconfigure.
_PROFILE_LOCK = threading.Lock()
_PROFILE = {"every": 0, "count": 0, "active": None}


def configure_profile(spec):
    """(Re)configure ``MXNET_TPU_PROFILE`` auto-capture: ``step:N``
    traces one full train step every N steps; empty disables."""
    spec = (spec or "").strip()
    every = 0
    if spec:
        m = re.match(r"^step:(\d+)$", spec)
        if not m or int(m.group(1)) < 1:
            raise ValueError(
                "perf.profile spec %r: expected 'step:N' (N >= 1)"
                % (spec,))
        every = int(m.group(1))
    with _PROFILE_LOCK:
        _PROFILE["every"] = every
        _PROFILE["count"] = 0


def _maybe_profile(source, step_idx):
    """Runs at step exit while the knob is armed: stop an active
    capture (it covered exactly the step that just finished) and fold
    it; every N completed steps, start the next one so the FOLLOWING
    step runs end-to-end under the device trace."""
    from . import telemetry as _telemetry
    with _PROFILE_LOCK:
        every = _PROFILE["every"]
        active = _PROFILE["active"]
        if active is not None:
            _PROFILE["active"] = None
            try:
                import jax
                jax.profiler.stop_trace()
                _telemetry.counter("perf.profiles_captured").inc()
            except Exception:  # noqa: BLE001 — a capture must never
                active = None  # kill the train loop
            if active is not None:
                _fold_device_trace(active)
        if every <= 0:
            return
        _PROFILE["count"] += 1
        if _PROFILE["count"] % every != 0:
            return
        from . import config as _config
        base = (_config.get("perf.profile_dir") or "").strip() or "."
        out = os.path.join(base, "perf_step_%s_%d" % (source, step_idx + 1))
        try:
            import jax
            os.makedirs(out, exist_ok=True)
            jax.profiler.start_trace(out)
            _PROFILE["active"] = out
        except Exception:  # noqa: BLE001 — profiler busy (mx.profiler
            _PROFILE["active"] = None  # capture running): skip this slot


def _fold_device_trace(trace_dir):
    """Best-effort fold of a finished step capture with the chrome span
    sink (tools/trace_merge.py) into ``<trace_dir>/merged.json``."""
    try:
        from . import tracing as _tracing
        host_path = _tracing.sink_path()
        if not host_path or not os.path.exists(host_path):
            return
        tm = _load_trace_merge()
        if tm is None:
            return
        host = tm.load_chrome_trace(host_path)
        dev = tm.resolve_device_trace(trace_dir)
        merged = tm.merge_traces(host, dev, align="zero")
        with open(os.path.join(trace_dir, "merged.json"), "w") as f:
            json.dump(merged, f)
    except Exception:  # noqa: BLE001 — evidence folding is optional
        pass


def _load_trace_merge():
    """tools/ is not a package; load trace_merge.py by path (repo
    checkouts only — None when the tree layout doesn't carry it)."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_merge.py")
    if not os.path.exists(path):
        return None
    import importlib.util
    spec = importlib.util.spec_from_file_location("_mxtpu_trace_merge",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# Install the step hook and honor MXNET_TPU_PROFILE at import.
# telemetry.py imports this module at its bottom (the tracing pattern),
# so any training-path import arms cost attribution; the hook is a slot
# on telemetry rather than an import so telemetry stays dependency-free.
from . import config as _config  # noqa: E402
from . import telemetry as _telemetry_mod  # noqa: E402

_telemetry_mod._PERF_STEP_HOOK = _on_step

try:
    configure_profile(_config.get("perf.profile"))
except KeyError:  # pragma: no cover — config stripped of the knob
    pass
