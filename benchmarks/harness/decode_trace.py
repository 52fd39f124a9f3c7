"""What the per-layer readers of a generation cell ask of the program's
names in a traced run, beside ``program_trace``'s own questions: the
decode programs' device time per execution, and the mean per
``engine.decode`` span of a number the engine wrote on it.  A program
without the table or the spans gives ``None``."""
from __future__ import annotations

import bisect
import re

from benchmarks.harness import manifest, peaks, program_trace


def decode_busy_ms(trace):
    """Device self time per whole execution of the engine's decode
    programs in the window, in ms (prefills are other programs)."""
    progs, executions = _decode_programs(trace)
    if not executions:
        return None
    return sum(p["busy_s"] for p in progs) * 1e3 / executions


def _decode_programs(trace):
    out = program_trace._loaded(trace)
    if out is None or out["device"] is None:
        return [], 0
    progs = [p for k, p in out["device"]["programs"].items()
             if k.startswith("serving/") and "/decode-" in k]
    return progs, sum(p["executions"] for p in progs)


#: the compiler's grouped-product kernels, by instruction name: what XLA
#: makes of ``lax.ragged_dot`` on the chip (``%ragged-dot-none.7 = ...``)
GROUPED_PRODUCT = re.compile(r"ragged-dot")


def grouped_product_ms(trace):
    """Device time per decode execution of the experts' two grouped
    products.  XLA makes them of ``lax.ragged_dot`` as kernels of its own
    and drops the ``mx.moe_experts`` scope on the way (PERF.md section 7),
    so they are matched by INSTRUCTION NAME (:data:`GROUPED_PRODUCT`) in a
    second pass over the trace's device events: self time of the matching
    operations inside whole executions of the decode programs in the
    window — the executions ``program_trace`` counts.  Nothing else is
    counted here: another unnamed kernel on the decode path stays with
    the time under no scope.  0.0 where the backend expands the product
    (the cpu)."""
    progs, executions = _decode_programs(trace)
    if not executions:
        return None
    out = program_trace._loaded(trace)
    lo, hi = out["window"]
    events = program_trace.read_events(program_trace.newest_xplane())
    decode = {p["module"] for p in progs}
    mods = sorted((s, e, n) for n, s, e in events["modules"])
    starts = [m[0] for m in mods]
    total = 0.0
    for text, self_ns, start in program_trace.self_times(events["ops"],
                                                         lo, hi):
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or i == len(mods) - 1 or mods[i][1] < start \
                or mods[i][2] not in decode \
                or not (lo <= mods[i][0] and mods[i][1] <= hi):
            continue
        if GROUPED_PRODUCT.match(program_trace._instruction(text)[0]
                                 .lstrip("%")):
            total += self_ns
    return total / 1e6 / executions


def decode_span_mean(trace, arg):
    """Mean of ``arg`` over the ``engine.decode`` spans in the window."""
    out = program_trace._loaded(trace)
    span = out["spans"].get("engine.decode") if out else None
    if not span or arg not in span["args"]:
        return None
    return span["args"][arg] / span["count"]


def share_of_roofline(obs, trace, scope, busy_ms):
    """The bytes ``ops_bytes/<config>.py`` says ``scope`` (or, for
    ``None``, the whole iteration) needs at what the traced iterations
    held, over the chip's HBM bandwidth, as a percentage of ``busy_ms``."""
    means = [decode_span_mean(trace, a) for a in
             ("state_rows", "held_tokens", "moe_experts_hit")]
    if not busy_ms or None in means:
        return None
    ops = manifest.load_module("ops_bytes", obs["ops_bytes"])
    need = ops.scope_bytes(obs["lm"], *means)
    need = sum(need.values()) if scope is None else need[scope]
    least_ms = need / peaks.peaks(obs["device_kind"])["hbm_bytes_per_s"] * 1e3
    return least_ms / busy_ms * 100.0
