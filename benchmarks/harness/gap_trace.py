"""The gap between two serving programs, split into legs on one clock.

The engine is serial: it calls a program (span ``engine.<kind>.dispatch``),
waits for and copies out its tokens (``engine.<kind>.fetch``), emits,
admits, prepares and calls the next.  The device sits idle from the end of
execution N to the start of execution N+1.  A trace holds both sides — the
executions (``XLA Modules``) on the device's clock, the spans on the
host's — and the profiler's conversion between the two is good to about a
millisecond, the size of the gap itself.  So nothing here trusts it beyond
telling which execution belongs to which call.  With ``delta = host -
device`` unknown, causality bounds it from every execution ``i``::

    delta >= dispatch_begin_i - start_i      the chip starts after the call
    delta <= fetch_end_i - end_i             the host returns after the chip

``L`` is the largest lower bound and ``U`` the smallest upper bound over a
block of ``BLOCK`` executions (a drift shows as a slope of the mid-point
over the blocks).  A gap then splits exactly, wherever in ``[L, U]`` the
true ``delta`` lies::

    gap_i  = start_{N+1} - end_N                    device clock alone
    host_i = dispatch_begin_{N+1} - fetch_end_N     host clock alone
    gap_i - host_i = (fetch_end_N - end_N) - (dispatch_begin_{N+1} - start_{N+1})
                   = (U - L)                        the floor
                   + (L - lower_{N+1})              launch over its least
                   + (upper_N - U)                  read-back over its least

The floor is the least launch plus the least read-back any step of the
block took: what no step does without.  An execution's end is moved past
the small programs that ran behind it before the next call (the slice
behind ``int(nxt[0])`` after a prefill, which the fetch waits for too), so
a gap is device-idle time by the union of operations.  Gaps that span an
``engine.wait`` are left out, and gaps over ``OUTLIER`` times the median
are counted apart, each with what covered it.

``read_runtime`` is this module's own pass over the file for what
``program_trace.read_events`` drops: the events of the runtime's own host
threads (``host_tracer_level`` 2), by which ``runtime_under`` says what lay
beneath one span and ``narrowed`` tightens ``[L, U]`` where the runtime
marks hand-over and completion.

    python3 benchmarks/harness/gap_trace.py <file.xplane.pb> [scopes.json]

prints the reduction of any ``jax.profiler`` capture of a running server.
"""
from __future__ import annotations

import bisect
import json
import os
import statistics
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.harness import program_trace, trace_reduce  # noqa: E402

DISPATCH, FETCH, WAIT = ".dispatch", ".fetch", "engine.wait"
BLOCK = 50
OUTLIER = 10.0
LEGS = ("gap", "host", "floor", "launch_var", "readback_var")
# the runtime's marks (TPU PJRT client): the call's last act on the host
# is handing the program to the chip's queue; completion is first seen by
# the thread that reads the chip's sync flag
HAND_OVER = "DoEnqueueProgram"
COMPLETION = "ReadSyncFlag"

_CACHE = {}


# ------------------------------------------------------------- the file
def read_runtime(path):
    """``[(name, start, end, line)]`` of the host plane's events that are
    no span of the program or the benchmark (their names are not dotted
    lower-case), times in ns."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not program_trace.SPAN_NAME.match(e.name):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns),
                                line.name))
    out.sort(key=lambda ev: ev[1])
    return out


# ---------------------------------------------------------- the matching
def engine_calls(spans):
    """``[(kind, dispatch_begin, dispatch_end, fetch_begin, fetch_end)]``
    in order, of the thread that wrote most ``*.dispatch`` spans: each
    dispatch with the fetch that follows it under the same prefix."""
    threads = {}
    for name, s, e, _, thread in spans:
        if name.endswith(DISPATCH) or name.endswith(FETCH):
            threads.setdefault(thread, []).append((s, e, name))
    counts = {t: sum(n.endswith(DISPATCH) for _, _, n in evs)
              for t, evs in threads.items()}
    if not counts or not max(counts.values()):
        return []
    calls, pending = [], None
    for s, e, name in sorted(threads[max(counts, key=counts.get)]):
        prefix = name.rsplit(".", 1)[0]
        if name.endswith(DISPATCH):
            pending = (prefix, s, e)
        elif pending is not None and pending[0] == prefix:
            calls.append((prefix.rsplit(".", 1)[-1],
                          pending[1], pending[2], s, e))
            pending = None
    return calls


def match(executions, calls, strict):
    """Per call the index of its execution, or a fault.  An execution
    belongs to the call whose ``[dispatch_begin, fetch_end]`` covers most
    of it, by the profiler's own alignment (good to a millisecond or two
    where an execution lasts several): more than half of it, else to
    none.  Of several under one call the longest is the program, the rest
    ran behind it.  Calls at either end of the trace whose execution the
    session cut find none and are left out (``(first, [index])``); a call
    without an execution between two that have one, or (``strict``: the
    executions are the registered serving programs') two executions under
    one call or one under none, is a fault (``(None, reason)``)."""
    begins = [c[1] for c in calls]
    under = [[] for _ in calls]
    orphans = []
    for i, (s, e, _) in enumerate(executions):
        j = bisect.bisect_right(begins, (s + e) / 2.0) - 1
        best, cover = None, 0.0
        for k in (j - 1, j, j + 1):
            if 0 <= k < len(calls):
                o = min(e, calls[k][4]) - max(s, calls[k][1])
                if o > cover:
                    best, cover = k, o
        if best is not None and cover > 0.5 * (e - s):
            under[best].append(i)
        else:
            orphans.append(i)
    have = [j for j, got in enumerate(under) if got]
    if not have:
        return None, "matching: no execution under any call"
    first, last = have[0], have[-1]
    chosen = []
    for j in range(first, last + 1):
        if not under[j]:
            return None, "matching: call %d of %d has no execution" % (
                j, len(calls))
        if strict and len(under[j]) > 1:
            return None, "matching: %d executions under call %d" % (
                len(under[j]), j)
        chosen.append(max(under[j], key=lambda i: executions[i][1]
                          - executions[i][0]))
    if strict:
        inside = [i for i in orphans
                  if chosen[0] < i < chosen[-1]]
        if inside:
            return None, "matching: %d executions under no call" % len(
                inside)
    return first, chosen


# --------------------------------------------------------------- the legs
def _module(event):
    """``jit_step(123)``, an execution's event -> ``jit_step``, the HLO
    module's name."""
    m = program_trace.MODULE_EVENT.match(event)
    return m.group(1) if m else event


def _busy_between(busy, ends, a, b):
    """Length of the sorted disjoint intervals ``busy`` inside ``[a, b]``."""
    total = 0.0
    for s, e in busy[bisect.bisect_right(ends, a):]:
        if s >= b:
            break
        total += min(e, b) - max(s, a)
    return total


def legs(events, serving=None, block=BLOCK, runtime=None):
    """The reduction described at the top, from ``program_trace.
    read_events``'s lists; ``serving`` the HLO module names of the
    registered serving programs (None: every module may be one).  None
    where there is nothing to read (no window, no ``*.dispatch`` span: the
    program does not write them, no execution); ``{"fault": reason, ...}``
    where the matching or the clock is at fault; times in ms."""
    spans = events["spans"]
    windows = [(s, e) for n, s, e, _, _ in spans
               if n == trace_reduce.WINDOW_SPAN]
    calls = engine_calls(spans)
    if not windows or not calls or not events["modules"]:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    mods = sorted((s, e, n) for n, s, e in events["modules"])[:-1]
    if serving is not None:
        mods = [m for m in mods if _module(m[2]) in serving]
    out = {"fault": None, "window": [lo, hi], "window_s": (hi - lo) / 1e9,
           "calls_in_trace": len(calls), "executions_in_trace": len(mods)}
    first, chosen = match(mods, calls, strict=serving is not None)
    if first is None:
        out["fault"] = chosen
        return out
    rows = [(calls[first + k], mods[i]) for k, i in enumerate(chosen)
            if calls[first + k][1] >= lo and calls[first + k][4] <= hi]
    if len(rows) < 2:
        return None
    busy = trace_reduce.union((s, e) for _, s, e in events["ops"])
    busy_ends = [b[1] for b in busy]
    n = len(rows)
    kinds = [c[0] for c, _ in rows]
    start = [m[0] for _, m in rows]
    # an execution's end, moved past what ran behind it before the next
    tail = [_busy_between(busy, busy_ends, rows[i][1][1], start[i + 1])
            for i in range(n - 1)] + [0.0]
    end = [m[1] + t for (_, m), t in zip(rows, tail)]
    lower = [c[1] - s for (c, _), s in zip(rows, start)]
    upper = [c[4] - e for (c, _), e in zip(rows, end)]
    blocks = []
    for b in range(0, n, block):
        L, U = max(lower[b:b + block]), min(upper[b:b + block])
        blocks.append((L, U, (start[b] + end[min(b + block, n) - 1]) / 2.0))
        if U < L:
            out["fault"] = (
                "clock: executions %d-%d of %d allow no offset (U - L = "
                "%.3f ms): the matching or the profiler's conversion"
                % (b, min(b + block, n) - 1, n, (U - L) / 1e6))
            return out
    waits = sorted((s, e) for nm, s, e, _, _ in spans if nm == WAIT)
    gaps = []
    for i in range(n - 1):
        a, b = rows[i][0][4], rows[i + 1][0][1]     # fetch end, next call
        L, U = blocks[(i + 1) // block][0], blocks[i // block][1]
        gaps.append({
            "i": i, "kind": "%s>%s" % (kinds[i], kinds[i + 1]),
            "start_s": (end[i] - lo) / 1e9,
            "gap": start[i + 1] - end[i], "host": b - a, "floor": U - L,
            "launch_var": L - lower[i + 1], "readback_var": upper[i] - U,
            "wait": any(s < b and e > a for s, e in waits)})
    counted = [g for g in gaps if not g["wait"]]
    if not counted:
        return None
    median = statistics.median(g["gap"] for g in counted)
    outliers = [g for g in counted if g["gap"] > OUTLIER * median]
    counted = [g for g in counted if g["gap"] <= OUTLIER * median]
    mids = [(L + U) / 2.0 for L, U, _ in blocks]
    decode_calls = [(c[2] - c[1]) / 1e6 for c, _ in rows if c[0] == "decode"]
    out.update({
        "executions": n, "gaps_counted": len(counted),
        "gaps_across_wait": sum(g["wait"] for g in gaps),
        "gap_median_ms": median / 1e6,
        "mean_ms": {}, "std_ms": {}, "by_kind": {},
        "outlier_share": sum(g["gap"] for g in outliers) / (hi - lo) * 100.0,
        "outliers": [_outlier(g, rows, spans, runtime) for g in outliers],
        "call_ms": statistics.fmean(decode_calls) if decode_calls else None,
        "clock": {
            "slack_ms": sum(U - L for L, U, _ in blocks) / len(blocks) / 1e6,
            "offset_ms": sum(mids) / len(mids) / 1e6,
            "drift_us_per_s": statistics.linear_regression(
                [t / 1e9 for _, _, t in blocks],
                [m / 1e3 for m in mids]).slope if len(blocks) > 1 else None,
            "blocks": [[(t - lo) / 1e9, L / 1e6, U / 1e6]
                       for L, U, t in blocks]}})
    for leg in LEGS:
        values = [g[leg] / 1e6 for g in counted]
        out["mean_ms"][leg] = statistics.fmean(values)
        out["std_ms"][leg] = statistics.pstdev(values)
    for kind in sorted({g["kind"] for g in counted}):
        of = [g for g in counted if g["kind"] == kind]
        out["by_kind"][kind] = dict(
            {leg: statistics.fmean(g[leg] / 1e6 for g in of) for leg in LEGS},
            gaps=len(of))
    if runtime is not None:
        out["clock"]["narrowed"] = narrowed(runtime, rows, start, block)
    return out


def _outlier(gap, rows, spans, runtime):
    """One gap over ``OUTLIER`` medians: its legs; the program's spans
    over the host interval from call N's begin to call N+1's return; and
    the longest stretch of that interval in which NO thread of the process
    began or ended anything — the program's spans, the benchmark's, the
    runtime's events — with what lay across it and who wrote the first
    edges after it.  A process that was not running shows so: every thread
    silent, another thread's timed wait overslept, all back within a
    fraction of a millisecond."""
    i = gap["i"]
    a, b = rows[i][0][1], rows[i + 1][0][4]
    events = [(s, e, name, _line_kind(thread))
              for name, s, e, _, thread in spans
              if name != trace_reduce.WINDOW_SPAN]
    events += [(s, e, name, _line_kind(line))
               for name, s, e, line in runtime or ()]
    events = [ev for ev in events if ev[0] < b and ev[1] > a]
    cover = {}
    for s, e, name, _ in events:
        if program_trace.SPAN_NAME.match(name) \
                and not name.startswith(trace_reduce.SPAN_PREFIX):
            cover[name] = cover.get(name, 0.0) + (min(e, b) - max(s, a)) / 1e6
    edges = sorted({a, b} | {t for s, e, _, _ in events for t in (s, e)
                             if a <= t <= b})
    q0, q1 = max(zip(edges, edges[1:]), key=lambda p: p[1] - p[0])
    return {"kind": gap["kind"], "start_s": gap["start_s"],
            "ms": {leg: gap[leg] / 1e6 for leg in LEGS},
            "spans_ms": dict(sorted(cover.items(),
                                    key=lambda kv: -kv[1])[:8]),
            "quiet_ms": (q1 - q0) / 1e6, "quiet_after_ms": (q0 - a) / 1e6,
            "across": sorted(([thread, name, (e - s) / 1e6]
                              for s, e, name, thread in events
                              if s <= q0 and e >= q1),
                             key=lambda r: r[2])[:8],
            "resumed": sorted({(thread, name)
                               for s, e, name, thread in events
                               if q1 <= s <= q1 + 2e5
                               or q1 <= e <= q1 + 2e5})[:8]}


# ------------------------------------------------- the runtime's own events
def _line_kind(line):
    """``pjrt-tpu-tasks/1234`` -> ``pjrt-tpu-tasks``; a thread without a
    name (a bare id) -> ``unnamed``."""
    head = line.rsplit("/", 1)[0]
    return head if head and not head.isdigit() else "unnamed"


def runtime_under(runtime, spans, name, lo, hi):
    """What the runtime's threads wrote beneath the spans called ``name``
    that lie wholly in the window: per (thread kind, event name) how many
    a span, their mean duration and where they begin after the span's
    begin and before its end (ms), the most time first."""
    hosts = sorted((s, e) for n, s, e, _, _ in spans
                   if n == name and s >= lo and e <= hi)
    if not hosts:
        return []
    begins = [h[0] for h in hosts]
    acc = {}
    for ev_name, s, e, line in runtime:
        j = bisect.bisect_right(begins, s) - 1
        if j < 0 or s >= hosts[j][1]:
            continue
        rec = acc.setdefault((_line_kind(line), ev_name), [0, 0.0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += e - s
        rec[2] += s - hosts[j][0]
        rec[3] += hosts[j][1] - e
    return [{"thread": k[0], "event": k[1], "per_span": v[0] / len(hosts),
             "mean_ms": v[1] / v[0] / 1e6,
             "after_begin_ms": v[2] / v[0] / 1e6,
             "before_end_ms": v[3] / v[0] / 1e6}
            for k, v in sorted(acc.items(), key=lambda kv: -kv[1][1])]


def narrowed(runtime, rows, start, block=BLOCK):
    """``[L, U]`` again with the runtime's marks beside the spans' edges:
    the chip cannot start before the first ``HAND_OVER`` event after the
    call began (it may lie after the call returned: another thread hands
    the program over), and has ended before the first ``COMPLETION`` event
    that ends after that hand-over (one may cover several programs: the
    slice behind a prefill).  Means over the blocks, ms: ``slack_ms`` the
    narrower ``U - L`` — the chip's start after the hand-over plus the
    completion's notice after its end, which no clock here tells apart;
    ``launch_floor_ms`` / ``readback_floor_ms`` the least launch and the
    least read-back as far as the narrower interval pins them, each ``[low,
    high]``; and on the host's clock alone ``hand_over_ms`` (call begin to
    hand-over) and ``return_ms`` (completion seen to the fetch's return).
    None where a call shows neither mark; a fault where they cross."""
    hands = sorted((s, e) for name, s, e, _ in runtime if name == HAND_OVER)
    dones = sorted(e for name, _, e, _ in runtime if name == COMPLETION)
    if not hands or not dones:
        return None
    hand_begins = [h[0] for h in hands]
    marks = []      # per execution: lower, upper by the marks; spans' too
    for (call, mod), s in zip(rows, start):
        j = bisect.bisect_left(hand_begins, call[1])
        if j == len(hands) or hands[j][0] >= call[4]:
            return None
        k = bisect.bisect_right(dones, hands[j][1])
        if k == len(dones):
            return None
        marks.append((hands[j][0] - s, dones[k] - mod[1],
                      call[1] - s, call[4] - mod[1],
                      hands[j][0] - call[1], call[4] - dones[k]))
    out = {"slack_ms": [], "offset_ms": [], "launch_floor_ms": [],
           "readback_floor_ms": []}
    for b in range(0, len(marks), block):
        part = marks[b:b + block]
        L, U = max(m[2] for m in part), min(m[3] for m in part)
        L2 = max(L, max(m[0] for m in part))
        U2 = min(U, min(m[1] for m in part))
        if U2 < L2:
            return {"fault": "the runtime's marks allow no offset in block "
                             "%d (U - L = %.3f ms)" % (b // block,
                                                       (U2 - L2) / 1e6)}
        out["slack_ms"].append((U2 - L2) / 1e6)
        out["offset_ms"].append((U2 + L2) / 2e6)
        out["launch_floor_ms"].append(((L2 - L) / 1e6, (U2 - L) / 1e6))
        out["readback_floor_ms"].append(((U - U2) / 1e6, (U - L2) / 1e6))
    n = len(out["slack_ms"])
    for key in ("slack_ms", "offset_ms"):
        out[key] = sum(out[key]) / n
    for key in ("launch_floor_ms", "readback_floor_ms"):
        out[key] = [sum(v[0] for v in out[key]) / n,
                    sum(v[1] for v in out[key]) / n]
    out["hand_over_ms"] = sum(m[4] for m in marks) / len(marks) / 1e6
    out["return_ms"] = sum(m[5] for m in marks) / len(marks) / 1e6
    return out


# ------------------------------------------------- what the readers ask
def serving_modules(tables):
    if not tables:
        return None
    return {t.get("module") for t in tables
            if t.get("family") == "serving"} or None


def load(path=None, tables=None, report=True):
    """The legs of the run's trace, parsed once a process; None where
    there is no trace or nothing to read in it."""
    path = path or program_trace.newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        tables = program_trace.program_tables() if tables is None else tables
        events = program_trace.read_events(path)
        out = None
        if engine_calls(events["spans"]):     # else: nothing more to parse
            runtime = read_runtime(path)
            out = legs(events, serving_modules(tables), runtime=runtime)
            if out is not None and not out["fault"]:
                out["under_decode_device"] = runtime_under(
                    runtime, events["spans"], "engine.decode.device",
                    *out["window"])[:40]
        _CACHE.clear()
        _CACHE[key] = out
        if report and out is not None:
            _write_report(path, out)
    return _CACHE[key]


def _write_report(path, out):
    """``chiprun_out/<cell>.gap_trace.json``, beside the run's other
    files: what PERF.md section 5 is written from."""
    cell = os.path.basename(os.path.dirname(
        path.split(os.sep + "plugins" + os.sep)[0]))
    try:
        os.makedirs(os.path.join(program_trace.ROOT, "chiprun_out"),
                    exist_ok=True)
        with open(os.path.join(program_trace.ROOT, "chiprun_out",
                               cell + ".gap_trace.json"), "w") as f:
            json.dump(out, f, indent=1, default=str)
    except OSError:
        pass


def field(trace, name):
    """One field of the reduction of the run's trace; None in an untraced
    run, for a program without the ``*.dispatch`` spans and where the
    reduction found a fault (the report says which)."""
    out = load() if trace is not None else None
    if out is None or out["fault"]:
        return None
    return out[name]


def leg_ms(trace, leg):
    """Mean of one leg over the counted gaps, in ms."""
    means = field(trace, "mean_ms")
    return None if means is None else means[leg]


if __name__ == "__main__":
    scopes = None
    if len(sys.argv) > 2:
        with open(sys.argv[2]) as f:
            scopes = json.load(f)
    got = load(sys.argv[1], tables=scopes or [], report=False)
    json.dump(got, sys.stdout, indent=1, default=str)
    print()
