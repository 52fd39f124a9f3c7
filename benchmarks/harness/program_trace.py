"""The program's own names in the run's profiler trace.

``trace_reduce`` gives the benchmark busy time, idle share and the largest
operations by the compiler's names, and labels gaps by the benchmark's own
``bench.*`` spans.  This module opens the same ``.xplane.pb`` a second time
for what the *program* wrote into it:

  host spans     ``mx.tracing.span`` is a ``jax.profiler.TraceAnnotation``:
                 ``engine.iteration/admit/prefill/decode(.prepare/.device/
                 .emit)/wait`` from the generation engine's thread,
                 ``spmd.step/shard_batch/prepare/dispatch/post`` from the
                 trainer, each with its keyword arguments as the event's
                 stats.
  device scopes  ``jax.named_scope("mx.<layer>")`` and
                 ``pallas_call(name="mx_<kernel>")`` in the program.  Under
                 the benchmark's profile options (no HLO protos) a device
                 event carries its HLO instruction and nothing else
                 (``%fusion.11 = bf16[...] fusion(...)``; PERF.md section
                 6, PR 25), so the scope comes from the table the program
                 keeps per compiled executable (``mx.perf.op_names()``:
                 instruction -> op_name from the executable's own text),
                 joined on the instruction name inside the ``XLA Modules``
                 event that encloses the operation.

Everything is clipped to the ``bench.window`` span.  Device time is SELF
time on device 0: an operation's duration less the operations nested in it
on the same line, so that a ``while`` holding the layer scan is not counted
a second time and the scopes add up to busy time.  A time per decode
iteration or per step is taken over the program's executions (``XLA
Modules`` events) that lie wholly in the window: a span that is open when
the session stops is never written, so host spans cannot count the
execution the window's edge cuts.  A program without the
table or the spans (the parent of the PR that added them) gives ``None``
and the metric is left out of the line; a scope with no event in the window
reads 0.0.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re

from benchmarks.harness import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODULES_LINE = "XLA Modules"
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = (.*)$")
SHAPE = re.compile(r"^(.*?) [a-z][\w\-]*\(")
SCOPE = re.compile(r"mx\.[a-z_0-9]+")
MODULE_EVENT = re.compile(r"^(.*?)\(\d+\)$")
GAP_MIN_NS = 1e6

_CACHE = {}


# ------------------------------------------------------------- the file
def newest_xplane(root=ROOT):
    """The ``.xplane.pb`` of the newest ``<root>/.bench_runs/*/trace``
    (``run.py`` wipes the cell's run directory at start, so within a run
    this is the run's own)."""
    dirs = [d for d in glob.glob(os.path.join(root, ".bench_runs", "*",
                                              "trace"))
            if glob.glob(os.path.join(d, "plugins", "profile", "*",
                                      "*.xplane.pb"))]
    if not dirs:
        return None
    return trace_reduce.find_xplane(max(dirs, key=os.path.getmtime))


def program_tables():
    """The running program's instruction -> op_name tables, or None where
    the program keeps none."""
    try:
        from mxnet_tpu import perf
        return perf.op_names()
    except (ImportError, AttributeError):
        return None


def read_events(path):
    """``{"ops": [(text, start, end)], "modules": [(name, start, end)],
    "spans": [(name, start, end, args, thread)]}`` of device 0 and of the
    host, times in ns."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    device_planes = sorted(p.name for p in data.planes
                           if trace_reduce._is_device_plane(p.name))
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if device_planes and plane.name == device_planes[0]:
            for line in plane.lines:
                into = {trace_reduce.OPS_LINE: ops,
                        MODULES_LINE: modules}.get(line.name)
                if into is None:
                    continue
                into.extend((e.name, float(e.start_ns),
                             float(e.start_ns) + float(e.duration_ns))
                            for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if SPAN_NAME.match(e.name):
                        spans.append((
                            e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns),
                            {str(k): v for k, v in e.stats}, line.name))
    return {"ops": ops, "modules": modules, "spans": spans}


# ------------------------------------------------------------ self time
def self_times(events, lo, hi):
    """``[(text, self_ns, start)]`` for events ``(text, start, end)``
    clipped to ``[lo, hi]``: the clipped duration less that of the events
    nested directly inside."""
    clipped = sorted(((max(s, lo), min(e, hi), t) for t, s, e in events
                      if min(e, hi) > max(s, lo)),
                     key=lambda x: (x[0], -x[1]))
    out, stack = [], []      # stack of [end, index into out]
    for s, e, text in clipped:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(e, stack[-1][0]) - s
        out.append([text, e - s, s])
        stack.append([e, len(out) - 1])
    return [(t, max(d, 0.0), s) for t, d, s in out]


def _instruction(text):
    m = INSTRUCTION.match(text)
    if m is None:
        return text, ""
    sh = SHAPE.match(m.group(2))
    return m.group(1), sh.group(1) if sh is not None else ""


def _pick_table(candidates, seen):
    """Of the programs that share an HLO module name (one decode program
    per page-table width, one prefill per bucket), the one whose table
    matches most of the (instruction, result shape) pairs seen."""
    if len(candidates) == 1:
        return candidates[0]
    best, best_score = None, -1
    for table in candidates:
        ops = table["ops"]
        score = sum(2 if ops[n][0] == shape else 1
                    for n, shape in seen if n in ops)
        if score > best_score:
            best, best_score = table, score
    return best


PATH_DEPTH = 2     # components kept below the outermost scope in paths_s


def _tally():
    return {"busy_s": 0.0, "scopes_s": {}, "paths_s": {}, "unscoped_s": 0.0,
            "backward_s": 0.0, "backward_of_forward_s": 0.0,
            "tpu_custom_call_s": 0.0, "named_s": 0.0}


def _add(tally, self_s, text, op_name, named):
    """One operation's self time into a tally, under the innermost ``mx.``
    scope of its name path (a fusion of several named operations lists
    them all: the first counts) and under the head of that path."""
    tally["busy_s"] += self_s
    if named:
        tally["named_s"] += self_s
    if 'custom_call_target="tpu_custom_call"' in text:
        tally["tpu_custom_call_s"] += self_s
    if "transpose(" in op_name:
        tally["backward_s"] += self_s
        if "mx.forward" in op_name:
            tally["backward_of_forward_s"] += self_s
    path = op_name.split(";", 1)[0]
    found = SCOPE.findall(path)
    if found:
        scopes = tally["scopes_s"]
        scopes[found[-1]] = scopes.get(found[-1], 0.0) + self_s
        # and by where under its outermost scope: for a training step
        # ``transpose(jvp(mx.forward))/resnetv10/resnetv10_stage1``, the
        # backward of one Gluon block
        parts = path.split("/")
        top = next(i for i, part in enumerate(parts) if "mx." in part)
        key = "/".join(parts[top:top + 1 + PATH_DEPTH])
        tally["paths_s"][key] = tally["paths_s"].get(key, 0.0) + self_s
    else:
        tally["unscoped_s"] += self_s
    return bool(found)


def device_scopes(ops, modules, tables, lo, hi):
    """Self time on device 0 by ``mx.`` scope (innermost named), the
    unscoped remainder, the backward's part and the largest unscoped
    operations: over the window (clipped to it), and under ``programs``
    per registered program (``family/key``) over its executions that lie
    wholly in the window, with their number — what a time *per execution*
    is taken from, so that an execution the window (or the end of the
    trace) cuts counts neither in the time nor in the number."""
    by_module = {}
    for table in tables:
        by_module.setdefault(table.get("module"), []).append(table)
    mods = sorted((s, e, n) for n, s, e in modules)
    starts = [m[0] for m in mods]

    def execution_of(start):
        i = bisect.bisect_right(starts, start) - 1
        return i if i >= 0 and mods[i][1] >= start else None

    seen = {}
    rows = []
    for text, self_ns, start in self_times(ops, lo, hi):
        i = execution_of(start)
        name, shape = _instruction(text)
        rows.append((i, name, text, self_ns / 1e9))
        seen.setdefault(mods[i][2] if i is not None else None,
                        set()).add((name, shape))
    chosen = {}      # module event name -> the table of the program
    for mod, pairs in seen.items():
        m = MODULE_EVENT.match(mod or "")
        cands = by_module.get(m.group(1) if m else mod, [])
        chosen[mod] = _pick_table(cands, pairs) if cands else None

    total, unscoped, programs = _tally(), {}, {}
    for i, name, text, self_s in rows:
        mod = mods[i][2] if i is not None else None
        table = chosen[mod]
        entry = table["ops"].get(name) if table else None
        op_name = entry[1] if entry else ""
        if not _add(total, self_s, text, op_name, bool(entry)):
            unscoped[text[:120]] = unscoped.get(text[:120], 0.0) + self_s
        # the last execution of the trace may be cut by the session's
        # stop (its event then ends early, inside the window): not whole
        if table and lo <= mods[i][0] and mods[i][1] <= hi \
                and i != len(mods) - 1:
            key = "%s/%s" % (table.get("family"), table.get("key"))
            prog = programs.setdefault(key, dict(_tally(), module=mod,
                                                 _executions=set()))
            prog["_executions"].add(i)
            _add(prog, self_s, text, op_name, bool(entry))
    for prog in programs.values():
        prog["executions"] = len(prog.pop("_executions"))
    # the union of the operations' intervals: what self times must add up to
    total["union_s"] = trace_reduce.length(trace_reduce.union(
        (max(s, lo), min(e, hi)) for _, s, e in ops)) / 1e9
    total["programs"] = programs
    total["top_unscoped"] = [[k, v] for k, v in sorted(
        unscoped.items(), key=lambda kv: -kv[1])[:12]]
    return total


# ----------------------------------------------------------- host spans
def leaf_segments(spans):
    """``{thread: [(start, end, name)]}``: per thread, sorted disjoint
    segments labelled with the innermost span covering them."""
    segments = {}
    threads = {}
    for name, s, e, _, thread in spans:
        threads.setdefault(thread, []).append((s, -e, name))
    for thread, items in threads.items():
        out = segments[thread] = []
        stack = []       # (end, name)
        cursor = None
        for s, neg_e, name in sorted(items):
            e = -neg_e
            while stack and stack[-1][0] <= s:
                end, top = stack.pop()
                if cursor < end:
                    out.append((cursor, end, top))
                cursor = end
            if stack and cursor < s:
                out.append((cursor, s, stack[-1][1]))
            stack.append((e, name))
            cursor = s
        while stack:
            end, top = stack.pop()
            if cursor < end:
                out.append((cursor, end, top))
            cursor = max(cursor, end)
    return segments


def idle_gaps(ops, spans, lo, hi, min_ns=GAP_MIN_NS):
    """Every interval over ``min_ns`` with no operation on device 0, with
    the time each innermost program span (not the benchmark's own) covers
    of it: ``[{"start_s", "ms", "label", "uncovered_ms", "cover_ms":
    {name: ms}}]``.  ``label`` is the span that covers most of the gap,
    None where no span of the program covers more than is left bare."""
    busy = trace_reduce.union((max(s, lo), min(e, hi)) for _, s, e in ops)
    gaps = [g for g in trace_reduce.subtract([[lo, hi]], busy)
            if g[1] - g[0] >= min_ns]
    threads = [(segs, [seg[1] for seg in segs]) for segs in leaf_segments(
        [s for s in spans
         if not s[0].startswith(trace_reduce.SPAN_PREFIX)]).values()]
    out = []
    for a, b in gaps:
        cover = {}
        for segs, ends in threads:
            for s, e, name in segs[bisect.bisect_right(ends, a):]:
                if s >= b:
                    break
                cover[name] = cover.get(name, 0.0) + min(b, e) - max(a, s)
        # on several threads the covers may overlap: never below zero
        bare = max(0.0, (b - a) - sum(cover.values()))
        label = max(cover, key=cover.get) \
            if cover and max(cover.values()) >= bare else None
        out.append({"start_s": (a - lo) / 1e9, "ms": (b - a) / 1e6,
                    "label": label, "uncovered_ms": bare / 1e6,
                    "cover_ms": {k: v / 1e6 for k, v in sorted(
                        cover.items(), key=lambda kv: -kv[1])}})
    return out


def span_stats(spans, lo, hi):
    """Per span name: how many lie wholly in the window, their total and
    mean duration, and the sum of each numeric argument."""
    out = {}
    for name, s, e, args, _ in spans:
        if s < lo or e > hi or name == trace_reduce.WINDOW_SPAN:
            continue
        rec = out.setdefault(name, {"count": 0, "total_ms": 0.0, "args": {}})
        rec["count"] += 1
        rec["total_ms"] += (e - s) / 1e6
        for key, value in args.items():
            try:
                rec["args"][key] = rec["args"].get(key, 0.0) + float(value)
            except (TypeError, ValueError):
                pass
    for rec in out.values():
        rec["mean_ms"] = rec["total_ms"] / rec["count"]
    return out


def parents_less_children(spans, parent, child_suffix, lo, hi):
    """Mean over ``parent`` spans wholly in the window of their duration
    less that of the spans inside them (same thread) whose name ends in
    ``child_suffix``, in ms; None without such a parent."""
    values = []
    for name, s, e, _, thread in spans:
        if name != parent or s < lo or e > hi:
            continue
        inside = sum(ce - cs for cn, cs, ce, _, ct in spans
                     if ct == thread and cn.endswith(child_suffix)
                     and cs >= s and ce <= e)
        values.append((e - s - inside) / 1e6)
    return sum(values) / len(values) if values else None


# ------------------------------------------------------------- the whole
def reduce_events(events, tables):
    spans = events["spans"]
    windows = [(s, e) for n, s, e, _, _ in spans
               if n == trace_reduce.WINDOW_SPAN]
    if not windows:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    out = {"window_s": (hi - lo) / 1e9, "window": [lo, hi],
           "spans": span_stats(spans, lo, hi),
           "gaps": idle_gaps(events["ops"], spans, lo, hi)
           if events["ops"] else [],
           "device": None}
    if tables and events["ops"]:
        out["device"] = device_scopes(events["ops"], events["modules"],
                                      tables, lo, hi)
    out["_spans"] = spans
    return out


def load(path=None, tables=None, report=True):
    """The reduction of the run's trace, parsed once per process; None
    where there is no trace.  ``out["device"]`` is None where the program
    keeps no op_name table; ``out["spans"]`` lacks what the program does
    not write."""
    path = path or newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        tables = program_tables() if tables is None else tables
        out = reduce_events(read_events(path), tables)
        _CACHE.clear()
        _CACHE[key] = out
        if report and out is not None:
            _write_report(path, out, tables)
    return _CACHE[key]


def _write_report(path, out, tables):
    """What PERF.md section 5 is written from, beside the run's other
    files in ``chiprun_out/``; and, in the run directory, the tables cut
    to the instructions the trace holds (a test fixture is made of the
    two)."""
    run_dir = os.path.dirname(path.split(os.sep + "plugins" + os.sep)[0])
    cell = os.path.basename(run_dir)
    body = {k: v for k, v in out.items() if not k.startswith("_")}
    body["gaps"] = sorted(body["gaps"], key=lambda g: -g["ms"])[:40]
    try:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out",
                               cell + ".program_trace.json"), "w") as f:
            json.dump(body, f, indent=1, default=str)
        if tables:
            with open(os.path.join(run_dir, "program_scopes.json"),
                      "w") as f:
                json.dump(tables, f)
    except OSError:
        pass


# ------------------------------------------------- what the readers ask
def _loaded(trace):
    """The reduction, for a reader handed ``trace`` (None in an untraced
    run: then nothing is read)."""
    return load() if trace is not None else None


def scope_ms(trace, scope, family, key_part=""):
    """Device self time under ``scope`` per execution, in ms, over the
    executions wholly in the window of the registered programs of
    ``family`` whose key contains ``key_part`` (``serving``, ``/decode-``:
    the engine's decode programs, so per decode iteration; ``spmd``: the
    trainer's step program, so per step).  None without the program's
    table or such an execution; 0.0 for a scope with no operation in
    them."""
    out = _loaded(trace)
    if out is None or out["device"] is None:
        return None
    progs = [p for k, p in out["device"]["programs"].items()
             if k.startswith(family + "/") and key_part in k]
    executions = sum(p["executions"] for p in progs)
    if not executions:
        return None
    return sum(p["scopes_s"].get(scope, 0.0) for p in progs) * 1e3 \
        / executions


def busy_share(trace, field):
    """``out["device"][field]`` as a percentage of device busy time."""
    out = _loaded(trace)
    if out is None or out["device"] is None or not out["device"]["busy_s"]:
        return None
    return out["device"][field] / out["device"]["busy_s"] * 100.0


def span_mean_ms(trace, name):
    out = _loaded(trace)
    if out is None or name not in out["spans"]:
        return None
    return out["spans"][name]["mean_ms"]


def span_args_ratio(trace, name, numerator, denominator):
    """Sum of one argument over the sum of another, over the spans of
    ``name`` in the window, in percent."""
    out = _loaded(trace)
    args = out["spans"].get(name, {}).get("args", {}) if out else {}
    if not args.get(denominator):
        return None
    return args.get(numerator, 0.0) / args[denominator] * 100.0


def host_ms(trace, parent, child_suffix):
    out = _loaded(trace)
    if out is None:
        return None
    lo, hi = out["window"]
    return parents_less_children(out["_spans"], parent, child_suffix, lo, hi)
