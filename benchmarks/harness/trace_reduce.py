"""From a profiler trace (``.xplane.pb``) to three numbers and two lists.

  busy_s        union of the intervals in which an operation ran on a device,
                clipped to the traced window, per device
  idle share    1 - busy / window
  device_ops    the operations with most device time, by the trace's names
  idle_gaps     the longest intervals with no operation on the device, each
                labelled with the benchmark's own host span that covers most
                of it (``bench.*`` ``TraceAnnotation``s), else a default
  collective_exposed_s   time in which a collective ran on the device and
                no other operation did

The window is the host span named ``bench.window``; device and host events
share the profiler's clock.  Nothing here matches a kernel by name: that
waits for kernels with stable names (PERF.md, Open questions).  Read with
``jax.profiler.ProfileData`` alone.
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective")


class TraceError(Exception):
    """The trace does not hold what the reduction needs."""


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise TraceError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


# ------------------------------------------------------------- intervals
def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def length(disjoint):
    return sum(b - a for a, b in disjoint)


def subtract(disjoint, holes):
    """The part of sorted disjoint ``disjoint`` that no interval of sorted
    disjoint ``holes`` covers."""
    out = []
    j = 0
    for a, b in disjoint:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            if holes[k][0] > cur:
                out.append([cur, holes[k][0]])
            cur = max(cur, holes[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


# ----------------------------------------------------------------- reading
def _is_device_plane(name):
    # "/device:TPU:0"; not "/device:TPU:0 SparseCore ..." or host planes
    head, _, index = name.rpartition(":")
    return head.startswith("/device:") and index.isdigit()


def read_planes(path, cpu_threads_as_device=False):
    """``{"devices": {plane: [(name, start, end)]}, "host": [(name, start,
    end)], "lines": {plane: {line: events}}}`` with times in ns.  A
    rehearsal on the cpu backend has no device plane: with
    ``cpu_threads_as_device`` XLA's cpu threads stand in for one, so that
    the control flow after the trace is walked (never a device number)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, lines = {}, [], {}
    for plane in data.planes:
        lines[plane.name] = {}
        for line in plane.lines:
            events = [(e.name, float(e.start_ns),
                       float(e.start_ns) + float(e.duration_ns))
                      for e in line.events]
            lines[plane.name][line.name] = len(events)
            if _is_device_plane(plane.name):
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(events)
            elif plane.name.startswith("/host:"):
                host.extend(ev for ev in events
                            if ev[0].startswith(SPAN_PREFIX))
                if cpu_threads_as_device and line.name.startswith("tf_XLA"):
                    devices.setdefault("/host:CPU (rehearsal)", []).extend(
                        ev for ev in events if ev[2] > ev[1])
    return {"devices": devices, "host": host, "lines": lines}


def _is_collective(name):
    low = name.lower()
    return any(w in low for w in COLLECTIVE_WORDS)


def reduce_planes(planes, default_gap_label="host", top=10):
    devices = planes["devices"]
    if not devices:
        raise TraceError(
            "no device plane with an %r line; the trace has: %s"
            % (OPS_LINE, planes["lines"]))
    windows = [(s, e) for n, s, e in planes["host"] if n == WINDOW_SPAN]
    if windows:
        lo, hi = max(windows, key=lambda w: w[1] - w[0])
    else:
        lo = min(s for evs in devices.values() for _, s, _ in evs)
        hi = max(e for evs in devices.values() for _, _, e in evs)
    if hi <= lo:
        raise TraceError("the traced window is empty")
    spans = [(n, s, e) for n, s, e in planes["host"] if n != WINDOW_SPAN]

    per_device = {}
    for plane in sorted(devices):
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[plane]
               if min(e, hi) > max(s, lo)]
        busy = union((s, e) for _, s, e in evs)
        coll = union((s, e) for n, s, e in evs if _is_collective(n))
        rest = union((s, e) for n, s, e in evs if not _is_collective(n))
        per_device[plane] = {
            "busy_s": length(busy) / 1e9,
            "idle_fraction": 1.0 - length(busy) / (hi - lo),
            "collective_s": length(coll) / 1e9,
            "collective_exposed_s": length(subtract(coll, rest)) / 1e9,
            "events": len(evs), "_evs": evs, "_busy": busy}
    first = per_device[sorted(per_device)[0]]
    by_name = {}
    for n, s, e in first["_evs"]:
        by_name[n] = by_name.get(n, 0.0) + (e - s)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    gaps = subtract([[lo, hi]], first["_busy"])
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    labelled = []
    for a, b in gaps:
        cover = {}
        for n, s, e in spans:
            o = max(0.0, min(b, e) - max(a, s))
            if o > 0:
                cover[n] = cover.get(n, 0.0) + o
        label = max(cover, key=cover.get) if cover and \
            max(cover.values()) >= 0.5 * (b - a) else default_gap_label
        labelled.append([label, (b - a) / 1e9])
    for d in per_device.values():
        del d["_evs"], d["_busy"]
    n = len(per_device)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "idle_fraction_max": max(d["idle_fraction"] for d in per_device.values()),
        "collective_exposed_s": first["collective_exposed_s"],
        "devices": per_device,
        "device_ops": [[name, t / 1e9] for name, t in ops],
        "idle_gaps": labelled,
        "spans": {name: sum(e - s for n, s, e in spans if n == name) / 1e9
                  for name in sorted({n for n, _, _ in spans})},
        "lines": planes["lines"],
    }


def reduce_file(path, default_gap_label="host", top=10,
                cpu_threads_as_device=False):
    return reduce_planes(read_planes(path, cpu_threads_as_device),
                         default_gap_label, top)
