"""Merge an mx.perf program-registry dump with a telemetry JSONL step log
into one MFU / roofline cost report.

Inputs:

  * ``--programs PROG.json`` — the ``mx.perf.export(path)`` dump: one
    record per compiled program (family, key, flops, bytes accessed,
    memory plan, trace/lower/compile phase breakdown, HLO op-class
    counts, roofline classification);
  * ``LOG.jsonl`` (optional) — the ``MXNET_TPU_TELEMETRY=jsonl:`` step
    log, whose per-step ``mfu``/``flops`` fields (stamped by the mx.perf
    step hook) give the achieved-utilization time series;
  * ``--trace DIR`` (optional) — an ``MXNET_TPU_PROFILE=step:N`` capture
    directory; its device-plane events are bucketed with the SAME
    op-class mapping the registry uses (mx.perf.classify_op), so the
    measured timeline and the compile-time cost table line up.

Anomaly flags (report content, not errors; ``--strict`` gates CI):

  * mfu_regression — the last rolling window's mean MFU fell below 70%
    of the best earlier window: the run got slower relative to itself
    (the compiled FLOPs are constants, so this is pure wall-time drift);
  * bandwidth_bound_hotspot — a bandwidth-bound program (roofline) owns
    >= 25% of its family's FLOPs: the top optimization target won't
    respond to more compute — fix layouts/fusion/precision instead;
  * compile_phase_blowup — one program's XLA compile phase took > 5x the
    median of all captured programs (and over a 250ms floor): a
    pathological program shape or a cache miss that should have hit.

Usage:
  python tools/perf_report.py --programs PROG.json RUN.jsonl
  python tools/perf_report.py --programs PROG.json --json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from telemetry_report import load_records  # noqa: E402

MFU_WINDOW = 8           # steps per rolling window
MFU_REGRESSION = 0.7     # final-window mean vs best earlier window
HOTSPOT_SHARE = 0.25     # family-FLOPs share before a bw-bound flag
COMPILE_BLOWUP_RATIO = 5.0
COMPILE_BLOWUP_FLOOR_MS = 250.0


def load_programs(path):
    with open(path, "r") as f:
        dump = json.load(f)
    if isinstance(dump, dict):
        progs = dump.get("programs") or []
    else:  # a bare list is accepted too
        progs, dump = dump, {"programs": dump}
    return [p for p in progs if isinstance(p, dict)], dump


def _mfu_series(records):
    """source -> [per-step mfu] in log order (compile steps excluded —
    their wall time measures XLA, not the program)."""
    series = {}
    for r in records:
        if r.get("event") != "step":
            continue
        mfu = r.get("mfu")
        if isinstance(mfu, (int, float)) and not r.get("compiles"):
            series.setdefault(r.get("source", "?"), []).append(float(mfu))
    return series


def _windows(vals, k):
    return [sum(vals[i:i + k]) / len(vals[i:i + k])
            for i in range(0, len(vals), k) if vals[i:i + k]]


def summarize(progs, records, trace_classes=None):
    anomalies = []

    # ------------------------------------------------- program cost table
    by_family = {}
    for p in progs:
        by_family.setdefault(p.get("family", "?"), []).append(p)
    family_flops = {fam: sum(float(p.get("flops") or 0) for p in ps)
                    for fam, ps in by_family.items()}

    compile_ms = sorted(
        float(p.get("phases_ms", {}).get("compile_ms") or 0)
        for p in progs if p.get("phases_ms", {}).get("compile_ms"))
    # lower median: with few programs the blowup candidate itself must
    # not drag the baseline up to meet it
    median_compile = (compile_ms[(len(compile_ms) - 1) // 2]
                      if compile_ms else 0.0)

    table = []
    for p in progs:
        fam = p.get("family", "?")
        flops = float(p.get("flops") or 0)
        roof = p.get("roofline") or {}
        phases = p.get("phases_ms") or {}
        share = flops / family_flops[fam] if family_flops.get(fam) else 0.0
        table.append({
            "family": fam,
            "key": p.get("key", "?"),
            "gflops": round(flops / 1e9, 4),
            "mbytes": round(float(p.get("bytes_accessed") or 0) / 1e6, 3),
            "ai": roof.get("arithmetic_intensity"),
            "bound": roof.get("bound"),
            "calls": p.get("calls", 0),
            "phases_ms": phases,
            "op_classes": p.get("op_classes") or {},
            "family_flops_share": round(share, 3),
        })
        if (roof.get("bound") == "bandwidth" and share >= HOTSPOT_SHARE
                and flops > 0):
            anomalies.append({
                "kind": "bandwidth_bound_hotspot",
                "source": "%s/%s" % (fam, p.get("key", "?")),
                "detail": "bandwidth-bound (AI %.2f vs device %.2f) with "
                          "%.0f%% of %s-family FLOPs: optimize memory "
                          "traffic, not compute"
                          % (roof.get("arithmetic_intensity") or 0,
                             roof.get("device_intensity") or 0,
                             100 * share, fam)})
        cms = float(phases.get("compile_ms") or 0)
        if (median_compile > 0 and cms > COMPILE_BLOWUP_FLOOR_MS and
                cms > COMPILE_BLOWUP_RATIO * median_compile):
            anomalies.append({
                "kind": "compile_phase_blowup",
                "source": "%s/%s" % (fam, p.get("key", "?")),
                "detail": "XLA compile %.0fms vs %.0fms median (> %.0fx)"
                          % (cms, median_compile, COMPILE_BLOWUP_RATIO)})

    # -------------------------------------------------- achieved MFU series
    mfu = {}
    for source, vals in sorted(_mfu_series(records).items()):
        wins = _windows(vals, MFU_WINDOW)
        mfu[source] = {
            "steps": len(vals),
            "mfu_mean": round(sum(vals) / len(vals), 5),
            "mfu_last_window": round(wins[-1], 5) if wins else None,
            "mfu_best_window": round(max(wins), 5) if wins else None,
        }
        if len(wins) >= 2:
            best_earlier = max(wins[:-1])
            if best_earlier > 0 and wins[-1] < MFU_REGRESSION * best_earlier:
                anomalies.append({
                    "kind": "mfu_regression", "source": source,
                    "detail": "final %d-step window MFU %.5f vs best "
                              "earlier window %.5f (< %.0f%%)"
                              % (MFU_WINDOW, wins[-1], best_earlier,
                                 100 * MFU_REGRESSION)})

    out = {"programs": table, "families": sorted(by_family),
           "mfu": mfu, "anomalies": anomalies}
    if trace_classes is not None:
        out["device_trace_op_classes"] = trace_classes
    return out


def trace_op_classes(trace_dir):
    """Bucket a device capture's complete events with the registry's own
    op-class mapping (imports mxnet_tpu, and so jax — only on --trace)."""
    import trace_merge
    from mxnet_tpu.perf import classify_op
    events = trace_merge.resolve_device_trace(trace_dir)
    classes = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        cls = classify_op(ev.get("name", ""))
        cur = classes.setdefault(cls, {"events": 0, "dur_us": 0.0})
        cur["events"] += 1
        cur["dur_us"] += float(ev.get("dur") or 0)
    for cur in classes.values():
        cur["dur_us"] = round(cur["dur_us"], 1)
    return classes


def render(summary):
    lines = []
    hdr = ("%-10s %-28s %12s %10s %8s %-9s %6s %9s %9s %9s"
           % ("family", "key", "gflops", "mbytes", "ai", "bound",
              "calls", "trace_ms", "lower_ms", "comp_ms"))
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for p in summary["programs"]:
        ph = p["phases_ms"]
        lines.append("%-10s %-28s %12s %10s %8s %-9s %6s %9s %9s %9s"
                     % (p["family"], p["key"][:28], p["gflops"],
                        p["mbytes"],
                        "-" if p["ai"] is None else p["ai"],
                        p["bound"] or "-", p["calls"],
                        ph.get("trace_ms", "-"), ph.get("lower_ms", "-"),
                        ph.get("compile_ms", "-")))
        ops = ", ".join("%s=%d" % kv
                        for kv in sorted(p["op_classes"].items()))
        if ops:
            lines.append("           ops: %s" % ops)
    if not summary["programs"]:
        lines.append("(no registered programs)")
    if summary["mfu"]:
        lines.append("")
        mh = ("%-8s %6s %10s %12s %12s"
              % ("source", "steps", "mfu_mean", "last_window",
                 "best_window"))
        lines.append(mh)
        lines.append("-" * len(mh))
        for source, t in summary["mfu"].items():
            lines.append("%-8s %6d %10s %12s %12s"
                         % (source, t["steps"], t["mfu_mean"],
                            "-" if t["mfu_last_window"] is None
                            else t["mfu_last_window"],
                            "-" if t["mfu_best_window"] is None
                            else t["mfu_best_window"]))
    trace = summary.get("device_trace_op_classes")
    if trace:
        lines.append("")
        lines.append("device trace op classes:")
        for cls, cur in sorted(trace.items(),
                               key=lambda kv: -kv[1]["dur_us"]):
            lines.append("  %-12s %8d events %12.1f us"
                         % (cls, cur["events"], cur["dur_us"]))
    lines.append("")
    if summary["anomalies"]:
        lines.append("ANOMALIES:")
        for a in summary["anomalies"]:
            lines.append("  [%s] %s: %s"
                         % (a["kind"], a["source"], a["detail"]))
    else:
        lines.append("no anomalies detected")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="mx.perf cost-attribution report: program registry "
                    "+ telemetry MFU series + optional device trace.")
    ap.add_argument("log", nargs="?",
                    help="telemetry JSONL step log (optional)")
    ap.add_argument("--programs", required=True,
                    help="mx.perf.export() JSON dump")
    ap.add_argument("--trace",
                    help="MXNET_TPU_PROFILE capture dir to bucket by "
                         "op class (imports jax)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as one JSON object")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any anomaly is flagged (CI gate)")
    args = ap.parse_args(argv)

    progs, dump = load_programs(args.programs)
    records, bad = load_records(args.log) if args.log else ([], 0)
    trace_classes = trace_op_classes(args.trace) if args.trace else None
    summary = summarize(progs, records, trace_classes)
    if args.json:
        summary["malformed_lines"] = bad
        print(json.dumps(summary))
    else:
        print(render(summary))
        if bad:
            print("malformed lines skipped: %d" % bad)
    return 1 if (args.strict and summary["anomalies"]) else 0


if __name__ == "__main__":
    sys.exit(main())
