"""Run one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1> [--rehearse] [--keep-trace]

The last line of standard output is the contract's JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); facts worth reading go on earlier lines and, in full, to
``chiprun_out/<workload>.seed<seed>.trace<t>.json``.  Without a TPU (or
with fewer chips than the cell asks for) the run exits non-zero and prints
no such line.  ``--rehearse`` walks the same control flow at the tiny sizes
the configuration and traffic files carry under ``rehearse``, on whatever
backend jax has, and prints a ``rehearsed`` line instead: never a result.

Everything that belongs to one cell, configuration, mix or metric is a file
found by name (``harness/manifest.py``); nothing here branches on a name.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest, peaks, trace_reduce  # noqa: E402

NO_ACCELERATOR = 3


class Ctx:
    """What a driver is handed."""

    def __init__(self, cell, args):
        self.cell = cell
        self.config = cell.config
        part = cell.config["rehearse"] if args.rehearse else cell.config
        self.sizes = part["sizes"]
        self.knobs = dict(cell.config.get("knobs", {}))
        if args.rehearse:
            self.knobs.update(part.get("knobs", {}))
        self.traffic = dict(cell.traffic)
        if args.rehearse:
            self.traffic.update(cell.traffic.get("rehearse", {}))
        self.chips = cell.chips
        self.seed = args.seed
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearse = args.rehearse
        self.t_start = T_START
        self.run_dir = os.path.join(cell.root, ".bench_runs", cell.name)
        self.trace_dir = os.path.join(self.run_dir, "trace")

    def module(self, kind, name):
        return self.cell.module(kind, name)

    def log(self, what, **facts):
        print(json.dumps(dict(what=what, **facts), default=str), flush=True)


def _device_dict(jax, chips):
    devs = jax.devices()
    peak = None
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use") is not None:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy the raw .xplane.pb to chiprun_out/")
    ap.add_argument("--override", action="append", default=[],
                    metavar="traffic.key.path=JSON",
                    help="exploration only (a rate sweep): replace one "
                         "value of the traffic file; such a run prints an "
                         "'explored' line, never a result")
    args = ap.parse_args(argv)

    cell = manifest.Cell(args.workload)
    if args.seconds is None:
        args.seconds = cell.manifest["run_seconds"]
    if args.rehearse and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # the cpu backend reads its virtual device count at start-up
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=%d"
            % max(cell.chips, 1)).strip()

    import jax
    backend = jax.default_backend()
    n_dev = len(jax.devices())
    if not args.rehearse and backend != "tpu":
        print("benchmarks/run.py: jax found no TPU (default backend %r); "
              "no result" % backend, file=sys.stderr)
        return NO_ACCELERATOR
    if n_dev < cell.chips:
        print("benchmarks/run.py: cell %s asks for %d chip(s), jax reports "
              "%d; no result" % (cell.name, cell.chips, n_dev),
              file=sys.stderr)
        return NO_ACCELERATOR
    if not args.rehearse:
        peaks.peaks(jax.devices()[0].device_kind)   # unknown kind: error

    import mxnet_tpu as mx
    cache_dir = mx.runtime.configure_compile_cache()
    ctx = Ctx(cell, args)
    for knob, value in ctx.knobs.items():
        mx.config.set(knob, value)
    for item in args.override:
        path, _, value = item.partition("=")
        keys = path.split(".")
        if keys[0] != "traffic":
            ap.error("--override takes traffic.<key>[.<key>]=<json>")
        node = ctx.traffic
        for key in keys[1:-1]:
            node = node[key] = dict(node[key])
        node[keys[-1]] = json.loads(value)
    shutil.rmtree(ctx.run_dir, ignore_errors=True)
    os.makedirs(ctx.run_dir)
    ctx.log("start", workload=cell.name, seed=args.seed,
            seconds=args.seconds, trace=args.trace, rehearse=args.rehearse,
            backend=backend, devices=n_dev, compile_cache=cache_dir,
            knobs=ctx.knobs)

    driver = ctx.module("drivers", cell.config["driver"])
    obs = driver.run(ctx)

    trace = None
    if ctx.trace:
        xplane = trace_reduce.find_xplane(ctx.trace_dir)
        trace = trace_reduce.reduce_file(
            xplane, default_gap_label=obs.get("gap_label", "host"),
            cpu_threads_as_device=args.rehearse and backend == "cpu")
        if args.keep_trace:
            os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
            shutil.copy(xplane, os.path.join(
                ROOT, "chiprun_out", "%s%s.xplane.pb" % (
                    cell.name, ".rehearse" if args.rehearse else "")))

    group, kind = ("per_layer", "layer_metrics") if ctx.trace else \
        ("end_to_end", "end_to_end")
    metrics = {}
    for entry in cell.metrics(group):
        reader = cell.module(kind, entry["name"], fallback_to_base=True)
        try:
            value = reader.read(obs, trace)
        except peaks.UnknownDevice:
            if not args.rehearse:   # a rehearsal's cpu has no peak: skip
                raise
            value = None
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}

    device = _device_dict(jax, cell.chips)
    line = {"correct": bool(obs["correct"]),
            "attempted": int(obs["attempted"]), "failed": int(obs["failed"]),
            "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        # the trace names an operation by its whole HLO line: keep its head
        line["breakdown"] = {
            "device_ops": [[n[:160], t] for n, t in trace["device_ops"][:10]],
            "idle_gaps": trace["idle_gaps"][:10]}

    full = dict(line, workload=cell.name, seed=args.seed,
                seconds=args.seconds, rehearse=args.rehearse,
                checks=obs.get("checks"), facts=obs.get("facts"),
                trace=trace)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "%s.seed%d.trace%d%s.json" % (
            cell.name, args.seed, args.trace,
            ".rehearse" if args.rehearse else "")), "w") as f:
        json.dump(full, f, indent=1, default=str)
    ctx.log("checks", correct=obs["correct"], checks=obs.get("checks"))
    ctx.log("facts", **(obs.get("facts") or {}))
    if args.rehearse:
        # a rehearsal proves control flow; its numbers are of no device
        print(json.dumps({"rehearsed": cell.name,
                          "correct": bool(obs["correct"]),
                          "metric_names": sorted(metrics),
                          "device": {k: device[k] for k in
                                     ("platform", "kind", "count")}}),
              flush=True)
        return 0 if obs["correct"] else 1
    if args.override:
        line = {"explored": args.override, "not_a_result": line}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
