"""``BENCHMARK.json`` and the files it names.

One thing is one file, found by name:

  configs/<config>.json          a model configuration
  traffic/<traffic>.json         a traffic mix (parameters of one generator)
  generators/<name>.py           named by the traffic file's ``generator``
  drivers/<name>.py              named by the configuration's ``driver``
  reference/<name>.py            named by the configuration's ``reference``
  ops_bytes/<name>.py            named by the configuration's ``ops_bytes``
  end_to_end/<metric>.py         ``read(obs, trace) -> number | None``
  layer_metrics/<metric>.py      the same, for a per-layer metric

A metric ``base.suffix`` (the manifest splits a quantity whose cells report
different end-to-end metrics) falls back to ``<base>.py`` when it has no
file of its own.  There is no registry and no branch on a name.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(Exception):
    """The manifest, or a file it names, is missing or inconsistent."""


def load_json(*parts, bench_dir=BENCH_DIR):
    path = os.path.join(bench_dir, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError("no such file: %s" % path) from None


def load_module(kind, name, bench_dir=BENCH_DIR, fallback_to_base=False):
    """The module ``<bench_dir>/<kind>/<name>.py`` (or, with
    ``fallback_to_base``, ``<name up to its first dot>.py``)."""
    names = [name]
    if fallback_to_base and "." in name:
        names.append(name.split(".", 1)[0])
    for cand in names:
        path = os.path.join(bench_dir, kind, cand + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "benchmarks_%s_%s" % (kind, cand.replace(".", "_").replace(
                    "-", "_")), path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            return module
    raise ManifestError("no %s/%s.py" % (kind, name))


class Cell:
    """One entry of ``workloads`` with everything it names resolved."""

    def __init__(self, workload, root=ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "benchmarks")
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise ManifestError("no workload %r in BENCHMARK.json (has: %s)"
                                % (workload, ", ".join(sorted(cells))))
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in self.manifest["configs"]}[
            self.entry["config"]]
        with open(os.path.join(root, cfg_entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.entry["traffic"] + ".json",
                                 bench_dir=self.bench_dir)

    def metrics(self, group):
        """The manifest's metrics of ``group`` (``end_to_end`` or
        ``per_layer``) that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def module(self, kind, name, **kw):
        return load_module(kind, name, bench_dir=self.bench_dir, **kw)
