#!/usr/bin/env python
"""mxlint — the mx.analysis static-analysis CLI (docs/ANALYSIS.md).

Runs the jit-purity, lock-discipline, registry-drift, shard-spec,
compile-cache and step-seam passes over the framework tree and exits
non-zero on any active finding:

    python tools/mxlint.py                 # lint, human output
    python tools/mxlint.py --json          # machine output
    python tools/mxlint.py --passes drift  # one pass family
    python tools/mxlint.py --fix-docs      # regenerate ENV_VARS.md +
                                           # the OBSERVABILITY metric
                                           # index, then re-lint
    python tools/mxlint.py --changed-only HEAD~1
                                           # pre-commit fast path: lint
                                           # only files git reports
                                           # changed vs the ref
    python tools/mxlint.py --baseline-write
                                           # regenerate the baseline
                                           # from live findings, keeping
                                           # justifications for keys
                                           # that survive

Findings are suppressed either inline (``# mxlint: disable=pass.rule``)
or through tools/mxlint_baseline.json, where every entry carries a
one-line justification; baseline entries that no longer match anything
are reported as expired and fail the lint, so the ledger cannot rot.
Entries may carry ``expires: YYYY-MM`` — past that month the entry
stops suppressing and is reported as date-expired (the step-seam
burn-down ledger for ROADMAP item 3 uses this).

The pass package lives at mxnet_tpu/analysis/ but is loaded here
*without* importing ``mxnet_tpu`` itself (which would pull in jax): a
full-tree lint stays fast enough for the CI smoke
(tools/check_analysis.py).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(ROOT, "tools", "mxlint_baseline.json")

_SHIM_NAME = "_mx_analysis_standalone"


def load_analysis(root=ROOT):
    """Import mxnet_tpu/analysis as a standalone package.

    ``import mxnet_tpu.analysis`` would execute mxnet_tpu/__init__.py
    (jax, the full framework) just to lint source text; instead the
    package is loaded under a private name with its own search path so
    its relative imports resolve without touching the parent package.
    """
    if _SHIM_NAME in sys.modules:
        return sys.modules[_SHIM_NAME]
    pkg_dir = os.path.join(root, "mxnet_tpu", "analysis")
    spec = importlib.util.spec_from_file_location(
        _SHIM_NAME, os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[_SHIM_NAME] = mod
    try:
        spec.loader.exec_module(mod)
    except Exception:
        del sys.modules[_SHIM_NAME]
        raise
    return mod


def _changed_files(root, ref, ap):
    """Changed .py files under the lint targets, per git diff vs ref."""
    import subprocess
    try:
        proc = subprocess.run(
            ["git", "-C", root, "diff", "--name-only", ref],
            capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.TimeoutExpired) as e:
        ap.error("--changed-only: git diff failed: %s" % e)
    if proc.returncode != 0:
        ap.error("--changed-only: git diff --name-only %s failed: %s"
                 % (ref, proc.stderr.strip()))
    out = []
    for name in proc.stdout.splitlines():
        name = name.strip()
        if not name.endswith(".py"):
            continue
        if name.split("/")[0] in ("mxnet_tpu", "tools"):
            out.append(name)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="mxlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--root", default=ROOT,
                    help="repo root to lint (default: this checkout)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated pass ids (jit,locks,drift,"
                         "shard,cache,seam); default all")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="suppression file (default: "
                         "tools/mxlint_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (show everything)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit one JSON object instead of text")
    ap.add_argument("--fix-docs", action="store_true",
                    help="regenerate docs/ENV_VARS.md and the "
                         "docs/OBSERVABILITY.md metric index, then lint")
    ap.add_argument("--changed-only", nargs="?", const="HEAD",
                    default=None, metavar="REF",
                    help="lint only .py files `git diff --name-only "
                         "REF` reports (default REF: HEAD); whole-tree "
                         "rules and baseline-expiry reporting are "
                         "skipped in this mode")
    ap.add_argument("--baseline-write", action="store_true",
                    help="rewrite the baseline from the live findings, "
                         "carrying forward reasons/expiry for keys that "
                         "still match; new keys get a FIXME reason")
    args = ap.parse_args(argv)

    analysis = load_analysis(args.root if os.path.isdir(
        os.path.join(args.root, "mxnet_tpu", "analysis")) else ROOT)

    passes = None
    if args.passes:
        passes = [p.strip() for p in args.passes.split(",") if p.strip()]
        unknown = [p for p in passes if p not in analysis.PASSES]
        if unknown:
            ap.error("unknown pass id(s): %s (have: %s)"
                     % (", ".join(unknown),
                        ", ".join(analysis.PASSES)))

    fixed = []
    if args.fix_docs:
        repo = analysis.Repo(args.root)
        fixed = analysis.drift.fix_docs(repo)

    if args.baseline_write:
        prev = analysis.Baseline.load(args.baseline)
        report = analysis.run(args.root, passes=passes, baseline=None)
        entries = prev.write(
            args.baseline,
            [f for f in report.findings if not f.suppressed])
        fixme = sum(1 for e in entries
                    if e["reason"].startswith("FIXME"))
        print("mxlint: wrote %d suppression(s) to %s%s"
              % (len(entries), args.baseline,
                 " (%d need a justification)" % fixme if fixme else ""))
        return 0

    baseline = None if args.no_baseline else args.baseline
    if args.changed_only is not None:
        changed = _changed_files(args.root, args.changed_only, ap)
        if not changed:
            print("mxlint: no changed .py files under %s"
                  % "/".join(sorted(
                      t.split(os.sep)[0]
                      for t in analysis.walker.DEFAULT_TARGETS)))
            return 0
        # registries the per-file rules consult (knob + mesh axis)
        support = [s for s in ("mxnet_tpu/config.py",
                               "mxnet_tpu/parallel/mesh.py")
                   if os.path.isfile(os.path.join(args.root, s))]
        targets = tuple(dict.fromkeys(changed + support))
        report = analysis.run(args.root, passes=passes,
                              baseline=baseline, targets=targets)
        # whole-tree verdicts (dead-knob &c) and baseline-expiry
        # reporting need the full tree — the fast path only reports
        # findings living in the changed files themselves
        changed_set = set(changed)
        keep = [f for f in report.findings
                if f.path.replace(os.sep, "/") in changed_set
                and f.rule not in analysis.WHOLE_TREE_RULES]
        report = analysis.Report(keep, [], report.repo)
    else:
        report = analysis.run(args.root, passes=passes, baseline=baseline)

    if args.as_json:
        out = report.to_dict()
        out["fixed_docs"] = fixed
        print(json.dumps(out, sort_keys=True))
        return 0 if report.ok else 1

    for rel in fixed:
        print("mxlint: rewrote %s" % rel)
    for rel, err in report.repo.parse_errors:
        print("%s:0: [parse-error] %s" % (rel, err))
    for f in report.active:
        print(f.format())
    n_active = len(report.active) + len(report.repo.parse_errors)
    n_sup = len(report.suppressed)
    if n_active:
        print("mxlint: %d finding(s)%s" % (
            n_active,
            " (%d suppressed)" % n_sup if n_sup else ""))
        return 1
    print("mxlint: clean%s" % (
        " (%d suppressed by baseline/inline)" % n_sup if n_sup else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
