"""Fast CPU smoke for the mx.analysis static-analysis suite (~3s).

Proves the six mxlint pass families end-to-end, with one parseable
JSON line on stdout:

  1. clean   — ``python tools/mxlint.py`` run as a subprocess over THIS
               tree exits 0 against the checked-in baseline
               (tools/mxlint_baseline.json): the codebase carries no
               unsuppressed finding from any pass family, and every
               baseline entry still matches (an expired entry would
               fail this step);
  2. catches — a synthetic bad tree (tracer branch + host sync +
               trace-time impurity, an unguarded cross-thread write,
               an unregistered-knob read, an undeclared/unbound mesh
               axis + in_specs arity mismatch + replicated embedding
               spec, a config read reaching a cached program + an
               unkeyed shape capture + an immediately-invoked jit, and
               a hand-rolled fused-step builder) makes the CLI exit
               non-zero with file:line findings for all six pass
               families;
  3. exact   — the in-process API pins the synthetic findings to their
               exact rule ids and line numbers, so the passes don't
               merely fire — they point at the right code.

The analysis package is pure stdlib (no jax import), so the whole
smoke is AST-bound.

Usage: JAX_PLATFORMS=cpu python tools/check_analysis.py
Wired as a `not slow` test in tests/test_analysis.py.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

BAD_JIT = '''\
import time
import jax


@jax.jit
def leaky(x, y):
    if x > 0:
        y = y + 1
    t = time.time()
    v = float(x)
    return y + v + t
'''
# expected: tracer-branch@7, impure-time@9, host-sync@10

BAD_LOCKS = '''\
import threading


class Worker(object):
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            self._count += 1

    def snapshot(self):
        return self._count
'''
# expected: unguarded write@13 (background thread), unguarded read@16

BAD_DRIFT = '''\
from . import config


def setup():
    return config.get("phantom.knob")
'''
# expected: unregistered-knob@5

BAD_SHARD = '''\
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

AXES = ("dp",)


def lookup(table, ids, mesh):
    def _shard(tbl, u):
        return jax.lax.psum(tbl, "tp")
    return shard_map(_shard, mesh=mesh, in_specs=(P("dp", None),),
                     out_specs=P())(table, ids)


SPECS = {"embed": P()}
'''
# expected: undeclared-axis@10 + unbound-axis@10 ("tp" vs AXES/("dp",)
# in_spec), spec-arity@11 (1 spec, 2 params), replicated-embedding@15

BAD_CACHE = '''\
import jax
from . import config


class Runner(object):
    def __init__(self):
        self._progs = {}
        self.items = ()

    def set_items(self, xs):
        self.items = xs

    def _prog(self, shape):
        cap = config.get("io.depth")
        n = len(self.items)

        def run(x):
            return x * cap + n

        prog = self._progs[shape] = jax.jit(run)
        return prog


def hot(x):
    return jax.jit(lambda v: v + 1)(x)
'''
# expected: stale-knob-key@14 (config read baked into a cached program,
# no epoch), unkeyed-capture@15 (len of mutable state, not in the key),
# uncached-jit@25

BAD_SEAM = '''\
import jax
from . import resilience as _res


class Stepper(object):
    def _build(self):
        def step(p, g, s):
            finite = _res.all_finite(g)
            p2 = _res.select_tree(finite, p, p)
            s2 = _res.guarded_streak(finite, s, "x")
            return p2, s2
        return jax.jit(step, donate_argnums=(0,))
'''
# expected: duplicate-step@8 (Stepper._build: traced fold + donation
# outside the sanctioned core)

FIXTURE_CONFIG = '''\
def register_knob(name, env, type_, default, doc=""):
    pass


def get(name):
    return None


register_knob("io.depth", "MXTPU_IO_DEPTH", int, 2, "fixture knob")
'''


def write_bad_tree(root):
    pkg = os.path.join(root, "mxnet_tpu")
    os.makedirs(pkg)
    for rel, body in (("__init__.py", ""),
                      ("config.py", FIXTURE_CONFIG),
                      ("bad_jit.py", BAD_JIT),
                      ("bad_locks.py", BAD_LOCKS),
                      ("bad_drift.py", BAD_DRIFT),
                      ("bad_shard.py", BAD_SHARD),
                      ("bad_cache.py", BAD_CACHE),
                      ("bad_seam.py", BAD_SEAM)):
        with open(os.path.join(pkg, rel), "w") as f:
            f.write(body)


def run_cli(*argv):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mxlint.py")]
        + list(argv),
        capture_output=True, text=True, timeout=60)
    return proc.returncode, proc.stdout + proc.stderr


def main():
    t_main = time.perf_counter()
    result = {"ok": False}
    try:
        # 1. the live tree lints clean under the checked-in baseline
        rc, out = run_cli()
        assert rc == 0, "mxlint failed on the live tree:\n%s" % out
        assert "mxlint: clean" in out, "unexpected CLI output:\n%s" % out
        result["clean"] = {"rc": rc,
                           "suppressed": "suppressed" in out}

        with tempfile.TemporaryDirectory() as tmp:
            write_bad_tree(tmp)

            # 2. the CLI fails the synthetic bad tree with file:line
            #    findings from every pass family
            rc, out = run_cli("--root", tmp, "--no-baseline")
            assert rc != 0, "mxlint passed a tree with planted bugs"
            for needle in ("bad_jit.py:", "bad_locks.py:",
                           "bad_drift.py:5:", "unregistered-knob",
                           "bad_shard.py:11:", "spec-arity",
                           "bad_cache.py:25:", "uncached-jit",
                           "bad_seam.py:8:", "duplicate-step"):
                assert needle in out, \
                    "CLI output lacks %r:\n%s" % (needle, out)
            result["catches"] = {"rc": rc,
                                 "lines": out.count("[")}

            # 3. exact rule ids + line numbers through the API
            import mxlint
            analysis = mxlint.load_analysis()
            rep = analysis.run(tmp)
            got = {(f.path.split(os.sep)[-1], f.rule, f.line)
                   for f in rep.active}
            for want in (("bad_jit.py", "tracer-branch", 7),
                         ("bad_jit.py", "impure-time", 9),
                         ("bad_jit.py", "host-sync", 10),
                         ("bad_locks.py", "unguarded-write", 13),
                         ("bad_locks.py", "unguarded-read", 16),
                         ("bad_drift.py", "unregistered-knob", 5),
                         ("bad_shard.py", "undeclared-axis", 10),
                         ("bad_shard.py", "unbound-axis", 10),
                         ("bad_shard.py", "spec-arity", 11),
                         ("bad_shard.py", "replicated-embedding", 15),
                         ("bad_cache.py", "stale-knob-key", 14),
                         ("bad_cache.py", "unkeyed-capture", 15),
                         ("bad_cache.py", "uncached-jit", 25),
                         ("bad_seam.py", "duplicate-step", 8)):
                assert want in got, "missing finding %r; got %r" \
                    % (want, sorted(got))
            result["exact"] = {"findings": len(rep.active)}

        # typical: ~3s. The hard ceiling is deliberately loose — it
        # exists to catch pathological regressions (an accidental jax
        # import, a pass losing its prefilter), not scheduler noise on
        # the single-core CI box running the full not-slow tier
        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        assert result["elapsed_s"] < 10.0, \
            "smoke exceeded the 10s ceiling: %.3fs" % result["elapsed_s"]
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
