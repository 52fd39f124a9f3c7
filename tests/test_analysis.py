"""mx.analysis static-analysis suite: per-pass bad/clean fixture twins,
inline and baseline suppression (including expiry), the live-tree
self-run, and the tools/check_analysis.py smoke as a subprocess.

The analysis package is pure stdlib; it is loaded through the
tools/mxlint.py shim so these tests never pay a jax import for linting.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import mxlint  # noqa: E402

analysis = mxlint.load_analysis()


# ----------------------------------------------------------- fixtures
def make_tree(tmp_path, **files):
    """Write a minimal mxnet_tpu package into tmp_path and return its
    root; ``files`` maps relpath-under-mxnet_tpu -> dedented source."""
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for rel, body in files.items():
        path = pkg / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(body))
    return str(tmp_path)


def findings(root, passes=None, baseline=None):
    rep = analysis.run(root, passes=passes, baseline=baseline)
    return rep, [(os.path.basename(f.path), f.rule, f.line)
                 for f in rep.active]


# ---------------------------------------------------------- jit purity
BAD_JIT = """\
    import time
    import random
    import jax
    import numpy as np


    @jax.jit
    def leaky(x, y):
        if x > 0:
            y = y + 1
        while y < 9:
            y = y * 2
        t = time.time()
        r = random.random()
        v = float(x)
        h = np.asarray(y)
        print(x)
        return y + v + t + r + h
    """


def test_jit_bad_fixture_flags_every_leak(tmp_path):
    _, got = findings(make_tree(tmp_path, **{"bad.py": BAD_JIT}),
                      passes=["jit"])
    assert ("bad.py", "tracer-branch", 9) in got
    assert ("bad.py", "tracer-branch", 11) in got
    assert ("bad.py", "impure-time", 13) in got
    assert ("bad.py", "impure-random", 14) in got
    assert ("bad.py", "host-sync", 15) in got
    assert ("bad.py", "host-sync", 16) in got
    assert ("bad.py", "impure-print", 17) in got


def test_jit_clean_twin_static_facts_dont_taint(tmp_path):
    # the same shapes of code, but every branch/host use is on a static
    # fact (shape, isinstance, len) — none of it may fire
    clean = """\
    import jax


    @jax.jit
    def fine(x, y):
        if x.ndim == 2:
            y = y + 1
        if isinstance(x, tuple):
            y = y * 2
        n = len(x.shape)
        if n == 2:
            y = y + n
        return x + y
    """
    rep, got = findings(make_tree(tmp_path, **{"clean.py": clean}),
                        passes=["jit"])
    assert got == [], got


def test_jit_donated_reuse(tmp_path):
    src = """\
    import jax


    def step(p, g):
        return p - g


    def train(p, g):
        fn = jax.jit(step, donate_argnums=(0,))
        out = fn(p, g)
        bad = p + 1
        return out, bad
    """
    _, got = findings(make_tree(tmp_path, **{"m.py": src}),
                      passes=["jit"])
    assert ("m.py", "donated-reuse", 11) in got


def test_jit_static_argnums_not_tainted(tmp_path):
    src = """\
    import jax


    @jax.jit(static_argnums=(1,))
    def fn(x, flag):
        if flag:
            return x + 1
        return x
    """
    _, got = findings(make_tree(tmp_path, **{"m.py": src}),
                      passes=["jit"])
    assert got == [], got


# ------------------------------------------------------ lock discipline
BAD_LOCKS = """\
    import threading


    class Worker(object):
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            self._thread = threading.Thread(target=self._run)
            self._thread.start()

        def _run(self):
            while True:
                self._count += 1

        def snapshot(self):
            return self._count
    """


def test_locks_bad_fixture_flags_both_sides(tmp_path):
    _, got = findings(make_tree(tmp_path, **{"bad.py": BAD_LOCKS}),
                      passes=["locks"])
    assert ("bad.py", "unguarded-write", 13) in got
    assert ("bad.py", "unguarded-read", 16) in got


def test_locks_clean_twin_guarded(tmp_path):
    clean = BAD_LOCKS.replace(
        "            self._count += 1",
        "            with self._lock:\n"
        "                self._count += 1").replace(
        "        return self._count",
        "        with self._lock:\n"
        "            return self._count")
    rep, got = findings(make_tree(tmp_path, **{"clean.py": clean}),
                        passes=["locks"])
    assert got == [], got


def test_locks_guarded_by_annotation_checks_all_accesses(tmp_path):
    src = """\
    import threading


    class Pool(object):
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []      # guarded-by: _lock

        def add(self, x):
            self._items.append(x)

        def drain(self):
            with self._lock:
                out, self._items = self._items, []
            return out
    """
    _, got = findings(make_tree(tmp_path, **{"m.py": src}),
                      passes=["locks"])
    assert ("m.py", "unguarded-read", 10) in got
    assert all(line != 14 for (_, _, line) in got), got


def test_locks_writes_mode_allows_lockfree_reads(tmp_path):
    src = """\
    import threading

    _LOCK = threading.Lock()
    _SINK = None      # guarded-by[writes]: _LOCK


    def configure(path):
        global _SINK
        with _LOCK:
            _SINK = path


    def enabled():
        return _SINK is not None


    def break_it(path):
        global _SINK
        _SINK = path
    """
    _, got = findings(make_tree(tmp_path, **{"m.py": src}),
                      passes=["locks"])
    assert ("m.py", "unguarded-write", 19) in got
    assert all(line != 14 for (_, _, line) in got), got


def test_locks_holds_annotation_trusts_callers(tmp_path):
    src = """\
    import threading


    class Box(object):
        def __init__(self):
            self._lock = threading.Lock()
            self._v = 0           # guarded-by: _lock

        def _bump(self):  # mxlint: holds(_lock)
            self._v += 1

        def bump(self):
            with self._lock:
                self._bump()
    """
    rep, got = findings(make_tree(tmp_path, **{"m.py": src}),
                        passes=["locks"])
    assert got == [], got


# ----------------------------------------------------------- drift
def drift_tree(tmp_path, use="config.get('io.depth')"):
    return make_tree(tmp_path, **{
        "config.py": """\
        def register_knob(name, env, type_, default, doc=""):
            pass


        def get(name):
            return None


        register_knob("io.depth", "MXTPU_IO_DEPTH", int, 2, "fixture")
        """,
        "user.py": "from . import config\n\n\ndef f():\n    return %s\n"
                   % use})


def test_drift_unregistered_knob(tmp_path):
    root = drift_tree(tmp_path, use="config.get('phantom.knob')")
    _, got = findings(root, passes=["drift"])
    assert ("user.py", "unregistered-knob", 5) in got
    # io.depth is now unread -> dead
    assert any(rule == "dead-knob" and name == "config.py"
               for (name, rule, _) in got), got


def test_drift_live_knob_and_generated_docs_are_clean(tmp_path):
    root = drift_tree(tmp_path)
    mxlint_mod = analysis
    repo = mxlint_mod.Repo(root)
    mxlint_mod.drift.fix_docs(repo)
    _, got = findings(root, passes=["drift"])
    assert got == [], got


def test_drift_stale_doc_detected_after_registry_change(tmp_path):
    root = drift_tree(tmp_path)
    analysis.drift.fix_docs(analysis.Repo(root))
    cfg = os.path.join(root, "mxnet_tpu", "config.py")
    with open(cfg) as f:
        src = f.read()
    with open(cfg, "w") as f:
        f.write(src + "\nregister_knob(\"io.extra\", \"MXTPU_IO_EXTRA\","
                      " int, 1, \"fixture\")\n")
    with open(os.path.join(root, "mxnet_tpu", "user.py"), "a") as f:
        f.write("\n\ndef g():\n    return config.get('io.extra')\n")
    _, got = findings(root, passes=["drift"])
    assert any(rule == "stale-doc" for (_, rule, _) in got), got


def test_drift_metric_index_both_directions(tmp_path):
    root = drift_tree(tmp_path)
    (os.path.join(root, "mxnet_tpu"))
    with open(os.path.join(root, "mxnet_tpu", "emit.py"), "w") as f:
        f.write("from . import telemetry as _telemetry\n\n\n"
                "def f():\n"
                "    _telemetry.counter(\"io.reads\").inc()\n")
    with open(os.path.join(root, "mxnet_tpu", "telemetry.py"), "w") as f:
        f.write("def counter(name):\n    return None\n")
    analysis.drift.fix_docs(analysis.Repo(root))
    _, got = findings(root, passes=["drift"])
    assert got == [], got
    # now stop emitting it -> dead-metric
    os.remove(os.path.join(root, "mxnet_tpu", "emit.py"))
    _, got = findings(root, passes=["drift"])
    assert any(rule == "dead-metric" for (_, rule, _) in got), got


# ----------------------------------------------------------- shard spec
BAD_SHARD = """\
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
    """


def test_shard_bad_fixture_flags_every_rule(tmp_path):
    _, got = findings(make_tree(tmp_path, **{"bad.py": BAD_SHARD}),
                      passes=["shard"])
    assert ("bad.py", "undeclared-axis", 10) in got
    assert ("bad.py", "unbound-axis", 10) in got
    assert ("bad.py", "spec-arity", 11) in got
    assert ("bad.py", "replicated-embedding", 15) in got


def test_shard_clean_twin(tmp_path):
    # same shapes: axis declared, arity matches, the collective's axis is
    # bound by an in_spec, and the embedding spec shards the vocab axis
    clean = """\
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    AXES = ("dp", "tp")


    def lookup(table, ids, mesh):
        def _shard(tbl, u):
            return jax.lax.psum(tbl, "tp")
        return shard_map(_shard, mesh=mesh,
                         in_specs=(P("tp", None), P("dp")),
                         out_specs=P())(table, ids)


    SPECS = {"embed": P("tp", None)}
    """
    _, got = findings(make_tree(tmp_path, **{"clean.py": clean}),
                      passes=["shard"])
    assert got == [], got


def test_shard_undeclared_axis_stands_down_without_registry(tmp_path):
    # no mesh construction site in the tree -> the axis universe is
    # unknown, so undeclared-axis must not guess; the site-local rules
    # (arity, unbound collective axis) still hold
    src = BAD_SHARD.replace('AXES = ("dp",)', "MESHLESS = True")
    _, got = findings(make_tree(tmp_path, **{"bad.py": src}),
                      passes=["shard"])
    rules = {rule for (_, rule, _) in got}
    assert "undeclared-axis" not in rules, got
    assert "spec-arity" in rules and "unbound-axis" in rules


# -------------------------------------------------------- compile cache
BAD_CACHE = """\
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
    """


def test_cache_bad_fixture_flags_every_rule(tmp_path):
    _, got = findings(make_tree(tmp_path, **{"bad.py": BAD_CACHE}),
                      passes=["cache"])
    assert ("bad.py", "stale-knob-key", 14) in got
    assert ("bad.py", "unkeyed-capture", 15) in got
    assert ("bad.py", "uncached-jit", 25) in got


def test_cache_epoch_aware_owner_is_clean(tmp_path):
    # consulting config.epoch() is the sanctioned invalidation contract
    # (symbol.py fused_step_fn): the owner may bake knobs in freely
    clean = """\
    import jax
    from . import config


    class Runner(object):
        def __init__(self):
            self._progs = {}

        def _prog(self, shape):
            epoch = config.epoch()
            cap = config.get("io.depth")

            def run(x):
                return x * cap

            prog = self._progs[(shape, epoch)] = jax.jit(run)
            return prog
    """
    _, got = findings(make_tree(tmp_path, **{"clean.py": clean}),
                      passes=["cache"])
    assert got == [], got


def test_cache_value_in_key_is_clean(tmp_path):
    # the captured size IS part of the cache key -> no unkeyed-capture
    clean = """\
    import jax


    class Runner(object):
        def __init__(self):
            self._progs = {}
            self.items = ()

        def set_items(self, xs):
            self.items = xs

        def _prog(self, shape):
            n = len(self.items)

            def run(x):
                return x * n

            prog = self._progs[(shape, n)] = jax.jit(run)
            return prog
    """
    _, got = findings(make_tree(tmp_path, **{"clean.py": clean}),
                      passes=["cache"])
    assert got == [], got


def test_cache_tools_one_shot_jit_is_sanctioned(tmp_path):
    # tools/ check scripts are one-shot CLIs: an immediate jit dispatch
    # is the point there, not a per-call retrace bug
    root = make_tree(tmp_path)
    tools = os.path.join(root, "tools")
    os.makedirs(tools)
    with open(os.path.join(tools, "check_x.py"), "w") as f:
        f.write("import jax\n\n\ndef main():\n"
                "    return jax.jit(lambda v: v + 1)(0)\n")
    _, got = findings(root, passes=["cache"])
    assert got == [], got


# ------------------------------------------------------------ step seam
BAD_SEAM = """\
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
    """


def test_seam_flags_fused_step_outside_core(tmp_path):
    rep, got = findings(make_tree(tmp_path, **{"stepper.py": BAD_SEAM}),
                        passes=["seam"])
    assert got == [("stepper.py", "duplicate-step", 8)], got
    assert rep.active[0].symbol == "Stepper._build"


def test_seam_sanctioned_core_is_exempt(tmp_path):
    # byte-identical machinery inside runtime.py is the real thing, not
    # a duplicate
    _, got = findings(make_tree(tmp_path, **{"runtime.py": BAD_SEAM}),
                      passes=["seam"])
    assert got == [], got


# ------------------------------------------------- suppression plumbing
def test_inline_disable_suppresses_and_names_reason(tmp_path):
    src = BAD_JIT.replace(
        "        t = time.time()",
        "        t = time.time()  # mxlint: disable=jit.impure-time"
        " -- wall clock is part of this fixture")
    rep, got = findings(make_tree(tmp_path, **{"bad.py": src}),
                        passes=["jit"])
    assert all(rule != "impure-time" for (_, rule, _) in got), got
    sup = [f for f in rep.suppressed if f.rule == "impure-time"]
    assert sup and "inline" in sup[0].reason


def test_baseline_suppresses_with_reason(tmp_path):
    root = make_tree(tmp_path, **{"bad.py": BAD_LOCKS})
    rep = analysis.run(root, passes=["locks"])
    keys = [f.key for f in rep.findings]
    bl = analysis.Baseline(
        [{"id": k, "reason": "fixture: known benign"} for k in keys])
    rep2 = analysis.run(root, passes=["locks"], baseline=bl)
    assert rep2.ok
    assert len(rep2.suppressed) == len(keys)
    assert all("benign" in f.reason for f in rep2.suppressed)


def test_baseline_expiry_fails_the_lint(tmp_path):
    root = make_tree(tmp_path, **{"clean.py": "X = 1\n"})
    bl = analysis.Baseline(
        [{"id": "locks.unguarded-write:mxnet_tpu/gone.py:Gone:_x:",
          "reason": "stale"}])
    rep = analysis.run(root, passes=["locks"], baseline=bl)
    assert not rep.ok
    assert rep.expired and rep.expired[0].rule == "expired"


def test_baseline_keys_are_line_insensitive(tmp_path):
    root = make_tree(tmp_path, **{"bad.py": BAD_LOCKS})
    rep = analysis.run(root, passes=["locks"])
    bl = analysis.Baseline([{"id": f.key, "reason": "pinned"}
                            for f in rep.findings])
    # shift every line down by one: the keys must still match
    pkg = os.path.join(root, "mxnet_tpu", "bad.py")
    with open(pkg) as f:
        src = f.read()
    with open(pkg, "w") as f:
        f.write("# shifted\n" + src)
    rep2 = analysis.run(root, passes=["locks"], baseline=bl)
    assert rep2.ok, [x.format() for x in rep2.active]


def test_baseline_future_expiry_still_suppresses(tmp_path):
    root = make_tree(tmp_path, **{"bad.py": BAD_LOCKS})
    rep = analysis.run(root, passes=["locks"])
    bl = analysis.Baseline(
        [{"id": f.key, "reason": "burn-down", "expires": "2030-01"}
         for f in rep.findings])
    rep2 = analysis.run(root, passes=["locks"], baseline=bl,
                        today="2026-08")
    assert rep2.ok
    assert len(rep2.suppressed) == len(rep.findings)


def test_baseline_past_expiry_reactivates_findings(tmp_path):
    root = make_tree(tmp_path, **{"bad.py": BAD_LOCKS})
    rep = analysis.run(root, passes=["locks"])
    bl = analysis.Baseline(
        [{"id": f.key, "reason": "burn-down", "expires": "2026-07"}
         for f in rep.findings])
    rep2 = analysis.run(root, passes=["locks"], baseline=bl,
                        today="2026-08")
    assert not rep2.ok
    rules = {f.rule for f in rep2.active}
    # the deadline is reported AND the findings come back live
    assert "date-expired" in rules, rules
    assert "unguarded-write" in rules, rules


def test_baseline_write_round_trip(tmp_path):
    root = make_tree(tmp_path, **{"bad.py": BAD_LOCKS})
    rep = analysis.run(root, passes=["locks"])
    kept_key = rep.findings[0].key
    prev = analysis.Baseline(
        [{"id": kept_key, "reason": "kept: known benign",
          "expires": "2027-01"},
         {"id": "locks.unguarded-write:mxnet_tpu/gone.py:Gone:_x:",
          "reason": "stale entry for code that no longer exists"}])
    path = str(tmp_path / "bl.json")
    entries = prev.write(path, rep.findings)
    by_id = {e["id"]: e for e in entries}
    # surviving key keeps its justification and deadline
    assert by_id[kept_key]["reason"] == "kept: known benign"
    assert by_id[kept_key]["expires"] == "2027-01"
    # the stale key is dropped; new keys demand a justification
    assert "locks.unguarded-write:mxnet_tpu/gone.py:Gone:_x:" not in by_id
    fresh = [e for e in entries if e["id"] != kept_key]
    assert fresh and all(e["reason"].startswith("FIXME") for e in fresh)
    # the written ledger suppresses exactly the live findings
    rep2 = analysis.run(root, passes=["locks"], baseline=path)
    assert rep2.ok
    assert len(rep2.suppressed) == len(rep.findings)


def test_changed_only_lints_only_changed_files(tmp_path):
    root = make_tree(tmp_path, **{"stale.py": BAD_LOCKS})
    env = dict(os.environ,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

    def git(*argv):
        subprocess.run(["git", "-C", root] + list(argv), check=True,
                       capture_output=True, env=env)

    git("init", "-q")
    git("add", "-A")
    git("commit", "-q", "-m", "seed")
    # a new violation lands in fresh.py; stale.py keeps its old one
    with open(os.path.join(root, "mxnet_tpu", "fresh.py"), "w") as f:
        f.write(textwrap.dedent(BAD_JIT))
    git("add", "-A")
    cli = [sys.executable, os.path.join(ROOT, "tools", "mxlint.py"),
           "--root", root, "--no-baseline", "--changed-only", "HEAD"]
    proc = subprocess.run(cli, capture_output=True, text=True,
                          timeout=60, env=env)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "fresh.py" in proc.stdout
    # the unchanged file's pre-existing finding is not re-reported
    assert "stale.py" not in proc.stdout, proc.stdout
    # nothing changed vs HEAD -> fast clean exit
    git("commit", "-q", "-m", "wip")
    proc = subprocess.run(cli, capture_output=True, text=True,
                          timeout=60, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no changed" in proc.stdout


def test_parse_error_fails_the_lint(tmp_path):
    root = make_tree(tmp_path, **{"broken.py": "def f(:\n"})
    rep = analysis.run(root, passes=["jit"])
    assert not rep.ok
    assert rep.repo.parse_errors


# ------------------------------------------------------- live self-run
def test_live_tree_is_clean_under_checked_in_baseline():
    rep = analysis.run(ROOT, baseline=os.path.join(
        ROOT, "tools", "mxlint_baseline.json"))
    assert rep.ok, "\n".join(f.format() for f in rep.active)


def test_live_serving_and_kernel_surfaces_have_no_false_positives():
    # PR 13's decode/prefill builders (generation/serving/deploy) and
    # PR 12's pallas_call routing (kernels) are the densest jit surfaces
    # in the tree: the purity, lock and shard passes must stay silent on
    # them without any suppression
    targets = ("mxnet_tpu/kernels.py", "mxnet_tpu/generation.py",
               "mxnet_tpu/serving.py", "mxnet_tpu/deploy.py")
    rep = analysis.run(ROOT, passes=["jit", "locks", "shard"],
                       targets=targets)
    assert rep.ok, "\n".join(f.format() for f in rep.active)


def test_jit_kernel_knob_routing_clean_both_branches(tmp_path):
    # the kernels.py dispatch idiom: the knob gate lives OUTSIDE the
    # traced code and picks between two jitted impls, so neither knob
    # state can produce a tracer-branch or retrace finding
    src = """\
    import jax
    from . import config


    @jax.jit
    def _reference(q, k, v):
        return q + k + v


    @jax.jit
    def _pallas(q, k, v):
        return q * k * v


    def attention(q, k, v):
        if config.get("kernels.flash_attention"):
            return _pallas(q, k, v)
        return _reference(q, k, v)
    """
    _, got = findings(make_tree(tmp_path, **{"m.py": src}),
                      passes=["jit", "cache"])
    assert got == [], got


def test_checked_in_baseline_entries_all_carry_reasons():
    with open(os.path.join(ROOT, "tools", "mxlint_baseline.json")) as f:
        data = json.load(f)
    assert data["suppressions"], "baseline exists but suppresses nothing"
    for entry in data["suppressions"]:
        assert entry.get("id") and entry.get("reason"), entry


# ------------------------------------------------------- smoke wrapper
def test_check_analysis_smoke():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_analysis.py")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["clean"]["rc"] == 0
    assert report["catches"]["rc"] != 0
    assert report["elapsed_s"] < 10.0, report
