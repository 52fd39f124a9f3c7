"""Registry/doc drift checking (pass id: ``drift``).

Three registries must agree with the code that uses them:

* **Knobs** — every literal ``config.get("...")`` / ``config.set``
  name must be registered in ``mxnet_tpu/config.py``
  (``unregistered-knob``), and every registered knob must actually be
  read somewhere — via ``config.get`` or a direct read of its env var
  (``dead-knob``; reference-compat informational knobs are baselined
  with a justification, not silently exempted).
* **Env vars** — every ``MXNET_*``/``MXTPU_*`` environment variable the
  code reads must appear in ``docs/ENV_VARS.md``: knob-backed vars land
  in the generated table, the rest in the generated "Non-knob
  environment variables" section, whose one-line descriptions live in
  ``_ENV_NOTES`` below (``undocumented-env`` when neither knows the
  name).  The whole doc is generated — ``stale-doc`` fires when the
  file on disk differs from what the registry would generate, and
  ``python tools/mxlint.py --fix-docs`` rewrites it.
* **Metrics** — every telemetry ``counter/gauge/timer`` name emitted
  anywhere (dynamic segments normalized: ``"serving.shed_requests.%s" %
  req.model`` becomes ``serving.shed_requests.<model>``) must appear in
  the generated metric-name index in ``docs/OBSERVABILITY.md``
  (``undocumented-metric``), and index entries no call site emits any
  more are reported as ``dead-metric`` — both directions, so the doc
  can neither lag nor lie.

Everything is AST-level: the pass never imports the framework, so it
cannot be fooled by import-time side effects and runs in milliseconds.
"""
from __future__ import annotations

import ast
import os
import re

from .walker import Finding, dotted_name

PASS_ID = "drift"

_ENV_NAME_RE = re.compile(r"^(MXNET|MXTPU)_")

_INDEX_BEGIN = "<!-- mxlint:metric-index:begin -->"
_INDEX_END = "<!-- mxlint:metric-index:end -->"

#: one-liners for env vars read directly without a knob registration;
#: --fix-docs folds these into ENV_VARS.md.  An env read the registry
#: does not know and this table does not describe is a finding.
_ENV_NOTES = {
    "MXTPU_CHAOS_STEPS":
        "tools/dist_chaos_worker.py only: total optimisation steps of "
        "the elastic chaos smoke job (default 10).",
    "MXTPU_CHAOS_CKPT":
        "tools/dist_chaos_worker.py only: directory for the worker's "
        "CoordinatedCheckpointManager; unset = no checkpointing "
        "(the baseline leg).",
    "MXTPU_CHAOS_OUT":
        "tools/dist_chaos_worker.py only: rank 0 writes the result JSON "
        "(losses, params, wire telemetry) here for the harness.",
    "MXTPU_CHAOS_PREEMPT_RANK":
        "tools/dist_chaos_worker.py only: rank that self-injects a "
        "deterministic peer_preempt fault in generation 0.",
    "MXTPU_CHAOS_PREEMPT_STEP":
        "tools/dist_chaos_worker.py only: step at which the faulted "
        "rank draws its peer_preempt (default 5).",
    "MXNET_TENSORRT_USE_FP16":
        "contrib.tensorrt API-compat shim: the reference's TensorRT "
        "fp16 toggle, stored/echoed verbatim (no TensorRT on TPU).",
}

_ENV_VARS_HEADER = """\
# Runtime knob registry

Generated from `mx.config.describe()` — the env_var.md analog
(reference: docs/static_site/src/pages/api/faq/env_var.md).  Every
knob is typed; `mx.config.set(name, value)` overrides at runtime and
invalidates compiled-program caches when the value or its source
(default, env, override) changes.

Regenerate this file with `python tools/mxlint.py --fix-docs`;
`tools/mxlint.py` (the `drift` pass, docs/ANALYSIS.md) fails CI when
it goes stale.
"""

_METRIC_INDEX_HEADER = """\
## Metric name index

Generated by `python tools/mxlint.py --fix-docs` from every
`telemetry.counter/gauge/timer` call site in the tree; the `drift`
pass (docs/ANALYSIS.md) fails CI when an emitted name is missing here
or an indexed name is no longer emitted.  `<x>` marks a dynamic
segment filled at runtime (a model name, a fault kind, a step-log
source).
"""


# ------------------------------------------------------------ knob registry
class Knob(object):
    __slots__ = ("name", "env", "type_name", "default", "doc", "lineno")

    def __init__(self, name, env, type_name, default, doc, lineno):
        self.name = name
        self.env = env
        self.type_name = type_name
        self.default = default
        self.doc = doc
        self.lineno = lineno


def parse_registry(repo):
    """AST-parse register_knob(...) literals out of mxnet_tpu/config.py
    (no import: config must stay analyzable without executing it)."""
    cfg = repo.by_relpath.get(os.path.join("mxnet_tpu", "config.py"))
    knobs = {}
    if cfg is None:
        return knobs
    for node in ast.walk(cfg.tree):
        if not (isinstance(node, ast.Call) and
                dotted_name(node.func) and
                dotted_name(node.func).split(".")[-1] == "register_knob"):
            continue
        args = node.args
        if len(args) < 4:
            continue
        try:
            name = ast.literal_eval(args[0])
            env = ast.literal_eval(args[1])
            type_name = dotted_name(args[2]) or "str"
            default = ast.literal_eval(args[3])
            doc = ast.literal_eval(args[4]) if len(args) > 4 else ""
        except (ValueError, SyntaxError):
            continue
        knobs[name] = Knob(name, env, type_name, default, doc,
                           node.lineno)
    return knobs


# ------------------------------------------------------------- code scans
_METRIC_FNS = ("counter", "gauge", "timer")


def _inventory(module):
    """One ast.walk per module yielding all three drift inventories:
    (config_calls, env_reads, metric_emits).  Cached on the module —
    run() + the doc generators would otherwise re-walk the whole tree
    several times."""
    cached = getattr(module, "_mxa_drift", None)
    if cached is not None:
        return cached
    config_calls, env_reads, metric_emits = [], [], []
    is_config = module.modname == "mxnet_tpu.config"
    is_telemetry = module.modname == "mxnet_tpu.telemetry"
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call):
            d = dotted_name(node.func)
            if d:
                _scan_config_call(module, node, d, is_config,
                                  config_calls)
                _scan_metric_call(module, node, d, is_telemetry,
                                  metric_emits)
                if d in ("os.environ.get", "os.getenv",
                         "os.environ.setdefault", "os.environ.pop") and \
                        node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        isinstance(node.args[0].value, str):
                    env_reads.append((node.args[0].value, node.lineno))
        elif isinstance(node, ast.Subscript):
            if dotted_name(node.value) == "os.environ" and \
                    isinstance(node.slice, ast.Constant) and \
                    isinstance(node.slice.value, str):
                env_reads.append((node.slice.value, node.lineno))
        elif isinstance(node, ast.Compare):
            if len(node.ops) == 1 and \
                    isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                    dotted_name(node.comparators[0]) == "os.environ" and \
                    isinstance(node.left, ast.Constant) and \
                    isinstance(node.left.value, str):
                env_reads.append((node.left.value, node.lineno))
    module._mxa_drift = (config_calls, env_reads, metric_emits)
    return module._mxa_drift


def _scan_config_call(module, node, d, is_config, out):
    parts = d.split(".")
    if parts[-1] not in ("get", "set"):
        return
    if len(parts) == 1:
        # bare get()/set() only count inside config.py itself (the
        # _autostart/_ON_SET plumbing)
        if not is_config:
            return
    else:
        base = parts[-2]
        resolved = module.resolve_alias(parts[0])
        if not (base == "config" or base.endswith("_config") or
                (resolved and resolved.endswith(".config"))):
            return
        if resolved and not resolved.startswith("mxnet_tpu"):
            return                      # some other package's config
    if node.args and isinstance(node.args[0], ast.Constant) and \
            isinstance(node.args[0].value, str):
        out.append((parts[-1], node.args[0].value, node.lineno))


def _iter_config_calls(module):
    """(kind, name, lineno) for literal config.get/set calls."""
    return _inventory(module)[0]


def _iter_env_reads(module):
    """(env_name, lineno) for os.environ/os.getenv accesses with a
    string-literal name."""
    return _inventory(module)[1]


def _metric_placeholder(node):
    if isinstance(node, ast.Name):
        return "<%s>" % node.id.strip("_")
    if isinstance(node, ast.Attribute):
        return "<%s>" % node.attr.strip("_")
    return "<dyn>"


def _normalize_metric_name(arg):
    """Constant/format/concat metric-name expr -> normalized name with
    ``<x>`` dynamic segments, or None for registry plumbing (a bare
    variable) that names no new metric."""
    if isinstance(arg, ast.Constant):
        return arg.value if isinstance(arg.value, str) else None
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Mod) and \
            isinstance(arg.left, ast.Constant) and \
            isinstance(arg.left.value, str):
        fmt = arg.left.value
        fills = arg.right.elts if isinstance(arg.right, ast.Tuple) \
            else [arg.right]
        out, i = fmt, 0
        while "%s" in out and i < len(fills):
            out = out.replace("%s", _metric_placeholder(fills[i]), 1)
            i += 1
        return out
    if isinstance(arg, ast.BinOp) and isinstance(arg.op, ast.Add):
        left = _normalize_metric_name(arg.left)
        right = _normalize_metric_name(arg.right)
        left = left if left is not None else _metric_placeholder(arg.left)
        right = right if right is not None \
            else _metric_placeholder(arg.right)
        return left + right
    if isinstance(arg, ast.JoinedStr):
        out = []
        for part in arg.values:
            if isinstance(part, ast.Constant):
                out.append(str(part.value))
            elif isinstance(part, ast.FormattedValue):
                out.append(_metric_placeholder(part.value))
        return "".join(out)
    return None


def _scan_metric_call(module, node, d, is_telemetry, out):
    if not node.args:
        return
    parts = d.split(".")
    kind = parts[-1]
    if kind not in _METRIC_FNS:
        return
    if len(parts) > 1:
        resolved = module.resolve_alias(parts[0]) or parts[0]
        if not resolved.endswith("telemetry"):
            return
    else:
        src = module.from_imports.get(kind)
        if not (is_telemetry or (src and src[0].endswith("telemetry"))):
            return
    name = _normalize_metric_name(node.args[0])
    if name:
        out.append((kind, name, node.lineno))


def _iter_metric_emits(module):
    """(kind, normalized_name, lineno) for telemetry instrument calls."""
    return _inventory(module)[2]


# ------------------------------------------------------------ doc builders
def _describe_lines(knobs):
    fmt = "%-28s %-34s %-8s %-10s %s"
    lines = [fmt % ("Knob", "Env var", "Type", "Default", "Doc")]
    for k in sorted(knobs.values(), key=lambda k: k.name):
        lines.append(fmt % (k.name, k.env, k.type_name, k.default, k.doc))
    return lines


def _non_knob_envs(repo, knobs):
    """Sorted (env_name, [relpath, ...]) for MX* env reads with no knob."""
    knob_envs = {k.env for k in knobs.values()}
    seen = {}
    for module in repo.modules:
        for name, _lineno in _iter_env_reads(module):
            if _ENV_NAME_RE.match(name) and name not in knob_envs:
                seen.setdefault(name, set()).add(module.relpath)
    return sorted((n, sorted(paths)) for n, paths in seen.items())


def generate_env_vars_doc(repo, knobs=None):
    knobs = knobs if knobs is not None else parse_registry(repo)
    out = [_ENV_VARS_HEADER, "```"]
    out.extend(_describe_lines(knobs))
    out.append("```")
    extras = _non_knob_envs(repo, knobs)
    if extras:
        out.append("")
        out.append("## Non-knob environment variables")
        out.append("")
        out.append("Read directly via `os.environ` (no `mx.config` "
                   "knob backs them):")
        out.append("")
        for name, paths in extras:
            note = _ENV_NOTES.get(
                name, "(undocumented — describe it in "
                      "mxnet_tpu/analysis/drift.py:_ENV_NOTES)")
            out.append("* `%s` — %s  [%s]" % (name, note,
                                              ", ".join(paths)))
    return "\n".join(out) + "\n"


def collect_metrics(repo):
    """name -> {"kinds": set, "files": set}."""
    metrics = {}
    for module in repo.modules:
        if module.relpath.startswith("mxnet_tpu" + os.sep + "analysis"):
            continue                    # the linter itself emits nothing
        for kind, name, lineno in _iter_metric_emits(module):
            rec = metrics.setdefault(
                name, {"kinds": set(), "files": set(), "site": None})
            rec["kinds"].add(kind)
            rec["files"].add(module.relpath)
            if rec["site"] is None:
                rec["site"] = (module, lineno)
    return metrics


def generate_metric_index(repo, metrics=None):
    metrics = metrics if metrics is not None else collect_metrics(repo)
    lines = [_INDEX_BEGIN, _METRIC_INDEX_HEADER,
             "| Name | Kinds | Emitted from |", "|---|---|---|"]
    for name in sorted(metrics):
        rec = metrics[name]
        files = ", ".join(sorted(os.path.basename(f)
                                 for f in rec["files"]))
        lines.append("| `%s` | %s | %s |" % (
            name, ", ".join(sorted(rec["kinds"])), files))
    lines.append(_INDEX_END)
    return "\n".join(lines) + "\n"


def splice_metric_index(doc_text, index_text):
    """Replace the marked index section (or append one)."""
    if _INDEX_BEGIN in doc_text and _INDEX_END in doc_text:
        head = doc_text.split(_INDEX_BEGIN)[0]
        tail = doc_text.split(_INDEX_END, 1)[1]
        return head + index_text.rstrip("\n") + tail
    return doc_text.rstrip("\n") + "\n\n" + index_text


def _documented_metric_names(doc_text):
    """(name, lineno) rows parsed from the marked index section."""
    out = []
    in_section = False
    for i, line in enumerate(doc_text.splitlines(), 1):
        if _INDEX_BEGIN in line:
            in_section = True
            continue
        if _INDEX_END in line:
            break
        if in_section:
            m = re.match(r"\|\s*`([^`]+)`\s*\|", line)
            if m:
                out.append((m.group(1), i))
    return out


# ------------------------------------------------------------------ pass
class Drift(object):
    def __init__(self, repo):
        self.repo = repo
        self.findings = []

    def emit(self, path, lineno, rule, symbol, detail, message):
        self.findings.append(Finding(PASS_ID, rule, path, lineno, symbol,
                                     detail, message))

    def _read_doc(self, relpath):
        full = os.path.join(self.repo.root, relpath)
        if not os.path.exists(full):
            return None
        with open(full, "r") as f:
            return f.read()

    def run(self):
        repo = self.repo
        knobs = parse_registry(repo)
        knob_envs = {k.env: k for k in knobs.values()}
        used_knobs = set()

        # --- config.get/set names must be registered
        for module in repo.modules:
            for kind, name, lineno in _iter_config_calls(module):
                if name in knobs:
                    used_knobs.add(name)
                else:
                    self.emit(module.relpath, lineno, "unregistered-knob",
                              "", name,
                              "config.%s(%r): knob is not registered in "
                              "mxnet_tpu/config.py" % (kind, name))

        # --- env reads: knob-env reads count as knob uses; MX* reads
        # must be documented
        env_doc = self._read_doc(os.path.join("docs", "ENV_VARS.md"))
        for module in repo.modules:
            for name, lineno in _iter_env_reads(module):
                if name in knob_envs:
                    used_knobs.add(knob_envs[name].name)
                    continue
                if not _ENV_NAME_RE.match(name):
                    continue
                documented = (name in _ENV_NOTES) or \
                    (env_doc is not None and name in env_doc)
                if not documented:
                    self.emit(module.relpath, lineno, "undocumented-env",
                              "", name,
                              "env var %s is read here but appears "
                              "neither in the knob registry nor in "
                              "docs/ENV_VARS.md (register a knob, or "
                              "describe it in drift._ENV_NOTES and run "
                              "--fix-docs)" % name)

        # --- dead knobs: registered but never read anywhere
        cfg_rel = os.path.join("mxnet_tpu", "config.py")
        for name, knob in sorted(knobs.items()):
            if name not in used_knobs:
                self.emit(cfg_rel, knob.lineno, "dead-knob", "", name,
                          "knob %r is registered but no config.get/env "
                          "read uses it" % name)

        # --- ENV_VARS.md staleness (the whole file is generated)
        expected = generate_env_vars_doc(repo, knobs)
        if env_doc is None:
            self.emit(os.path.join("docs", "ENV_VARS.md"), 1, "stale-doc",
                      "", "ENV_VARS.md",
                      "docs/ENV_VARS.md is missing — run "
                      "'python tools/mxlint.py --fix-docs'")
        elif env_doc != expected:
            self.emit(os.path.join("docs", "ENV_VARS.md"), 1, "stale-doc",
                      "", "ENV_VARS.md",
                      "docs/ENV_VARS.md does not match the registry — "
                      "run 'python tools/mxlint.py --fix-docs'")

        # --- metric names vs the OBSERVABILITY index, both directions
        obs_rel = os.path.join("docs", "OBSERVABILITY.md")
        obs_doc = self._read_doc(obs_rel)
        metrics = collect_metrics(repo)
        documented = _documented_metric_names(obs_doc or "")
        documented_names = {n for n, _ in documented}
        if obs_doc is None or _INDEX_BEGIN not in obs_doc:
            self.emit(obs_rel, 1, "missing-index", "", "metric-index",
                      "docs/OBSERVABILITY.md has no generated metric "
                      "name index — run 'python tools/mxlint.py "
                      "--fix-docs'")
        for name in sorted(metrics):
            if name not in documented_names:
                module, lineno = metrics[name]["site"]
                self.emit(module.relpath, lineno, "undocumented-metric",
                          "", name,
                          "telemetry name %r is emitted here but missing "
                          "from the docs/OBSERVABILITY.md metric index "
                          "(run --fix-docs)" % name)
        for name, lineno in documented:
            if name not in metrics:
                self.emit(obs_rel, lineno, "dead-metric", "", name,
                          "metric %r is in the docs/OBSERVABILITY.md "
                          "index but no call site emits it any more "
                          "(run --fix-docs)" % name)
        return self.findings


def run(repo):
    return Drift(repo).run()


def fix_docs(repo):
    """Rewrite both generated doc surfaces; returns the relpaths
    written."""
    written = []
    os.makedirs(os.path.join(repo.root, "docs"), exist_ok=True)
    env_rel = os.path.join("docs", "ENV_VARS.md")
    env_full = os.path.join(repo.root, env_rel)
    new_env = generate_env_vars_doc(repo)
    old = None
    if os.path.exists(env_full):
        with open(env_full, "r") as f:
            old = f.read()
    if old != new_env:
        with open(env_full, "w") as f:
            f.write(new_env)
        written.append(env_rel)
    obs_rel = os.path.join("docs", "OBSERVABILITY.md")
    obs_full = os.path.join(repo.root, obs_rel)
    obs_doc = ""
    if os.path.exists(obs_full):
        with open(obs_full, "r") as f:
            obs_doc = f.read()
    new_obs = splice_metric_index(obs_doc,
                                  generate_metric_index(repo))
    if new_obs != obs_doc:
        with open(obs_full, "w") as f:
            f.write(new_obs)
        written.append(obs_rel)
    return written
