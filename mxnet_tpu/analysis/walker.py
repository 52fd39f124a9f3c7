"""Shared AST infrastructure for the mx.analysis static-analysis suite.

Everything here is plain-stdlib (ast/tokenize/re/json): the passes must
run in tools/mxlint.py without importing jax or the framework itself,
so a full-tree lint stays well under a second and can gate CI.

The pieces the passes build on:

* ``Repo`` — parses every framework source file once (``mxnet_tpu/``,
  ``tools/``) into ``SourceModule`` records and resolves
  cross-module references through each module's import-alias table, so
  a pass can follow ``_resilience.select_tree`` from a traced step body
  into ``mxnet_tpu/resilience.py``.
* ``SourceModule`` — one parsed file: AST, raw lines, the per-line
  comment map (recovered with ``tokenize`` — ``ast`` drops comments,
  and the ``# guarded-by:`` / ``# mxlint:`` conventions live in them),
  import aliases, and top-level function/class tables.
* ``Finding`` — a single diagnostic with a *line-insensitive* identity
  key (pass.rule:path:symbol:detail) so baseline suppressions survive
  unrelated line churn.
* ``Baseline`` — the checked-in suppression file
  (tools/mxlint_baseline.json): every entry needs a justification, and
  entries that no longer match a live finding are reported as expired
  so the file cannot rot.

Comment conventions (see docs/ANALYSIS.md):

* ``# guarded-by: _lock`` on an attribute or module-global assignment
  declares its guarding lock; ``# guarded-by[writes]: _lock`` guards
  writes only (reads are documented lock-free).
* ``# mxlint: holds(_lock)`` on a ``def`` line declares every caller
  holds the lock already (the assertHeld analog).
* ``# mxlint: disable=pass.rule`` on a finding's line suppresses it in
  place; prefer the baseline for anything needing a justification.
"""
from __future__ import annotations

import ast
import io
import json
import os
import re
import time
import tokenize

__all__ = [
    "Finding", "SourceModule", "Repo", "Baseline",
    "dotted_name", "GUARD_RE", "HOLDS_RE", "DISABLE_RE",
]

GUARD_RE = re.compile(
    r"guarded-by(?:\[(?P<mode>[a-z]+)\])?:\s*(?P<lock>[A-Za-z_]\w*)")
HOLDS_RE = re.compile(r"mxlint:\s*holds\((?P<lock>[A-Za-z_]\w*)\)")
DISABLE_RE = re.compile(r"mxlint:\s*disable=(?P<rules>[\w.,-]+)")

#: directories/files a Repo scans, relative to the repo root.
DEFAULT_TARGETS = ("mxnet_tpu", "tools")


def dotted_name(node):
    """``a.b.c`` for a Name/Attribute chain, else None.

    ``self._cond.wait`` -> "self._cond.wait"; calls/subscripts in the
    chain make it dynamic and return None.
    """
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class Finding(object):
    """One diagnostic. ``key`` is line-insensitive on purpose: baseline
    entries keyed on it survive edits elsewhere in the file."""

    __slots__ = ("pass_id", "rule", "path", "line", "symbol", "detail",
                 "message", "suppressed", "reason")

    def __init__(self, pass_id, rule, path, line, symbol, detail, message):
        self.pass_id = pass_id
        self.rule = rule
        self.path = path
        self.line = line
        self.symbol = symbol or ""
        self.detail = detail or ""
        self.message = message
        self.suppressed = False
        self.reason = ""

    @property
    def key(self):
        return "%s.%s:%s:%s:%s" % (self.pass_id, self.rule, self.path,
                                   self.symbol, self.detail)

    def format(self):
        return "%s:%d: [%s.%s] %s" % (self.path, self.line, self.pass_id,
                                      self.rule, self.message)

    def to_dict(self):
        return {"pass": self.pass_id, "rule": self.rule, "path": self.path,
                "line": self.line, "symbol": self.symbol,
                "detail": self.detail, "message": self.message,
                "key": self.key, "suppressed": self.suppressed,
                "reason": self.reason}

    def __repr__(self):
        return "Finding(%s)" % self.format()


def _comment_map(text):
    """lineno -> comment text (without '#'), via tokenize so '#' inside
    string literals never miscounts as a comment."""
    out = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.COMMENT:
                out[tok.start[0]] = tok.string.lstrip("#").strip()
    except (tokenize.TokenError, IndentationError, SyntaxError):
        # fall back to a naive scan; good enough for fixture fragments
        for i, line in enumerate(text.splitlines(), 1):
            if "#" in line:
                out[i] = line.split("#", 1)[1].strip()
    return out


class SourceModule(object):
    """One parsed source file plus the lookup tables passes need."""

    def __init__(self, path, relpath, modname, text):
        self.path = path
        self.relpath = relpath
        self.modname = modname          # dotted, e.g. "mxnet_tpu.io"
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=relpath)
        self._comments = None
        # local alias -> dotted module ("_np" -> "numpy")
        self.import_aliases = {}
        # local name -> (dotted module, attr) ("select_tree" ->
        # ("mxnet_tpu.resilience", "select_tree"))
        self.from_imports = {}
        self.top_funcs = {}             # name -> FunctionDef (module level)
        self.classes = {}               # name -> ClassDef (module level)
        self._collect_imports()
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.top_funcs[node.name] = node
            elif isinstance(node, ast.ClassDef):
                self.classes[node.name] = node

    # ---------------------------------------------------------- imports
    def _package_parts(self):
        if not self.modname:
            return []
        return self.modname.split(".")[:-1]

    def _resolve_relative(self, level, module):
        base = self._package_parts()
        if level > len(base) + 1:
            return None
        if level:
            base = base[:len(base) - (level - 1)]
        if module:
            base = base + module.split(".")
        return ".".join(base) if base else None

    def _collect_imports(self):
        # Collect from the WHOLE tree, not just module top level:
        # hot-path modules import lazily inside functions ("from .. import
        # resilience as _resilience" inside a step builder) and alias
        # names are consistent per file.
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    target = alias.name if alias.asname else \
                        alias.name.split(".")[0]
                    self.import_aliases.setdefault(local, target)
            elif isinstance(node, ast.ImportFrom):
                mod = node.module
                if node.level:
                    mod = self._resolve_relative(node.level, node.module)
                if mod is None:
                    continue
                for alias in node.names:
                    local = alias.asname or alias.name
                    if alias.name == "*":
                        continue
                    # "from x import y" can bind a module or an attr;
                    # record both interpretations, passes disambiguate
                    # via Repo.by_modname.
                    self.from_imports.setdefault(
                        local, (mod, alias.name))
                    self.import_aliases.setdefault(
                        local, mod + "." + alias.name)

    def resolve_alias(self, name):
        """Local name -> dotted module path it refers to, or None."""
        return self.import_aliases.get(name)

    # ------------------------------------------------------ annotations
    @property
    def comments(self):
        """Lazy: tokenizing is the slow part of parsing and only files
        carrying mxlint/guarded-by annotations need their comments."""
        if self._comments is None:
            if "guarded-by" in self.text or "mxlint" in self.text:
                self._comments = _comment_map(self.text)
            else:
                self._comments = {}
        return self._comments

    def comment_on(self, lineno):
        return self.comments.get(lineno, "")

    def guard_decl(self, lineno):
        """(lock, mode) from a ``# guarded-by:`` comment on this line."""
        m = GUARD_RE.search(self.comments.get(lineno, ""))
        if not m:
            return None
        return m.group("lock"), (m.group("mode") or "all")

    def holds_decl(self, node):
        """Lock named by ``# mxlint: holds(...)`` on a def line."""
        m = HOLDS_RE.search(self.comments.get(node.lineno, ""))
        return m.group("lock") if m else None

    def disabled_rules(self, lineno):
        m = DISABLE_RE.search(self.comments.get(lineno, ""))
        if not m:
            return ()
        return tuple(r.strip() for r in m.group("rules").split(",") if r)


class Repo(object):
    """The parsed framework tree: every module, plus cross-module
    function resolution through import aliases."""

    def __init__(self, root, targets=DEFAULT_TARGETS):
        self.root = os.path.abspath(root)
        self.modules = []
        self.by_relpath = {}
        self.by_modname = {}
        self.parse_errors = []          # (relpath, message)
        for target in targets:
            full = os.path.join(self.root, target)
            if os.path.isfile(full):
                self._add_file(full)
            elif os.path.isdir(full):
                for dirpath, dirnames, filenames in os.walk(full):
                    dirnames[:] = sorted(
                        d for d in dirnames
                        if d != "__pycache__" and not d.startswith("."))
                    for fn in sorted(filenames):
                        if fn.endswith(".py"):
                            self._add_file(os.path.join(dirpath, fn))

    def _modname_for(self, relpath):
        if not relpath.endswith(".py"):
            return None
        parts = relpath[:-3].split(os.sep)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        if not parts:
            return None
        return ".".join(parts)

    def _add_file(self, path):
        relpath = os.path.relpath(path, self.root)
        try:
            with open(path, "r") as f:
                text = f.read()
            mod = SourceModule(path, relpath, self._modname_for(relpath),
                              text)
        except (SyntaxError, UnicodeDecodeError, OSError) as e:
            self.parse_errors.append((relpath, str(e)))
            return
        self.modules.append(mod)
        self.by_relpath[relpath] = mod
        if mod.modname:
            self.by_modname[mod.modname] = mod

    def module_for(self, dotted):
        """Dotted module path -> SourceModule (tries pkg/__init__ too)."""
        return self.by_modname.get(dotted)

    def resolve_function(self, module, name):
        """Resolve a dotted callee *from module's namespace* to
        (owner_module, FunctionDef), or None.

        Handles "f" (module-level or from-import), "_mod.f" (aliased
        module attr), and "pkg.mod.f".  Methods/dynamic dispatch stay
        unresolved by design — passes treat those as opaque.
        """
        parts = name.split(".")
        if len(parts) == 1:
            local = parts[0]
            if local in module.top_funcs:
                return module, module.top_funcs[local]
            if local in module.from_imports:
                src, attr = module.from_imports[local]
                owner = self.module_for(src)
                if owner and attr in owner.top_funcs:
                    return owner, owner.top_funcs[attr]
            return None
        base, attr = ".".join(parts[:-1]), parts[-1]
        target = module.resolve_alias(parts[0])
        if target and len(parts) > 2:
            target = ".".join([target] + parts[1:-1])
        for cand in (target, base):
            owner = self.module_for(cand) if cand else None
            if owner and attr in owner.top_funcs:
                return owner, owner.top_funcs[attr]
        return None


class Baseline(object):
    """tools/mxlint_baseline.json: suppressions with justifications.

    Applying a baseline marks matching findings suppressed and returns
    synthetic ``baseline.expired`` findings for entries that matched
    nothing — an expired entry fails the lint just like a real finding,
    so the file stays an honest ledger.

    An entry may carry ``expires: "YYYY-MM"``: past that month the
    entry stops suppressing (its findings surface again) and a
    ``baseline.date-expired`` finding names the overdue entry — the
    burn-down analog of a TODO with a deadline (the step-seam ledger
    uses this, docs/ANALYSIS.md).  ``write()`` regenerates the file
    from a finding set, carrying forward reasons/expiry dates for keys
    that survive so ``mxlint --baseline-write`` beats hand-editing
    JSON."""

    def __init__(self, entries=None, path=None):
        self.path = path
        self.entries = list(entries or [])

    @classmethod
    def load(cls, path):
        if not os.path.exists(path):
            return cls([], path=path)
        with open(path, "r") as f:
            data = json.load(f)
        return cls(data.get("suppressions", []), path=path)

    def _relpath(self):
        return os.path.relpath(self.path, start=os.getcwd()) \
            if self.path else "mxlint_baseline.json"

    def apply(self, findings, today=None):
        if today is None:
            today = time.strftime("%Y-%m")
        by_key = {}
        for f in findings:
            by_key.setdefault(f.key, []).append(f)
        expired = []
        for entry in self.entries:
            eid = entry.get("id", "")
            matched = by_key.get(eid, [])
            if not matched:
                expired.append(Finding(
                    "baseline", "expired", self._relpath(), 0, "", eid,
                    "baseline entry %r no longer matches any finding — "
                    "delete it" % eid))
                continue
            expiry = entry.get("expires")
            if expiry and today > expiry:
                # overdue: the matched findings stay ACTIVE, and the
                # stale suppression is called out by name
                expired.append(Finding(
                    "baseline", "date-expired", self._relpath(), 0, "",
                    eid,
                    "baseline suppression %r expired %s — fix the "
                    "finding or renew the entry (--baseline-write keeps "
                    "the reason, the expiry must be re-justified)"
                    % (eid, expiry)))
                continue
            for f in matched:
                f.suppressed = True
                f.reason = entry.get("reason", "")
        return expired

    _COMMENT = (
        "mxlint suppression ledger (docs/ANALYSIS.md). Every entry "
        "carries a one-line justification; entries that stop matching "
        "a live finding are reported as baseline.expired and FAIL the "
        "lint, so this file can only shrink or stay honest. Optional "
        "'expires: YYYY-MM' turns an entry into a burn-down deadline.")

    def write(self, path, findings):
        """Regenerate the ledger from active findings, keeping each
        surviving key's reason and expiry.  Returns the entries."""
        prev = {e.get("id"): e for e in self.entries}
        entries = []
        for key in sorted({f.key for f in findings}):
            entry = {"id": key}
            old = prev.get(key, {})
            entry["reason"] = old.get(
                "reason", "FIXME: justify this suppression")
            if "expires" in old:
                entry["expires"] = old["expires"]
            entries.append(entry)
        with open(path, "w") as f:
            json.dump({"_comment": self._COMMENT,
                       "suppressions": entries}, f, indent=2)
            f.write("\n")
        return entries
