"""sharding-consistency pass (pass id: ``shard``).

Cross-checks every ``shard_map`` / ``NamedSharding`` / ``PartitionSpec``
/ collective site in the tree against the mesh-axis registry built from
the tree's own mesh construction sites (``AXES`` tuples, ``make_mesh``
dict literals, ``Mesh(devices, (...))`` name tuples, ``pmap(axis_name=
...)``).  Four rules:

* ``undeclared-axis``  — a string axis name (in a ``P(...)`` spec, a
  collective's axis argument, or an ``axis``-named keyword default)
  that no mesh construction site declares.  Axis names held in
  variables are opaque and skipped — the registry only judges
  literals, so the rule cannot false-positive on parameterized
  helpers.
* ``spec-arity``       — ``in_specs`` tuple length vs the wrapped
  function's signature at a ``shard_map`` site (or a site of an
  in-repo wrapper such as ``parallel.pipeline.shmap``), unwrapping
  ``functools.partial`` and counting bound positionals/keywords.
* ``unbound-axis``     — a collective inside the wrapped body names a
  literal axis that no literal ``in_specs`` entry binds.  Only checked
  when every spec term at the site is a literal; one variable term
  makes the site opaque.
* ``replicated-embedding`` — a param-spec dict literal maps an
  ``*embed*`` key to ``P()`` full replication.  Embedding tables are
  the largest parameters in the tree; replicating one is either an
  explicit decision (justify in the baseline, pointing at
  ``parallel.embedding.ShardedEmbedding`` as the sharded path) or a
  bug.

The registry is repo-wide: declaring an axis anywhere (mesh.py's
``AXES`` is the canonical site — see docs/ANALYSIS.md) legalizes it
everywhere.  When no construction site exists at all the
``undeclared-axis`` rule stands down rather than flag every literal.
"""
from __future__ import annotations

import ast

from .jit_purity import _base_module, _collect_scopes
from .walker import Finding, dotted_name

PASS_ID = "shard"

#: jax collective -> positional index of its axis-name argument.
_COLLECTIVES = {"psum": 1, "pmean": 1, "pmax": 1, "pmin": 1,
                "all_gather": 1, "all_to_all": 1, "ppermute": 1,
                "psum_scatter": 1, "axis_index": 0}

_PREFILTER = ("shard_map", "PartitionSpec", "NamedSharding", "psum",
              "pmean", "all_gather", "all_to_all", "ppermute",
              "axis_index", "pmap(")


def _is_jax_name(module, d, attr_names, jax_prefix="jax"):
    """Dotted callee ``d`` whose final attr is in ``attr_names`` and
    whose base resolves into jax (directly or via a from-import)."""
    last = d.split(".")[-1]
    if last not in attr_names and d not in attr_names:
        # bare from-import under an alias: `shard_map as _raw`
        src = module.from_imports.get(d) if "." not in d else None
        return bool(src and src[1] in attr_names
                    and src[0].split(".")[0] == jax_prefix)
    if "." not in d:
        src = module.from_imports.get(d)
        return bool(src and src[0].split(".")[0] == jax_prefix)
    return _base_module(module, d).split(".")[0] == jax_prefix


def _is_shardmap_callee(module, func_node):
    d = dotted_name(func_node)
    if not d:
        return False
    return _is_jax_name(module, d, ("shard_map",))


def _is_pspec_callee(module, func_node):
    d = dotted_name(func_node)
    if not d:
        return False
    last = d.split(".")[-1]
    if "." not in d:
        src = module.from_imports.get(d)
        return bool(src and src[1] == "PartitionSpec"
                    and src[0].split(".")[0] == "jax")
    return last == "PartitionSpec" and \
        _base_module(module, d).split(".")[0] == "jax"


def _is_collective(module, call):
    """(axis_expr, name) for a jax collective call, else None."""
    d = dotted_name(call.func)
    if not d:
        return None
    last = d.split(".")[-1]
    if last not in _COLLECTIVES:
        return None
    if not _is_jax_name(module, d, (last,)):
        return None
    idx = _COLLECTIVES[last]
    for kw in call.keywords:
        if kw.arg == "axis_name":
            return kw.value, last
    if len(call.args) > idx:
        return call.args[idx], last
    return None


# --------------------------------------------------------------- registry
def axis_registry(repo):
    """Every axis name declared by a mesh construction site."""
    declared = set()
    for module in repo.modules:
        if not any(tok in module.text
                   for tok in ("AXES", "make_mesh", "Mesh", "pmap")):
            continue
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "AXES" and \
                            isinstance(node.value, (ast.Tuple, ast.List)):
                        for e in node.value.elts:
                            if isinstance(e, ast.Constant) and \
                                    isinstance(e.value, str):
                                declared.add(e.value)
                continue
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            last = d.split(".")[-1] if d else ""
            if last == "make_mesh":
                for a in list(node.args) + \
                        [kw.value for kw in node.keywords]:
                    if isinstance(a, ast.Dict):
                        for k in a.keys:
                            if isinstance(k, ast.Constant) and \
                                    isinstance(k.value, str):
                                declared.add(k.value)
            elif last == "Mesh":
                names = None
                if len(node.args) > 1:
                    names = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "axis_names":
                        names = kw.value
                if isinstance(names, (ast.Tuple, ast.List)):
                    for e in names.elts:
                        if isinstance(e, ast.Constant) and \
                                isinstance(e.value, str):
                            declared.add(e.value)
                elif isinstance(names, ast.Constant) and \
                        isinstance(names.value, str):
                    declared.add(names.value)
            elif last == "pmap":
                for kw in node.keywords:
                    if kw.arg == "axis_name" and \
                            isinstance(kw.value, ast.Constant) and \
                            isinstance(kw.value.value, str):
                        declared.add(kw.value.value)
    return declared


# ------------------------------------------------------------- spec terms
class _SpecTerms(object):
    """Literal axis names + opacity across every spec expression."""

    def __init__(self):
        self.literals = set()
        self.opaque = False

    def add_term(self, node):
        """One argument inside a P(...) call."""
        if isinstance(node, ast.Constant):
            if isinstance(node.value, str):
                self.literals.add(node.value)
            elif node.value is not None:
                self.opaque = True
        elif isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                self.add_term(e)
        else:
            self.opaque = True

    def add_spec(self, module, node):
        """A whole spec expression: P(...), a tuple of them, or opaque."""
        if isinstance(node, ast.Call) and \
                _is_pspec_callee(module, node.func):
            for a in node.args:
                self.add_term(a)
            if node.keywords:
                self.opaque = True
        elif isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                self.add_spec(module, e)
        elif isinstance(node, ast.Constant) and node.value is None:
            pass
        else:
            self.opaque = True


# ------------------------------------------------------ wrapped-fn lookup
def _unwrap_partial(expr):
    """Peel functools.partial layers: (inner, bound_pos, bound_kw)."""
    bound_pos, bound_kw = 0, set()
    while isinstance(expr, ast.Call):
        d = dotted_name(expr.func)
        if not (d and d.split(".")[-1] == "partial" and expr.args):
            break
        bound_pos += len(expr.args) - 1
        bound_kw |= {kw.arg for kw in expr.keywords if kw.arg}
        expr = expr.args[0]
    return expr, bound_pos, bound_kw


def _resolve_fn(repo, module, scopes, parents, site, expr):
    """A shard_map'd function expression -> (FunctionDef|Lambda, name)."""
    if isinstance(expr, ast.Lambda):
        return expr, "<lambda>"
    if isinstance(expr, ast.Name):
        # nearest PRECEDING def with that name in the enclosing scope:
        # one builder commonly defines several local `_shard` variants
        # (branch-dependent signatures), and the scope table keeps only
        # one per name.
        anc = parents.get(site)
        while anc is not None and not isinstance(
                anc, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
            anc = parents.get(anc)
        fn = None
        if anc is not None:
            for n in ast.walk(anc):
                if isinstance(n, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)) and \
                        n.name == expr.id and n.lineno <= site.lineno:
                    if fn is None or n.lineno > fn.lineno:
                        fn = n
        if fn is None:
            sc_anc = parents.get(site)
            while sc_anc is not None and sc_anc not in scopes:
                sc_anc = parents.get(sc_anc)
            sc = scopes.get(sc_anc, scopes[module.tree])[0]
            fn = sc.lookup(expr.id) if sc else None
        if fn is None:
            fn = module.top_funcs.get(expr.id)
        if fn is None:
            resolved = repo.resolve_function(module, expr.id)
            if resolved:
                fn = resolved[1]
        return fn, expr.id
    return None, None


def _arity(fn, bound_pos, bound_kw):
    """(required, total) positional slots after partial binding; total
    is None for *args."""
    args = fn.args
    names = [a.arg for a in args.posonlyargs + args.args]
    n_def = len(args.defaults)
    defaulted = set(names[len(names) - n_def:] if n_def else [])
    required = len(names) - n_def - bound_pos
    total = None if args.vararg else len(names) - bound_pos
    for k in bound_kw:
        if k in names:
            if total is not None:
                total -= 1
            if k not in defaulted:
                required -= 1
    return max(required, 0), total


# ------------------------------------------------------------------- pass
class ShardSpec(object):
    def __init__(self, repo):
        self.repo = repo
        self.declared = axis_registry(repo)
        self.findings = []
        self.wrappers = self._wrapper_registry()

    def _wrapper_registry(self):
        """In-repo functions that forward to jax shard_map, mapped to
        the positional slots of (fn, in_specs, out_specs)."""
        wrappers = {}
        for module in self.repo.modules:
            if "shard_map" not in module.text:
                continue
            for name, fn in module.top_funcs.items():
                if not any(isinstance(n, ast.Call) and
                           _is_shardmap_callee(module, n.func)
                           for n in ast.walk(fn)):
                    continue
                params = [a.arg for a in fn.args.args]
                info = {"fn": 0}
                for i, p in enumerate(params):
                    if p in ("in_specs", "in_spec"):
                        info["in"] = i
                    elif p in ("out_specs", "out_spec"):
                        info["out"] = i
                if "in" in info:
                    wrappers[(module.modname, name)] = info
        return wrappers

    def emit(self, module, lineno, rule, symbol, detail, message):
        self.findings.append(Finding(PASS_ID, rule, module.relpath,
                                     lineno, symbol, detail, message))

    # ------------------------------------------------- undeclared literals
    def _check_literal_axes(self, module):
        if not self.declared:
            return
        seen = set()

        def check(node, where):
            names = []
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                names = [node.value]
            elif isinstance(node, (ast.Tuple, ast.List)):
                names = [e.value for e in node.elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str)]
            for name in names:
                if name in self.declared or name in seen:
                    continue
                seen.add(name)
                self.emit(module, node.lineno, "undeclared-axis", where,
                          name,
                          "axis %r is not declared by any mesh "
                          "construction site (mesh.py AXES / make_mesh "
                          "/ Mesh axis_names) — a typo here fails only "
                          "at dispatch time" % name)

        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                if _is_pspec_callee(module, node.func):
                    for a in node.args:
                        check(a, "P")
                else:
                    col = _is_collective(module, node)
                    if col is not None:
                        check(col[0], col[1])
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                names = [a.arg for a in args.posonlyargs + args.args]
                n_def = len(args.defaults)
                for a, dflt in zip(names[len(names) - n_def:],
                                   args.defaults):
                    if "axis" in a:
                        check(dflt, node.name)
                for a, dflt in zip(args.kwonlyargs, args.kw_defaults):
                    if dflt is not None and "axis" in a.arg:
                        check(dflt, node.name)

    # -------------------------------------------------- shard_map sites
    def _site_parts(self, module, call):
        """(fn_expr, in_specs_expr, out_specs_expr) or None."""
        if _is_shardmap_callee(module, call.func):
            slots = {"fn": 0, "in": 2, "out": 3}
            kwnames = {"f": "fn", "in_specs": "in", "out_specs": "out"}
        else:
            d = dotted_name(call.func)
            resolved = d and self.repo.resolve_function(module, d)
            if not resolved:
                return None
            owner, fn = resolved
            info = self.wrappers.get((owner.modname, fn.name))
            if not info:
                return None
            slots = info
            params = [a.arg for a in fn.args.args]
            kwnames = {}
            for key, idx in info.items():
                if idx < len(params):
                    kwnames[params[idx]] = key
        parts = {}
        for key, idx in slots.items():
            if idx < len(call.args):
                parts[key] = call.args[idx]
        for kw in call.keywords:
            if kw.arg in kwnames:
                parts[kwnames[kw.arg]] = kw.value
        if "fn" not in parts:
            return None
        return parts.get("fn"), parts.get("in"), parts.get("out")

    def _check_sites(self, module):
        scopes = self._scopes(module)
        parents = self._parents(module)
        for call in ast.walk(module.tree):
            if not isinstance(call, ast.Call):
                continue
            site = self._site_parts(module, call)
            if site is None:
                continue
            fn_expr, in_expr, out_expr = site
            inner, bound_pos, bound_kw = _unwrap_partial(fn_expr)
            fn, fname = _resolve_fn(self.repo, module, scopes, parents,
                                    call, inner)
            # spec-arity: literal in_specs tuple vs wrapped signature
            if fn is not None and \
                    isinstance(in_expr, (ast.Tuple, ast.List)):
                n = len(in_expr.elts)
                required, total = _arity(fn, bound_pos, bound_kw)
                if n < required or (total is not None and n > total):
                    span = str(required) if total == required else \
                        "%s..%s" % (required, total if total is not None
                                    else "*")
                    self.emit(
                        module, call.lineno, "spec-arity", fname or "",
                        "%d-specs" % n,
                        "in_specs has %d entries but %s takes %s "
                        "positional argument(s) — shard_map fails at "
                        "dispatch with a pytree mismatch"
                        % (n, fname or "<lambda>", span))
            # unbound-axis: only on fully-literal specs
            terms = _SpecTerms()
            if in_expr is not None:
                terms.add_spec(module, in_expr)
            if fn is None or terms.opaque or in_expr is None:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                axes = []
                col = _is_collective(module, node)
                if col is not None:
                    axis_expr = col[0]
                    if isinstance(axis_expr, ast.Constant) and \
                            isinstance(axis_expr.value, str):
                        axes = [axis_expr.value]
                    elif isinstance(axis_expr, (ast.Tuple, ast.List)):
                        axes = [e.value for e in axis_expr.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str)]
                for ax in axes:
                    if ax not in terms.literals:
                        self.emit(
                            module, node.lineno, "unbound-axis",
                            fname or "", ax,
                            "collective over axis %r inside %s, but no "
                            "in_spec at the shard_map site on line %d "
                            "binds %r — the reduction spans an axis no "
                            "input is sharded over"
                            % (ax, fname or "<lambda>", call.lineno, ax))

    # ------------------------------------------- replicated embedding specs
    def _check_embedding_is_replicated(self, module):
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Dict):
                continue
            for k, v in zip(node.keys, node.values):
                if not (isinstance(k, ast.Constant) and
                        isinstance(k.value, str) and "embed" in k.value):
                    continue
                if not (isinstance(v, ast.Call) and
                        _is_pspec_callee(module, v.func)):
                    continue
                if v.keywords or any(
                        not (isinstance(a, ast.Constant) and
                             a.value is None) for a in v.args):
                    continue
                self.emit(
                    module, v.lineno, "replicated-embedding", "",
                    k.value,
                    "parameter %r is fully replicated (%s) — embedding "
                    "tables are usually the largest parameters; shard "
                    "the vocab axis (parallel.embedding.ShardedEmbedding"
                    ") or justify the replication in the baseline"
                    % (k.value, "P()" if not v.args else "P(None, ...)"))

    # ------------------------------------------------------------ plumbing
    def _scopes(self, module):
        if not hasattr(module, "_mxa_scopes"):
            module._mxa_scopes = _collect_scopes(module.tree)
        return module._mxa_scopes

    def _parents(self, module):
        if not hasattr(module, "_mxa_parents"):
            parents = {}
            for node in ast.walk(module.tree):
                for child in ast.iter_child_nodes(node):
                    parents[child] = node
            module._mxa_parents = parents
        return module._mxa_parents

    def run(self):
        for module in self.repo.modules:
            if not any(tok in module.text for tok in _PREFILTER):
                continue
            self._check_literal_axes(module)
            self._check_sites(module)
            self._check_embedding_is_replicated(module)
        return self.findings


def run(repo):
    return ShardSpec(repo).run()
