"""``mx.perf.autotune`` — measured config search over the kernel tier.

Reference analog: MXNET_CUDNN_AUTOTUNE_DEFAULT — the reference framework
measures cuDNN conv algorithms per shape at bind time and caches the
winner for the process.  TPU-native redesign: the discrete config space
of the Pallas kernel tier (flash-attention ``block_q`` divisors, the
fused optimizer+cast epilogue on/off, ``runtime.stack_mode`` ×
``runtime.remat``, conv layouts) is enumerated per *program site*,
each candidate is measured through the same jit machinery the real
program uses (wall time over warmed dispatches), and the winner is
persisted so later processes apply it at trace time with ZERO
re-measurement.

Cache key contract (mirrors the compile-cache discipline that the
``compile_cache`` lint pass enforces):

* the persisted key carries the program family + site signature, the
  device kind, the dominant dtype AND a fingerprint of the knob VALUES
  the kernels lower against (``kernels.vmem_budget``) — the in-process
  ``config.epoch()`` counter resets across processes, so values, not
  the counter, make the key stable on disk;
* in-process, applied picks are memoized per ``config.epoch()`` — any
  knob change clears the memo so the next trace re-consults the cache
  under the new fingerprint;
* every *recorded* winner bumps ``generation()``, which the program
  caches (SPMDTrainer, module fused_step_fn, gluon _CachedGraph) fold
  into their keys, so a winner that lands mid-process retraces the
  affected programs exactly once.

Default-on graduation gate (``kernels.enabled`` default since round
16): while the knob sits at its *default*, a routed site only takes the
Pallas kernel after the search proves bitwise-or-tolerance parity plus
a measured speedup >= 1.0x; losing sites fall back permanently to the
XLA lowering.  "Lost" means slower or not bit-close: a candidate that
fails to lower or compile is an ERROR that propagates out of the search
and is never recorded, in memory or on disk — a broken kernel must not
pass for a slow one.  On
interpreted backends (CPU/GPU) a kernel can never beat the compiled XLA
lowering, so ``'auto'`` mode routes default-knob programs to XLA
statically — no measurement, programs byte-identical to the pre-tier
lowering.  An *explicit* ``kernels.enabled`` (env or ``set()``) bypasses
the gate entirely: on means kernels wherever feasible (with tuned block
sizes when a winner is cached), off means the pre-tier program.

Telemetry: ``autotune.search`` (searches run), ``autotune.measure``
(candidate measurements), ``autotune.cache_hit`` / ``cache_miss`` /
``cache_invalid`` (corrupt or wrong-schema cache file ignored), and
``autotune.applied`` (cached picks applied at trace time).  The
zero-re-measurement reload contract is asserted in CI as
``cache_hit > 0 and measure == 0`` in a fresh process
(tools/check_autotune.py, tests/test_autotune.py).
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time

from . import config as _config
from . import telemetry as _telemetry

__all__ = ["enabled", "mode", "cache_path", "config_fingerprint",
           "generation", "reset", "lookup", "record", "attention_pick",
           "fused_step_pick", "stack_pick",
           "search_attention", "search_fused",
           "search_step", "search_stack",
           "export_entries", "CACHE_VERSION"]

CACHE_VERSION = 1

_MISS = object()  # negative-lookup memo sentinel

_LOCK = threading.RLock()
_ENTRIES = [None]   # guarded-by: _LOCK — loaded disk entries (or None)
_LOADED_PATH = [None]  # guarded-by: _LOCK — path _ENTRIES came from
_PICKS = {}         # guarded-by: _LOCK — key -> applied pick | _MISS
_PICK_EPOCH = [None]  # guarded-by: _LOCK — config epoch _PICKS is valid for
_GENERATION = [0]   # guarded-by[writes]: _LOCK — bumped per recorded winner
_WARNED = set()     # guarded-by: _LOCK — one-shot warning dedup


# ------------------------------------------------------------ knob surface
def mode():
    """The validated ``perf.autotune`` mode: 'off' | 'auto' | 'measure'."""
    return (_config.get("perf.autotune") or "").strip().lower() or "auto"


def enabled():
    return mode() != "off"


def cache_path():
    """Resolved tuning-cache file: the ``perf.autotune_cache`` knob, or
    ``autotune.json`` in the checkout's cache directory (beside the XLA
    compile cache) — no state from outside the tree decides a route."""
    p = _config.get("perf.autotune_cache")
    if p:
        return os.path.expanduser(p)
    from . import runtime as _runtime
    return os.path.join(_runtime.cache_root(), "autotune.json")


def config_fingerprint():
    """Knob VALUES that change what the kernels lower to, rendered into
    the persisted key.  kernels.vmem_budget sizes every ``_row_block``
    pick, so a budget change can never reload winners measured under a
    different VMEM window (the round-16 invalidation bugfix)."""
    return "vmem=%d" % int(_config.get("kernels.vmem_budget"))


def generation():
    """Monotonic count of winners recorded (or state resets) in this
    process — program-cache keys fold it in so fresh winners retrace."""
    return _GENERATION[0]


def reset():
    """Forget in-memory picks and the loaded cache (tests/tools: the
    next lookup reloads from disk exactly like a fresh process).  The
    disk file is untouched."""
    with _LOCK:
        _ENTRIES[0] = None
        _LOADED_PATH[0] = None
        _PICKS.clear()
        _PICK_EPOCH[0] = None
        _WARNED.clear()
        _GENERATION[0] += 1


# ----------------------------------------------------------- cache backend
def _warn_once(tag, msg):
    with _LOCK:
        if tag in _WARNED:
            return
        _WARNED.add(tag)
    import warnings
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _load_entries():
    """Disk entries for the current cache path (memoized).  A corrupt,
    unreadable or wrong-schema file counts ``autotune.cache_invalid``
    and behaves exactly like an empty cache — defaults, no error."""
    with _LOCK:
        path = cache_path()
        if _ENTRIES[0] is not None and _LOADED_PATH[0] == path:
            return _ENTRIES[0]
        entries = {}
        try:
            with open(path) as f:
                raw = json.load(f)
            if (not isinstance(raw, dict)
                    or raw.get("version") != CACHE_VERSION
                    or not isinstance(raw.get("entries"), dict)):
                raise ValueError("unrecognized tuning-cache schema")
            entries = {k: v for k, v in raw["entries"].items()
                       if isinstance(k, str) and isinstance(v, dict)}
        except FileNotFoundError:
            pass
        except Exception as exc:  # noqa: BLE001 — any corruption ->
            # defaults; tuning is an optimization, never a crash
            _telemetry.counter("autotune.cache_invalid").inc()
            _warn_once("load:%s" % path,
                       "ignoring corrupt autotune cache %s (%s); "
                       "falling back to defaults" % (path, exc))
            entries = {}
        _ENTRIES[0] = entries
        _LOADED_PATH[0] = path
        return entries


def _write_entries(entries):
    """Atomic write-through (tmp + rename); an unwritable location is a
    warning, not an error — the in-memory winner still applies."""
    path = cache_path()
    try:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": entries},
                      f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as exc:
        _warn_once("write:%s" % path,
                   "cannot persist autotune cache to %s (%s); winners "
                   "apply in-process only" % (path, exc))


def _key(family, site, dtype):
    from . import perf as _perf
    return "|".join((family, site, _perf.device_kind(), str(dtype),
                     config_fingerprint()))


def _check_epoch_locked():  # mxlint: holds(_LOCK)
    ep = _config.epoch()
    if _PICK_EPOCH[0] != ep:
        # a knob changed: shapes of the feasible space (vmem budget,
        # stack knobs, the tier switch itself) may have moved — drop the
        # memo and re-consult the cache under the new fingerprint
        _PICKS.clear()
        _PICK_EPOCH[0] = ep


def lookup(family, site, dtype):
    """The cached winner for a site, or None.  Hits are memoized per
    config epoch and counted ``autotune.cache_hit`` + ``applied`` once;
    misses memoize a negative so repeated traces don't re-stat disk."""
    with _LOCK:
        _check_epoch_locked()
        key = _key(family, site, dtype)
        pick = _PICKS.get(key)
        if pick is _MISS:
            return None
        if pick is not None:
            return pick
        entry = _load_entries().get(key)
        if entry is not None:
            _telemetry.counter("autotune.cache_hit").inc()
            _telemetry.counter("autotune.applied").inc()
            _PICKS[key] = entry
            return entry
        _telemetry.counter("autotune.cache_miss").inc()
        _PICKS[key] = _MISS
        return None


def record(family, site, dtype, entry):
    """Persist one searched winner (write-through) and apply it to this
    process: the pick memo updates and ``generation()`` bumps so program
    caches that baked earlier picks in retrace."""
    with _LOCK:
        _check_epoch_locked()
        key = _key(family, site, dtype)
        entries = dict(_load_entries())
        entries[key] = entry
        _ENTRIES[0] = entries
        _write_entries(entries)
        _PICKS[key] = entry
        _GENERATION[0] += 1
        _telemetry.counter("autotune.search").inc()
    return entry


def _remember(family, site, dtype, pick):
    """Memoize a statically-derived pick in-process only (never written
    to disk — it is rederivable from the platform in O(1))."""
    with _LOCK:
        _check_epoch_locked()
        _PICKS[_key(family, site, dtype)] = pick
    return pick


def export_entries():
    """The autotune state as one JSON-serializable dict — the
    tuned-vs-default evidence tools/perf_report.py renders."""
    with _LOCK:
        applied = {k: v for k, v in _PICKS.items() if v is not _MISS}
        return {
            "generation": _GENERATION[0],
            "mode": mode(),
            "path": cache_path(),
            "entries": dict(_load_entries()),
            "applied": applied,
        }


# ------------------------------------------------------------ measurement
def _interpreted():
    from .rtc import interpret_mode
    return interpret_mode()


def _synth(shape, dtype):
    """Deterministic, well-conditioned synthetic operand (measurement
    must not depend on live training data, which may be tracers)."""
    import numpy as np
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


def _measure_ms(fn, args, repeats=3):
    """Median wall-clock ms of one warmed jitted dispatch of
    ``fn(*args)``; counts one ``autotune.measure``.  The first call
    compiles (excluded from timing, like PerfProgram's capture)."""
    import jax
    jitted = jax.jit(fn)
    out = jitted(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = jitted(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3)
    _telemetry.counter("autotune.measure").inc()
    times.sort()
    return times[len(times) // 2]


def _parity(got, ref, dtype):
    """Bitwise-or-tolerance parity verdict over output trees:
    'bitwise' | 'tolerance' | None (failed).  Tolerances mirror the
    tools/check_kernels.py gates (f32 float-ulps, bf16 a few ulps)."""
    import jax
    import numpy as np
    g_leaves = jax.tree_util.tree_leaves(got)
    r_leaves = jax.tree_util.tree_leaves(ref)
    if len(g_leaves) != len(r_leaves):
        return None
    tol = 3e-2 if "16" in str(dtype) else 2e-5
    verdict = "bitwise"
    for g, r in zip(g_leaves, r_leaves):
        ga = np.asarray(g, np.float32)
        ra = np.asarray(r, np.float32)
        if ga.shape != ra.shape:
            return None
        if np.array_equal(ga, ra):
            continue
        if np.allclose(ga, ra, rtol=tol, atol=tol):
            verdict = "tolerance"
            continue
        return None
    return verdict


def _eager(search):
    """Run a search on the device NOW, even when the trace-time pick that
    missed the cache sits inside a jit or export trace.  There every jnp
    op of the search would be staged into the outer program and the
    search would time — and try to read back — tracers.  jax's trace
    state is thread-local, so a fresh thread starts outside any trace;
    the caller waits for it, and an exception in the search is raised in
    the caller."""
    @functools.wraps(search)
    def run(*args, **kwargs):
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="mx-autotune") as pool:
            return pool.submit(search, *args, **kwargs).result()
    return run


# --------------------------------------------------- attention site search
def _attention_candidates(S, Skv):
    """Deduplicated effective block_q candidates for a length-S query:
    each base divides down through the _row_block divisor walk, so two
    bases that snap to the same divisor measure once.  Bases whose f32
    [block_q, Skv] score block would not fit the VMEM budget are left
    out — every candidate the search compiles has to be one the chip can
    hold, because a compile failure is an error, not a verdict."""
    from .ops.pallas_kernels import _row_block
    fit = max(1, int(_config.get("kernels.vmem_budget")) // (4 * Skv))
    bases = [b for b in (64, 128, 256, 512, S) if b <= max(fit, 64)]
    return sorted({_row_block(S, 1, budget=min(b, S)) for b in bases})


@_eager
def search_attention(q_shape, kv_shape, dtype, causal, scale=None):
    """Measure the flash kernel over its block_q candidates against the
    XLA attention lowering at one site signature; persist and return the
    winner.  Gate: parity (bitwise-or-tolerance) AND speedup >= 1.0x —
    a site that loses either falls back to XLA permanently.  A candidate
    that fails to lower or compile raises out of the search: nothing is
    recorded, so the next run meets the same error, not a stale verdict."""
    from .parallel.ring_attention import attention as _xla_attention
    B, H, Sq, D = q_shape
    site = _attention_site(q_shape, kv_shape, causal)
    q = _synth(q_shape, dtype)
    k = _synth(kv_shape, dtype)
    v = _synth(kv_shape, dtype)

    def xla_fn(q, k, v):
        return _xla_attention(q, k, v, causal=causal, scale=scale)

    entry = {"impl": "xla", "site": site, "causal": bool(causal)}
    base_ms = _measure_ms(xla_fn, (q, k, v))
    import jax
    jit_ref = jax.jit(xla_fn)  # parity reference: jit-vs-jit only
    ref = jit_ref(q, k, v)
    cands = {}
    best_bq, best_ms, best_parity = None, None, None
    from .ops.pallas_kernels import flash_attention
    for bq in _attention_candidates(Sq, kv_shape[2]):
        # bind block_q eagerly (a partial, not a default-arg
        # closure): the block size is a trace-time static
        flash_fn = functools.partial(flash_attention, causal=causal,
                                     scale=scale, block_q=bq)
        ms = _measure_ms(flash_fn, (q, k, v))
        jit_cand = jax.jit(flash_fn)
        par = _parity(jit_cand(q, k, v), ref, dtype)
        cands["flash/bq=%d" % bq] = round(ms, 4)
        if par is None:
            continue
        if best_ms is None or ms < best_ms:
            best_bq, best_ms, best_parity = bq, ms, par
    entry.update(baseline_ms=round(base_ms, 4), candidates=cands)
    if best_bq is not None:
        entry.update(block_q=best_bq, best_ms=round(best_ms, 4),
                     parity=best_parity,
                     speedup=round(base_ms / best_ms, 4))
        if best_ms <= base_ms:
            entry["impl"] = "flash"
        else:
            entry["reason"] = "slower than XLA lowering"
    else:
        entry["reason"] = "no candidate passed parity"
    return record("attention", site, dtype, entry)


def _attention_site(q_shape, kv_shape, causal):
    B, H, Sq, D = q_shape
    return "attn/b%d/h%d/q%d/kv%d/d%d/causal=%d" % (
        B, H, Sq, kv_shape[2], D, int(causal))


def attention_pick(q_shape, kv_shape, dtype, causal, scale=None):
    """Trace-time pick for one routed attention site (consumed by
    ``mx.kernels.attention``).  None = no autotune opinion, legacy
    routing (flash wherever feasible).  Takes shapes + dtype string,
    never arrays — the pick is a static host fact, so routing stays
    trace-time python with no value ever read back."""
    if not enabled():
        return None
    explicit = _config.source("kernels.enabled") != "default"
    site = _attention_site(tuple(q_shape), tuple(kv_shape), causal)
    dtype = str(dtype)
    pick = lookup("attention", site, dtype)
    if pick is None:
        if mode() == "auto" and _interpreted():
            if explicit:
                # forced-on without a measured winner: legacy flash
                return None
            # a Pallas kernel in the interpreter can never beat the
            # compiled XLA lowering — statically route default-knob
            # programs to XLA, byte-identical to the pre-tier program
            pick = _remember("attention", site, dtype,
                             {"impl": "xla", "reason": "interpreted",
                              "static": True})
        else:
            pick = search_attention(tuple(q_shape), tuple(kv_shape),
                                    dtype, causal, scale)
    if explicit and pick.get("impl") != "flash":
        # the operator's explicit on overrides the gate; tuned block_q
        # still applies when the search measured one
        return {"impl": "flash", "block_q": int(pick.get("block_q")
                                                or 128)}
    return pick


# -------------------------------------------------- fused-epilogue search
_FUSED_SHAPE = (256, 128)  # representative master block for the epilogue


def _fused_kind(optimizer):
    name = type(optimizer).__name__.lower()
    if name == "sgd":
        return "sgd/mom" if getattr(optimizer, "momentum", 0.0) else "sgd"
    if name == "adam":
        return "adam"
    return None


@_eager
def search_fused(optimizer):
    """Measure the optimizer's fused Pallas update+cast epilogue against
    its own ``step()`` + astype (the exact pair the trainers route
    between) on a representative f32 master block; persist the verdict."""
    import jax
    import jax.numpy as jnp
    kind = _fused_kind(optimizer)
    site = "fused/%s" % kind
    w = _synth(_FUSED_SHAPE, jnp.float32)
    g = _synth(_FUSED_SHAPE, jnp.float32)
    if kind == "adam":
        state = (jnp.zeros_like(w), jnp.zeros_like(w))
    elif kind == "sgd/mom":
        state = jnp.zeros_like(w)
    else:
        state = None
    lr, wd, t = 0.1, 0.01, 1

    def fused_fn(w, g):
        return optimizer.step_fused(w, g, state, lr, wd, t,
                                    out_dtype=jnp.bfloat16)

    def xla_fn(w, g):
        nw, ns = optimizer.step(w, g, state, lr, wd, t)
        return nw.astype(jnp.bfloat16), nw, ns

    entry = {"impl": "xla", "site": site}
    base_ms = _measure_ms(xla_fn, (w, g))
    fused_ms = _measure_ms(fused_fn, (w, g))
    jit_fused, jit_base = jax.jit(fused_fn), jax.jit(xla_fn)
    par = _parity(jit_fused(w, g), jit_base(w, g), "float32")
    entry.update(baseline_ms=round(base_ms, 4),
                 best_ms=round(fused_ms, 4),
                 speedup=round(base_ms / fused_ms, 4))
    if par is not None:
        entry["parity"] = par
        if fused_ms <= base_ms:
            entry["impl"] = "fused"
        else:
            entry["reason"] = "slower than XLA lowering"
    else:
        entry["reason"] = "parity failed"
    return record("fused_step", site, "float32", entry)


def fused_step_pick(optimizer):
    """Trace-time verdict for the fused optimizer epilogue (consumed by
    ``mx.kernels.fused_step_enabled``).  None = no autotune opinion
    (legacy: fuse whenever the optimizer can)."""
    if not enabled():
        return None
    kind = _fused_kind(optimizer)
    if kind is None:
        # no synthesizable search for this optimizer — legacy routing
        return None
    explicit = _config.source("kernels.enabled") != "default"
    site = "fused/%s" % kind
    pick = lookup("fused_step", site, "float32")
    if pick is None:
        if mode() == "auto" and _interpreted():
            if explicit:
                return None
            pick = _remember("fused_step", site, "float32",
                             {"impl": "xla", "reason": "interpreted",
                              "static": True})
        else:
            pick = search_fused(optimizer)
    if explicit and pick.get("impl") != "fused":
        return None  # explicit on: legacy fused routing wins the gate
    return pick


# ------------------------------------------------- knob-space step search
def search_step(site, make_fn, args, space, family="step", dtype="-"):
    """Generic measured search over knob assignments for one step
    program: for each candidate dict {knob: value}, apply, build via
    ``make_fn()``, measure, then restore every knob to the exact
    override/env/default state it started in.  Persists the winner
    with its knob dict so it can be re-applied wholesale."""
    knobs = sorted({k for cand in space for k in cand})
    saved = {k: (_config.source(k), _config.get(k)) for k in knobs}
    results = {}
    best_label, best_ms, best_knobs = None, None, None
    try:
        for cand in space:
            for k in knobs:
                _config.set(k, cand.get(k, saved[k][1]))
            label = "/".join("%s=%s" % (k.split(".")[-1], cand[k])
                             for k in sorted(cand))
            fn = make_fn()
            ms = _measure_ms(fn, args)
            results[label] = round(ms, 4)
            if best_ms is None or ms < best_ms:
                best_label, best_ms, best_knobs = label, ms, dict(cand)
    finally:
        for name, (src, val) in saved.items():
            if src == "override":
                _config.set(name, val)
            else:
                _config.unset(name)
    entry = {"impl": best_label, "knobs": best_knobs,
             "best_ms": round(best_ms, 4), "candidates": results,
             "site": site}
    return record(family, site, dtype, entry)


def search_stack(make_fn, args, site="default", dtype="-"):
    """Measured ``runtime.stack_mode`` × ``runtime.remat`` sweep for one
    step program; the winner is applied transparently by
    ``runtime.stack_tuning`` while both knobs sit at their defaults."""
    from . import runtime as _runtime
    space = [{"runtime.stack_mode": m, "runtime.remat": r}
             for m, r in _runtime.stack_candidates()]
    return search_step(site, make_fn, args, space, family="stack",
                       dtype=dtype)


def stack_pick():
    """The persisted (mode, remat) winner for the layer stack, or None.
    Only consulted while BOTH runtime knobs are untouched defaults —
    an explicit knob always wins over a tuned pick."""
    if not enabled():
        return None
    if (_config.source("runtime.stack_mode") != "default"
            or _config.source("runtime.remat") != "default"):
        return None
    pick = lookup("stack", "default", "-")
    if not pick:
        return None
    knobs = pick.get("knobs") or {}
    m = knobs.get("runtime.stack_mode")
    r = knobs.get("runtime.remat")
    if m not in ("scan", "unroll") or r not in ("", "dots", "full"):
        return None
    return m, r
