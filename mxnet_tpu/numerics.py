"""``mx.numerics`` — in-program tensor statistics, nanguard forensics,
and quantization drift monitoring.

Reference: the framework this repo reproduces answered "are the numbers
right?" with ``Monitor`` (python/mxnet/monitor.py), which taps every
intermediate through the executor monitor callback
(src/executor/graph_executor.cc:1410).  That design forces a host sync
per tensor per step — fine for eager executors, fatal for our fused
one-program steps where intermediates never materialize on the host at
all.  This module is the fused-era replacement:

  * **tap registry** — ``tap(site, x)`` inside a traced program records a
    per-site summary vector (:data:`STAT_FIELDS`: amax/amin/rms,
    non-finite count, bf16 overflow/underflow fraction) into the ambient
    :func:`collect` context.  The stats ride OUT of the compiled step as
    an extra side-output pytree; nothing inside the program syncs.
  * **cadence knob** — ``numerics.capture = off | step:N``
    (``MXNET_TPU_NUMERICS``).  Each step seam asks
    :func:`should_capture` once per step and picks the instrumented or
    the plain program variant; the variant is a SEPARATE program-cache
    entry (:func:`capture_token` folds into every cache key), so with
    capture off the lowered program is byte-identical to a build without
    this module and toggling the knob never evicts compiled steps.  The
    knob is registered epoch-NEUTRAL in config.py for the same reason.
  * **zero happy-path host sync** — seams :func:`publish` device stat
    arrays into a bounded pending queue drained by :func:`poll` only
    when ``.is_ready()`` (the ``watch_streak``/``poll_streaks`` pattern
    from mx.resilience).
  * **nanguard forensics** — seams park a replay closure via
    :func:`hold_replay` while the nanguard is armed; when the guard
    finally aborts, :func:`run_forensics` re-runs the held failing batch
    once through the instrumented variant and reports the FIRST
    non-finite site in topological order (trace-time tap order, kept in
    a global first-seen registry because jit output pytrees sort dict
    keys) into the watchdog flight-recorder dump, a
    ``nanguard_forensics`` JSONL record and the
    ``numerics.first_nonfinite_site.<source>`` gauge.
  * **quantization drift** — :func:`update_quant_drift` maintains a
    per-site EWMA of runtime amax over the calibration manifest
    thresholds; mx.serving samples every ``quant.drift_every``-th
    quantized dispatch through the stats-twin program exported next to
    each int8 artifact and the ratios land on ``/metrics`` as
    ``quant.drift_ratio.<model>.<site>`` gauges (two-label family in
    mx.obs).  ``tools/telemetry_report.py`` folds the ``quant_drift``
    JSONL events into an anomaly.

Overhead contract: with capture off the tap sites cost literally zero
(the plain variant never calls into this module inside the trace); at
``step:N`` cadence the instrumented variant runs every Nth step only,
so the amortized overhead is the instrumented-step delta / N.

Schema, forensics record layout and the drift math live in
docs/OBSERVABILITY.md ("Numerics plane").
"""
from __future__ import annotations

import contextlib
import logging
import threading
from collections import OrderedDict

_LOG = logging.getLogger("mxnet_tpu.numerics")

#: Per-site summary statistics, in field order of the (6,) float32
#: vector :func:`summarize` produces.  ``amax``/``amin``/``rms`` are
#: computed over the FINITE |x| mass (a single inf must not wipe out the
#: magnitude picture), ``nonfinite`` counts NaN/inf elements, and the
#: bf16 fractions measure how much of the tensor sits outside bf16's
#: representable magnitude band — the early-warning signal for loss
#: scaling and for quantization drift.
STAT_FIELDS = ("amax", "amin", "rms", "nonfinite",
               "bf16_overflow", "bf16_underflow")

# bf16 shares float32's exponent range, so true overflow is rare; the
# actionable band is "would round to inf when cast" (> bf16 max finite)
# and "would flush toward zero" (non-zero but below the float32/bf16
# normal floor).
_BF16_MAX = 3.3895313892515355e38
_TINY = 1.1754943508222875e-38  # smallest normal (float32 == bf16 floor)

_LOCK = threading.RLock()
_COUNTS = {}                    # guarded-by: _LOCK — per-source step counter
_PENDING = {}                   # guarded-by: _LOCK — source -> [(step, stats)]
_PENDING_MAX = 64               # same bound as resilience._STREAK_PENDING
_LATEST = {}                    # guarded-by: _LOCK — source -> (step, host stats)
_LISTENERS = []                 # guarded-by: _LOCK — fn(source, step, stats)
_REPLAY = {}                    # guarded-by: _LOCK — source -> zero-arg closure
_FORENSICS = []                 # guarded-by: _LOCK — forensics records, newest last
_FORENSICS_MAX = 16
# site -> monotonic first-tap sequence number.  Taps fire at TRACE time,
# which walks the program in topological order; jit returns the stats
# dict with pytree-sorted keys, so this registry is the only place the
# original order survives.  Monotonic across programs: a site keeps its
# first-seen rank for the process lifetime.
_SITE_ORDER = {}                # guarded-by: _LOCK
_SITE_SEQ = [0]                 # guarded-by: _LOCK

_TLS = threading.local()        # .collectors: stack of OrderedDicts


# --------------------------------------------------------------- summarize
def summarize(x):
    """(6,) float32 summary of ``x`` (:data:`STAT_FIELDS` order),
    computed in-graph — safe to call on tracers.  Returns the stats
    array; never syncs."""
    import jax.numpy as jnp
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.inexact):
        x = x.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    finite = jnp.isfinite(xf)
    absx = jnp.abs(jnp.where(finite, xf, 0.0))
    n = jnp.maximum(xf.size, 1)
    amax = jnp.max(absx)
    # amin over the finite mass (masked elements would win a plain min)
    amin = jnp.min(jnp.where(finite, jnp.abs(xf), jnp.inf))
    amin = jnp.where(jnp.isfinite(amin), amin, 0.0)
    rms = jnp.sqrt(jnp.sum(absx * absx) / n)
    nonfinite = jnp.sum(~finite).astype(jnp.float32)
    over = jnp.mean((absx > _BF16_MAX).astype(jnp.float32))
    # underflow = subnormal magnitudes (bf16 shares f32's exponent range,
    # and accelerators flush subnormals to zero).  Detected on the BIT
    # pattern: float comparisons against subnormals themselves flush, so
    # an arithmetic (absx > 0) & (absx < tiny) test can never fire
    import jax as _jax
    bits = _jax.lax.bitcast_convert_type(xf, jnp.int32) & 0x7FFFFFFF
    under = jnp.mean(((bits > 0) & (bits < 0x00800000))
                     .astype(jnp.float32))
    return jnp.stack([amax, amin, rms, nonfinite, over, under]
                     ).astype(jnp.float32)


def stats_dict(vec):
    """Host-side view of one (6,) stats vector as a plain dict of
    floats, keyed by :data:`STAT_FIELDS`."""
    import numpy as _np
    v = _np.asarray(vec, dtype=_np.float64).reshape(-1)
    return {f: float(v[i]) for i, f in enumerate(STAT_FIELDS)}


# --------------------------------------------------------------- the knob
def configure(spec):
    """Validate a ``numerics.capture`` spec: ``''``/``'off'`` disables,
    ``'step:N'`` captures every Nth step per source.  Raises ValueError
    on anything else (the config hook reverts the knob).  Returns the
    parsed cadence."""
    spec = (spec or "").strip().lower()
    if spec in ("", "off", "0"):
        return 0
    if spec.startswith("step:"):
        try:
            every = int(spec[5:])
        except ValueError:
            raise ValueError(
                "numerics.capture: bad cadence %r — want step:<int>"
                % (spec,))
        if every < 1:
            raise ValueError(
                "numerics.capture: cadence must be >= 1, got %d" % every)
        return every
    raise ValueError(
        "numerics.capture: unrecognized spec %r — want 'off' or "
        "'step:N'" % (spec,))


def capture_every():
    """Current cadence N (0 = capture off).  Read from the config knob
    each call so MXNET_TPU_NUMERICS works without a set(); set() specs
    are validated by the config hook, so a junk ENV spec (the only
    unvalidated path) degrades to off with one warning."""
    from . import config as _config
    spec = _config.get("numerics.capture")
    try:
        return configure(spec)
    except ValueError:
        if not _TLS.__dict__.get("warned_spec"):
            _TLS.warned_spec = True
            _LOG.warning(
                "numerics: ignoring bad MXNET_TPU_NUMERICS spec %r "
                "(want 'off' or 'step:N')", spec)
        return 0


def capture_active():
    """True when the capture knob is on (any cadence)."""
    return capture_every() > 0


def should_capture(source):
    """One call per step per seam: True when THIS step should run the
    instrumented program variant.  Advances the per-source step counter
    only while capture is on, so ``step:N`` means "every Nth captured-era
    step", first step included."""
    every = capture_every()
    if every <= 0:
        return False
    with _LOCK:
        n = _COUNTS.get(source, 0)
        _COUNTS[source] = n + 1
        return n % every == 0


def capture_token(instrument):
    """Program-cache key element for the chosen variant.  The OFF value
    is ``()`` — identical to a build without numerics — so cache keys
    (and therefore lowered programs) are untouched until a seam actually
    instruments.  Both variants coexist in the cache: toggling the knob
    never evicts or recompiles (``fused_compiles`` stays flat)."""
    return ("numerics",) if instrument else ()


# ------------------------------------------------------------ tap registry
def _collectors():
    stack = getattr(_TLS, "collectors", None)
    if stack is None:
        stack = _TLS.collectors = []
    return stack


@contextlib.contextmanager
def collect():
    """Open a tap collector for the current thread; yields an
    OrderedDict that :func:`tap` calls (in this thread, typically at
    trace time) fill with ``site -> (6,) stats`` entries, in tap
    (= topological) order."""
    stack = _collectors()
    sink = OrderedDict()
    stack.append(sink)
    try:
        yield sink
    finally:
        stack.pop()


def collecting():
    """True when a :func:`collect` context is open on this thread."""
    return bool(_collectors())


def _register_site(sink, site):
    if site in sink:
        k = 2
        while "%s#%d" % (site, k) in sink:
            k += 1
        site = "%s#%d" % (site, k)
    with _LOCK:
        if site not in _SITE_ORDER:
            _SITE_ORDER[site] = _SITE_SEQ[0]
            _SITE_SEQ[0] += 1
    return site


def tap(site, x):
    """Record summary stats for ``x`` under ``site`` in the ambient
    collector (no-op without one) and return ``x`` unchanged — taps
    drop into expressions.  Non-inexact tensors (int ids, masks) are
    skipped: their stats are noise and their cast would cost."""
    stack = _collectors()
    if not stack:
        return x
    import jax.numpy as jnp
    arr = jnp.asarray(x) if not hasattr(x, "dtype") else x
    if not jnp.issubdtype(arr.dtype, jnp.inexact):
        return x
    sink = stack[-1]
    sink[_register_site(sink, site)] = summarize(arr)
    return x


def record(sink, site, x):
    """Seam-side tap into an EXPLICIT stats mapping (outer-trace sites
    like per-param grads/updates, where no :func:`collect` context is
    open): registers ``site`` in the global topological order and stores
    ``summarize(x)`` in ``sink``."""
    sink[_register_site(sink, site)] = summarize(x)


def tap_stacked(site, stacked):
    """Record an already-stacked ``(L, 6)`` per-layer stats array (the
    scan-ys shape from ``runtime.scan_stack``) under ``site``; the host
    side expands it to ``site[i]`` entries.  No-op without a
    collector."""
    stack = _collectors()
    if not stack:
        return
    sink = stack[-1]
    site = _register_site(sink, site)
    sink[site] = stacked
    # pre-register the expanded names so topological order is stable
    try:
        n = int(stacked.shape[0])
    except Exception:  # noqa: BLE001 — abstract dim: order resolved later
        n = 0
    with _LOCK:
        for i in range(n):
            name = "%s[%d]" % (site, i)
            if name not in _SITE_ORDER:
                _SITE_ORDER[name] = _SITE_SEQ[0]
                _SITE_SEQ[0] += 1


def expand_stats(stats):
    """Host-side: flatten a stats mapping to ``site -> (6,) numpy``,
    expanding stacked ``(L, 6)`` entries to ``site[i]``."""
    import numpy as _np
    out = OrderedDict()
    with _LOCK:
        order = dict(_SITE_ORDER)
    for site in sorted(stats, key=lambda s: order.get(s, 1 << 30)):
        v = _np.asarray(stats[site])
        if v.ndim == 2 and v.shape[-1] == len(STAT_FIELDS):
            for i in range(v.shape[0]):
                out["%s[%d]" % (site, i)] = v[i]
        else:
            out[site] = v.reshape(-1)
    return out


# ----------------------------------------------------- async stats fetch
def publish(source, step, stats):
    """Hand a step's device stats pytree to the pending queue.  Never
    blocks on the device unless the queue overflows (the step seam got
    > ``_PENDING_MAX`` steps ahead of transfers — same backpressure
    contract as ``resilience.watch_streak``)."""
    if not stats:
        return
    with _LOCK:
        q = _PENDING.setdefault(source, [])
        q.append((int(step), dict(stats)))
        overflow = len(q) > _PENDING_MAX
    poll(source, block=overflow)


def _entry_ready(stats):
    for v in stats.values():
        is_ready = getattr(v, "is_ready", None)
        if is_ready is not None and not is_ready():
            return False
    return True


def poll(source=None, block=False):
    """Drain pending stats whose device arrays are ready (all leaves
    ``.is_ready()``); with ``block=True`` drain everything.  Each
    drained step lands in :func:`latest`, fires listeners, and (sink
    armed) emits a ``numerics`` JSONL event.  Returns the number of
    steps drained."""
    from . import telemetry as _telemetry
    with _LOCK:
        sources = [source] if source is not None else list(_PENDING)
    drained = 0
    for src in sources:
        while True:
            with _LOCK:
                q = _PENDING.get(src)
                if not q:
                    break
                step, stats = q[0]
                if not block and not _entry_ready(stats):
                    break
                q.pop(0)
            host = expand_stats(stats)
            with _LOCK:
                _LATEST[src] = (step, host)
                listeners = list(_LISTENERS)
            drained += 1
            if _telemetry.enabled():
                worst = max(
                    (float(v[3]) for v in host.values()), default=0.0)
                _telemetry.log_event(
                    "numerics", source=src, step=step,
                    sites=len(host), nonfinite=worst,
                    stats={s: stats_dict(v) for s, v in host.items()})
            for fn in listeners:
                try:
                    fn(src, step, host)
                except Exception:  # noqa: BLE001 — listeners are best-effort
                    _LOG.exception("numerics listener failed")
    return drained


def latest(source):
    """Most recent drained ``(step, {site: (6,) numpy})`` for
    ``source``, or None.  Call :func:`poll` first for freshness."""
    with _LOCK:
        return _LATEST.get(source)


def add_listener(fn):
    """Register ``fn(source, step, host_stats)`` to fire on every
    drained step."""
    with _LOCK:
        _LISTENERS.append(fn)


def remove_listener(fn):
    with _LOCK:
        try:
            _LISTENERS.remove(fn)
        except ValueError:
            pass


# -------------------------------------------------- nanguard forensics
def hold_replay(source, fn):
    """Park a zero-arg closure that re-runs the seam's last batch
    through the INSTRUMENTED program variant and returns its stats
    mapping.  Seams refresh it while the nanguard streak is armed; the
    guard's abort path consumes it via :func:`run_forensics`.  Costs
    one closure per step — no tensors are copied (the closure reads the
    seam's live last-good state at replay time)."""
    with _LOCK:
        _REPLAY[source] = fn


def drop_replay(source):
    with _LOCK:
        _REPLAY.pop(source, None)


def first_nonfinite(host_stats):
    """First site (topological tap order) whose non-finite count is
    > 0, or None."""
    with _LOCK:
        order = dict(_SITE_ORDER)
    for site in sorted(host_stats, key=lambda s: order.get(s, 1 << 30)):
        if float(host_stats[site][3]) > 0:
            return site
    return None


def run_forensics(source):
    """Nanguard abort path: consume the held replay for ``source``,
    re-run the failing batch once through the instrumented program, and
    report the first non-finite site.  Returns the forensics record (or
    None without a held replay).  The record is appended to
    :func:`forensics_records`, emitted as a ``nanguard_forensics``
    JSONL event + flight-recorder ring event, and the site name lands on
    the ``numerics.first_nonfinite_site.<source>`` gauge."""
    from . import telemetry as _telemetry
    with _LOCK:
        fn = _REPLAY.pop(source, None)
    if fn is None:
        return None
    try:
        stats = fn()
    except Exception:  # noqa: BLE001 — the replay re-runs the very batch
        # that blew up; a crash here must not mask the nanguard abort
        _LOG.exception("numerics: forensics replay for %r failed", source)
        return None
    host = expand_stats(stats or {})
    site = first_nonfinite(host)
    bad = [s for s in host if float(host[s][3]) > 0]
    record = {
        "source": source,
        "first_nonfinite_site": site,
        "nonfinite_sites": bad,
        "sites": len(host),
        "stats": {s: stats_dict(host[s]) for s in bad} or
                 {s: stats_dict(v) for s, v in host.items()},
    }
    with _LOCK:
        _FORENSICS.append(record)
        del _FORENSICS[:-_FORENSICS_MAX]
    _telemetry.gauge(
        "numerics.first_nonfinite_site.%s" % source).set(site or "none")
    if _telemetry.enabled():
        _telemetry.log_event("nanguard_forensics", **record)
    try:
        from . import tracing as _tracing
        _tracing.record_event(
            "numerics", "nanguard_forensics", source=source,
            first_nonfinite_site=site, nonfinite_sites=len(bad))
    except Exception:  # noqa: BLE001 — forensics must not break the abort
        pass
    _LOG.error(
        "numerics: nanguard forensics for %r — first non-finite site: "
        "%s (%d/%d sites non-finite)", source, site, len(bad), len(host))
    return record


def forensics_records():
    """Recent forensics records, oldest first (bounded ring)."""
    with _LOCK:
        return list(_FORENSICS)


# ---------------------------------------------------- quantization drift
def update_quant_drift(model, sites, amaxes, thresholds, ewma,
                       alpha=0.2, threshold_ratio=None):
    """Fold one stats-twin sample into the per-site drift EWMA.

    ``sites`` names the twin's output order, ``amaxes`` is the host
    (S,) runtime-amax sample, ``thresholds`` the calibration manifest
    (site -> calibrated amax), ``ewma`` the caller-owned mutable state
    dict (site -> smoothed ratio).  Sets the
    ``quant.drift_ratio.<model>.<site>`` gauges and, past
    ``threshold_ratio`` (default: the ``quant.drift_threshold`` knob),
    emits one ``quant_drift`` JSONL event per newly-drifted site.
    Returns the list of currently-drifted site names."""
    import numpy as _np
    from . import config as _config
    from . import telemetry as _telemetry
    if threshold_ratio is None:
        threshold_ratio = float(_config.get("quant.drift_threshold"))
    vals = _np.asarray(amaxes, dtype=_np.float64).reshape(-1)
    drifted = []
    for site, amax in zip(sites, vals):
        cal = float(thresholds.get(site, 0.0) or 0.0)
        if cal <= 0.0:
            continue
        ratio = float(amax) / cal
        prev = ewma.get(site)
        sm = ratio if prev is None else alpha * ratio + (1 - alpha) * prev
        was_drifted = prev is not None and prev > threshold_ratio
        ewma[site] = sm
        _telemetry.gauge(
            "quant.drift_ratio.%s.%s" % (model, site)).set(round(sm, 6))
        if sm > threshold_ratio:
            drifted.append(site)
            if not was_drifted:
                _telemetry.counter("quant.drift_trips").inc()
                if _telemetry.enabled():
                    _telemetry.log_event(
                        "quant_drift", model=model, site=site,
                        ratio=round(sm, 6), sample=round(float(amax), 6),
                        calibrated=round(cal, 6),
                        threshold=threshold_ratio)
                _LOG.warning(
                    "numerics: quantization drift on %s/%s — runtime "
                    "amax EWMA %.4g is %.2fx the calibrated %.4g",
                    model, site, sm * cal, sm, cal)
    return drifted


# ------------------------------------------------------------------ reset
def reset():
    """Test hook: forget counters, queues, replays, forensics and site
    order (the capture cadence itself lives on the config knob)."""
    with _LOCK:
        _COUNTS.clear()
        _PENDING.clear()
        _LATEST.clear()
        _LISTENERS[:] = []
        _REPLAY.clear()
        _FORENSICS[:] = []
        _SITE_ORDER.clear()
        _SITE_SEQ[0] = 0
