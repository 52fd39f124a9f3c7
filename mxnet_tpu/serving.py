"""``mx.serving`` — continuous-batching inference over the StableHLO
export path.

Reference deployment story: the C predict API served one process-local
model per handle (include/mxnet/c_predict_api.h) and TensorRT subgraph
serving owned the batched GPU path (SURVEY §2, §5).  The TPU-native analog
is a REQUEST QUEUE in front of the ``mx.deploy`` artifact: concurrent
``submit()`` calls coalesce into batches padded up to the shared
``io.pad_buckets`` bucket set, so a SMALL, FIXED family of AOT-compiled
programs (one per ``(model, bucket)``) serves every request size — the
same pad-bucket policy the PR-5 input pipeline uses to keep training
compiles flat now keeps serving compiles flat.

Architecture (one background batcher thread per :class:`Server`, run
under a restart supervisor):

  submit(name, x) ──► admission check ──► per-server FIFO ──► batcher:
                      (bounded queue,                          take first request
                       breaker state)                          reap expired deadlines
                                                               coalesce same-model requests
                                                                 until rows == max_batch or
                                                                 max_queue_delay_ms elapses
                                                               concat + wrap-pad → bucket
                                                               AOT program(params, batch)
                                                               scatter rows → caller futures

Key properties:

  * **Row-stable batching** — each output row of a bucketed dispatch
    equals the row the unbatched ``StableHLOPredictor.predict`` produces
    to f32 rounding (row-independent inference math; bit for bit only
    when the bucket is the request's own shape — XLA promises no two
    program shapes the same bits; ``tools/check_serving.py`` holds it
    under concurrent ragged traffic, ``tools/check_serving_chaos.py``
    under injected faults).
  * **Zero steady-state compiles** — every ``(model, bucket)`` program is
    compiled eagerly at :meth:`Server.start`; ragged request sizes never
    reach the compiler.  ``start()`` turns on jax's persistent
    compilation cache (``runtime.configure_compile_cache``: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, else one fixed path
    in the checkout) so a RESTARTED server skips even those.
  * **Fail-fast under overload** — the pending queue is bounded
    (``serving.max_pending``): a submit past the bound raises a retryable
    :class:`ServerOverloadedError` instead of queuing until memory dies.
  * **Deadlines** — ``submit(name, x, deadline_ms=...)`` (default from
    ``serving.default_deadline_ms``): a request still queued past its
    deadline completes with :class:`DeadlineExceededError` at
    batch-formation time and is NEVER dispatched — no compute is spent on
    answers nobody is waiting for.  ``predict(timeout=...)`` cancels its
    queued request on timeout the same way.
  * **Failure isolation** — a per-model circuit breaker opens after K
    consecutive dispatch failures (``serving.breaker_threshold``),
    fails that model's submits fast with :class:`CircuitOpenError` while
    other models keep serving, then goes half-open after the cooldown and
    probes with a single batch (success closes it, failure re-opens).
  * **Batcher supervision** — an unexpected batcher crash fails every
    pending future with the causal exception, bumps
    ``serving.batcher_crashes``, and restarts the loop under the
    ``mx.resilience`` retry budget/backoff; once the budget is exhausted
    submits fail fast instead of hanging.  The PR-3 watchdog carries a
    serving stall probe (``tracing.register_stall_probe``) that
    flight-records open requests and breaker state whenever the queue is
    non-empty but no dispatch completed within the watchdog interval.
  * **Device-resident params** — uploaded once at ``register()`` (by the
    underlying :class:`~mxnet_tpu.deploy.StableHLOPredictor`), never per
    request.
  * **Multi-model** — a bounded LRU table of registered models; the least
    recently used model (programs + device params) is evicted when
    ``max_models`` is exceeded.
  * **Quantized models** — ``register(name, prefix, quantized=True)``
    serves an int8 deploy-v3 artifact (``mx.quantization``): int8 params
    stage once, the int8 program AOT-compiles per bucket exactly like
    fp32 (compiles stay flat), ``serving.quantized_dispatches`` counts
    its batches and the ``quantized`` flag rides ``stats()`` and every
    per-dispatch JSONL record (docs/QUANTIZATION.md).
  * **Telemetry** — ``serving.requests`` / ``serving.batch_dispatches`` /
    ``serving.compiles`` / ``serving.shed_requests[.model]`` /
    ``serving.deadline_exceeded[.model]`` / ``serving.breaker_open
    [.model]`` / ``serving.batcher_crashes`` counters, a
    ``serving.breaker_state.<model>`` gauge (0 closed / 1 half-open / 2
    open), ``serving.queue_delay_ms`` / ``serving.batch_fill`` /
    ``serving.dispatch_ms`` / ``serving.request_ms`` timer histograms,
    one ``serving`` JSONL record per dispatch on the telemetry sink
    (now carrying shed/deadline/breaker state for
    ``tools/telemetry_report.py``'s overload anomaly), and
    ``serving.submit`` / ``serving.dispatch`` spans with cross-thread
    parentage (the batcher runs under ``tracing.wrap_context``, the
    ``io.prefetch`` pattern).

Deterministic chaos: the ``serving_dispatch`` (fail a dispatch) and
``serving_slow`` (delay a dispatch) fault kinds plug into the shared
``MXNET_TPU_FAULTS`` harness, so every failure path above is scriptable —
``tools/check_serving_chaos.py`` proves shed counts, deadline counts,
breaker transitions and crash-restart bitwise-deterministically in <5s.

Knobs (config.py): ``serving.max_batch`` (MXNET_TPU_SERVING_MAX_BATCH),
``serving.max_queue_delay_ms`` (MXNET_TPU_SERVING_MAX_QUEUE_DELAY_MS),
``serving.max_pending`` (MXNET_TPU_SERVING_MAX_PENDING),
``serving.default_deadline_ms`` (MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS),
``serving.breaker_threshold`` / ``serving.breaker_cooldown_ms``; the
bucket POLICY is the shared ``io.pad_buckets`` knob.  docs/SERVING.md has
the full architecture + fault-tolerance note.
"""
from __future__ import annotations

import logging
import threading
import time as _time
from collections import OrderedDict, deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutureTimeout

import numpy as _np

import jax

from . import config as _config
from . import io as _io
from . import obs as _obs
from . import runtime as _runtime
from . import telemetry as _telemetry

__all__ = ["Server", "ServingError", "ServerOverloadedError",
           "DeadlineExceededError", "CircuitOpenError", "load_server"]

_LOG = logging.getLogger("mxnet_tpu.serving")

#: sleep injected by the ``serving_slow`` fault kind: long enough to trip a
#: sub-second watchdog interval and make shed/deadline schedules
#: deterministic, short enough that chaos smokes stay under their budget.
_SLOW_DISPATCH_S = 0.25


class ServingError(RuntimeError):
    """Raised for serving lifecycle errors (stopped server, evicted or
    unknown model, oversized request on a fixed-batch artifact, dead
    batcher)."""


class ServerOverloadedError(ServingError, OSError):
    """The pending queue is at ``serving.max_pending``: the request was
    shed instead of queued.  Subclasses OSError so
    ``resilience.call_with_retry`` treats it as retryable — back off and
    resubmit."""


class DeadlineExceededError(ServingError):
    """The request's deadline expired while it was still queued: it was
    completed with this error at batch-formation time and never
    dispatched (or cancelled by ``predict(timeout=...)``)."""


class CircuitOpenError(ServingError, OSError):
    """The model's circuit breaker is open after consecutive dispatch
    failures: failing fast instead of queuing onto a broken model.
    Retryable (OSError subclass) — the breaker goes half-open after its
    cooldown and probes with a single batch."""


class _BatcherCrashError(OSError):
    """Internal: wraps an arbitrary batcher-loop crash so
    ``resilience.call_with_retry`` (which retries OSError) drives the
    restart backoff and bounds the restart budget."""


def _access_outcome(exc):
    """Map a request-terminal exception to its access-log outcome (the
    mx.obs vocabulary: ok|shed|deadline|breaker|error)."""
    if isinstance(exc, CircuitOpenError):
        return "breaker"
    if isinstance(exc, DeadlineExceededError):
        return "deadline"
    if isinstance(exc, ServerOverloadedError):
        return "shed"
    return "error"


class _Request:
    """One caller request: host-side rows plus the future its output rows
    resolve, stamped with the submit time for queue-delay accounting, an
    optional absolute deadline, and the submit span's trace_id so the
    mx.obs access-log record joins against the Chrome trace."""

    __slots__ = ("model", "data", "rows", "future", "t_submit", "deadline",
                 "trace_id")

    def __init__(self, model, data, future, deadline_ms=0.0,
                 trace_id=None):
        self.model = model
        self.data = data
        self.rows = int(data.shape[0])
        self.future = future
        self.t_submit = _time.perf_counter()
        self.deadline = (self.t_submit + float(deadline_ms) * 1e-3) \
            if deadline_ms and deadline_ms > 0 else None
        self.trace_id = trace_id

    def expired(self, now=None):
        if self.deadline is None:
            return False
        return (now if now is not None else _time.perf_counter()) \
            >= self.deadline


_BREAKER_STATE_VALUE = {"closed": 0, "half_open": 1, "open": 2}


class _Breaker:
    """Per-model circuit breaker: ``closed`` → ``open`` after
    ``threshold`` consecutive dispatch failures → ``half_open`` once the
    cooldown elapses (ONE probe batch goes through) → ``closed`` on probe
    success / back to ``open`` on probe failure.  ``threshold <= 0``
    disables the breaker (every check short-circuits)."""

    __slots__ = ("model", "threshold", "cooldown_s", "state", "failures",
                 "opened_at", "_lock")

    def __init__(self, model, threshold, cooldown_s):
        self.model = model
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        # Reads on the submit fast path are deliberately lock-free (a
        # stale read only delays a fast-fail by one batch), so only the
        # writes are lock-checked.
        self.state = "closed"    # guarded-by[writes]: _lock
        self.failures = 0        # guarded-by[writes]: _lock
        self.opened_at = 0.0     # guarded-by[writes]: _lock
        self._lock = threading.Lock()

    def _set_state(self, state):  # mxlint: holds(_lock)
        self.state = state
        _telemetry.gauge("serving.breaker_state.%s" % self.model).set(
            _BREAKER_STATE_VALUE[state])

    def cooldown_remaining_ms(self):
        return max(0.0, (self.cooldown_s
                         - (_time.perf_counter() - self.opened_at))) * 1e3

    def rejects_submit(self):
        """Fast-fail check on the submit path: only while OPEN and still
        inside the cooldown.  Once the cooldown elapses submits are
        accepted again — they feed the half-open probe."""
        if self.threshold <= 0 or self.state != "open":
            return False
        return _time.perf_counter() - self.opened_at < self.cooldown_s

    def allow_dispatch(self):
        """Dispatch-side gate: closed/half-open batches dispatch; an open
        breaker whose cooldown elapsed transitions to half-open and lets
        this ONE batch through as the probe."""
        if self.threshold <= 0:
            return True
        with self._lock:
            if self.state != "open":
                return True
            if _time.perf_counter() - self.opened_at < self.cooldown_s:
                return False
            self._set_state("half_open")
        _LOG.info("serving: breaker for model %r half-open after %.0fms "
                  "cooldown; probing with one batch",
                  self.model, self.cooldown_s * 1e3)
        return True

    def record_success(self):
        if self.threshold <= 0:
            return
        with self._lock:
            closing = self.state != "closed"
            self.failures = 0
            if closing:
                self._set_state("closed")
        if closing:
            _LOG.info("serving: breaker for model %r closed after a "
                      "successful probe", self.model)

    def record_failure(self):
        if self.threshold <= 0:
            return
        with self._lock:
            if self.state == "half_open":
                # the probe failed: straight back to open, fresh cooldown
                self.failures += 1
                self.opened_at = _time.perf_counter()
                self._set_state("open")
                opened = True
            else:
                self.failures += 1
                opened = self.state == "closed" \
                    and self.failures >= self.threshold
                if opened:
                    self.opened_at = _time.perf_counter()
                    self._set_state("open")
        if opened:
            _telemetry.counter("serving.breaker_open").inc()
            _telemetry.counter("serving.breaker_open.%s" % self.model).inc()
            try:
                from . import tracing as _tracing
                _tracing.record_event(
                    "serving", "breaker_open", model=self.model,
                    failures=self.failures)
            except Exception:  # noqa: BLE001 — telemetry must not break it
                pass
            _LOG.warning(
                "serving: breaker for model %r OPEN after %d consecutive "
                "dispatch failure(s); failing fast for %.0fms",
                self.model, self.failures, self.cooldown_s * 1e3)


class _ModelEntry:
    """A registered model: reloaded artifact, device-resident params, the
    per-bucket AOT program table, plus its breaker and fault-tolerance
    tallies (cumulative shed / deadline-expired requests)."""

    __slots__ = ("name", "prefix", "predictor", "buckets", "programs",
                 "item_shape", "in_dtype", "breaker", "shed",
                 "deadline_exceeded", "quantized", "cost_per_item",
                 "drift_call", "drift_sites", "drift_count", "drift_ewma")

    def __init__(self, name, prefix, predictor, buckets):
        self.name = name
        self.prefix = prefix
        self.predictor = predictor
        self.quantized = bool(getattr(predictor, "quantized", False))
        # quantization drift probe (docs/OBSERVABILITY.md): the stats
        # twin exported next to the int8 program, lazily loaded on the
        # first sampled dispatch; False = tried and absent
        self.drift_call = None
        meta = getattr(predictor, "meta", None) or {}
        self.drift_sites = tuple(meta.get("stats_sites") or ())
        self.drift_count = 0
        self.drift_ewma = {}
        self.buckets = tuple(buckets)
        self.programs = {}
        shape = predictor.meta.get("input_shape") or []
        self.item_shape = tuple(int(s) for s in shape[1:])
        self.in_dtype = _np.dtype(predictor.meta.get("input_dtype",
                                                     "float32"))
        self.breaker = None   # assigned by Server.register
        self.shed = 0
        self.deadline_exceeded = 0
        self.cost_per_item = None  # set by _compile from cost_analysis

    @property
    def capacity(self):
        return self.buckets[-1]


class Server:
    """Continuous-batching inference server over ``mx.deploy`` artifacts.

    Usage::

        srv = mx.serving.Server(max_batch=32, max_queue_delay_ms=2.0)
        srv.register("resnet", "/models/resnet50")   # params → device
        srv.start()                                  # AOT-compile buckets
        fut = srv.submit("resnet", batch_of_images)  # any request size
        probs = fut.result()                         # host numpy rows
        srv.stop()                                   # graceful drain

    ``submit`` is thread-safe; requests from any number of caller threads
    coalesce into bucketed batches on the single batcher thread.  Requests
    larger than the biggest bucket are transparently split into chunks and
    their outputs re-concatenated.  ``Server`` is also a context manager
    (``with Server() as srv: ...`` starts and drains it).

    Fault tolerance (docs/SERVING.md): submits past ``max_pending`` shed
    with :class:`ServerOverloadedError`; ``submit(deadline_ms=...)``
    requests that expire in queue complete with
    :class:`DeadlineExceededError` and never dispatch; a per-model
    breaker fails a broken model fast (:class:`CircuitOpenError`) while
    other models keep serving; and the batcher thread is supervised —
    a crash fails pending futures with the causal exception and restarts
    the loop under the ``mx.resilience`` retry budget.
    """

    def __init__(self, max_batch=None, max_queue_delay_ms=None,
                 buckets=None, max_models=8, max_pending=None,
                 default_deadline_ms=None, breaker_threshold=None,
                 breaker_cooldown_ms=None):
        if max_batch is None:
            max_batch = _config.get("serving.max_batch")
        if max_queue_delay_ms is None:
            max_queue_delay_ms = _config.get("serving.max_queue_delay_ms")
        if buckets is None:
            buckets = _config.get("io.pad_buckets")
        if max_pending is None:
            max_pending = _config.get("serving.max_pending")
        if default_deadline_ms is None:
            default_deadline_ms = _config.get("serving.default_deadline_ms")
        if breaker_threshold is None:
            breaker_threshold = _config.get("serving.breaker_threshold")
        if breaker_cooldown_ms is None:
            breaker_cooldown_ms = _config.get("serving.breaker_cooldown_ms")
        self.max_batch = int(max_batch)
        self.max_queue_delay_ms = float(max_queue_delay_ms)
        self._bucket_policy = buckets
        self.max_models = int(max_models)
        self.max_pending = int(max_pending)
        self.default_deadline_ms = float(default_deadline_ms)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_ms = float(breaker_cooldown_ms)
        # Cross-thread state below is lock-checked by tools/mxlint.py
        # (docs/ANALYSIS.md): every access must hold _cond unless the
        # annotation says writes-only.
        self._models = OrderedDict()     # guarded-by: _cond — _ModelEntry, LRU order
        self._generation = {}            # guarded-by: _cond — GenerationEngine per model
        self._pending = deque()          # guarded-by: _cond
        self._cond = threading.Condition()
        # guarded-by[writes]: _cond — stop() joins outside the lock
        self._thread = None
        self._leaked_thread = None       # batcher that missed stop()'s join
        self._batcher_dead = None        # guarded-by: _cond — exc once restarts exhaust
        self._started = False            # guarded-by: _cond
        self._stopping = False           # guarded-by: _cond
        self._last_dispatch_done = _time.perf_counter()  # guarded-by: _cond
        self._probe_name = "serving-%x" % id(self)

    # ------------------------------------------------------------ models
    def _policy_buckets(self, cap):
        sizes = _io.bucket_sizes(self._bucket_policy, cap)
        # serving must always have at least one compiled shape; policy
        # 'off' (natural shapes) degenerates to the single full bucket
        return sizes or (cap,)

    def register(self, name, prefix, quantized=False, generate=False,
                 params=None):
        """Load the ``mx.deploy`` artifact at ``prefix`` under ``name``:
        params go device-resident now; bucket programs compile now if the
        server is already started (else at :meth:`start`).  Re-registering
        a name replaces the entry (and resets its breaker).  The table is
        LRU-bounded at ``max_models`` — registering past it evicts the
        least recently used model (its programs and device params become
        collectable).

        ``quantized=True`` registers an int8 (deploy format v3) artifact
        written by ``mx.quantization.export_quantized``: its int8 bucket
        programs AOT-compile exactly like fp32 ones (``serving.compiles``
        stays == bucket count under ragged traffic, persistent compile
        cache included) and the model is flagged ``quantized`` in
        :meth:`stats` and every per-dispatch JSONL record.  The flag must
        match the artifact — a v3 artifact without it (or an fp32
        artifact with it) raises, so int8 numerics are always explicit.

        ``generate=True`` registers a GENERATION (deploy format v4)
        artifact written by ``deploy.export_generation``: instead of
        joining the one-shot batcher, the model gets its own
        :class:`~mxnet_tpu.generation.GenerationEngine` — a per-iteration
        continuous-batching scheduler over a paged device-resident KV
        cache (``serving.kv_pages`` x ``serving.kv_page_size`` tokens,
        ``serving.decode_slots`` concurrent sequences).  Drive it with
        :meth:`submit_generate` / :meth:`generate`; plain :meth:`submit`
        refuses it.  Generation models sit outside the one-shot LRU
        table (an engine holds live sequences — evicting it mid-flight
        would kill them) and are removed by :meth:`unregister`.
        ``params`` (generation only): the pytree the artifact was
        exported with, for a process that exports and serves — arrays
        already on the device are served as they are, and the artifact
        may have been written with ``include_params=False``."""
        from . import deploy as _deploy
        if generate:
            if quantized:
                raise ServingError(
                    "model %r: generate=True with quantized=True is not "
                    "supported — KV quantization for generation is baked "
                    "at EXPORT time (export_generation(..., "
                    "kv_quantized=True), int8 KV pages), not applied at "
                    "register" % (name,))
            return self._register_generation(name, prefix, params)
        if params is not None:
            raise ServingError(
                "model %r: params= is for generate=True artifacts"
                % (name,))
        predictor = _deploy.StableHLOPredictor(prefix, quantized=quantized)
        if predictor._params is None:
            raise ServingError(
                "model %r: artifact %r was exported with "
                "include_params=False; serving needs shipped params"
                % (name, prefix))
        if predictor.dynamic_batch:
            buckets = self._policy_buckets(self.max_batch)
        else:
            # fixed-shape artifact (v1, or a model whose lowering
            # constrains the batch dim): its one exported batch size IS
            # the bucket set
            fixed = int(predictor.meta["input_shape"][0])
            buckets = (fixed,)
        entry = _ModelEntry(name, prefix, predictor, buckets)
        entry.breaker = _Breaker(name, self.breaker_threshold,
                                 self.breaker_cooldown_ms * 1e-3)
        with self._cond:
            self._models.pop(name, None)
            self._models[name] = entry
            evicted = []
            while len(self._models) > self.max_models:
                victim, _ = self._models.popitem(last=False)
                evicted.append(victim)
            started = self._started
        for victim in evicted:
            _telemetry.counter("serving.models_evicted").inc()
            _LOG.info("serving: evicted LRU model %r (max_models=%d)",
                      victim, self.max_models)
        if started:
            self._compile_entry(entry)
        return entry

    def _register_generation(self, name, prefix, params=None):
        from . import deploy as _deploy
        from .generation import GenerationEngine
        predictor = _deploy.load_generator(prefix, params=params)
        if predictor._params is None:
            raise ServingError(
                "model %r: artifact %r was exported with "
                "include_params=False; serving needs shipped params"
                % (name, prefix))
        engine = GenerationEngine(
            name, predictor,
            breaker=_Breaker(name, self.breaker_threshold,
                             self.breaker_cooldown_ms * 1e-3),
            max_pending=self.max_pending,
            default_deadline_ms=self.default_deadline_ms)
        with self._cond:
            old = self._generation.pop(name, None)
            self._generation[name] = engine
            started = self._started
        if old is not None:
            old.stop(drain=False)
        if started:
            engine.start()
        return engine

    def unregister(self, name):
        with self._cond:
            self._models.pop(name, None)
            engine = self._generation.pop(name, None)
        if engine is not None:
            engine.stop(drain=False)

    def models(self):
        """Registered model names, least recently used first (one-shot
        models; generation models follow)."""
        with self._cond:
            return list(self._models) + list(self._generation)

    def _entry(self, name):
        with self._cond:
            entry = self._models.get(name)
            if entry is not None:
                self._models.move_to_end(name)  # LRU touch
            is_generation = entry is None and name in self._generation
        if is_generation:
            raise ServingError(
                "model %r is a GENERATION model (registered with "
                "generate=True): it serves token streams, not one-shot "
                "predicts — use submit_generate()/generate()" % (name,))
        if entry is None:
            raise ServingError(
                "unknown model %r (registered: %s — evicted models must "
                "be register()ed again)" % (name, self.models()))
        return entry

    def _engine(self, name):
        with self._cond:
            engine = self._generation.get(name)
            is_oneshot = engine is None and name in self._models
        if is_oneshot:
            raise ServingError(
                "model %r is a one-shot predict model: register it with "
                "generate=True (a deploy.export_generation artifact) to "
                "generate — use submit()/predict() for it" % (name,))
        if engine is None:
            raise ServingError(
                "unknown generation model %r (registered: %s)"
                % (name, self.models()))
        return engine

    # ----------------------------------------------------------- compile
    def _compile_entry(self, entry):
        for bucket in entry.buckets:
            if bucket not in entry.programs:
                entry.programs[bucket] = self._compile(entry, bucket)

    def _compile(self, entry, bucket):
        from . import tracing as _tracing
        exported = entry.predictor._exported
        params = entry.predictor._params
        fn = jax.jit(lambda ps, x: exported.call(ps, x))
        pspec = tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                      for p in params)
        xspec = jax.ShapeDtypeStruct((bucket,) + entry.item_shape,
                                     entry.in_dtype)
        t0 = _time.perf_counter()
        with _tracing.span("serving.compile", cat="serving",
                           model=entry.name, bucket=bucket):
            traced = fn.trace(pspec, xspec)
            t1 = _time.perf_counter()
            lowered = traced.lower()
            t2 = _time.perf_counter()
            program = lowered.compile()
            t3 = _time.perf_counter()
        _telemetry.counter("serving.compiles").inc()
        _telemetry.timer("serving.compile_ms").observe(
            (_time.perf_counter() - t0) * 1e3)
        from . import perf as _perf
        rec = _perf.register_compiled(
            "serving", "%s/b%d" % (entry.name, bucket), program,
            phases_ms={"trace_ms": (t1 - t0) * 1e3,
                       "lower_ms": (t2 - t1) * 1e3,
                       "compile_ms": (t3 - t2) * 1e3},
            dtype=str(entry.in_dtype))
        if rec is not None and rec["flops"] > 0:
            # per-request cost from the largest bucket compiled so far —
            # its amortization is what a full batch actually achieves
            prev = entry.cost_per_item
            if prev is None or bucket >= prev["bucket"]:
                entry.cost_per_item = {
                    "flops": rec["flops"] / bucket,
                    "bytes": rec["bytes_accessed"] / bucket,
                    "bucket": bucket,
                }
                _telemetry.gauge(
                    "serving.flops_per_request.%s" % entry.name).set(
                    round(entry.cost_per_item["flops"], 1))
                _telemetry.gauge(
                    "serving.bytes_per_request.%s" % entry.name).set(
                    round(entry.cost_per_item["bytes"], 1))
        return program

    # ------------------------------------------------- quantization drift
    def _load_drift_twin(self, entry):
        """Deserialize ``<prefix>-stats.stablehlo`` (the per-site runtime
        amax program exported next to the int8 artifact) into a jitted
        call over the entry's staged params; ``False`` when the artifact
        ships no twin (pre-PR-18 exports, nothing quantized)."""
        import os
        from jax import export as jexport
        path = entry.prefix + "-stats.stablehlo"
        if not os.path.exists(path):
            return False
        with open(path, "rb") as f:
            stats_exp = jexport.deserialize(f.read())
        return jax.jit(lambda ps, x: stats_exp.call(ps, x))

    def _maybe_sample_drift(self, entry, padded):
        """Every ``quant.drift_every``-th quantized dispatch, re-run the
        dispatched batch through the artifact's stats twin and fold the
        per-site runtime activation amax into the drift EWMA
        (``quant.drift_ratio.<model>.<site>`` gauges, ``quant_drift``
        JSONL events past ``quant.drift_threshold``).  The probe is an
        extra device program per sampled dispatch — off (0) by
        default."""
        every = int(_config.get("quant.drift_every") or 0)
        if every <= 0 or not entry.drift_sites:
            return
        entry.drift_count += 1
        if entry.drift_count % every:
            return
        if entry.drift_call is None:
            entry.drift_call = self._load_drift_twin(entry)
        if entry.drift_call is False:
            return
        from . import numerics as _numerics
        amaxes = _np.asarray(
            entry.drift_call(entry.predictor._params, padded))
        cal = (entry.predictor.meta.get("calibration") or {})
        thresholds = cal.get("thresholds") or {}
        _numerics.update_quant_drift(entry.name, entry.drift_sites,
                                     amaxes, thresholds, entry.drift_ewma)

    # --------------------------------------------------------- lifecycle
    def start(self):
        """Compile every registered ``(model, bucket)`` program eagerly
        (restart-warm via the persistent compile cache) and start the
        supervised
        batcher thread.  Idempotent while running; restartable after
        ``stop`` — unless a previous batcher missed its join deadline and
        is STILL running, in which case this raises instead of racing two
        batchers on one queue (the ``PrefetchingIter.reset`` contract)."""
        from . import tracing as _tracing
        with self._cond:
            if self._started:
                return self
        if self._leaked_thread is not None:
            if self._leaked_thread.is_alive():
                raise ServingError(
                    "a previous batcher thread missed its stop() join "
                    "deadline and is still running; refusing to start a "
                    "second batcher over the same queue — wait for it to "
                    "exit (then start() again) or recreate the Server")
            self._leaked_thread = None
        _runtime.configure_compile_cache()
        with self._cond:
            entries = list(self._models.values())
            engines = list(self._generation.values())
        for entry in entries:
            self._compile_entry(entry)
        for engine in engines:
            engine.start()
        # lifecycle flags flip under _cond: _enqueue and the batcher read
        # them under the same lock, so a submit racing start() sees either
        # the fully-started server or the stopped one — never a torn state
        with self._cond:
            self._stopping = False
            self._batcher_dead = None
            self._last_dispatch_done = _time.perf_counter()
            self._started = True
            # wrap_context: dispatch spans keep the starter's trace
            # parentage across the thread hop (the io.prefetch pattern)
            self._thread = threading.Thread(
                target=_tracing.wrap_context(self._supervise), daemon=True,
                name="mx-serving-batcher")
        self._thread.start()
        _tracing.register_stall_probe(self._probe_name, self._stall_probe)
        _obs.register_health_source(self._probe_name, self._health)
        return self

    def stop(self, drain=True, timeout_s=30.0):
        """Stop the server.  New submits fail immediately; with ``drain``
        (default) every already-queued request is dispatched before the
        batcher exits, so no accepted future is left unresolved; with
        ``drain=False`` pending futures fail promptly with ServingError.
        A batcher that misses the join deadline is remembered — a later
        ``start()`` refuses while it is still alive."""
        with self._cond:
            if not self._started:
                return
            self._stopping = True
            if not drain:
                abandoned = list(self._pending)
                self._pending.clear()
                _telemetry.gauge("serving.pending").set(0)
            else:
                abandoned = []
            self._cond.notify_all()
        for req in abandoned:
            if not req.future.done():
                req.future.set_exception(
                    ServingError("server stopped without drain"))
                _obs.log_access(req.model, "error",
                                request_id=req.trace_id,
                                error="ServingError: server stopped "
                                "without drain")
        thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout_s)
            if thread.is_alive():
                _telemetry.counter("serving.stop_timeout").inc()
                self._leaked_thread = thread
                _LOG.warning(
                    "serving: batcher did not drain within %.1fs and was "
                    "leaked; start() will refuse until it exits",
                    timeout_s)
        from . import tracing as _tracing
        _tracing.unregister_stall_probe(self._probe_name)
        _obs.unregister_health_source(self._probe_name)
        with self._cond:
            engines = list(self._generation.values())
        for engine in engines:
            engine.stop(drain=drain, timeout_s=timeout_s)
        with self._cond:
            self._started = False
            self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------ submit
    def _validate(self, entry, arr):
        if arr.ndim != len(entry.item_shape) + 1:
            raise ValueError(
                "model %r: request rank mismatch — exported signature is "
                "%s, got shape %s" % (entry.name,
                                      entry.predictor.signature(),
                                      tuple(arr.shape)))
        if tuple(arr.shape[1:]) != entry.item_shape:
            raise ValueError(
                "model %r: request item shape %s does not match the "
                "exported signature %s" % (entry.name, tuple(arr.shape),
                                           entry.predictor.signature()))
        if arr.dtype != entry.in_dtype:
            raise ValueError(
                "model %r: request dtype %s does not match the exported "
                "dtype %s" % (entry.name, arr.dtype, entry.in_dtype))
        if arr.shape[0] < 1:
            raise ValueError("model %r: empty request" % (entry.name,))

    def submit(self, name, data, deadline_ms=None):
        """Enqueue one request (any row count) for model ``name``; returns
        a ``concurrent.futures.Future`` resolving to the host numpy output
        rows for exactly the submitted rows (padding is invisible).

        ``deadline_ms`` (default: the ``serving.default_deadline_ms``
        knob; 0 = none) bounds how long the request may sit in queue: a
        request still queued past it completes with
        :class:`DeadlineExceededError` and is never dispatched.  Raises
        :class:`ServerOverloadedError` when the pending queue is at
        ``serving.max_pending`` and :class:`CircuitOpenError` while the
        model's breaker is open."""
        from . import tracing as _tracing
        from .ndarray.ndarray import NDArray
        with _tracing.span("serving.submit", cat="serving",
                           model=name) as sp:
            # the submit span's trace_id rides the request so the access
            # log joins the Chrome trace (None while tracing is off)
            trace_id = sp.trace_id
            entry = self._entry(name)
            arr = _np.asarray(data._data if isinstance(data, NDArray)
                              else data)
            self._validate(entry, arr)
            _telemetry.counter("serving.requests").inc()
            breaker = entry.breaker
            if breaker is not None and breaker.rejects_submit():
                _telemetry.counter("serving.breaker_rejected").inc()
                _obs.log_access(name, "breaker", request_id=trace_id)
                raise CircuitOpenError(
                    "model %r circuit breaker is OPEN after %d "
                    "consecutive dispatch failure(s); failing fast for "
                    "%.0fms more — other models keep serving, retry "
                    "after the cooldown"
                    % (name, breaker.failures,
                       breaker.cooldown_remaining_ms()))
            if deadline_ms is None:
                deadline_ms = self.default_deadline_ms
            deadline_ms = float(deadline_ms or 0.0)
            cap = entry.capacity
            if arr.shape[0] <= cap:
                req = _Request(name, arr, Future(), deadline_ms,
                               trace_id=trace_id)
                fut = self._enqueue(req)
                fut._mx_requests = (req,)
                return fut
            # oversized request: split into cap-row chunks, re-concatenate
            # (each admitted chunk gets its own access record, all sharing
            # the submit span's request_id)
            chunks = [arr[i:i + cap] for i in range(0, arr.shape[0], cap)]
            _telemetry.counter("serving.request_chunks").inc(len(chunks))
            reqs = [_Request(name, c, Future(), deadline_ms,
                             trace_id=trace_id)
                    for c in chunks]
            enqueued = []
            try:
                for r in reqs:
                    self._enqueue(r)
                    enqueued.append(r)
            except BaseException:
                # admission failed mid-way: unwind the sibling chunks so
                # no queued orphan is dispatched for a dead combined future
                self._cancel_queued(enqueued, ServingError(
                    "sibling chunk was rejected; oversized request "
                    "aborted"))
                raise
            futures = [r.future for r in reqs]
            combined = Future()
            remaining = [len(futures)]
            lock = threading.Lock()

            def _one_done(_f):
                with lock:
                    remaining[0] -= 1
                    last = remaining[0] == 0
                if not last or combined.done():
                    return
                try:
                    combined.set_result(_np.concatenate(
                        [f.result() for f in futures], axis=0))
                except BaseException as exc:  # noqa: BLE001
                    combined.set_exception(exc)

            for f in futures:
                f.add_done_callback(_one_done)
            combined._mx_requests = tuple(reqs)
            return combined

    def _enqueue(self, req):
        shed = False
        with self._cond:
            if self._batcher_dead is not None:
                exc = self._batcher_dead
                raise ServingError(
                    "batcher thread crashed (%s: %s) and exhausted its "
                    "restart budget (resilience.retry_attempts); submit() "
                    "rejected — recreate the Server"
                    % (type(exc).__name__, exc))
            if self._stopping or not self._started:
                raise ServingError(
                    "server is %s; submit() rejected"
                    % ("stopping" if self._stopping else "not started"))
            if self.max_pending > 0 \
                    and len(self._pending) >= self.max_pending:
                entry = self._models.get(req.model)
                if entry is not None:
                    entry.shed += 1
                shed = True
            else:
                self._pending.append(req)
                _telemetry.gauge("serving.pending").set(len(self._pending))
                self._cond.notify_all()
        if shed:
            _telemetry.counter("serving.shed_requests").inc()
            _telemetry.counter("serving.shed_requests.%s" % req.model).inc()
            _obs.log_access(req.model, "shed", request_id=req.trace_id)
            raise ServerOverloadedError(
                "server overloaded: %d request(s) already pending "
                "(serving.max_pending=%d); request shed — back off and "
                "retry" % (self.max_pending, self.max_pending))
        return req.future

    def _cancel_queued(self, reqs, exc):
        """Remove still-queued requests and fail their futures with
        ``exc``; requests already popped into a forming batch are left to
        complete.  Returns the list actually cancelled."""
        removed = []
        with self._cond:
            for req in reqs:
                try:
                    self._pending.remove(req)
                except ValueError:
                    continue
                removed.append(req)
            if removed:
                _telemetry.gauge("serving.pending").set(len(self._pending))
        outcome = _access_outcome(exc)
        for req in removed:
            if not req.future.done():
                req.future.set_exception(exc)
                if _obs.access_log_enabled():
                    _obs.log_access(
                        req.model, outcome, request_id=req.trace_id,
                        queue_ms=(_time.perf_counter() - req.t_submit)
                        * 1e3,
                        error="%s: %s" % (type(exc).__name__, exc)
                        if outcome == "error" else None)
        return removed

    def predict(self, name, data, timeout=None, deadline_ms=None):
        """Synchronous convenience: ``submit(...).result(timeout)``.  On
        timeout the queued request is CANCELLED (completed with
        :class:`DeadlineExceededError`, never dispatched) instead of
        left to burn compute for a caller that gave up; a request
        already mid-dispatch completes normally but the call still raises
        DeadlineExceededError."""
        fut = self.submit(name, data, deadline_ms=deadline_ms)
        try:
            return fut.result(timeout)
        except _FutureTimeout:
            reqs = getattr(fut, "_mx_requests", ())
            cancelled = self._cancel_queued(reqs, DeadlineExceededError(
                "predict(%r) timed out after %.3fs; queued request "
                "cancelled before dispatch" % (name, timeout)))
            for req in cancelled:
                self._count_deadline_exceeded(req.model)
            raise DeadlineExceededError(
                "predict(%r) timed out after %.3fs (%d queued chunk(s) "
                "cancelled undispatched)"
                % (name, timeout, len(cancelled))) from None

    # -------------------------------------------------------- generation
    def submit_generate(self, name, prompt, max_new_tokens, eos_id=None,
                        deadline_ms=None, temperature=0.0, top_k=0,
                        top_p=1.0, seed=None, return_replay=False):
        """Enqueue one prompt on generation model ``name``; returns a
        Future resolving to the generated token ids (np.int32, EOS
        included when hit) — or, with ``return_replay`` (an artifact
        exported with ``replay=True``), to ``(ids, replay)``: each
        token's log-probability (``replay["logprobs"]``) and the experts
        each prompt token and each generated token but the last chose
        (``replay["routed_experts"]`` [E blocks, tokens fed, top_k]
        int16), so the sequence can be replayed elsewhere with the same
        routing and compared number for number.  With ``temperature`` 0 (the default) that
        is the eager ``greedy_decode`` stream regardless of co-scheduled
        traffic (up to argmax flips between near-tied bf16 logits on the
        chip); ``temperature`` > 0 samples with optional
        ``top_k`` / ``top_p`` truncation under a per-request ``seed``
        (sampling-enabled v5 artifacts only — fresh entropy when the
        seed is None, a fixed seed replays one deterministic stream).

        The request joins the model's per-iteration scheduler: it
        prefills into a free decode slot as soon as the KV page pool
        covers ``prompt + max_new_tokens``, decodes alongside whatever
        else is in flight and exits mid-flight on EOS/budget.  The PR-7
        admission semantics apply: sheds past ``serving.max_pending``
        (:class:`ServerOverloadedError`), ``deadline_ms`` bounds QUEUE
        time (:class:`DeadlineExceededError`, never prefilled), an open
        breaker fails fast (:class:`CircuitOpenError`)."""
        from . import tracing as _tracing
        with _tracing.span("serving.submit", cat="serving", model=name):
            return self._engine(name).submit(
                prompt, max_new_tokens, eos_id=eos_id,
                deadline_ms=deadline_ms, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                return_replay=return_replay)

    def generate(self, name, prompt, max_new_tokens, eos_id=None,
                 timeout=None, deadline_ms=None, temperature=0.0,
                 top_k=0, top_p=1.0, seed=None):
        """Synchronous convenience:
        ``submit_generate(...).result(timeout)``."""
        fut = self.submit_generate(name, prompt, max_new_tokens,
                                   eos_id=eos_id, deadline_ms=deadline_ms,
                                   temperature=temperature, top_k=top_k,
                                   top_p=top_p, seed=seed)
        try:
            return fut.result(timeout)
        except _FutureTimeout:
            raise DeadlineExceededError(
                "generate(%r) timed out after %.3fs (the sequence keeps "
                "decoding; resubmit with deadline_ms to bound queue "
                "time)" % (name, timeout)) from None

    def _count_deadline_exceeded(self, model):
        _telemetry.counter("serving.deadline_exceeded").inc()
        _telemetry.counter("serving.deadline_exceeded.%s" % model).inc()
        with self._cond:
            entry = self._models.get(model)
            if entry is not None:
                entry.deadline_exceeded += 1

    # ----------------------------------------------------------- batcher
    def _take_fitting(self, model, budget):  # mxlint: holds(_cond)
        """Pop the first queued request for ``model`` with rows <=
        ``budget`` (caller holds the condition lock).  Queued requests
        whose deadline has expired are harvested as a second return value —
        the caller completes them typed, they are never dispatched."""
        now = _time.perf_counter()
        take = None
        dead = []
        for req in self._pending:
            if req.expired(now):
                dead.append(req)
                continue
            if take is None and req.model == model and req.rows <= budget:
                take = req
        for req in dead:
            self._pending.remove(req)
        if take is not None:
            self._pending.remove(take)
        if dead or take is not None:
            _telemetry.gauge("serving.pending").set(len(self._pending))
        return take, dead

    def _expire(self, reqs, reason="expired in queue before dispatch"):
        """Complete deadline-expired requests with the typed error; they
        never reach a program — no compute is wasted on them."""
        for req in reqs:
            self._count_deadline_exceeded(req.model)
            if not req.future.done():
                queued_ms = (_time.perf_counter() - req.t_submit) * 1e3
                req.future.set_exception(DeadlineExceededError(
                    "request for model %r %s (queued %.1fms, deadline "
                    "passed)" % (req.model, reason, queued_ms)))
                _obs.log_access(req.model, "deadline",
                                request_id=req.trace_id,
                                queue_ms=queued_ms)

    def _supervise(self):
        """Batcher supervisor (the thread target): runs ``_loop`` under
        the ``mx.resilience`` retry budget.  Each crash fails the pending
        futures with the causal exception and restarts the loop after
        backoff; once the budget is exhausted the server is marked dead —
        ``submit()`` then fails fast instead of hanging forever."""
        from . import resilience as _resilience
        try:
            _resilience.call_with_retry(self._run_batcher,
                                        kind="serving_batcher")
        except BaseException as exc:  # noqa: BLE001 — budget exhausted
            cause = exc.__cause__ if exc.__cause__ is not None else exc
            with self._cond:
                self._batcher_dead = cause
                pending = list(self._pending)
                self._pending.clear()
                _telemetry.gauge("serving.pending").set(0)
                self._cond.notify_all()
            for req in pending:
                if not req.future.done():
                    req.future.set_exception(cause)
                    _obs.log_access(req.model, "error",
                                    request_id=req.trace_id,
                                    error="%s: %s"
                                    % (type(cause).__name__, cause))
            _LOG.error(
                "serving: batcher crashed and exhausted its restart "
                "budget (%s: %s); all submits now fail fast — recreate "
                "the Server", type(cause).__name__, cause)

    def _run_batcher(self):
        """One supervised batcher incarnation: a clean ``_loop`` return
        (stop/drain) ends the thread; a crash fails every pending future
        with the CAUSAL exception, counts ``serving.batcher_crashes``,
        flight-records the crash, and re-raises as a retryable wrapper so
        the supervisor's ``call_with_retry`` restarts it with backoff."""
        try:
            self._loop()
        except BaseException as exc:  # noqa: BLE001 — supervised crash
            _telemetry.counter("serving.batcher_crashes").inc()
            try:
                from . import tracing as _tracing
                _tracing.record_event(
                    "serving", "batcher_crash",
                    error="%s: %s" % (type(exc).__name__, exc))
            except Exception:  # noqa: BLE001
                pass
            with self._cond:
                pending = list(self._pending)
                self._pending.clear()
                _telemetry.gauge("serving.pending").set(0)
            for req in pending:
                if not req.future.done():
                    req.future.set_exception(exc)
                    _obs.log_access(req.model, "error",
                                    request_id=req.trace_id,
                                    error="%s: %s"
                                    % (type(exc).__name__, exc))
            _LOG.warning(
                "serving: batcher thread crashed (%s: %s); %d pending "
                "future(s) failed with the causal exception; restarting "
                "under the resilience retry budget",
                type(exc).__name__, exc, len(pending))
            raise _BatcherCrashError(
                "serving batcher crashed: %s: %s"
                % (type(exc).__name__, exc)) from exc

    def _loop(self):
        while True:
            with self._cond:
                while not self._pending:
                    if self._stopping:
                        return
                    self._cond.wait(timeout=0.05)
                first = self._pending.popleft()
                _telemetry.gauge("serving.pending").set(len(self._pending))
                entry = self._models.get(first.model)
            if entry is None:  # model evicted with requests in flight
                first.future.set_exception(ServingError(
                    "model %r was evicted while queued" % (first.model,)))
                continue
            if first.expired():
                self._expire([first])
                continue
            batch = [first]
            rows = first.rows
            cap = entry.capacity
            deadline = first.t_submit + self.max_queue_delay_ms * 1e-3
            while rows < cap:
                with self._cond:
                    req, expired = self._take_fitting(first.model,
                                                      cap - rows)
                    wait = None
                    if req is None:
                        remaining = deadline - _time.perf_counter()
                        if remaining <= 0 or self._stopping:
                            wait = 0.0
                        else:
                            wait = min(remaining, 0.005)
                if expired:
                    self._expire(expired)
                if req is not None:
                    batch.append(req)
                    rows += req.rows
                    continue
                if wait == 0.0:
                    break
                with self._cond:
                    self._cond.wait(timeout=wait)
            # batch-formation deadline check: anything that expired while
            # the coalescing window was open completes typed, undispatched
            now = _time.perf_counter()
            dead = [r for r in batch if r.expired(now)]
            if dead:
                self._expire(dead)
                batch = [r for r in batch if not r.expired(now)]
                if not batch:
                    continue
                rows = sum(r.rows for r in batch)
            self._dispatch(entry, batch, rows)

    def _dispatch(self, entry, batch, rows):
        from . import resilience as _resilience
        from . import tracing as _tracing
        t0 = _time.perf_counter()
        bucket = _io.pick_bucket(entry.buckets, rows) or entry.capacity
        for req in batch:
            _telemetry.timer("serving.queue_delay_ms").observe(
                (t0 - req.t_submit) * 1e3)
        breaker = entry.breaker
        if breaker is not None and not breaker.allow_dispatch():
            # open breaker, cooldown still running: fail the batch fast
            # (requests admitted before the breaker opened)
            _telemetry.counter("serving.breaker_rejected").inc(len(batch))
            exc = CircuitOpenError(
                "model %r circuit breaker is OPEN (%d consecutive "
                "dispatch failure(s)); batch failed fast, retry after "
                "the cooldown" % (entry.name, breaker.failures))
            for req in batch:
                if not req.future.done():
                    req.future.set_exception(exc)
                    _obs.log_access(req.model, "breaker",
                                    request_id=req.trace_id,
                                    queue_ms=(t0 - req.t_submit) * 1e3)
            with self._cond:
                self._last_dispatch_done = _time.perf_counter()
            return
        try:
            if _resilience.faults_active("serving_slow") \
                    and _resilience.should_inject("serving_slow"):
                _time.sleep(_SLOW_DISPATCH_S)
            _resilience.inject("serving_dispatch")
            cat = batch[0].data if len(batch) == 1 else \
                _np.concatenate([req.data for req in batch], axis=0)
            padded = _io.pad_rows_to(cat, bucket) if bucket > rows else cat
            with _tracing.span("serving.dispatch", cat="serving",
                               model=entry.name, requests=len(batch),
                               rows=rows, bucket=bucket):
                program = entry.programs.get(bucket)
                if program is None:
                    # a bucket registered after start(), or a fixed-batch
                    # artifact's single shape — compile once, then cached
                    program = entry.programs[bucket] = \
                        self._compile(entry, bucket)
                out = program(entry.predictor._params, padded)
            if isinstance(out, (tuple, list)):
                out = out[0]
            host = _np.asarray(out)
        except BaseException as exc:  # noqa: BLE001 — fail the batch's
            # futures (and feed the breaker), never the batcher thread
            _telemetry.counter("serving.dispatch_errors").inc()
            if breaker is not None:
                breaker.record_failure()
            err = "%s: %s" % (type(exc).__name__, exc)
            for req in batch:
                if not req.future.done():
                    req.future.set_exception(exc)
                    _obs.log_access(req.model, "error",
                                    request_id=req.trace_id,
                                    queue_ms=(t0 - req.t_submit) * 1e3,
                                    error=err)
            with self._cond:
                self._last_dispatch_done = _time.perf_counter()
            return
        if breaker is not None:
            breaker.record_success()
        t1 = _time.perf_counter()
        access_on = _obs.access_log_enabled()
        row_nbytes = host.nbytes // max(1, host.shape[0]) if access_on \
            else 0
        ofs = 0
        for req in batch:
            if not req.future.done():
                req.future.set_result(host[ofs:ofs + req.rows])
                if access_on:
                    _obs.log_access(req.model, "ok",
                                    request_id=req.trace_id,
                                    queue_ms=(t0 - req.t_submit) * 1e3,
                                    dispatch_ms=(t1 - t0) * 1e3,
                                    bytes=req.rows * row_nbytes)
            ofs += req.rows
            _telemetry.timer("serving.request_ms").observe(
                (t1 - req.t_submit) * 1e3)
        _telemetry.counter("serving.batch_dispatches").inc()
        if entry.quantized:
            _telemetry.counter("serving.quantized_dispatches").inc()
            try:
                self._maybe_sample_drift(entry, padded)
            except Exception as exc:  # noqa: BLE001 — the probe is
                # observability; it must never fail a served batch
                _LOG.warning("serving: drift probe failed for %r: %s: %s",
                             entry.name, type(exc).__name__, exc)
        _telemetry.timer("serving.batch_fill").observe(rows / bucket)
        _telemetry.timer("serving.dispatch_ms").observe((t1 - t0) * 1e3)
        with self._cond:
            self._last_dispatch_done = t1
        # one JSONL record per dispatch (no-op when the sink is off);
        # tools/telemetry_report.py folds these into the serving table,
        # the queue-delay anomaly and the overload-shedding anomaly
        if _telemetry.enabled():
            cost = entry.cost_per_item
            _telemetry.log_event(
                "serving", model=entry.name, requests=len(batch),
                rows=rows, bucket=bucket, quantized=entry.quantized,
                fill=round(rows / bucket, 4),
                queue_delay_ms=round(max(
                    (t0 - req.t_submit) * 1e3 for req in batch), 4),
                wall_ms=round((t1 - t0) * 1e3, 4),
                budget_ms=self.max_queue_delay_ms,
                shed=entry.shed,
                deadline_exceeded=entry.deadline_exceeded,
                # useful work in this dispatch, from the registered
                # program's compile-time cost analysis (mx.perf)
                flops=round(rows * cost["flops"], 1)
                if cost is not None else None,
                bytes=round(rows * cost["bytes"], 1)
                if cost is not None else None,
                breaker=breaker.state if breaker is not None else "closed")

    # ---------------------------------------------------------- watchdog
    def _stall_probe(self, interval_s):
        """PR-3 watchdog hook (``tracing.register_stall_probe``): when
        the queue is non-empty but no dispatch has completed within the
        watchdog interval, return a flight-recordable snapshot — open
        requests, breaker states, batcher liveness.  None while
        healthy."""
        now = _time.perf_counter()
        with self._cond:
            if not self._pending:
                return None
            stalled_s = now - self._last_dispatch_done
            if stalled_s < interval_s:
                return None
            open_reqs = [
                {"model": r.model, "rows": r.rows,
                 "queued_s": round(now - r.t_submit, 4),
                 "deadline_in_s": round(r.deadline - now, 4)
                 if r.deadline is not None else None}
                for r in list(self._pending)[:16]]
            pending = len(self._pending)
            breakers = {name: e.breaker.state if e.breaker is not None
                        else "closed"
                        for name, e in self._models.items()}
            thread = self._thread
        return {"pending": pending,
                "since_last_dispatch_s": round(stalled_s, 4),
                "batcher_alive": bool(thread is not None
                                      and thread.is_alive()),
                "open_requests": open_reqs,
                "breakers": breakers}

    def _health(self):
        """mx.obs health source (registered in :meth:`start`): the
        ``/healthz`` slice of this server — batcher liveness, per-model
        breaker state, per-engine decode-loop liveness and KV-pool
        saturation.  KV saturation is reported but does NOT flip
        ``healthy`` (transient pool exhaustion under load is expected
        back-pressure, not an outage)."""
        with self._cond:
            breakers = {name: e.breaker.state if e.breaker is not None
                        else "closed"
                        for name, e in self._models.items()}
            batcher_dead = self._batcher_dead
            started = self._started
            thread = self._thread
            pending = len(self._pending)
            engines = dict(self._generation)
        reasons = []
        if batcher_dead is not None:
            reasons.append("batcher_dead")
        batcher_alive = bool(thread is not None and thread.is_alive())
        if started and not batcher_alive:
            reasons.append("batcher_thread_dead")
        for name, state in breakers.items():
            if state == "open":
                reasons.append("breaker_open:%s" % name)
        generation = {}
        for name, eng in engines.items():
            s = eng.stats()
            if started and not s["engine_alive"]:
                reasons.append("engine_dead:%s" % name)
            if s["breaker"] == "open":
                reasons.append("breaker_open:%s" % name)
            generation[name] = {
                "engine_alive": s["engine_alive"],
                "breaker": s["breaker"],
                "queued": s["queued"],
                "active": s["active"],
                "kv_pages": s["kv_pages"],
                "kv_pages_free": s["kv_pages_free"],
                "kv_saturated": s["kv_pages_free"] == 0,
            }
        return {
            "healthy": not reasons,
            "reasons": reasons,
            "started": started,
            "pending": pending,
            "batcher_alive": batcher_alive,
            "breakers": breakers,
            "generation": generation,
        }

    # ------------------------------------------------------------- stats
    def stats(self):
        """Serving-slice snapshot of the telemetry registry (counters,
        gauges and timer histograms whose names start with ``serving.``)
        plus live server state: registered models, queue depth, breaker
        states, batcher liveness."""
        snap = _telemetry.snapshot()
        with self._cond:
            breakers = {name: e.breaker.state if e.breaker is not None
                        else "closed"
                        for name, e in self._models.items()}
            quantized = {name: e.quantized
                         for name, e in self._models.items()}
            cost_per_item = {name: dict(e.cost_per_item)
                             if e.cost_per_item is not None else None
                             for name, e in self._models.items()}
            pending = len(self._pending)
            thread = self._thread
            engines = dict(self._generation)
        generation = {name: eng.stats() for name, eng in engines.items()}
        return {
            "counters": {k: v for k, v in snap["counters"].items()
                         if k.startswith("serving.")},
            "generation": generation,
            "gauges": {k: v for k, v in snap["gauges"].items()
                       if k.startswith("serving.")},
            "timers": {k: v for k, v in snap["timers"].items()
                       if k.startswith("serving.")},
            "models": self.models(),
            "quantized": quantized,
            "cost_per_item": cost_per_item,
            "pending": pending,
            "breakers": breakers,
            "batcher_alive": bool(thread is not None and thread.is_alive()),
        }


def load_server(prefixes, **kwargs):
    """Convenience: build, register and start a server from
    ``{name: prefix}``.  All-or-nothing: if any ``register()`` (or the
    ``start()``) raises, previously registered models — and with them any
    staged params / compiled programs — are unwound before the exception
    propagates, so a partial failure cannot keep device memory alive
    through the raised traceback."""
    srv = Server(**kwargs)
    registered = []
    try:
        for name, prefix in dict(prefixes).items():
            srv.register(name, prefix)
            registered.append(name)
        return srv.start()
    except BaseException:
        for name in registered:
            try:
                srv.unregister(name)
            except Exception:  # noqa: BLE001 — unwind is best-effort
                pass
        raise
