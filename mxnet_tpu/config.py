"""``mx.config`` — the typed, documented runtime-knob registry.

Reference: ~80 ``MXNET_*`` environment variables read via dmlc::GetEnv at
point of use and documented in
docs/static_site/src/pages/api/faq/env_var.md:43-258 (engine type/threads,
memory-pool knobs, bulk-exec sizes, kvstore tree/bigarray, profiler
autostart, cuDNN autotune ...).

TPU-native re-design: one declarative registry.  Every knob has a TYPE, a
DEFAULT, its ENV VAR, and a DOCSTRING — `mx.config.describe()` prints the
whole table (the env_var.md property, kept in code so it can't go stale),
`mx.config.get/set` read and override programmatically, and env variables
are re-read lazily so launcher scripts keep working.  Knobs whose reference
meaning is owned by XLA on TPU (memory pools, cuDNN autotune) are documented
as such rather than silently dropped.
"""
from __future__ import annotations

import os
from collections import namedtuple

__all__ = ["register_knob", "get", "set", "unset", "source", "describe",
           "knobs", "Knob"]

Knob = namedtuple("Knob", ["name", "env", "type", "default", "doc"])

_KNOBS = {}
_OVERRIDES = {}
_ON_SET = {}  # knob name -> callback(value), fired after set()

# Knobs that never bump the cache epoch on change.  Everything these knobs
# influence is either pure host-side state or threaded into program-cache
# keys as its OWN key element (numerics.capture's variant token), so both
# knob states coexist in the caches and a toggle must not evict compiled
# programs.  Side-effect hooks still fire.
_EPOCH_NEUTRAL = {"numerics.capture", "quant.drift_every",
                  "quant.drift_threshold",
                  # elastic state is pure host-side bookkeeping: restart
                  # generation / heartbeat cadence must not evict programs
                  "elastic.dir", "elastic.generation",
                  "elastic.heartbeat_s", "elastic.on_peer_loss"}


def register_knob(name, env, type_, default, doc):
    """Declare a knob.  `env` is its environment variable; `type_` one of
    bool/int/float/str."""
    _KNOBS[name] = Knob(name, env, type_, default, doc)
    return _KNOBS[name]


def _parse(knob, raw):
    if knob.type is bool:
        return raw not in ("0", "false", "False", "")
    return knob.type(raw)


def get(name):
    """Current value: programmatic override > env var > default."""
    knob = _KNOBS[name]
    if name in _OVERRIDES:
        return _OVERRIDES[name]
    raw = os.environ.get(knob.env)
    if raw is not None:
        return _parse(knob, raw)
    return knob.default


def source(name):
    """Where the current value of ``name`` comes from: ``'override'``
    (programmatic set()), ``'env'`` (its environment variable) or
    ``'default'`` (the registry default).  Policy code uses this to
    distinguish an operator's explicit choice from a shipped default:
    the kernel tier keeps interpreted backends on the XLA lowering only
    while ``kernels.enabled`` is still at its default."""
    knob = _KNOBS[name]
    if name in _OVERRIDES:
        return "override"
    if os.environ.get(knob.env) is not None:
        return "env"
    return "default"


def set(name, value):  # noqa: A001 — reference-parity name
    if name not in _KNOBS:
        raise KeyError("unknown knob %r (see mx.config.describe())" % name)
    knob = _KNOBS[name]
    # strings coerce through the same parser as env vars, so
    # set('x', '0') and ENV_X=0 agree (notably for bools)
    parsed = _parse(knob, value) if isinstance(value, str) \
        else knob.type(value)
    hook = _ON_SET.get(name)
    # a set that changes neither the value nor its source() (the same
    # override again) keeps compiled-program caches; one that makes a
    # default or env value an explicit choice retraces, since routing
    # reads the source (kernels._route_reason).  The side-effect hook
    # re-fires either way, so external state it mirrors (jax_enable_x64)
    # re-syncs even if someone flipped it behind the knob's back
    changed = parsed != get(name) or name not in _OVERRIDES
    _OVERRIDES[name] = parsed
    if changed and name not in _EPOCH_NEUTRAL:
        global _EPOCH
        _EPOCH += 1
    if hook is not None:
        hook(parsed)


def unset(name):
    """Drop a programmatic override so ``name`` falls back to its env
    var / registry default — including its *source*, so dropping an
    override bumps the epoch; the side-effect hook re-fires only when
    the effective value actually changes."""
    if name not in _KNOBS:
        raise KeyError("unknown knob %r (see mx.config.describe())" % name)
    if name not in _OVERRIDES:
        return
    old = get(name)
    del _OVERRIDES[name]
    new = get(name)
    if name not in _EPOCH_NEUTRAL:
        global _EPOCH
        _EPOCH += 1
    hook = _ON_SET.get(name)
    if new != old and hook is not None:
        hook(new)


# Bumped by every set() / unset() that changes a knob's value or its
# source(): compiled-program caches that bake knob values in at
# trace time (Executor forward programs, _CachedGraph) key on epoch() so a
# knob change invalidates them instead of silently not applying.
_EPOCH = 0


def epoch():
    return _EPOCH


def knobs():
    return dict(_KNOBS)


def describe():
    """The env_var.md table, generated from the registry."""
    lines = ["%-28s %-34s %-8s %-10s %s" % ("Knob", "Env var", "Type",
                                            "Default", "Doc")]
    for k in sorted(_KNOBS.values()):
        lines.append("%-28s %-34s %-8s %-10s %s"
                     % (k.name, k.env, k.type.__name__, k.default, k.doc))
    return "\n".join(lines)


# ----------------------------------------------------------- the registry
# engine / dispatch (reference env_var.md:50-68)
register_knob(
    "engine.type", "MXNET_ENGINE_TYPE", str, "ThreadedEnginePerDevice",
    "NaiveEngine forces synchronous per-op completion (serial debug mode); "
    "the default maps to jax async dispatch.")
register_knob(
    "engine.bulk_size", "MXNET_ENGINE_BULK_SIZE", int, 15,
    "Reference bulking segment size; informational on TPU — one jitted "
    "step is a single fused program, bulking has no residual role.")

register_knob(
    "model_store.root", "MXNET_HOME", str, "",
    "root of the local pretrained-weight cache (models live under "
    "<root>/models); empty = ~/.mxnet.  The reference's env var, honored "
    "by gluon.model_zoo.model_store on this zero-egress target.")

# distributed rendezvous (parallel/__init__.py)
register_knob(
    "dist.coordinator", "MXTPU_COORDINATOR", str, "",
    "host:port of the jax.distributed coordinator (the ps-lite scheduler "
    "analog); set by tools/launch.py.")
register_knob(
    "dist.num_processes", "MXTPU_NUM_PROCESSES", int, 1,
    "world size for multi-process jax.distributed runs.")
register_knob(
    "dist.process_id", "MXTPU_PROCESS_ID", int, 0,
    "this process's rank in the multi-process run.")

# numerics: the recorded x64 POLICY.  TPU-native default is x64 OFF —
# float64 has no MXU path and jax truncates it to float32 (the warnings
# numpy-parity sweeps see are that truncation).  Scripts that genuinely
# need f64 math (host-side numerics) opt in explicitly; flipping the knob
# calls jax.config.update("jax_enable_x64", ...), which only takes full
# effect before arrays are created.
register_knob(
    "numpy.enable_x64", "MXTPU_ENABLE_X64", bool, False,
    "enable 64-bit dtypes in the jax backend (mx.np float64/int64 stay "
    "true 64-bit instead of truncating to 32-bit). TPU compute should "
    "stay 32/16-bit: f64 is emulated and slow on MXU hardware.")


def _apply_x64(value):
    import jax
    jax.config.update("jax_enable_x64", bool(value))


_ON_SET["numpy.enable_x64"] = _apply_x64

# honor the documented env var at import: the recorded policy and the jax
# state must never diverge
if os.environ.get("MXTPU_ENABLE_X64"):
    _apply_x64(get("numpy.enable_x64"))


def enable_x64(flag=True):
    """Programmatic x64 switch (pairs with the numpy.enable_x64 knob)."""
    set("numpy.enable_x64", bool(flag))


# conv internal layout experiment (docs/PERF_NOTES.md): "native" keeps the
# NCHW dimension numbers; "NHWC" transposes inside the Convolution lowering
# so channels ride the TPU lane dimension (XLA cancels the transposes
# between adjacent convs).  Knob-gated because the win is model-shape
# dependent.
register_knob(
    "conv.internal_layout", "MXTPU_CONV_LAYOUT", str, "native",
    "internal conv layout: native (NCHW dimension numbers) or NHWC "
    "(channels-last inside the lowering; logical API stays NCHW).")
register_knob(
    "conv.weights_layout", "MXTPU_CONV_WEIGHTS_LAYOUT", str, "ref",
    "conv weight storage inside SPMDTrainer: ref (OIHW — the reference "
    "and checkpoint layout) or HWIO (channels-last END-TO-END: weights, "
    "their gradients and optimizer state all live channels-last, so the "
    "HBM-bound 1x1 convs never pay a weight relayout; docs/PERF_NOTES.md). "
    "Single-file checkpoints are always converted to OIHW on save (and "
    "back on load) so they stay interchangeable; sharded orbax "
    "checkpoints store the active layout and must be reloaded under the "
    "same knob.")

# symbolic Module executor (the CachedOp static_alloc analog)
register_knob(
    "module.fused_step", "MXTPU_MODULE_FUSED_STEP", str, "auto",
    "symbolic Module train-step mode: auto (default — Module.fit / "
    "forward_backward+update fuse forward, backward and the optimizer "
    "update into ONE donated jit program per shape signature whenever the "
    "optimizer is jit-traceable) or off (always the stage-at-a-time eager "
    "path; also forced by NaiveEngine).  docs/PERF_NOTES.md.")

# profiler (reference env_var.md:201-205)
register_knob(
    "profiler.autostart", "MXNET_PROFILER_AUTOSTART", bool, False,
    "start the profiler at import, mirroring MXNET_PROFILER_AUTOSTART.")
register_knob(
    "profiler.filename", "MXNET_PROFILER_FILENAME", str, "profile.json",
    "default Chrome-trace output path for mx.profiler.dump().")

# telemetry step log (docs/OBSERVABILITY.md)
register_knob(
    "telemetry.sink", "MXNET_TPU_TELEMETRY", str, "",
    "structured step-event log sink: 'jsonl:<path>' appends one JSON "
    "record per train step (Module/SPMDTrainer/gluon.Trainer) with wall "
    "time, dispatch path, compile/host-sync deltas, throughput, and the "
    "device memory watermark; summarize with tools/telemetry_report.py. "
    "Empty (default) disables the log; the metrics registry itself stays "
    "on at near-zero cost.")


def _apply_telemetry_sink(value):
    from . import telemetry
    telemetry.configure_sink(value)


_ON_SET["telemetry.sink"] = _apply_telemetry_sink

# causal tracing + hang watchdog (docs/OBSERVABILITY.md)
register_knob(
    "tracing.sink", "MXNET_TPU_TRACE", str, "",
    "causal span trace sink: 'chrome:<path>' streams framework spans "
    "(step/fwd/bwd/opt-update/prefetch/push/pull/allreduce, with "
    "contextvars-propagated parent/child links that survive thread hops) "
    "as Chrome trace-event JSON; merge with a jax.profiler device capture "
    "via tools/trace_merge.py. Empty (default) disables — span() is then "
    "only the jax.profiler.TraceAnnotation (no ids, no registry, no lock): "
    "any profiler session still finds the spans.")
register_knob(
    "tracing.watchdog", "MXNET_TPU_WATCHDOG", float, 0.0,
    "hang-watchdog deadline in seconds: > 0 starts a daemon thread that, "
    "when no train step completes within the deadline, dumps thread "
    "stacks, open spans with ages, the flight-recorder event ring, device "
    "memory and gauge snapshots to a timestamped watchdog_report_*.json — "
    "then keeps the job running. 0 (default) disables.")
register_knob(
    "tracing.watchdog_dir", "MXNET_TPU_WATCHDOG_DIR", str, "",
    "directory for watchdog flight-recorder reports; empty (default) = "
    "the current working directory.")
register_knob(
    "tracing.ring_size", "MXNET_TPU_TRACE_RING", int, 256,
    "flight-recorder bound: how many recent span/step events the "
    "in-memory ring keeps for the watchdog report.")


def _apply_tracing_sink(value):
    from . import tracing
    tracing.configure_sink(value)


def _apply_tracing_watchdog(value):
    from . import tracing
    tracing.configure_watchdog(value, report_dir=get("tracing.watchdog_dir"))


def _apply_tracing_ring(value):
    from . import tracing
    tracing.configure_ring(value)


def _apply_tracing_watchdog_dir(_value):
    # the dir must land even when only it changes — an on-demand
    # dump_watchdog_report (e.g. the nanguard abort) reads it without the
    # watchdog deadline ever being armed
    _apply_tracing_watchdog(get("tracing.watchdog"))


_ON_SET["tracing.sink"] = _apply_tracing_sink
_ON_SET["tracing.watchdog"] = _apply_tracing_watchdog
_ON_SET["tracing.watchdog_dir"] = _apply_tracing_watchdog_dir
_ON_SET["tracing.ring_size"] = _apply_tracing_ring

# operational plane: exporter + access log + SLOs (docs/OBSERVABILITY.md)
register_knob(
    "obs.listen", "MXNET_TPU_OBS_LISTEN", str, "",
    "operational-plane exporter address as 'host:port' (port 0 binds an "
    "ephemeral port; obs.exporter_address() reports it): starts a daemon "
    "HTTP thread serving /metrics (Prometheus text rendered from the "
    "telemetry registry, plus SLO burn rates), /healthz (breaker states, "
    "batcher/engine liveness, KV-pool saturation, last-step age; non-200 "
    "when unhealthy), and /varz (effective knobs with provenance). Empty "
    "(default) disables — no thread, no socket.")
register_knob(
    "obs.access_log", "MXNET_TPU_OBS_ACCESS_LOG", str, "",
    "per-request access log sink: 'jsonl:<path>' appends one JSON record "
    "per serving/generation request (request_id = the generation "
    "engine's own request number, or a one-shot request's span trace_id; "
    "trace_id where a causal span enclosed a generation submit; model, "
    "queue_ms, dispatch_ms, ttft_ms, tokens, bytes, outcome "
    "ok|shed|deadline|breaker|error) that joins against the tracing.sink "
    "Chrome trace on trace_id. Empty (default) disables — the serving hot "
    "path gains one predicate per request.")
register_knob(
    "obs.slo", "MXNET_TPU_OBS_SLO", str, "",
    "serving SLO objectives as 'key=value[,key=value...]': "
    "'availability=99.9' (percent of requests that must not end "
    "shed/deadline/breaker/error) and 'latency_p99_ms=50' (windowed p99 "
    "bound on the timer named by 'timer=', default serving.request_ms). "
    "Arms multi-window burn-rate tracking (5m/1h fast, 30m/6h slow) "
    "exposed on /metrics and obs.slo_status(). Empty (default) disables.")


def _apply_obs_listen(value):
    from . import obs
    try:
        obs.configure_listen(value)
    except (ValueError, OSError):
        # reject at set() time and revert (the perf.profile pattern): a
        # typo'd address or un-bindable port must not linger as the override
        _OVERRIDES.pop("obs.listen", None)
        raise


def _apply_obs_access_log(value):
    from . import obs
    try:
        obs.configure_access_log(value)
    except ValueError:
        _OVERRIDES.pop("obs.access_log", None)
        raise


def _apply_obs_slo(value):
    from . import obs
    try:
        obs.configure_slo(value)
    except ValueError:
        _OVERRIDES.pop("obs.slo", None)
        raise


_ON_SET["obs.listen"] = _apply_obs_listen
_ON_SET["obs.access_log"] = _apply_obs_access_log
_ON_SET["obs.slo"] = _apply_obs_slo

# compiled-program cost attribution (docs/OBSERVABILITY.md)
register_knob(
    "perf.profile", "MXNET_TPU_PROFILE", str, "",
    "periodic device-trace auto-capture: 'step:N' runs one full train "
    "step under a jax.profiler trace every N completed steps (written "
    "under perf.profile_dir, folded with the chrome span sink through "
    "tools/trace_merge.py when tracing.sink is active). Empty (default) "
    "disables — the mx.perf step hook then costs one gauge update.")
register_knob(
    "perf.profile_dir", "MXNET_TPU_PROFILE_DIR", str, "",
    "directory for MXNET_TPU_PROFILE step captures (one "
    "perf_step_<source>_<n>/ subdir per capture); empty (default) = the "
    "current working directory.")


def _apply_perf_profile(value):
    from . import perf
    try:
        perf.configure_profile(value)
    except ValueError:
        # reject at set() time and revert (the nanguard pattern): a typo'd
        # spec must not linger as the stored override
        _OVERRIDES.pop("perf.profile", None)
        raise


_ON_SET["perf.profile"] = _apply_perf_profile

# fault tolerance (docs/RESILIENCE.md)
register_knob(
    "resilience.nanguard", "MXNET_TPU_NANGUARD", str, "",
    "non-finite step guard folded into the fused train steps: 'skip' "
    "drops the optimizer update on steps whose loss/grads go NaN/Inf "
    "(params keep their last-good values, <source>.nonfinite_steps "
    "counts them) and aborts-with-checkpoint after nanguard_patience "
    "consecutive bad steps; 'abort' aborts on the first bad step. The "
    "all-finite check runs on device — no host sync on the happy path. "
    "Empty (default) disables.")
register_knob(
    "resilience.nanguard_patience", "MXNET_TPU_NANGUARD_PATIENCE", int, 25,
    "consecutive non-finite steps tolerated under nanguard=skip before "
    "the watchdog flight recorder dumps and the run aborts with a "
    "checkpoint (abort mode always uses 1).")
register_knob(
    "resilience.on_preempt", "MXNET_TPU_ON_PREEMPT", str, "",
    "'save_and_exit' installs SIGTERM/SIGINT handlers: the training "
    "loops finish the in-flight step, checkpoint, flush telemetry/trace "
    "sinks and exit 0 (a second signal kills immediately). Empty "
    "(default) leaves signals untouched.")
register_knob(
    "resilience.faults", "MXNET_TPU_FAULTS", str, "",
    "deterministic fault-injection spec, e.g. "
    "'io:0.05,ckpt_write:1@step=3,nan:1@step=7' — kind:probability per "
    "opportunity, or kind:count@step=N (1-based). Kinds: io (batch "
    "fetch), kvstore (push/pull), ckpt_write (inside atomic_write), nan "
    "(poison a training batch), serving_dispatch (fail an mx.serving "
    "batch dispatch — feeds the circuit breaker), serving_slow (delay a "
    "serving dispatch ~250ms — stall/deadline/shed testing), "
    "peer_preempt (simulate a peer preemption inside mx.elastic's "
    "cluster agreement — every rank checkpoints and exits together), "
    "dcn_push (fail a kvstore DCN allreduce hop — exercises "
    "retry/backoff on the slow axis). Empty (default) disables the "
    "harness.")
register_knob(
    "resilience.fault_seed", "MXNET_TPU_FAULT_SEED", int, 0,
    "seed for the fault-injection RNGs and retry jitter; two runs with "
    "the same spec+seed inject identical faults.")
register_knob(
    "resilience.retry_attempts", "MXNET_TPU_RETRY_ATTEMPTS", int, 3,
    "total attempts for retryable I/O (io batch fetch, kvstore "
    "push/pull, checkpoint writes) on OSError; retries bump "
    "resilience.retries[.<kind>].")
register_knob(
    "resilience.retry_base_s", "MXNET_TPU_RETRY_BASE_S", float, 0.05,
    "first retry backoff in seconds; doubles per attempt with seeded "
    "jitter, capped at 2s.")
register_knob(
    "resilience.ckpt_every_n_steps", "MXNET_TPU_CKPT_EVERY", int, 0,
    "CheckpointManager default cadence: maybe_save() writes every N "
    "steps (0 = only explicit save() calls).")
register_knob(
    "resilience.ckpt_keep", "MXNET_TPU_CKPT_KEEP", int, 3,
    "CheckpointManager retention: keep the newest K checkpoints, prune "
    "older ones (<=0 keeps everything).")


def _apply_resilience_nanguard(value):
    v = (value or "").strip()
    if v not in ("", "skip", "abort"):
        # reject at set() time and revert, so a typo can't silently leave
        # training unguarded (or half-guarded) until the next step
        _OVERRIDES.pop("resilience.nanguard", None)
        raise ValueError("resilience.nanguard must be '', 'skip' or "
                         "'abort', got %r" % (value,))


def _apply_resilience_faults(_value):
    from . import resilience
    resilience.configure_faults()


def _apply_resilience_preempt(value):
    from . import resilience
    resilience.configure_preemption(value)


def _apply_resilience_retry(_value):
    from . import resilience
    resilience.configure_retry()


_ON_SET["resilience.nanguard"] = _apply_resilience_nanguard
_ON_SET["resilience.faults"] = _apply_resilience_faults
_ON_SET["resilience.fault_seed"] = _apply_resilience_faults
_ON_SET["resilience.on_preempt"] = _apply_resilience_preempt
_ON_SET["resilience.retry_attempts"] = _apply_resilience_retry
_ON_SET["resilience.retry_base_s"] = _apply_resilience_retry

# kvstore / gradient sync
register_knob(
    "kvstore.grad_compression_threshold",
    "MXTPU_GRAD_COMPRESSION_THRESHOLD", float, 0.5,
    "threshold for 2-bit gradient compression (kvstore."
    "set_gradient_compression), reference gradient_compression.cc:44.")
register_knob(
    "kvstore.grad_compress", "MXNET_TPU_GRAD_COMPRESS", str, "",
    "gradient-sync wire compression: '2bit' folds two_bit_compress -> "
    "allreduce codes -> decompress + error-feedback residual into (a) the "
    "kvstore dist_sync DCN hop (packed 4 codes/byte, 16x fewer wire bytes "
    "than f32) and (b) the fused SPMD train step on meshes that declare a "
    "'dcn' axis (ICI psum stays full-precision). Residuals ride as "
    "donated opt-state so compression composes with nanguard rollback. "
    "Telemetry: kvstore.compressed_bytes / kvstore.compression_ratio. "
    "Empty (default) disables.")


def _apply_kvstore_grad_compress(value):
    v = (value or "").strip()
    if v not in ("", "2bit"):
        # reject at set() time and revert (the nanguard pattern): a typo'd
        # codec must not silently train uncompressed while claiming otherwise
        _OVERRIDES.pop("kvstore.grad_compress", None)
        raise ValueError("kvstore.grad_compress must be '' or '2bit', "
                         "got %r" % (value,))


_ON_SET["kvstore.grad_compress"] = _apply_kvstore_grad_compress

# multi-host elasticity (docs/RESILIENCE.md "Multi-host elasticity")
register_knob(
    "elastic.dir", "MXTPU_ELASTIC_DIR", str, "",
    "state directory for elastic multi-host runs (set by tools/launch.py "
    "--elastic): heartbeat lease files, preemption flags and the "
    "coordinated checkpoint protocol live here. Non-empty activates "
    "mx.elastic's per-step cluster preemption agreement.")
register_knob(
    "elastic.generation", "MXTPU_ELASTIC_GENERATION", int, 0,
    "restart generation of an elastic run (0 = first launch); exported "
    "by tools/launch.py --elastic so workers and fault rules can "
    "distinguish a fresh world from a re-formed one.")
register_knob(
    "elastic.heartbeat_s", "MXTPU_ELASTIC_HEARTBEAT_S", float, 1.0,
    "heartbeat interval for the elastic lease loop; a peer whose lease "
    "file goes stale for 5x this interval is declared lost "
    "(elastic.peer_lease_expired).")
register_knob(
    "elastic.on_peer_loss", "MXTPU_ELASTIC_ON_PEER_LOSS", str, "abort",
    "reaction when a peer's heartbeat lease expires: 'abort' (default) "
    "flushes sinks and exits with code 75 so the elastic launcher can "
    "re-form the world (rescues ranks blocked in a collective on a dead "
    "peer); 'flag' only records it (HeartbeatMonitor.peer_lost) for "
    "harness/test inspection.")


def _apply_elastic_on_peer_loss(value):
    v = (value or "").strip()
    if v not in ("abort", "flag"):
        _OVERRIDES.pop("elastic.on_peer_loss", None)
        raise ValueError("elastic.on_peer_loss must be 'abort' or 'flag', "
                         "got %r" % (value,))


_ON_SET["elastic.on_peer_loss"] = _apply_elastic_on_peer_loss

# data loading / device-resident input pipeline (docs/PERF_NOTES.md)
register_knob(
    "io.device_prefetch", "MXNET_TPU_IO_DEVICE_PREFETCH", bool, True,
    "DevicePrefetcher staging: True (default) pads + device_puts each "
    "batch on the background prefetch thread so the training loop "
    "receives device-resident, donation-ready arrays and never blocks on "
    "H2D in steady state; False degrades DevicePrefetcher to host-side "
    "prefetch only (A/B baseline and debugging).")
register_knob(
    "io.prefetch_depth", "MXNET_TPU_IO_PREFETCH_DEPTH", int, 2,
    "default ring depth for DevicePrefetcher/PrefetchingIter: how many "
    "staged batches the background thread keeps ahead of the consumer "
    "(the dmlc::ThreadedIter buffer count analog). With jax async "
    "dispatch 2 is enough to hide host batch prep; raise it for bursty "
    "decode pipelines.")
register_knob(
    "io.decode_workers", "MXNET_TPU_IO_DECODE_WORKERS", int, 0,
    "thread-pool size for per-sample decode/augment in mx.image.ImageIter "
    "(RecordIO/image paths): 0 or 1 (default 0) decodes serially on the "
    "batch thread; N > 1 maps samples over N workers (PIL decode releases "
    "the GIL; RecordIO random reads are lock-serialized per file handle). "
    "Each worker read retries with backoff and draws 'io' injected faults "
    "— the reference's preprocess_threads analog.")
register_knob(
    "io.pad_buckets", "MXNET_TPU_IO_PAD_BUCKETS", str, "pow2",
    "DevicePrefetcher bucketed-padding policy for ragged (short) batches: "
    "'full' wrap-pads every batch to the iterator batch_size (ONE shape "
    "per epoch — zero recompiles), 'pow2' (default) pads up to the next "
    "power-of-two row count (<= log2 distinct shapes), 'off' stages "
    "batches at their natural shape (each ragged tail compiles a fresh "
    "program). DataBatch.pad counts the fill rows so losses/metrics can "
    "mask them.")
register_knob(
    "dataloader.start_method", "MXTPU_DATALOADER_START_METHOD", str,
    "spawn",
    "multiprocessing start method for DataLoader process workers: spawn "
    "(default — safe with the multithreaded jax parent), forkserver, or "
    "fork (opt-in: cheapest, but forking a live XLA runtime risks "
    "deadlock; reference dataloader.py:558 is likewise spawn-capable).")

# INT8 post-training quantization (docs/QUANTIZATION.md)
register_knob(
    "quant.calib_mode", "MXNET_TPU_QUANT_CALIB_MODE", str, "entropy",
    "default mx.quantization calibration mode: 'entropy' (KL-divergence "
    "threshold search over activation histograms, clips outliers — the "
    "reference's calib_mode='entropy') or 'naive' (observed |max|). "
    "Degenerate histograms fall back to naive and count "
    "quantization.calib_fallback.")
register_knob(
    "quant.calib_bins", "MXNET_TPU_QUANT_CALIB_BINS", int, 4001,
    "histogram bins for entropy calibration (reference calibrate.cc uses "
    "8001/4001-class histograms); more bins = finer KL threshold search, "
    "slower calibration.")
register_knob(
    "quant.error_budget", "MXNET_TPU_QUANT_ERROR_BUDGET", float, 0.05,
    "mx.quantization accuracy guardrail: max relative L2 error "
    "(||int8 - fp32||/||fp32||, worst calibration batch) an "
    "export_quantized artifact may show before the export REFUSES to "
    "emit (QuantizationError). Raise only with model-level accuracy "
    "evidence; exclude sensitive sites instead where possible.")


def _apply_quant_calib_mode(value):
    v = (value or "").strip().lower()
    if v not in ("naive", "entropy"):
        # reject at set() time and revert (the nanguard pattern) so a typo
        # can't silently select an undefined calibration mode later
        _OVERRIDES.pop("quant.calib_mode", None)
        raise ValueError("quant.calib_mode must be 'naive' or 'entropy', "
                         "got %r" % (value,))


_ON_SET["quant.calib_mode"] = _apply_quant_calib_mode

# numerics plane (docs/OBSERVABILITY.md "Numerics plane")
register_knob(
    "numerics.capture", "MXNET_TPU_NUMERICS", str, "",
    "in-program tensor-statistics capture cadence: 'step:N' makes each "
    "step seam (module fused step, SPMDTrainer, gluon Trainer) run its "
    "stats-instrumented program variant every Nth step, riding per-site "
    "amax/amin/rms/non-finite/bf16-saturation summaries out as an extra "
    "side-output pytree (mx.numerics; zero happy-path host sync — stats "
    "drain through the is-ready poll). Empty/'off' (default) disables: "
    "lowered step programs stay byte-identical to a build without taps. "
    "Epoch-NEUTRAL: the instrumented variant is its own program-cache "
    "entry, so toggling never evicts compiled steps.")
register_knob(
    "quant.drift_every", "MXNET_TPU_QUANT_DRIFT_EVERY", int, 0,
    "quantization drift sampling: every Nth quantized mx.serving "
    "dispatch also runs the artifact's stats-twin program over the same "
    "batch and folds each site's runtime |max| into an EWMA against the "
    "calibration manifest (quant.drift_ratio.<model>.<site> gauges on "
    "/metrics; a quant_drift JSONL event fires past "
    "quant.drift_threshold). 0 (default) disables sampling.")
register_knob(
    "quant.drift_threshold", "MXNET_TPU_QUANT_DRIFT_THRESHOLD", float, 1.5,
    "drift alarm bound: a quantized site whose smoothed runtime-amax / "
    "calibrated-amax ratio exceeds this is counted drifted (ratio 1.0 = "
    "exactly the calibrated range; int8 saturates above it).")


def _apply_numerics_capture(value):
    from . import numerics
    try:
        numerics.configure(value)
    except ValueError:
        # reject at set() time and revert (the nanguard pattern): a typo'd
        # cadence must not linger as the stored override
        _OVERRIDES.pop("numerics.capture", None)
        raise


def _apply_quant_drift_every(value):
    if int(value) < 0:
        _OVERRIDES.pop("quant.drift_every", None)
        raise ValueError("quant.drift_every must be >= 0, got %r"
                         % (value,))


def _apply_quant_drift_threshold(value):
    if float(value) <= 0:
        _OVERRIDES.pop("quant.drift_threshold", None)
        raise ValueError("quant.drift_threshold must be > 0, got %r"
                         % (value,))


_ON_SET["numerics.capture"] = _apply_numerics_capture
_ON_SET["quant.drift_every"] = _apply_quant_drift_every
_ON_SET["quant.drift_threshold"] = _apply_quant_drift_threshold

# inference serving (docs/SERVING.md)
register_knob(
    "serving.max_batch", "MXNET_TPU_SERVING_MAX_BATCH", int, 32,
    "mx.serving batch capacity: the batcher coalesces queued requests "
    "for one model up to this many rows before dispatch; also the top "
    "pad bucket, so it bounds the compiled-program set per model.")
register_knob(
    "serving.max_queue_delay_ms", "MXNET_TPU_SERVING_MAX_QUEUE_DELAY_MS",
    float, 2.0,
    "mx.serving batching window in milliseconds: how long the batcher "
    "holds the OLDEST queued request waiting for co-batchable traffic "
    "before dispatching a partial batch. 0 dispatches immediately "
    "(batch-1 under light load); raise it to trade p50 latency for "
    "batch fill under bursty traffic.")
register_knob(
    "serving.max_pending", "MXNET_TPU_SERVING_MAX_PENDING", int, 1024,
    "mx.serving admission bound: submit() past this many queued requests "
    "fails fast with ServerOverloadedError (retryable — it subclasses "
    "OSError so resilience.call_with_retry backs off on it) instead of "
    "queuing unboundedly; shed load counts in serving.shed_requests. "
    "<= 0 disables the bound (PR-6 behavior).")
register_knob(
    "serving.default_deadline_ms", "MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS",
    float, 0.0,
    "default per-request deadline for mx.serving submit()/predict() in "
    "milliseconds (overridable per call via submit(deadline_ms=...)): a "
    "request still queued past its deadline completes with "
    "DeadlineExceededError at batch-formation time and is never "
    "dispatched — no compute is spent on an answer nobody is waiting "
    "for (serving.deadline_exceeded counts them). 0 (default) = no "
    "deadline.")
register_knob(
    "serving.breaker_threshold", "MXNET_TPU_SERVING_BREAKER_THRESHOLD",
    int, 5,
    "consecutive dispatch failures that open one model's mx.serving "
    "circuit breaker: while open, submits for that model fail fast with "
    "CircuitOpenError (other models keep serving); after the cooldown "
    "the breaker goes half-open and probes with a single batch — success "
    "closes it, failure re-opens. 0 disables the breaker.")
register_knob(
    "serving.breaker_cooldown_ms", "MXNET_TPU_SERVING_BREAKER_COOLDOWN_MS",
    float, 1000.0,
    "how long an OPEN mx.serving circuit breaker rejects before "
    "transitioning to half-open and letting one probe batch through.")
register_knob(
    "serving.kv_page_size", "MXNET_TPU_SERVING_KV_PAGE_SIZE", int, 16,
    "tokens per KV-cache page for mx.serving generation (docs/SERVING.md "
    "\"Generation\"): position t of a sequence lives at slot t %% "
    "page_size of page-table entry t // page_size. Baked into v4 "
    "deploy.export_generation programs at export time; at serve time the "
    "artifact's own page size wins. Smaller pages waste less pool memory "
    "per sequence tail but widen page tables (more decode-program "
    "shapes).")
register_knob(
    "serving.kv_pages", "MXNET_TPU_SERVING_KV_PAGES", int, 256,
    "device-resident KV page-pool capacity per generation model: the "
    "GenerationEngine allocates this many pages (each "
    "kv_page_size tokens x num_layers x heads) at register time and "
    "recycles them as sequences finish. Admission WAITS when the pool "
    "cannot cover a request's prompt + max_new_tokens "
    "(serving.kv_pool_exhausted counts the stalls) — size it for the "
    "target concurrency x context length. Pool size is a runtime "
    "dimension (jax.export symbolic), so changing it never recompiles.")
register_knob(
    "serving.decode_slots", "MXNET_TPU_SERVING_DECODE_SLOTS", int, 8,
    "decode-batch width for mx.serving generation: how many sequences "
    "one per-iteration decode step advances together. Finished sequences "
    "free their slot mid-flight and queued prefills join without "
    "recompiling (batch is a symbolic dimension of the exported decode "
    "program). Raise for throughput, lower for per-token latency.")
register_knob(
    "serving.shared_prefix", "MXNET_TPU_SHARED_PREFIX", bool, True,
    "share full prompt-prefix KV pages between concurrent generation "
    "requests with a common prefix (the system-prompt case): pages are "
    "content-hashed at submit, refcounted in the pool and freed when "
    "the last reader exits. Causal attention makes the shared bytes "
    "identical no matter which request wrote them, so token streams are "
    "unchanged; serving.prefix_hits / serving.prefix_pages_shared count "
    "the wins. Off = every request gets private pages.")


def _positive_int_knob(name):
    def apply(value):
        if int(value) <= 0:
            # reject at set() time and revert (the nanguard pattern)
            _OVERRIDES.pop(name, None)
            raise ValueError("%s must be a positive integer, got %r"
                             % (name, value))
    return apply


_ON_SET["serving.kv_page_size"] = _positive_int_knob("serving.kv_page_size")
_ON_SET["serving.kv_pages"] = _positive_int_knob("serving.kv_pages")
_ON_SET["serving.decode_slots"] = _positive_int_knob("serving.decode_slots")

# Pallas kernel tier (docs/PERF_NOTES.md "Kernel tier")
register_knob(
    "kernels.enabled", "MXNET_TPU_KERNELS", bool, True,
    "the Pallas kernel tier (mx.kernels). One static rule routes "
    "attention, paged decode attention, the held experts' grouped "
    "product and the retention update at trace time: off -> the XLA "
    "lowering, byte-identical to a program without the tier; at its "
    "default on a backend that interprets Pallas (CPU/GPU) -> the XLA "
    "lowering (kernels.gated_fallback); a shape the kernel cannot take "
    "-> the XLA lowering (kernels.fallback / kernels.paged_fallback / "
    "kernels.grouped_fallback / kernels.retention_fallback); "
    "else the kernel. Set explicitly on (env or set()) the kernels also "
    "run in the interpreter.")
register_knob(
    "kernels.vmem_budget", "MXNET_TPU_KERNELS_VMEM_BUDGET", int,
    2097152,  # 2 MiB — a literal, so static doc/drift tooling can read it
    "per-block VMEM budget in bytes for the Pallas row-block kernels "
    "(ops/pallas_kernels.py _row_block): block row counts are the "
    "largest divisor of n_rows whose block fits the budget; flash "
    "attention also checks one head's full K/V against it before "
    "engaging, the paged decode kernel sizes its double-buffered "
    "K/V page tiles to it (128 tokens at most), and the grouped product "
    "streams the widest column tile of a group's [K, N] matrix whose "
    "[K, tile] block fits it. Must be > 0; ~16MB/core is the hardware ceiling, the "
    "2MB default leaves headroom for double buffering.")


def _apply_kernels_vmem_budget(value):
    if int(value) <= 0:
        # reject at set() time and revert (the nanguard pattern): a
        # non-positive budget would degrade every kernel to 1-row blocks
        # or divide-by-zero much later
        _OVERRIDES.pop("kernels.vmem_budget", None)
        raise ValueError("kernels.vmem_budget must be > 0 bytes, got %r"
                         % (value,))


_ON_SET["kernels.vmem_budget"] = _apply_kernels_vmem_budget

# transformer layer-stack program tuning (runtime.scan_stack,
# docs/PERF_NOTES.md "Kernel tier")
register_knob(
    "runtime.stack_mode", "MXNET_TPU_STACK_MODE", str, "scan",
    "layer-stack program shape for runtime.scan_stack: 'scan' (default) "
    "traces the layer body ONCE under lax.scan so trace/compile time "
    "stays flat in depth; 'unroll' inlines every layer (the A/B "
    "baseline tools/check_kernels.py builds against).")
register_knob(
    "runtime.remat", "MXNET_TPU_REMAT", str, "",
    "selective rematerialization wrapped around the scanned layer body "
    "(runtime.scan_stack): '' (default) saves all residuals — no "
    "jax.checkpoint, traces identical to pre-knob programs; 'dots' "
    "keeps matmul outputs and recomputes the cheap elementwise tail in "
    "the backward (jax.checkpoint_policies dots_saveable); 'full' "
    "saves nothing — maximum live-memory savings for roughly 1/3 more "
    "FLOPs.")


def _apply_runtime_stack_mode(value):
    v = (value or "").strip().lower()
    if v not in ("scan", "unroll"):
        _OVERRIDES.pop("runtime.stack_mode", None)
        raise ValueError("runtime.stack_mode must be 'scan' or 'unroll', "
                         "got %r" % (value,))


def _apply_runtime_remat(value):
    v = (value or "").strip().lower()
    if v not in ("", "dots", "full"):
        _OVERRIDES.pop("runtime.remat", None)
        raise ValueError("runtime.remat must be '', 'dots' or 'full', "
                         "got %r" % (value,))


_ON_SET["runtime.stack_mode"] = _apply_runtime_stack_mode
_ON_SET["runtime.remat"] = _apply_runtime_remat

# sharded embeddings (docs/PERF_NOTES.md "Sharded embeddings")
register_knob(
    "embedding.sharded", "MXNET_TPU_EMBEDDING_SHARDED", bool, True,
    "route trainable sparse-grad embedding tables "
    "(gluon.nn.Embedding(sparse_grad=True)) through the mesh-sharded "
    "deduplicated row-sparse lookup/update path (parallel/embedding.py) "
    "inside SPMDTrainer's fused step: table sharded on the vocab axis, "
    "ids deduplicated per batch, only touched rows of the table and "
    "optimizer state rewritten. False = dense gradients + dense "
    "optimizer step (the full-table-gradient baseline). Read when a "
    "trainer is constructed/materialized.")
register_knob(
    "embedding.unique_size", "MXNET_TPU_EMBEDDING_UNIQUE_SIZE", int, 0,
    "static per-batch unique-id capacity for the deduplicated embedding "
    "lookup (the size= of jnp.unique, so compiled shapes stay flat). "
    "0 (default) = the batch's id count, which is always safe; a "
    "positive cap shrinks the gathered buffers but ids beyond the cap "
    "are DROPPED — only set it when the per-batch unique count is known "
    "to be bounded. Read at program-build time.")


def _apply_embedding_unique_size(value):
    if int(value) < 0:
        # reject at set() time and revert (the nanguard pattern): a
        # negative capacity would crash program build much later
        _OVERRIDES.pop("embedding.unique_size", None)
        raise ValueError("embedding.unique_size must be >= 0, got %r"
                         % (value,))


_ON_SET["embedding.unique_size"] = _apply_embedding_unique_size

# testing
register_knob(
    "test.seed", "MXNET_TEST_SEED", int, -1,
    "fixed seed for test_utils randomness; -1 draws a fresh one "
    "(reference tests/python/unittest/common.py with_seed).")

# documented-as-XLA-owned (reference knobs with no TPU-side effect)
register_knob(
    "xla.memory_pool", "MXNET_GPU_MEM_POOL_TYPE", str, "xla",
    "reference memory-pool knobs (env_var.md:88-105) are owned by the XLA "
    "allocator on TPU; value is informational.")
register_knob(
    "xla.autotune", "MXNET_CUDNN_AUTOTUNE_DEFAULT", int, 0,
    "cuDNN autotune (env_var.md:234) maps to XLA's internal autotuning; "
    "value is informational.")
register_knob(
    "perf.autotune", "MXNET_TPU_AUTOTUNE", str, "auto",
    "retired: the timed kernel search it steered is gone (mx.kernels "
    "routes by one static rule). Still accepted ('off', 'auto', "
    "'measure') because benchmark configs set it; read by nothing.")
register_knob(
    "bn_two_pass_stats", "MXTPU_BN_TWO_PASS_STATS", bool, False,
    "BatchNorm training statistics: False (default) = single-pass "
    "moving-mean-shifted moments (one HBM read, the fast path); True = "
    "exact two-pass jnp.var for offset-heavy inputs whose |mean|/std "
    "exceeds ~3000 at cold start.")


def _autostart():
    if get("profiler.autostart"):
        from . import profiler
        profiler.set_config(filename=get("profiler.filename"))
        profiler.start()


_autostart()
