"""mx.kernels — routing tier for the hand-written Pallas kernels.

The raw kernels live in ``ops/pallas_kernels.py`` and stay policy-free;
this module owns WHEN they run.  Reference analog: the graph optimizer
deciding when to swap a library op for a hand-fused RTC kernel
(src/common/rtc.cc + graph passes) — here the decision is an explicit
config knob plus a shape/platform feasibility check, because silent
kernel swaps are how frameworks grow haunted performance.

Routing contract (docs/PERF_NOTES.md "Kernel tier" + "Autotune"):

* the tier is ON by default since round 16, but a *default-source* knob
  is GATED: each routed site only takes a kernel after mx.perf.autotune
  proves bitwise-or-tolerance parity plus a measured speedup >= 1.0x on
  this device (``kernels.gated_fallback`` counts losing sites, which
  fall back to the XLA lowering permanently — the PR 11 AOT-rejection
  contract).  On interpreted backends the gate statically routes to
  XLA, so default-knob CPU programs stay byte-identical to the
  pre-tier lowering;
* an EXPLICIT ``kernels.enabled`` (env var or ``config.set``) bypasses
  the gate: off traces the exact pre-tier XLA ops (byte-identical
  programs); on routes supported shapes through the Pallas kernel
  (``kernels.flash_attention`` counter) with tuned block sizes when a
  winner is cached, falling back only on infeasible shapes
  (``kernels.fallback`` counter) — never an error;
* the decision is trace-time python, so a jitted program contains one
  path only; toggling the knob or landing a new autotune winner
  retraces (config epoch / autotune generation in the cache keys).

On CPU the kernels run through the Pallas interpreter — same numerics,
no TPU needed — which is what the parity gates in
``tools/check_kernels.py`` rely on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import contextlib

from . import config as _config
from . import telemetry as _telemetry
from .ops.pallas_kernels import (flash_attention, fused_adam_step,
                                 fused_sgd_step, pallas_paged_attention)

__all__ = ["enabled", "attention", "paged_attention",
           "flash_unsupported_reason", "paged_unsupported_reason",
           "record_paged_routes", "fused_step_enabled",
           "flash_attention", "pallas_paged_attention",
           "fused_sgd_step", "fused_adam_step", "measure"]

# one-row VMEM feasibility: a q block keeps its head's full K and V
# resident, so 2 * Skv * D * itemsize must fit the budget
_MAX_HEAD_DIM = 512


def enabled():
    """True when the kernel tier is switched on (``kernels.enabled`` /
    MXNET_TPU_KERNELS)."""
    return bool(_config.get("kernels.enabled"))


def fused_step_enabled(optimizer):
    """True when ``optimizer`` should update through its fused
    Pallas epilogue: tier on + the optimizer implements ``step_fused``
    + its step math is jit-safe + the autotune gate agrees (a
    default-source tier only fuses where the measured epilogue won;
    see mx.perf.autotune)."""
    if not (enabled()
            and getattr(optimizer, "fused_step", False)
            and getattr(optimizer, "jit_safe", True)):
        return False
    from . import autotune as _autotune
    pick = _autotune.fused_step_pick(optimizer)
    return pick is None or pick.get("impl") == "fused"


def note_fused_step():
    """Count one fused optimizer-epilogue launch (trace-time — counts
    program builds, not steps; the per-step signal is the program key)."""
    _telemetry.counter("kernels.fused_step").inc()


def flash_unsupported_reason(q, k, v, causal):
    """Why flash attention can NOT take this call, or None if it can.

    Trace-time shape/dtype checks only — everything here must be static
    under jit.  A non-None reason routes to the XLA fallback."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return "rank != 4 (got q%s k%s v%s)" % (q.ndim, k.ndim, v.ndim)
    # jax.export shape polymorphism: symbolic dims can't answer the
    # block/budget comparisons below, and a kernel specialized to one
    # concrete shape defeats the point of a polymorphic artifact
    if not all(isinstance(d, int)
               for d in tuple(q.shape) + tuple(k.shape) + tuple(v.shape)):
        return "symbolic shape (q%s kv%s)" % (q.shape, k.shape)
    if k.shape != v.shape:
        return "k/v shapes differ: %s vs %s" % (k.shape, v.shape)
    if q.shape[:2] != k.shape[:2]:
        return "q/kv batch-head mismatch: %s vs %s" % (
            q.shape[:2], k.shape[:2])
    if q.shape[3] != k.shape[3]:
        return "q/kv head dim mismatch: %d vs %d" % (
            q.shape[3], k.shape[3])
    if causal and q.shape[2] != k.shape[2]:
        return "causal needs Sq == Skv, got %d vs %d" % (
            q.shape[2], k.shape[2])
    if q.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return "unsupported dtype %s" % q.dtype
    if q.shape[3] > _MAX_HEAD_DIM:
        return "head dim %d > %d" % (q.shape[3], _MAX_HEAD_DIM)
    # K + V of one (batch, head) slice must fit the per-block VMEM budget
    kv_bytes = 2 * k.shape[2] * k.shape[3] * k.dtype.itemsize
    budget = _config.get("kernels.vmem_budget")
    if kv_bytes > budget:
        return "kv slice %d bytes > vmem budget %d" % (kv_bytes, budget)
    return None


def attention(q, k, v, causal=False, scale=None):
    """Dot-product attention with kernel routing.

    Tier off → the plain XLA lowering (parallel.ring_attention.attention),
    traced identically to the pre-kernel-tier program.  Tier on →
    the fused Pallas flash kernel when the shape qualifies
    (``kernels.flash_attention`` counter; the tuned ``block_q`` applies
    when mx.perf.autotune has a winner for this site), the XLA lowering
    when the shape can't take the kernel (``kernels.fallback``) or when
    the default-source gate measured the kernel slower / not bit-close
    (``kernels.gated_fallback``)."""
    from .parallel.ring_attention import attention as _xla_attention
    if enabled():
        q = jnp.asarray(q)
        k = jnp.asarray(k)
        v = jnp.asarray(v)
        reason = flash_unsupported_reason(q, k, v, causal)
        if reason is None:
            from . import autotune as _autotune
            pick = _autotune.attention_pick(tuple(q.shape), tuple(k.shape),
                                            str(q.dtype), causal, scale)
            if pick is None or pick.get("impl") == "flash":
                _telemetry.counter("kernels.flash_attention").inc()
                bq = int(pick.get("block_q") or 128) if pick else 128
                return flash_attention(q, k, v, causal=causal,
                                       scale=scale, block_q=bq)
            # the measured gate lost (or the platform statically can't
            # win): the XLA lowering IS the winner for this site
            _telemetry.counter("kernels.gated_fallback").inc()
        else:
            _telemetry.counter("kernels.fallback").inc()
    return _xla_attention(q, k, v, causal=causal, scale=scale)


def paged_unsupported_reason(q, k, v, valid, quantized=False):
    """Why the Pallas paged-attention kernel can NOT take this decode
    call, or None if it can.  Trace-time shape/dtype checks only —
    everything here must be static under jit.  A non-None reason routes
    to the XLA lowering (``kernels.paged_fallback``) and is surfaced in
    the export route sink (:func:`record_paged_routes`); either lowering
    runs under the ``mx.paged_attention`` name scope."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return "rank != 4 (got q%s k%s v%s)" % (q.ndim, k.ndim, v.ndim)
    # jax.export shape polymorphism: a symbolic batch/pool dim can't
    # answer the block/budget arithmetic below — decode programs that
    # want the kernel export with a concrete decode_batch (deploy v5)
    if not all(isinstance(d, int)
               for d in tuple(q.shape) + tuple(k.shape) + tuple(v.shape)
               + tuple(valid.shape)):
        return "symbolic shape (q%s kv%s)" % (q.shape, k.shape)
    if q.shape[2] != 1:
        return "needs one query row per sequence, got Sq=%d" % q.shape[2]
    if k.shape != v.shape:
        return "k/v shapes differ: %s vs %s" % (k.shape, v.shape)
    if q.shape[:2] != k.shape[:2]:
        return "q/kv batch-head mismatch: %s vs %s" % (
            q.shape[:2], k.shape[:2])
    if q.shape[3] != k.shape[3]:
        return "q/kv head dim mismatch: %d vs %d" % (
            q.shape[3], k.shape[3])
    if valid.shape != (q.shape[0], k.shape[2]):
        return "valid mask shape %s != (B, K)=%s" % (
            tuple(valid.shape), (q.shape[0], k.shape[2]))
    if q.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return "unsupported dtype %s" % q.dtype
    if quantized:
        if k.dtype != jnp.int8:
            return "quantized pages must be int8, got %s" % k.dtype
    elif k.dtype != q.dtype:
        return "q/kv dtype mismatch: %s vs %s" % (q.dtype, k.dtype)
    if q.shape[3] > _MAX_HEAD_DIM:
        return "head dim %d > %d" % (q.shape[3], _MAX_HEAD_DIM)
    # one (batch, head) row keeps its full gathered K and V resident
    kv_bytes = 2 * k.shape[2] * k.shape[3] * k.dtype.itemsize
    budget = _config.get("kernels.vmem_budget")
    if kv_bytes > budget:
        return "kv slice %d bytes > vmem budget %d" % (kv_bytes, budget)
    return None


# Export-time route capture: deploy.export_generation traces the decode
# program family under record_paged_routes() and lands the impl/reason of
# every routed paged site in the artifact meta — the serve path then
# counts kernels.paged_attention / paged_fallback per dispatch without
# re-tracing (the program is AOT; trace-time counters fire at export).
_PAGED_ROUTE_SINK = []


@contextlib.contextmanager
def record_paged_routes():
    """Collect ``{"impl", "reason", "quantized"}`` dicts for every paged
    route decision made while tracing under this context."""
    routes = []
    _PAGED_ROUTE_SINK.append(routes)
    try:
        yield routes
    finally:
        _PAGED_ROUTE_SINK.remove(routes)


def _note_paged_route(impl, reason, quantized):
    for routes in _PAGED_ROUTE_SINK:
        routes.append({"impl": impl, "reason": reason,
                       "quantized": bool(quantized)})


def _paged_attention_xla(q, k, v, valid, scale=None, k_scale=None,
                         v_scale=None):
    """The XLA paged-attention lowering — the pre-kernel-tier op
    sequence, byte-identical to what every release before the paged
    kernel traced.  The math mirrors ``parallel.ring_attention
    ._block_attn``: masked scores pin to the same ``-1e30`` floor, so
    masked keys contribute an EXACT ``0.0`` to both the softmax
    denominator and the value sum.  With ``k_scale``/``v_scale`` the
    int8 pages dequantize up front (one f32 broadcast multiply), the
    same f32 operands the kernel reconstructs in VMEM."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", e.astype(v.dtype), v)
    return (o / l.astype(o.dtype)).astype(q.dtype)


def paged_attention(q, k, v, valid, scale=None, k_scale=None,
                    v_scale=None):
    """Decode-step attention over a page-gathered context window.

    ``q`` is the single new query ``[B, H, 1, Dh]``; ``k``/``v`` are the
    context gathered through a request's page table ``[B, H, K, Dh]``
    (``K = page_table_width * page_size``, so slots past the sequence's
    true length hold stale or clipped-sentinel data); ``valid`` ``[B, K]``
    masks exactly the real positions.  With ``k_scale``/``v_scale``
    (``[B, H, K]`` f32 per-row scales from ``mx.quantization
    .quantize_rows``) the K/V operands are int8 KV pages and dequantize
    in the consumer — inside the kernel's VMEM pass, or up front on the
    XLA path.  Both lowerings pin masked scores to the ``-1e30`` floor
    of ``parallel.ring_attention._block_attn`` and track an unpadded
    forward bitwise-closely enough for greedy token parity
    (tools/check_generation.py enforces it).

    Routing (mirrors :func:`attention`): tier off → the plain XLA
    lowering, traced identically to the pre-kernel-tier program.  Tier
    on → the Pallas paged kernel when the shape qualifies
    (``kernels.paged_attention`` counter; the tuned ``block_bh`` applies
    when mx.perf.autotune has a "paged" winner for this site), the XLA
    lowering when the shape can't take the kernel
    (``kernels.paged_fallback``) or when the default-source gate
    measured the kernel slower / not bit-close
    (``kernels.gated_fallback``).  The decision and its reason land in
    those counters and, under :func:`record_paged_routes`, in the export
    route sink.  Whichever lowering runs, its device operations carry the
    ``mx.paged_attention`` name scope (the Pallas kernel is named
    ``mx_paged_attention``), so a profile finds them after a route
    change."""
    with jax.named_scope("mx.paged_attention"):
        return _paged_attention_routed(q, k, v, valid, scale, k_scale,
                                       v_scale)


def _paged_attention_routed(q, k, v, valid, scale, k_scale, v_scale):
    quant = k_scale is not None
    if enabled():
        q = jnp.asarray(q)
        k = jnp.asarray(k)
        v = jnp.asarray(v)
        reason = paged_unsupported_reason(q, k, v, valid, quantized=quant)
        if reason is None:
            from . import autotune as _autotune
            pick = _autotune.paged_pick(tuple(q.shape), tuple(k.shape),
                                        str(q.dtype), quant, scale)
            if pick is None or pick.get("impl") == "paged":
                _telemetry.counter("kernels.paged_attention").inc()
                _note_paged_route("paged", None, quant)
                bb = pick.get("block_bh") if pick else None
                return pallas_paged_attention(
                    q, k, v, valid, scale=scale, k_scale=k_scale,
                    v_scale=v_scale, block_bh=int(bb) if bb else None)
            # the measured gate lost (or the platform statically can't
            # win): the XLA lowering IS the winner for this site
            reason = pick.get("reason") or "autotune gate: xla won"
            _telemetry.counter("kernels.gated_fallback").inc()
        else:
            _telemetry.counter("kernels.paged_fallback").inc()
        _note_paged_route("xla", reason, quant)
        return _paged_attention_xla(q, k, v, valid, scale=scale,
                                    k_scale=k_scale, v_scale=v_scale)
    _note_paged_route("xla", "tier off", quant)
    return _paged_attention_xla(q, k, v, valid, scale=scale,
                                k_scale=k_scale, v_scale=v_scale)


def measure(key, fn, *args):
    """Register ``fn(*args)`` with mx.perf under the "kernels" family and
    run it once: returns ``(outputs, program_record)`` where the record
    carries cost_analysis FLOPs, phase times and the roofline bound.
    This is how bench/opperf secondaries report achieved FLOPs per op."""
    from . import perf as _perf
    wrapped = _perf.wrap(jax.jit(fn), "kernels", key)
    out = wrapped(*args)
    jax.block_until_ready(out)
    return out, _perf.program("kernels", key)
