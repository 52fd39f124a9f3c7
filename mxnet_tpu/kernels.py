"""mx.kernels — routing tier for the hand-written Pallas kernels.

The raw kernels live in ``ops/pallas_kernels.py`` and stay policy-free;
this module alone decides WHEN they run.  The ten routed sites
(:func:`attention`, :func:`paged_attention`,
:func:`latent_paged_attention`, :func:`sparse_latent_attention`,
:func:`sparse_prefill_route`, :func:`index_scores`,
:func:`grouped_matmul`, :func:`retention_update`,
:func:`block_sparse_attention`, :func:`block_sparse_prefill`) ask one rule
(:func:`_route_reason`), which reads three things it can see at trace
time and nothing else — nothing is timed, persisted or remembered:

1. ``kernels.enabled`` off → the XLA lowering, traced as if the tier
   did not exist;
2. the knob at its *default* on a backend that interprets Pallas
   (``rtc.interpret_mode()``: CPU, GPU) → the XLA lowering, since an
   interpreted kernel cannot beat a compiled program
   (``kernels.gated_fallback``); an explicit on still takes the kernel
   there, which is how the parity tests run it;
3. a shape the kernel cannot take (:func:`flash_unsupported_reason`,
   :func:`paged_unsupported_reason`, :func:`latent_unsupported_reason`,
   :func:`sparse_unsupported_reason`,
   :func:`sparse_prefill_unsupported_reason`,
   :func:`index_unsupported_reason`, :func:`grouped_unsupported_reason`,
   :func:`retention_unsupported_reason`,
   :func:`block_sparse_prefill_unsupported_reason`) → the XLA lowering
   (``kernels.fallback`` / ``kernels.paged_fallback`` /
   ``kernels.latent_fallback`` / ``kernels.sparse_latent_fallback`` /
   ``kernels.sparse_prefill_fallback`` / ``kernels.index_fallback`` /
   ``kernels.grouped_fallback`` / ``kernels.retention_fallback`` /
   ``kernels.block_sparse_fallback`` /
   ``kernels.block_sparse_prefill_fallback``), never an error;
4. else the kernel (``kernels.flash_attention`` /
   ``kernels.paged_attention`` / ``kernels.latent_paged`` /
   ``kernels.sparse_latent`` / ``kernels.sparse_prefill`` /
   ``kernels.index_scores`` / ``kernels.grouped_matmul`` /
   ``kernels.retention_update`` / ``kernels.block_sparse`` /
   ``kernels.block_sparse_prefill``), at block sizes that are constants
   or functions of the shapes.

The decision is trace-time python, so a jitted program contains one
path only; a knob change retraces (``config.epoch()`` in the program
cache keys, which moves when a knob's value or its source does).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import config as _config
from . import telemetry as _telemetry
from .ops.pallas_kernels import (flash_attention, flash_attention_tiled,
                                 grouped_col_tile, pallas_grouped_matmul,
                                 pallas_latent_paged_attention,
                                 pallas_paged_attention,
                                 pallas_index_scores,
                                 pallas_retention_update,
                                 pallas_sparse_latent_attention,
                                 retention_row_tile, _TILED_BLOCK)

__all__ = ["enabled", "attention", "paged_attention",
           "latent_paged_attention", "sparse_latent_attention",
           "sparse_prefill_route", "sparse_prefill_attention",
           "index_scores", "grouped_matmul", "retention_update",
           "block_sparse_attention", "block_sparse_prefill",
           "block_sparse_prefill_unsupported_reason",
           "flash_unsupported_reason", "tiled_unsupported_reason",
           "paged_unsupported_reason", "latent_unsupported_reason",
           "sparse_unsupported_reason", "sparse_prefill_unsupported_reason",
           "index_unsupported_reason",
           "grouped_unsupported_reason", "retention_unsupported_reason",
           "record_paged_routes", "record_grouped_routes",
           "record_retention_routes", "record_sparse_prefill_routes",
           "pallas_dynamic_shapes",
           "flash_attention", "flash_attention_tiled",
           "pallas_paged_attention", "pallas_latent_paged_attention",
           "pallas_sparse_latent_attention", "pallas_index_scores",
           "pallas_grouped_matmul", "pallas_retention_update"]

# one-row VMEM feasibility: a q block keeps its head's full K and V
# resident, so 2 * Skv * D * itemsize must fit the budget
_MAX_HEAD_DIM = 512


def enabled():
    """True when the kernel tier is switched on (``kernels.enabled`` /
    MXNET_TPU_KERNELS)."""
    return bool(_config.get("kernels.enabled"))


def _route_reason(unsupported_reason, fallback):
    """None when a routed site takes its kernel, else why it takes the
    XLA lowering (the module docstring's rule).  ``unsupported_reason``
    is the site's shape check, called only when the knob and the backend
    allow the kernel; ``fallback`` is the counter of the shapes it
    refuses."""
    if not enabled():
        return "tier off"
    from .rtc import interpret_mode
    if _config.source("kernels.enabled") == "default" and interpret_mode():
        _telemetry.counter("kernels.gated_fallback").inc()
        return "interpreted"
    reason = unsupported_reason()
    if reason is not None:
        fallback.inc()
    return reason


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


def tiled_unsupported_reason(q, k, v, causal):
    """Why the K/V-tiled flash kernel can NOT take this call, or None if
    it can.  It is asked only after :func:`flash_unsupported_reason` has
    refused, and takes what that kernel has no form for: value rows of
    another width than the query/key rows (at any length: it keeps no
    head's K/V resident).  Equal widths stay the resident kernel's or the
    XLA lowering's, as they were."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        return "rank != 4 (got q%s k%s v%s)" % (q.ndim, k.ndim, v.ndim)
    if q.shape[3] == v.shape[3]:
        return "value rows as wide as the keys' (%d)" % q.shape[3]
    if not all(isinstance(d, int)
               for d in tuple(q.shape) + tuple(k.shape) + tuple(v.shape)):
        return "symbolic shape (q%s kv%s)" % (q.shape, k.shape)
    if k.shape[:3] != v.shape[:3] or q.shape[:2] != k.shape[:2] \
            or q.shape[3] != k.shape[3]:
        return "q%s k%s v%s do not share batch, heads, K/V length and " \
            "query/key width" % (q.shape, k.shape, v.shape)
    if causal and q.shape[2] != k.shape[2]:
        return "causal needs Sq == Skv, got %d vs %d" % (
            q.shape[2], k.shape[2])
    if not (q.dtype == k.dtype == v.dtype) \
            or q.dtype not in (jnp.float32, jnp.bfloat16):
        return "operands %s/%s/%s, want all float32 or all bfloat16" % (
            q.dtype, k.dtype, v.dtype)
    if max(q.shape[3], v.shape[3]) > _MAX_HEAD_DIM:
        return "head dim %d > %d" % (max(q.shape[3], v.shape[3]),
                                     _MAX_HEAD_DIM)
    if q.shape[2] % 8 or k.shape[2] % 8:
        return "lengths %d/%d are not multiples of 8" % (
            q.shape[2], k.shape[2])
    return None


@functools.lru_cache(maxsize=None)
def _tiled_attention(causal, scale):
    """The tiled kernel as a differentiable function: its backward is the
    XLA lowering's (the kernel has none of its own; what routes here is a
    prefill)."""
    from .parallel.ring_attention import attention as _xla_attention

    @jax.custom_vjp
    def f(q, k, v):
        return flash_attention_tiled(q, k, v, causal=causal, scale=scale)

    def bwd(res, do):
        return jax.vjp(functools.partial(_xla_attention, causal=causal,
                                         scale=scale), *res)[1](do)

    f.defvjp(lambda q, k, v: (f(q, k, v), (q, k, v)), bwd)
    return f


def attention(q, k, v, causal=False, scale=None):
    """Dot-product attention with kernel routing (the module docstring's
    rule): the Pallas flash kernel where the tier is on and the shape
    qualifies (``kernels.flash_attention``), at the kernel's own default
    ``block_q=128`` — the one value every benchmark cell has run; where
    the value rows have another width than the keys' and the K/V-tiled
    kernel can take the call (:func:`tiled_unsupported_reason`) that
    kernel (``kernels.flash_attention_tiled``); else the plain XLA
    lowering (``parallel.ring_attention.attention``) — tier off, the
    default knob on an interpreted backend (``kernels.gated_fallback``),
    or a shape neither kernel can take (``kernels.fallback``)."""
    from .parallel.ring_attention import attention as _xla_attention
    if enabled():
        q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    # (None where the resident kernel takes the call, else the tiled
    # kernel's verdict: None again where that one does)
    reason = _route_reason(
        lambda: flash_unsupported_reason(q, k, v, causal)
        and tiled_unsupported_reason(q, k, v, causal),
        _telemetry.counter("kernels.fallback"))
    if reason is None \
            and flash_unsupported_reason(q, k, v, causal) is not None:
        _telemetry.counter("kernels.flash_attention_tiled").inc()
        return _tiled_attention(bool(causal), None if scale is None
                                else float(scale))(q, k, v)
    if reason is None:
        _telemetry.counter("kernels.flash_attention").inc()
        return flash_attention(q, k, v, causal=causal, scale=scale)
    return _xla_attention(q, k, v, causal=causal, scale=scale)


def paged_unsupported_reason(q, k_pages, v_pages, page_table, lengths,
                             quantized=False, layer=None):
    """Why the Pallas paged-attention kernel can NOT take this decode
    call, or None if it can.  Trace-time shape/dtype checks only —
    everything here must be static under jit.  A non-None reason routes
    to the XLA twin (``kernels.paged_fallback``) and is surfaced in the
    export route sink (:func:`record_paged_routes`)."""
    rank = 3 if layer is None else 4     # [P, psz, W], or [L, ...] whole
    if q.ndim != 4 or k_pages.ndim != rank or v_pages.ndim != rank:
        return "rank: q%s pools %s/%s, want 4 and %d/%d" % (
            q.ndim, k_pages.ndim, v_pages.ndim, rank, rank)
    if page_table.ndim != 2 or lengths.ndim != 1:
        return "rank: page_table%s lengths%s, want 2 and 1" % (
            page_table.ndim, lengths.ndim)
    # jax.export shape polymorphism: the grid walks the batch, so it has
    # to be concrete (export_generation's decode_batch); the pool's page
    # count may stay symbolic where Pallas lowers with dynamic shapes
    fixed = tuple(q.shape) + tuple(k_pages.shape[-2:]) \
        + tuple(page_table.shape) + tuple(lengths.shape)
    if not all(isinstance(d, int) for d in fixed):
        return "symbolic shape (q%s table%s)" % (q.shape, page_table.shape)
    if not isinstance(k_pages.shape[-3], int) \
            and not _pallas_dynamic_shapes():
        return "symbolic page count %s outside a dynamic-shape export" % (
            k_pages.shape[-3],)
    if q.shape[2] != 1:
        return "needs one query row per sequence, got Sq=%d" % q.shape[2]
    if k_pages.shape != v_pages.shape:
        return "k/v pool shapes differ: %s vs %s" % (
            k_pages.shape, v_pages.shape)
    kvh, rest = divmod(k_pages.shape[-1], q.shape[3])
    if rest or kvh < 1 or q.shape[1] % kvh:
        return "pool row width %d is not K/V heads x head dim %d, with " \
            "the %d query heads a multiple of them" % (
                k_pages.shape[-1], q.shape[3], q.shape[1])
    if quantized and kvh != q.shape[1]:
        return "int8 pages take equal head counts"
    if page_table.shape[0] != q.shape[0] \
            or lengths.shape[0] != q.shape[0]:
        return "page_table%s / lengths%s do not match the batch %d" % (
            tuple(page_table.shape), tuple(lengths.shape), q.shape[0])
    if q.dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
        return "unsupported dtype %s" % q.dtype
    if quantized:
        if k_pages.dtype != jnp.int8:
            return "quantized pages must be int8, got %s" % k_pages.dtype
    elif k_pages.dtype != q.dtype:
        return "q/kv dtype mismatch: %s vs %s" % (q.dtype, k_pages.dtype)
    if q.shape[3] > _MAX_HEAD_DIM:
        return "head dim %d > %d" % (q.shape[3], _MAX_HEAD_DIM)
    return None


def _pallas_core():
    """Pallas' tracing state, or None where this jax keeps it elsewhere
    (the dynamic-shape export is experimental in jax 0.9)."""
    try:
        from jax._src.pallas import core
    except ImportError:
        return None
    names = ("dynamic_shapes_export_enabled", "pallas_export_experimental")
    return core if all(hasattr(core, n) for n in names) else None


def _pallas_dynamic_shapes():
    """True while tracing under :func:`pallas_dynamic_shapes`."""
    core = _pallas_core()
    return core is not None and bool(core.dynamic_shapes_export_enabled())


def pallas_dynamic_shapes():
    """Context for one ``jax.export``: Pallas kernels lower with symbolic
    dimensions left symbolic, so a paged kernel takes a page pool whose
    page count the artifact leaves open.  Nothing where this jax has no
    such lowering: a symbolic page count then routes decode to the XLA
    twin, with its reason (:func:`paged_unsupported_reason`)."""
    core = _pallas_core()
    if core is None:
        return contextlib.nullcontext()
    return core.pallas_export_experimental(True)


# Export-time route capture: deploy.export_generation traces every
# program under record_paged_routes() / record_grouped_routes() /
# record_retention_routes() / record_sparse_prefill_routes() and lands
# the impl/reason of the routed sites in the artifact meta — the serve
# path then counts kernels.paged_attention / paged_fallback,
# kernels.grouped_matmul / grouped_fallback, kernels.retention_update /
# retention_fallback and kernels.sparse_prefill / sparse_prefill_fallback
# per dispatch without re-tracing (the program is AOT; trace-time
# counters fire at export).
_ROUTE_SINKS = {"paged": [], "grouped": [], "retention": [],
                "sparse_prefill": []}
# (a latent or sparse site's route rides in the paged sink: a model keeps
# one kind of page, so a decode program has one kind of paged site; the
# index-score site records no route)


@contextlib.contextmanager
def _record_routes(site):
    routes = []
    _ROUTE_SINKS[site].append(routes)
    try:
        yield routes
    finally:
        _ROUTE_SINKS[site].remove(routes)


def record_paged_routes():
    """Collect ``{"impl", "reason", "quantized"}`` dicts for every paged
    route decision made while tracing under this context."""
    return _record_routes("paged")


def record_grouped_routes():
    """Collect ``{"impl", "reason"}`` dicts (``impl`` "grouped" or "xla")
    for every grouped-product route decision made while tracing under
    this context."""
    return _record_routes("grouped")


def record_retention_routes():
    """Collect ``{"impl", "reason"}`` dicts (``impl`` "retention" or
    "xla") for every retention-update route decision made while tracing
    under this context."""
    return _record_routes("retention")


def record_sparse_prefill_routes():
    """Collect ``{"impl", "reason"}`` dicts (``impl`` "masked" or "xla")
    for every ``S`` block's prefill route decided while tracing under
    this context (:func:`sparse_prefill_route`)."""
    return _record_routes("sparse_prefill")


def _note_route(site, **route):
    for routes in _ROUTE_SINKS[site]:
        routes.append(dict(route))


def _paged_attention_xla(q, k_pages, v_pages, page_table, lengths,
                         scale=None, k_scale=None, v_scale=None,
                         layer=None):
    """The XLA twin of the paged kernel: gather each row's pages through
    its page table (under ``mx.kv_gather``; a sentinel id clips to a real
    page), then the masked one-pass softmax every release traced (under
    ``mx.paged_attention``).  The math mirrors ``parallel.ring_attention
    ._block_attn``: masked scores pin to the same ``-1e30`` floor, so
    masked keys contribute an EXACT ``0.0`` to both the softmax
    denominator and the value sum.  With ``k_scale``/``v_scale`` the
    int8 pages dequantize up front (one f32 broadcast multiply), the
    same f32 operands the kernel reconstructs in VMEM.  A row of length
    0 answers 0, as the kernel does.  Fewer K/V heads than query heads
    (pool rows ``KVH*Dh`` wide): query head h reads K/V head ``h // (H //
    KVH)``; ``layer`` (static or traced) picks one layer of whole ``[L,
    P, psz, W]`` pools, which are gathered from as ``L*P`` pages at the
    layer's ids: a traced ``k_pages[layer]`` would copy the layer's pool
    out first."""
    if layer is not None:
        P = k_pages.shape[1]
        page_table = jnp.clip(page_table, 0, P - 1) + layer * P
        k_pages, v_pages, k_scale, v_scale = (
            p if p is None else p.reshape((-1,) + p.shape[2:])
            for p in (k_pages, v_pages, k_scale, v_scale))
    B, H, _, d = q.shape
    if k_pages.shape[2] != H * d:
        return _paged_attention_xla_grouped(q, k_pages, v_pages,
                                            page_table, lengths, scale)
    K = page_table.shape[1] * k_pages.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def window(pages, last):
        # [P, psz, H*last] -> this batch's [B, H, K, last]
        return jnp.transpose(
            pages[page_table].reshape(B, K, H, last), (0, 2, 1, 3))

    with jax.named_scope("mx.kv_gather"):
        k = window(k_pages, d)
        v = window(v_pages, d)
        if k_scale is not None:
            k_scale = window(k_scale, 1)[..., 0]            # [B, H, K]
            v_scale = window(v_scale, 1)[..., 0]
    with jax.named_scope("mx.paged_attention"):
        if k_scale is not None:
            k = k.astype(jnp.float32) * k_scale[..., None]
            v = v.astype(jnp.float32) * v_scale[..., None]
        valid = jnp.arange(K, dtype=jnp.int32)[None, :] < lengths[:, None]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", e.astype(v.dtype), v)
        o = (o / l.astype(o.dtype)).astype(q.dtype)
        return jnp.where((lengths > 0)[:, None, None, None], o, 0)


def _paged_attention_xla_grouped(q, k_pages, v_pages, page_table, lengths,
                                 scale):
    """The twin at fewer K/V heads than query heads: the same gather,
    floor and one-pass softmax, with the query heads of one K/V head
    kept together (``[B, KVH, G, d]``) so K and V are not repeated."""
    B, H, _, d = q.shape
    kvh = k_pages.shape[2] // d
    K = page_table.shape[1] * k_pages.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    with jax.named_scope("mx.kv_gather"):
        k, v = (jnp.transpose(p[page_table].reshape(B, K, kvh, d),
                              (0, 2, 1, 3)) for p in (k_pages, v_pages))
    with jax.named_scope("mx.paged_attention"):
        valid = jnp.arange(K, dtype=jnp.int32)[None, :] < lengths[:, None]
        s = jnp.einsum("bngd,bnkd->bngk", q.reshape(B, kvh, H // kvh, d), k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, None, :], s, -1e30)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        l = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bngk,bnkd->bngd", e.astype(v.dtype), v)
        o = (o / l.astype(o.dtype)).astype(q.dtype).reshape(B, H, 1, d)
        return jnp.where((lengths > 0)[:, None, None, None], o, 0)


def paged_attention(q, k_pages, v_pages, page_table, lengths, scale=None,
                    k_scale=None, v_scale=None, layer=None):
    """Decode-step attention over the pages a page table names.

    ``q`` is the single new query ``[B, H, 1, Dh]``; ``k_pages`` /
    ``v_pages`` are a page pool ``[P, psz, H*Dh]`` (see ``layer``);
    ``page_table`` ``[B, W]`` int32 names each row's pages in order (an
    id >= P is the sentinel: it reads a real page that ``lengths``
    masks); ``lengths`` ``[B]`` int32 is how many positions each row
    attends over, at most ``W * psz``.  With ``k_scale``/``v_scale``
    (``[P, psz, H]`` f32 per-row scale pages from ``mx.quantization
    .quantize_rows``) the pools are int8 and dequantize in the consumer.
    The pool rows may hold fewer K/V heads than ``q`` has heads (``[P,
    psz, KVH*Dh]``: query head h reads K/V head ``h // (H // KVH)``), and
    with ``layer`` (a Python int, or the int32 scalar a layer scan
    traces) the pools are every layer's ``[L, P, psz, KVH*Dh]`` (scale
    pools ``[L, P, psz, H]``), handed over whole and read as ``L*P``
    pages at the layer's ids, so no pool is sliced.
    Both routes pin masked scores to the ``-1e30`` floor of
    ``parallel.ring_attention._block_attn`` and track an unpadded
    forward closely enough for greedy token parity
    (tools/check_generation.py enforces it).

    Routing: tier on and shape feasible → the Pallas kernel
    (``ops.pallas_kernels.pallas_paged_attention``, named
    ``mx_paged_attention``, under the ``mx.paged_attention`` scope),
    which reads each row's ``ceil(length / psz)`` pages where they lie;
    counter ``kernels.paged_attention``.  Otherwise → the XLA twin,
    which gathers the whole ``W * psz`` window (under ``mx.kv_gather``)
    and attends over it (under ``mx.paged_attention``): tier off, the
    default knob on an interpreted backend (``kernels.gated_fallback``),
    or a shape the kernel cannot take (``kernels.paged_fallback``).  The
    decision and its reason land in those counters and, under
    :func:`record_paged_routes`, in the export route sink."""
    quant = k_scale is not None
    reason = _route_reason(
        lambda: paged_unsupported_reason(q, k_pages, v_pages, page_table,
                                         lengths, quantized=quant,
                                         layer=layer),
        _telemetry.counter("kernels.paged_fallback"))
    if reason is None:
        _telemetry.counter("kernels.paged_attention").inc()
        _note_route("paged", impl="paged", reason=None, quantized=quant)
        with jax.named_scope("mx.paged_attention"):
            return pallas_paged_attention(
                q, k_pages, v_pages, page_table, lengths, scale=scale,
                k_scale=k_scale, v_scale=v_scale, layer=layer)
    _note_route("paged", impl="xla", reason=reason, quantized=quant)
    return _paged_attention_xla(q, k_pages, v_pages, page_table, lengths,
                                scale=scale, k_scale=k_scale,
                                v_scale=v_scale, layer=layer)


# ------------------------------------------------ latent paged attention
def latent_unsupported_reason(q, pages, page_table, lengths, value_width,
                              layer=None):
    """Why the Pallas latent-attention kernel can NOT take this decode
    call, or None if it can.  Trace-time shape/dtype checks only.  A
    non-None reason routes to the XLA twin (``kernels.latent_fallback``)
    and is surfaced in the export route sink
    (:func:`record_paged_routes`)."""
    rank = 3 if layer is None else 4     # [P, width, psz], or [L, ...]
    if q.ndim != 3 or pages.ndim != rank:
        return "rank: q%s pages %s, want 3 and %d" % (
            q.ndim, pages.ndim, rank)
    if page_table.ndim != 2 or lengths.ndim != 1:
        return "rank: page_table%s lengths%s, want 2 and 1" % (
            page_table.ndim, lengths.ndim)
    fixed = tuple(q.shape) + tuple(pages.shape[-2:]) \
        + tuple(page_table.shape) + tuple(lengths.shape)
    if not all(isinstance(d, int) for d in fixed):
        return "symbolic shape (q%s table%s)" % (q.shape, page_table.shape)
    if not isinstance(pages.shape[-3], int) \
            and not _pallas_dynamic_shapes():
        return "symbolic page count %s outside a dynamic-shape export" % (
            pages.shape[-3],)
    width, psz = pages.shape[-2:]
    if q.shape[2] != width:
        return "query rows are %d wide, the pages' rows %d" % (
            q.shape[2], width)
    if page_table.shape[0] != q.shape[0] \
            or lengths.shape[0] != q.shape[0]:
        return "page_table%s / lengths%s do not match the batch %d" % (
            tuple(page_table.shape), tuple(lengths.shape), q.shape[0])
    if q.dtype != pages.dtype or q.dtype not in (jnp.float32, jnp.bfloat16):
        return "operands %s and %s, want both float32 or both bfloat16" \
            % (q.dtype, pages.dtype)
    pack = 32 // q.dtype.itemsize        # rows of one packed sublane tile
    if psz % 128 or width % pack or value_width % pack \
            or not 0 < value_width <= width:
        return "a page [width %d, psz %d] with values in its first %d " \
            "rows: psz must be a multiple of 128, the widths of %d" % (
                width, psz, value_width, pack)
    return None


def _latent_paged_attention_xla(q, pages, page_table, lengths, scale,
                                value_width, layer=None):
    """The XLA twin of the latent kernel: gather each row's pages through
    its page table (under ``mx.kv_gather``), then the masked one-pass
    softmax of :func:`_paged_attention_xla` over the rows' whole width
    with the values their first ``value_width`` components (under
    ``mx.latent_attention``).  A row of length 0 answers 0."""
    if layer is not None:
        P = pages.shape[1]
        page_table = jnp.clip(page_table, 0, P - 1) + layer * P
        pages = pages.reshape((-1,) + pages.shape[2:])
    B, H, _ = q.shape
    W, psz = page_table.shape[1], pages.shape[2]
    with jax.named_scope("mx.kv_gather"):
        rows = pages[page_table]                        # [B, W, width, psz]
    with jax.named_scope("mx.latent_attention"):
        valid = jnp.arange(W * psz, dtype=jnp.int32)[None, :] \
            < lengths[:, None]
        s = jnp.einsum("bhc,bwcp->bhwp", q, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, :], s.reshape(B, H, W * psz), -1e30)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        l = jnp.sum(e, axis=-1, keepdims=True)
        o = jnp.einsum("bhwp,bwcp->bhc",
                       e.astype(rows.dtype).reshape(B, H, W, psz),
                       rows[:, :, :value_width],
                       preferred_element_type=jnp.float32)
        o = (o / l).astype(q.dtype)
        return jnp.where((lengths > 0)[:, None, None], o, 0)


def latent_paged_attention(q, pages, page_table, lengths, scale,
                           value_width, layer=None):
    """Decode-step attention over LATENT pages: a token keeps one cache
    row that every query head reads, for its score (over the whole row)
    and for its value (the row's first ``value_width`` components) — the
    absorbed form of multi-head latent attention (DeepSeek-V2,
    arXiv:2405.04434 section 2.1), where the caller has folded the keys'
    up-projection into the queries and un-folds the values' afterwards.

    ``q`` ``[B, H, width]`` is each head's absorbed query beside its
    rotary part; ``pages`` a pool ``[P, width, psz]`` whose pages hold
    their ``psz`` tokens on the lanes (row c of a page is component c of
    its tokens' cache rows), or with ``layer`` (a Python int or a traced
    int32 scalar) every layer's ``[L, P, width, psz]`` handed over whole;
    ``page_table`` ``[B, W]`` int32 (an id >= P is the sentinel);
    ``lengths`` ``[B]`` int32; ``scale`` multiplies the scores.  Returns
    ``[B, H, value_width]`` in q's dtype.  Both routes pin masked scores
    to ``-1e30`` and answer 0 for a row of length 0.

    Routing as :func:`paged_attention`: the Pallas kernel
    (``ops.pallas_kernels.pallas_latent_paged_attention``, named
    ``mx_latent_paged_attention``; counter ``kernels.latent_paged``),
    which streams a row's ``ceil(length / psz)`` pages once for all heads,
    or the XLA twin, which gathers the whole window (``mx.kv_gather``):
    tier off, the default knob on an interpreted backend
    (``kernels.gated_fallback``), or a shape the kernel cannot take
    (``kernels.latent_fallback``).  Either runs under the
    ``mx.latent_attention`` scope; the decision and its reason land in
    those counters and, as ``impl`` "latent" or "xla", in the export route
    sink of :func:`record_paged_routes`."""
    reason = _route_reason(
        lambda: latent_unsupported_reason(q, pages, page_table, lengths,
                                          value_width, layer=layer),
        _telemetry.counter("kernels.latent_fallback"))
    if reason is None:
        _telemetry.counter("kernels.latent_paged").inc()
        _note_route("paged", impl="latent", reason=None, quantized=False)
        with jax.named_scope("mx.latent_attention"):
            return pallas_latent_paged_attention(
                q, pages, page_table, lengths, scale, value_width,
                layer=layer)
    _note_route("paged", impl="xla", reason=reason, quantized=False)
    return _latent_paged_attention_xla(q, pages, page_table, lengths, scale,
                                       value_width, layer=layer)


# ------------------------------------------------ sparse latent attention
def sparse_unsupported_reason(q, pages, page_table, lengths, chosen,
                              value_width, layer=None):
    """Why the Pallas sparse latent kernel can NOT take this decode call,
    or None if it can: the latent kernel's reasons, with the queries
    scoring the first ``q.shape[2]`` rows of a page, and the selection
    ``[B, W, psz]``.  A non-None reason routes to the XLA twin
    (``kernels.sparse_latent_fallback``) and is surfaced in the export
    route sink (:func:`record_paged_routes`)."""
    if q.ndim != 3 or pages.ndim != (3 if layer is None else 4):
        return "rank: q%s pages %s" % (q.ndim, pages.ndim)
    kw, (width, psz) = q.shape[2], pages.shape[-2:]
    if not isinstance(kw, int) or not isinstance(width, int) \
            or not 0 < kw <= width:
        return "queries %s wide over page rows %s" % (kw, width)
    if tuple(chosen.shape) != (q.shape[0],) + tuple(page_table.shape[1:]) \
            + (psz,):
        return "chosen%s is not [batch, table width, psz]" % (
            tuple(chosen.shape),)
    wide = jax.ShapeDtypeStruct(tuple(q.shape[:2]) + (width,), q.dtype)
    reason = latent_unsupported_reason(wide, pages, page_table, lengths,
                                       value_width, layer=layer)
    if reason is None and kw % (32 // q.dtype.itemsize):
        reason = "query width %d is no multiple of the sublane packing" % kw
    return reason


def _sparse_latent_attention_xla(q, pages, page_table, lengths, chosen,
                                 scale, value_width, layer=None):
    """The XLA twin of the sparse latent kernel: the latent twin's gather
    of the whole window (under ``mx.kv_gather``) and masked one-pass
    softmax over the first ``q.shape[2]`` rows (under
    ``mx.sparse_attention``), every token ``chosen`` does not mark masked
    beside those past the length.  A row of length 0 answers 0."""
    if layer is not None:
        P = pages.shape[1]
        page_table = jnp.clip(page_table, 0, P - 1) + layer * P
        pages = pages.reshape((-1,) + pages.shape[2:])
    B, H, kw = q.shape
    W, psz = page_table.shape[1], pages.shape[2]
    with jax.named_scope("mx.kv_gather"):
        rows = pages[page_table][:, :, :kw]             # [B, W, kw, psz]
    with jax.named_scope("mx.sparse_attention"):
        valid = (jnp.arange(W * psz, dtype=jnp.int32)[None, :]
                 < lengths[:, None]) & (chosen.reshape(B, W * psz) != 0)
        s = jnp.einsum("bhc,bwcp->bhwp", q, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, None, :], s.reshape(B, H, W * psz), -1e30)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        o = jnp.einsum("bhwp,bwcp->bhc",
                       e.astype(rows.dtype).reshape(B, H, W, psz),
                       rows[:, :, :value_width],
                       preferred_element_type=jnp.float32)
        o = (o / jnp.sum(e, axis=-1, keepdims=True)).astype(q.dtype)
        return jnp.where((lengths > 0)[:, None, None], o, 0)


def sparse_latent_attention(q, pages, page_table, lengths, chosen, scale,
                            value_width, layer=None):
    """Decode-step attention over the cached tokens a selection keeps (an
    ``S`` block's top-k, DeepSeek Sparse Attention): the absorbed latent
    form of :func:`latent_paged_attention` with every token that
    ``chosen`` ``[B, W, psz]`` (int32, in page-table order: token ``w *
    psz + p`` of a row is lane p of its w-th page) leaves at 0 masked.
    ``q`` ``[B, H, kw]`` scores the first ``kw`` rows of a page (rows below
    them, index keys, are not scores' rows); values are the first
    ``value_width``.  Returns ``[B, H, value_width]`` in q's dtype.

    Routing as :func:`latent_paged_attention`: the Pallas kernel
    (``ops.pallas_kernels.pallas_sparse_latent_attention``, named
    ``mx_sparse_latent_attention``; counter ``kernels.sparse_latent``),
    which walks a row's pages where they lie, copies them whole and masks
    what is not chosen, or the XLA twin, which gathers the whole window
    (``mx.kv_gather``): tier off, the default knob on an interpreted
    backend (``kernels.gated_fallback``), or a shape the kernel cannot take
    (``kernels.sparse_latent_fallback``).  Either runs under the
    ``mx.sparse_attention`` scope; the decision lands in those counters
    and, as ``impl`` "sparse" or "xla", in the export route sink of
    :func:`record_paged_routes`."""
    reason = _route_reason(
        lambda: sparse_unsupported_reason(q, pages, page_table, lengths,
                                          chosen, value_width, layer=layer),
        _telemetry.counter("kernels.sparse_latent_fallback"))
    if reason is None:
        _telemetry.counter("kernels.sparse_latent").inc()
        _note_route("paged", impl="sparse", reason=None, quantized=False)
        with jax.named_scope("mx.sparse_attention"):
            return pallas_sparse_latent_attention(
                q, pages, page_table, lengths, chosen, scale, value_width,
                layer=layer)
    _note_route("paged", impl="xla", reason=reason, quantized=False)
    return _sparse_latent_attention_xla(q, pages, page_table, lengths,
                                        chosen, scale, value_width,
                                        layer=layer)


# ------------------------------------------------ block-sparse attention
def block_sparse_attention(q, k_pages, v_pages, page_table, lengths, scale,
                           layer=None):
    """Decode-step attention of a ``B`` block over the pages its selection
    kept: one row a (sequence, K/V head), its query heads ``q`` ``[B KVH,
    G, 1, Dh]`` against pools of one head a page (``[L, P KVH, psz, Dh]``:
    page ``p`` of head ``g`` is ``p KVH + g``), ``page_table`` ``[B KVH,
    K]`` the kept pages in order with the partial current page last and
    ``lengths`` the tokens they hold.  The paged kernel's shapes and
    rule (:func:`paged_unsupported_reason`): the Pallas kernel
    (``mx_paged_attention``, reading each row's ``ceil(length / psz)``
    kept pages where they lie; counter ``kernels.block_sparse``) or the
    XLA twin, which gathers the table's whole width
    (``kernels.block_sparse_fallback``).  The decision lands, as ``impl``
    "block_sparse" or "xla", in the export route sink of
    :func:`record_paged_routes`.  No scope of its own: the caller's
    (``mx.block_sparse_attention``) names its device time."""
    reason = _route_reason(
        lambda: paged_unsupported_reason(q, k_pages, v_pages, page_table,
                                         lengths, layer=layer),
        _telemetry.counter("kernels.block_sparse_fallback"))
    if reason is None:
        _telemetry.counter("kernels.block_sparse").inc()
        _note_route("paged", impl="block_sparse", reason=None,
                    quantized=False)
        return pallas_paged_attention(q, k_pages, v_pages, page_table,
                                      lengths, scale=scale, layer=layer)
    _note_route("paged", impl="xla", reason=reason, quantized=False)
    return _paged_attention_xla(q, k_pages, v_pages, page_table, lengths,
                                scale=scale, layer=layer)


def block_sparse_prefill_unsupported_reason(q, k, v, mask, block):
    """Why the masked K/V-tiled flash kernel can NOT take a ``B`` block's
    prefill, or None if it can: q ``[B, H, S, D]``, k and v ``[B, KVH, S,
    D]`` of one float dtype (H a multiple of KVH), a bfloat16 mask of
    whole blocks ``[B KVH, S, S / block]``, concrete shapes, a length of
    one K/V tile or a multiple of it, a block that divides the tile, and a
    mask no wider than the 128 lanes or a multiple of them."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4 or mask.ndim != 3:
        return "rank: q%s k%s v%s mask%s, want 4, 4, 4 and 3" % (
            q.ndim, k.ndim, v.ndim, mask.ndim)
    dims = tuple(q.shape) + tuple(k.shape) + tuple(v.shape) \
        + tuple(mask.shape)
    if not all(isinstance(d, int) for d in dims):
        return "symbolic shape (q%s mask%s)" % (q.shape, mask.shape)
    B, H, S, D = q.shape
    kvh = k.shape[1]
    if tuple(k.shape) != (B, kvh, S, D) or tuple(v.shape) != (B, kvh, S, D) \
            or H % kvh or S % block \
            or tuple(mask.shape) != (B * kvh, S, S // block):
        return "q%s k%s v%s mask%s are not [B,H,S,D], [B,KVH,S,D] twice " \
            "and [B KVH, S, S/%d]" % (tuple(q.shape), tuple(k.shape),
                                      tuple(v.shape), tuple(mask.shape),
                                      block)
    if not (q.dtype == k.dtype == v.dtype) \
            or q.dtype not in (jnp.float32, jnp.bfloat16):
        return "operands %s/%s/%s, want all float32 or all bfloat16" % (
            q.dtype, k.dtype, v.dtype)
    if mask.dtype != jnp.bfloat16:
        return "the mask is %s, want bfloat16" % mask.dtype
    if D > _MAX_HEAD_DIM:
        return "head dim %d > %d" % (D, _MAX_HEAD_DIM)
    tile = min(_TILED_BLOCK, S)
    if S % tile or tile % block or block & (block - 1):
        return "length %d is no whole number of %d-row tiles of whole " \
            "blocks of %d (a power of two)" % (S, tile, block)
    nb = S // block
    if nb > 128 and nb % 128:
        return "%d blocks: over 128 and no multiple of 128" % nb
    return None


def _block_sparse_prefill_xla(q, k, v, mask, scale, block):
    """The XLA twin of the block-masked prefill: a chunk of queries a step
    against every key, the block mask widened to the keys and the causal
    one beside it, a one-pass softmax (a row keeps its own block, so no row
    is empty)."""
    B, H, S, D = q.shape
    kvh = k.shape[1]
    G = H // kvh
    Q = min(128, S)
    steps = -(-S // Q)
    qg = jnp.pad(q, ((0, 0), (0, 0), (0, steps * Q - S), (0, 0))).reshape(
        B, kvh, G, steps * Q, D)
    mg = jnp.pad(mask, ((0, 0), (0, steps * Q - S), (0, 0))).reshape(
        B, kvh, steps * Q, S // block)
    pos = jnp.arange(S, dtype=jnp.int32)

    def chunk(i):
        qc = lax.dynamic_slice_in_dim(qg, i * Q, Q, axis=3)
        mc = lax.dynamic_slice_in_dim(mg, i * Q, Q, axis=2)
        t = i * Q + jnp.arange(Q, dtype=jnp.int32)
        keep = (jnp.repeat(mc, block, axis=-1) != 0) \
            & (pos[None, :] <= t[:, None])                   # [B,KVH,Q,S]
        s = jnp.einsum("bgrqd,bgsd->bgrqs", qc, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(keep[:, :, None], s, -1e30)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        o = jnp.einsum("bgrqs,bgsd->bgrqd", e.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return (o / jnp.sum(e, axis=-1)[..., None]).astype(q.dtype)

    o = lax.map(chunk, jnp.arange(steps, dtype=jnp.int32))
    return jnp.moveaxis(o, 0, 3).reshape(B, H, steps * Q, D)[:, :, :S]


def block_sparse_prefill(q, k, v, mask, scale, block):
    """Causal attention of a ``B`` block's whole prompt over the blocks its
    selection kept: q ``[B, H, S, D]``, k and v ``[B, KVH, S, D]`` (query
    head h reads K/V head ``h // (H / KVH)``), ``mask`` bfloat16 ``[B KVH,
    S, S / block]`` (1 where query t keeps block b of its K/V head) ->
    ``[B, H, S, D]``.  Routing by the module's rule: the masked K/V-tiled
    flash kernel (``flash_attention_tiled`` with the mask of blocks,
    ``mx_attention_tiled_masked``: K/V tiles above the diagonal skipped,
    the mask widened to a tile's keys on the MXU; counter
    ``kernels.block_sparse_prefill``) or the XLA twin
    (:func:`block_sparse_prefill_unsupported_reason`;
    ``kernels.block_sparse_prefill_fallback``).  The decision lands, as
    ``impl`` "masked" or "xla", in the export route sink of
    :func:`record_sparse_prefill_routes`.  No scope of its own: the
    caller's (``mx.block_sparse_prefill``) names its device time."""
    reason = _route_reason(
        lambda: block_sparse_prefill_unsupported_reason(q, k, v, mask,
                                                        block),
        _telemetry.counter("kernels.block_sparse_prefill_fallback"))
    _note_route("sparse_prefill", impl="xla" if reason else "masked",
                reason=reason)
    if reason is None:
        _telemetry.counter("kernels.block_sparse_prefill").inc()
        return flash_attention_tiled(q, k, v, causal=True, scale=scale,
                                     mask=mask, mask_block=block)
    return _block_sparse_prefill_xla(q, k, v, mask, scale, block)


# ------------------------------------------------ sparse prefill attention
def sparse_prefill_unsupported_reason(q, k, v, mask):
    """Why the masked K/V-tiled flash kernel can NOT take an ``S`` block's
    prefill, or None if it can: q, k ``[B, H, S, Dqk]`` and v ``[B, H, S,
    Dv]`` of one float dtype, an int8 selection ``[B, S, S]``, concrete
    shapes, heads no wider than ``_MAX_HEAD_DIM``, and a length the int8
    tile cuts into blocks (one whole block, or a multiple of 128).
    Arrays or shapes (``jax.ShapeDtypeStruct``): the caller asks before it
    builds them.  A non-None reason routes to the XLA twin
    (``kernels.sparse_prefill_fallback``)."""
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4 or mask.ndim != 3:
        return "rank: q%s k%s v%s mask%s, want 4, 4, 4 and 3" % (
            q.ndim, k.ndim, v.ndim, mask.ndim)
    dims = tuple(q.shape) + tuple(k.shape) + tuple(v.shape) \
        + tuple(mask.shape)
    if not all(isinstance(d, int) for d in dims):
        return "symbolic shape (q%s mask%s)" % (q.shape, mask.shape)
    B, H, S, D = q.shape
    if tuple(k.shape) != (B, H, S, D) or tuple(v.shape[:3]) != (B, H, S) \
            or tuple(mask.shape) != (B, S, S):
        return "q%s k%s v%s mask%s are not [B,H,S,Dqk] twice, [B,H,S,Dv] " \
            "and [B,S,S]" % (tuple(q.shape), tuple(k.shape),
                             tuple(v.shape), tuple(mask.shape))
    if not (q.dtype == k.dtype == v.dtype) \
            or q.dtype not in (jnp.float32, jnp.bfloat16):
        return "operands %s/%s/%s, want all float32 or all bfloat16" % (
            q.dtype, k.dtype, v.dtype)
    if mask.dtype != jnp.int8:
        return "the selection is %s, want int8" % mask.dtype
    if max(D, v.shape[3]) > _MAX_HEAD_DIM:
        return "head dim %d > %d" % (max(D, v.shape[3]), _MAX_HEAD_DIM)
    if S > _TILED_BLOCK and S % 128:
        return "length %d over one block of %d is no multiple of 128" % (
            S, _TILED_BLOCK)
    return None


def sparse_prefill_route(q, k, v, mask):
    """How an ``S`` block's prefill attends over what its indexer selected,
    by the module's rule: None where the masked K/V-tiled flash kernel
    takes it (:func:`sparse_prefill_attention`: one pass over the EXPANDED
    form, the selection as a mask; counter ``kernels.sparse_prefill``),
    else why the XLA twin does — the caller's own gather of each query's
    selected latent rows, attended in the absorbed form a chunk of queries
    at a time: tier off, the default knob on an interpreted backend
    (``kernels.gated_fallback``), or a shape the kernel cannot take
    (:func:`sparse_prefill_unsupported_reason`;
    ``kernels.sparse_prefill_fallback``).  The caller asks before it
    builds either route's operands, so ``q``, ``k``, ``v`` and ``mask``
    may be shapes.  The decision lands, as ``impl`` "masked" or "xla", in
    the export route sink of :func:`record_sparse_prefill_routes`."""
    reason = _route_reason(
        lambda: sparse_prefill_unsupported_reason(q, k, v, mask),
        _telemetry.counter("kernels.sparse_prefill_fallback"))
    if reason is None:
        _telemetry.counter("kernels.sparse_prefill").inc()
    _note_route("sparse_prefill", impl="xla" if reason else "masked",
                reason=reason)
    return reason


def sparse_prefill_attention(q, k, v, mask, scale):
    """Causal attention of a whole prompt over the pairs ``mask`` holds
    at non-zero, on the route :func:`sparse_prefill_route` gave the kernel:
    ``ops.pallas_kernels.flash_attention_tiled`` with the selection
    (``mx_attention_tiled_masked``; K/V blocks above the diagonal skipped
    and never fetched).  q, k ``[B, H, S, Dqk]``, v ``[B, H, S, Dv]``,
    mask int8 ``[B, S, S]`` -> ``[B, H, S, Dv]``.  No scope of its own:
    the caller's (``mx.sparse_attention``) names its device time."""
    return flash_attention_tiled(q, k, v, causal=True, scale=scale,
                                 mask=mask)


def index_unsupported_reason(q, w, pages, page_table, lengths, first_row,
                             layer=None):
    """Why the Pallas index-score kernel can NOT take this decode call, or
    None if it can: concrete shapes, pages of whole 128-lane tiles whose
    key rows start on a sublane tile, queries as wide as the key rows in
    the pool's dtype.  A non-None reason routes to the XLA twin
    (``kernels.index_fallback``)."""
    if q.ndim != 3 or w.ndim != 2 or pages.ndim != (3 if layer is None
                                                    else 4):
        return "rank: q%s w%s pages %s" % (q.ndim, w.ndim, pages.ndim)
    fixed = tuple(q.shape) + tuple(w.shape) + tuple(pages.shape[-2:]) \
        + tuple(page_table.shape) + tuple(lengths.shape)
    if not all(isinstance(d, int) for d in fixed):
        return "symbolic shape (q%s table%s)" % (q.shape, page_table.shape)
    if not isinstance(pages.shape[-3], int) \
            and not _pallas_dynamic_shapes():
        return "symbolic page count %s outside a dynamic-shape export" % (
            pages.shape[-3],)
    width, psz = pages.shape[-2:]
    di = q.shape[2]
    pack = 32 // pages.dtype.itemsize
    if psz % 128 or di % pack or first_row % pack \
            or not 0 <= first_row <= width - di:
        return "key rows [%d, %d) of a page [%d, psz %d]: psz must be a " \
            "multiple of 128, the rows of %d" % (first_row, first_row + di,
                                                 width, psz, pack)
    if q.dtype != pages.dtype or w.shape != q.shape[:2]:
        return "q %s %s, w %s over %s pages" % (
            q.dtype, tuple(q.shape), tuple(w.shape), pages.dtype)
    return None


def _index_scores_xla(q, w, pages, page_table, lengths, first_row,
                      layer=None):
    """The XLA twin of the index-score kernel: each page's key rows
    gathered (a slice a page), every token of the table scored."""
    L, P, width, psz = (1,) + tuple(pages.shape) if layer is None \
        else pages.shape
    di = q.shape[2]
    ids = jnp.clip(page_table, 0, P - 1) + (0 if layer is None
                                            else layer * P)
    flat = pages.reshape((-1, width, psz))
    keys = jax.vmap(jax.vmap(lambda i: lax.dynamic_slice(
        flat, (i, first_row, 0), (1, di, psz))[0]))(ids)    # [B,W,Di,psz]
    s = jnp.einsum("bhd,bwdp->bwhp", q, keys,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, None, :, None], axis=2)


def index_scores(q, w, pages, page_table, lengths, first_row, layer=None):
    """A decode step's index scores (an ``S`` block's indexer, DeepSeek
    Sparse Attention) over every token the rows' pages hold: ``sum_j w_j
    relu(q_j . k)`` with the index keys ``k`` in rows ``[first_row,
    first_row + Di)`` of latent pages whose tokens lie on the lanes.  ``q``
    ``[B, Hi, Di]``, ``w`` ``[B, Hi]`` float32; ``pages``, ``page_table``,
    ``lengths`` and ``layer`` as :func:`latent_paged_attention`'s.  Returns
    ``[B, W, psz]`` float32 in page-table order; what lies past a row's
    length is the caller's to mask.

    Routing by the module's rule: the Pallas kernel
    (``ops.pallas_kernels.pallas_index_scores``, named ``mx_index_scores``;
    counter ``kernels.index_scores``), which copies only the key rows of a
    row's own pages, or the XLA twin, which gathers them for the whole
    table (``kernels.index_fallback`` where the shape is refused).  The
    call carries no scope of its own: the caller's (``mx.dsa_indexer``)
    names its device time."""
    reason = _route_reason(
        lambda: index_unsupported_reason(q, w, pages, page_table, lengths,
                                         first_row, layer=layer),
        _telemetry.counter("kernels.index_fallback"))
    if reason is None:
        _telemetry.counter("kernels.index_scores").inc()
        return pallas_index_scores(q, w, pages, page_table, lengths,
                                   first_row, layer=layer)
    return _index_scores_xla(q, w, pages, page_table, lengths, first_row,
                             layer=layer)


# ------------------------------------------------------- grouped product
# XLA's grouped product wants whole row tiles: on the chip a row count
# off the tile gave wrong products (PERF.md section 6, PR 27: 4,532 rows,
# every token off by 20% of the logits' scale)
_XLA_GROUPED_ROW_TILE = 256


def grouped_unsupported_reason(rows, w, sizes, w_b=None):
    """Why the Pallas grouped product can NOT take this call, or None if
    it can.  Trace-time shape/dtype checks only.  A non-None reason
    routes to ``lax.ragged_dot`` (``kernels.grouped_fallback``) and is
    surfaced in the export route sink (:func:`record_grouped_routes`)."""
    if rows.ndim != 2 or w.ndim != 3 or sizes.ndim != 1:
        return "rank: rows%s w%s sizes%s, want 2, 3 and 1" % (
            rows.ndim, w.ndim, sizes.ndim)
    dims = tuple(rows.shape) + tuple(w.shape) + tuple(sizes.shape)
    if not all(isinstance(d, int) for d in dims):
        return "symbolic shape (rows%s w%s)" % (rows.shape, w.shape)
    if w.shape[:2] != (sizes.shape[0], rows.shape[1]):
        return "w%s is not [groups %d, K %d, N]" % (
            tuple(w.shape), sizes.shape[0], rows.shape[1])
    if rows.dtype != w.dtype or rows.dtype not in (jnp.float32,
                                                   jnp.bfloat16):
        return "operands %s and %s, want both float32 or both bfloat16" \
            % (rows.dtype, w.dtype)
    if w_b is not None and (w_b.shape != w.shape or w_b.dtype != w.dtype):
        return "the second matrix %s %s is not like the first %s %s" % (
            tuple(w_b.shape), w_b.dtype, tuple(w.shape), w.dtype)
    k, n = w.shape[1:]
    if k % 128 or n % 128:
        return "K=%d and N=%d must be multiples of 128" % (k, n)
    if grouped_col_tile(k * (1 if w_b is None else 2), n,
                        w.dtype.itemsize) is None:
        return "a [K=%d, 128] block of the weights exceeds the vmem " \
            "budget %d" % (k, _config.get("kernels.vmem_budget"))
    return None


def _grouped_matmul_xla(rows, w, sizes, epilogue, out_dtype, w_b=None):
    """The XLA twin of the grouped product: ``lax.ragged_dot`` over the
    rows padded to whole tiles of :data:`_XLA_GROUPED_ROW_TILE` (the rows
    added join no group), accumulated in float32; once a matrix where a
    group has two."""
    m = rows.shape[0]
    rows = jnp.pad(rows, ((0, -m % _XLA_GROUPED_ROW_TILE), (0, 0)))
    outs = [lax.ragged_dot(rows, mat, sizes,
                           preferred_element_type=jnp.float32)[:m]
            for mat in ((w,) if w_b is None else (w, w_b))]
    out = outs[0] if epilogue is None else epilogue(*outs)
    return out.astype(out_dtype)


def grouped_matmul(rows, w, sizes, epilogue=None, out_dtype=jnp.float32,
                   w_b=None):
    """Grouped matrix product with kernel routing: ``rows [M, K]`` lie
    sorted by group, group ``g`` owns the next ``sizes[g]`` of them and
    multiplies them by ``w[g] [K, N]``; the float32 product goes through
    ``epilogue`` (an elementwise function, if given) and is cast to
    ``out_dtype``: ``[M, N]``.  A row behind the last group is whatever
    the product left there.  With ``w_b`` (a second matrix a group, like
    ``w``: the gate and up matrices of a gated expert) the rows multiply
    both and ``epilogue(a, b)`` folds the two float32 products into the
    one result.

    Routing: tier on and shape feasible (float32 or bfloat16 operands,
    ``K`` and ``N`` multiples of 128, a ``[K, 128]`` block inside
    ``kernels.vmem_budget``) → the Pallas kernel
    (``ops.pallas_kernels.pallas_grouped_matmul``, named
    ``mx_grouped_matmul``), which reads the groups that have rows, each
    once, multiplies row tiles sized from the shapes and applies the
    epilogue to the rows it walks; counter ``kernels.grouped_matmul``.
    Otherwise → ``lax.ragged_dot`` and the epilogue over every row: tier
    off, the default knob on an interpreted backend
    (``kernels.gated_fallback``), or a shape the kernel cannot take
    (``kernels.grouped_fallback``).  Both round where the other does:
    float32 accumulators, the epilogue in float32, one cast.  The
    decision and its reason land in those counters and, under
    :func:`record_grouped_routes`, in the export route sink.  The call
    carries no scope of its own: the caller's (``mx.moe_experts``) names
    the kernel's device time."""
    reason = _route_reason(
        lambda: grouped_unsupported_reason(rows, w, sizes, w_b),
        _telemetry.counter("kernels.grouped_fallback"))
    if reason is None:
        _telemetry.counter("kernels.grouped_matmul").inc()
        _note_route("grouped", impl="grouped", reason=None)
        return pallas_grouped_matmul(rows, w, sizes, epilogue=epilogue,
                                     out_dtype=out_dtype, w_b=w_b)
    _note_route("grouped", impl="xla", reason=reason)
    return _grouped_matmul_xla(rows, w, sizes, epilogue, out_dtype, w_b)


# ------------------------------------------------------ retention update
def retention_unsupported_reason(state, z, pk, pq, g, v):
    """Why the Pallas retention update can NOT take this call, or None if
    it can.  Trace-time shape/dtype checks only.  A non-None reason
    routes to the XLA twin (``kernels.retention_fallback``) and is
    surfaced in the export route sink (:func:`record_retention_routes`)."""
    ranks = tuple(a.ndim for a in (state, z, pk, pq, g, v))
    if ranks != (4, 3, 3, 4, 2, 3):
        return "rank: state, z, pk, pq, g, v %s, want (4, 3, 3, 4, 2, 3)" \
            % (ranks,)
    dims = sum((tuple(a.shape) for a in (state, z, pk, pq, g, v)), ())
    if not all(isinstance(d, int) for d in dims):
        return "symbolic shape (state%s pq%s)" % (state.shape, pq.shape)
    b, kvh, n, dh = state.shape
    if z.shape != (b, kvh, n) or pk.shape != z.shape or g.shape != (b, kvh) \
            or v.shape != (b, kvh, dh) or pq.shape[:2] + pq.shape[3:] \
            != (b, kvh, n):
        return "z%s pk%s pq%s g%s v%s do not match the state %s" % (
            tuple(z.shape), tuple(pk.shape), tuple(pq.shape),
            tuple(g.shape), tuple(v.shape), tuple(state.shape))
    if state.dtype != jnp.float32:
        return "the state is %s, want float32" % state.dtype
    if dh % 128:
        return "Dh=%d must be a multiple of 128" % dh
    if retention_row_tile(n, dh) is None:
        return "no row tile divides N=%d in multiples of 8 inside the " \
            "vmem budget %d" % (n, _config.get("kernels.vmem_budget"))
    return None


def _retention_update_xla(state, z, pk, pq, g, v):
    """The XLA twin of the retention update: the decayed state plus the
    token's outer product, the normaliser beside it, and each query head's
    read-out of the new state as a sum over its rows (not an MXU product:
    the state is not rounded on the way), all float32."""
    f32 = jnp.float32
    state = g[..., None, None] * state \
        + pk[..., None] * v.astype(f32)[:, :, None, :]
    z = g[..., None] * z + pk
    num = jnp.sum(pq[..., None] * state[:, :, None], axis=3)
    den = jnp.sum(pq * z[:, :, None], axis=-1)
    return state, z, num, den


def retention_update(state, z, pk, pq, g, v):
    """One token a row through a power-retention layer's state, with
    kernel routing: ``S <- g S + phi(k) v^T`` and ``z <- g z + phi(k)``
    in float32, then the ``R`` query heads of each K/V head read the new
    state, ``num = phi(q)^T S`` and ``den = phi(q) . z`` (the layer's
    output is ``num / den``).

    ``state`` ``[B, KVH, N, Dh]`` and ``z`` ``[B, KVH, N]`` float32;
    ``pk`` ``[B, KVH, N]`` and ``pq`` ``[B, KVH, R, N]`` the expanded key
    and queries, float32; ``g`` ``[B, KVH]`` the gates; ``v`` ``[B, KVH,
    Dh]``.  Returns ``(state, z, num [B, KVH, R, Dh], den [B, KVH, R])``.
    A zero key under a gate of one leaves state and normaliser as they
    were, bit for bit, on both routes.

    Routing: tier on and shape feasible (concrete dims, a float32 state,
    ``Dh`` a multiple of 128, a row tile that divides ``N`` inside
    ``kernels.vmem_budget``) → the Pallas kernel
    (``ops.pallas_kernels.pallas_retention_update``, named
    ``mx_retention_update``), which streams each head's state through
    on-chip memory once — update, write back, read-outs of the resident
    tile; counter ``kernels.retention_update``.  Otherwise → the XLA twin,
    two fusions that read the state twice: tier off, the default knob on
    an interpreted backend (``kernels.gated_fallback``), or a shape the
    kernel cannot take (``kernels.retention_fallback``).  Both keep
    float32 products and sums; the read-outs differ by the order of their
    sums.  The decision and its reason land in those counters and, under
    :func:`record_retention_routes`, in the export route sink.  The call
    carries no scope of its own: the caller's (``mx.retention_update``)
    names the kernel's device time."""
    reason = _route_reason(
        lambda: retention_unsupported_reason(state, z, pk, pq, g, v),
        _telemetry.counter("kernels.retention_fallback"))
    if reason is None:
        _telemetry.counter("kernels.retention_update").inc()
        _note_route("retention", impl="retention", reason=None)
        return pallas_retention_update(state, z, pk, pq, g, v)
    _note_route("retention", impl="xla", reason=reason)
    return _retention_update_xla(state, z, pk, pq, g, v)
