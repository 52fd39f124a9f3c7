"""``mx.serving`` generation engine — token-level continuous batching
over a paged device-resident KV cache.

Reference: the C predict API's stateful RNN serving
(include/mxnet/c_predict_api.h MXPredCreatePartialOut + state handles)
kept one sequence's recurrent state device-resident across calls; the
TPU-native analog generalizes that to MANY concurrent sequences sharing
one page pool, scheduled per decode ITERATION (Orca) instead of per
request, with vLLM-style block-paged KV memory so cache capacity is
pooled instead of pre-reserved per slot.

Architecture (one :class:`GenerationEngine` thread per generation model,
run under the same restart supervisor as the one-shot batcher):

  submit ──► admission check ──► FIFO ──► engine loop, per iteration:
             (bounded queue,              1. emit what the last turn
              breaker state)                 fetched: tokens reach their
                                             rows, finished sequences
                                             (EOS / max_new) resolve their
                                             futures, and a row no queued
                                             program carries any more
                                             returns its pages and slot
                                          2. harvest expired deadlines;
                                             admit queue head into a free
                                             decode slot IF the page pool
                                             covers prompt+max_new pages
                                             (head-of-line wait otherwise:
                                             serving.kv_pool_exhausted)
                                          3. dispatch a PREFILL for each
                                             new request (B=1 program at
                                             its prompt bucket)
                                          4. dispatch one DECODE step for
                                             every row with tokens left
                                             (B=slots program at the
                                             page-table width bucket)
                                          5. fetch every program but that
                                             decode step: the host waits
                                             with the next step queued
                                             behind the one it waits on

Key properties:

* **One decode step in flight ahead of the host** — step N+1 is
  dispatched before step N's tokens are fetched, so the device runs N+1
  while the host copies N's tokens out, emits them, admits and dispatches
  the next programs.  A step's token ids never leave the device: N+1
  reads them from N's output array (and a prefill's first token is put
  into its row there) by two tiny programs compiled at ``start()``.  A
  row whose ``max_new`` budget the dispatched steps already cover is left
  out of the next step; a row that ends on an EOS rides one step more,
  whose token is dropped.  Pages and state slots return to the pool only
  once every dispatched program that carries the row has been fetched.
* **Flat compiles** — programs are AOT-compiled at ``start()``: one
  prefill program per prompt bucket and one decode program per
  page-table width, all at fixed batch (1 and ``decode_slots``).  Ragged
  traffic — any prompt-length mix, mid-flight exits, joins — never
  reaches the compiler (``tools/check_generation.py`` proves it).
* **Paged KV memory** — position ``t`` of a sequence lives at slot
  ``t % page_size`` of page ``table[t // page_size]``; pages come from a
  shared free list and return to it once the programs that carry their
  sequence's last step have been fetched.  The pool dimension is
  symbolic in the v4 artifact, so ``serving.kv_pages`` is a pure runtime
  choice.
* **Oracle parity** — the token stream each request receives is that of
  the eager greedy oracle (``models.TransformerLM.greedy_decode``)
  regardless of what else is in flight: prefill runs the exact
  ``apply()`` attention math and the decode step's masked paged
  attention contributes exact zeros for padding
  (kernels.paged_attention).  Bit for bit on the cpu backend at f32
  (``tools/check_generation.py``); on the chip in bf16 the paths round
  differently and an argmax between near-tied logits can flip — 1 token
  in 256 at the default config, 0.013 below the oracle's best logit
  (``chip_smoke.py``, PR 21), which checks the gap, not the bits.
* **Donated pool** — the page pool is donated into every program call
  and comes back in the buffer it went in: the programs carry it whole
  through their layers and write the new rows in place, so it is the
  only O(pool) buffer, with no second copy among a program's
  temporaries.  A dispatch failure therefore poisons it, so the engine
  fails every in-flight sequence with the causal error, rebuilds the
  pool zeroed, feeds the model's circuit breaker and keeps serving.
* **Shared-prefix pages** (``serving.shared_prefix``) — full prompt-
  prefix pages are content-hashed at admission; concurrent requests with
  a common prefix (the system-prompt case) map to the SAME physical
  pages with refcounted sharing, freed only when the last reader exits.
  Causal attention makes a prefix position's K/V depend only on the
  tokens before it, so the shared bytes are identical no matter which
  sharer wrote them; divergence is page-granular copy-on-write by
  construction — the first token past the shared full pages lands in a
  private page.  ``serving.prefix_hits`` / ``serving.prefix_pages_shared``
  count the wins; ``kv_pages_in_use`` counts every physical page ONCE.
* **Sampling** (v5 artifacts) — per-request temperature / top-k / top-p
  ride the decode program family with a per-request PRNG key folded by
  position, so a fixed seed yields ONE deterministic stream regardless
  of batch composition.  Greedy (temperature 0) stays the default and
  keeps the bitwise oracle contract.
* **PR-7 fault tolerance per slot** — admission sheds past
  ``serving.max_pending`` (ServerOverloadedError), queued requests whose
  deadline lapses complete typed and never prefill
  (DeadlineExceededError), an open breaker fails submits fast
  (CircuitOpenError), and the engine thread restarts under the
  ``mx.resilience`` budget.

Telemetry: ``serving.tokens_generated[.model]`` counters,
``serving.kv_pages_in_use.<model>`` gauge, ``serving.prefill_ms`` /
``serving.decode_step_ms`` / ``serving.ttft_ms`` /
``serving.generate_request_ms`` timers,
``serving.kv_pool_exhausted[.model]`` counters, ``serving.decode_ahead``
(decode steps dispatched while an earlier program's tokens were not yet
fetched), and one
``serving_generate`` JSONL record per finished request (prompt_len,
new_tokens, ttft_ms, wall_ms — ``tools/telemetry_report.py`` folds these
into per-model TTFT/tokens-per-second columns and the
``kv_pool_exhaustion`` anomaly).

Knobs (config.py): ``serving.kv_page_size`` (baked at export),
``serving.kv_pages``, ``serving.decode_slots``; docs/SERVING.md
"Generation" has the full walkthrough.
"""
from __future__ import annotations

import itertools as _itertools
import logging
import math as _math
import threading
import time as _time
from collections import deque
from concurrent.futures import Future

import numpy as _np

import jax

from . import config as _config
from . import io as _io
from . import obs as _obs
from . import telemetry as _telemetry
from . import tracing as _tracing
from .models.transformer import SAMPLE_TIERS, sample_tier
from .serving import (CircuitOpenError, DeadlineExceededError,
                      ServerOverloadedError, ServingError,
                      _access_outcome)

__all__ = ["GenerationEngine"]

_LOG = logging.getLogger("mxnet_tpu.generation")


def _kernels_enabled():
    from . import kernels as _kernels
    return _kernels.enabled()


class _EngineCrashError(OSError):
    """Internal: wraps an engine-loop crash so
    ``resilience.call_with_retry`` drives the restart backoff."""


def _access_ids(request_id, trace_id):
    """``log_access`` identity: the request id, and the trace id as an
    extra field where a causal span enclosed the submit."""
    if trace_id is None:
        return {"request_id": str(request_id)}
    return {"request_id": str(request_id), "trace_id": str(trace_id)}


def _begin(name, **args):
    """Open a detached ``serving`` span (it outlives the loop's turn)."""
    sp = _tracing.detached_span(name, cat="serving", **args)
    sp.__enter__()
    return sp


def _end(sp):
    if sp is not None:
        sp.__exit__(None, None, None)


class _GenRequest:
    """One generation request: prompt + budget + the future its token
    stream resolves, stamped for TTFT / deadline accounting."""

    __slots__ = ("prompt", "plen", "max_new", "eos_id", "future",
                 "t_submit", "deadline", "need", "stall_counted",
                 "request_id", "trace_id", "temperature", "top_k", "top_p",
                 "key_words", "prefix_keys", "want_replay")

    def __init__(self, prompt, max_new, eos_id, deadline_ms, need,
                 trace_id=None, temperature=0.0, top_k=0, top_p=1.0,
                 seed=0, prefix_keys=(), request_id=None,
                 want_replay=False):
        self.prompt = prompt
        self.plen = int(prompt.shape[0])
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.future = Future()
        # the engine's own number for the request: on the future, in the
        # access log and in the serving_generate event, tracing on or off
        self.request_id = request_id
        self.future.request_id = request_id
        self.t_submit = _time.perf_counter()
        self.deadline = (self.t_submit + float(deadline_ms) * 1e-3) \
            if deadline_ms and deadline_ms > 0 else None
        self.need = int(need)          # pages for prompt + max_new
        self.stall_counted = False     # kv_pool_exhausted counted once
        self.trace_id = trace_id       # enclosing span's trace, if any
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        # raw uint32 key words in jax.random.PRNGKey layout — built
        # host-side once so every dispatch sees the same stream identity
        s = int(seed) & 0xFFFFFFFFFFFFFFFF
        self.key_words = (s >> 32, s & 0xFFFFFFFF)
        # content hashes of the FULL prompt-prefix pages, page 0 first:
        # key i covers tokens [0, (i+1)*page_size) — admission maps them
        # to shared physical pages
        self.prefix_keys = tuple(prefix_keys)
        self.want_replay = bool(want_replay)

    def expired(self, now):
        return self.deadline is not None and now >= self.deadline

    def access_ids(self):
        return _access_ids(self.request_id, self.trace_id)


class _Slot:
    """One active decode slot: the sequence's pages, cached length and
    generated tokens.  Engine-thread-only state (``prefix_keys`` names
    the leading ``slot.pages`` entries owned by the shared-prefix map —
    released through ``_release_pages_locked``, never freed directly)."""

    __slots__ = ("req", "pages", "pos", "tokens", "ttft_ms",
                 "prefix_keys", "t_admit", "t_prefill_start", "token_t",
                 "routed", "logprobs", "sent", "inflight", "ended", "src")

    def __init__(self, req, pages, t_admit, prefix_keys=()):
        self.req = req
        self.pages = pages
        # tokens in the cache once the programs dispatched so far have
        # run; the tokens emitted
        self.pos = req.plen
        self.tokens = []
        self.ttft_ms = None
        self.prefix_keys = tuple(prefix_keys)
        # host-clock stamps (perf_counter): admission, the prefill call,
        # and one per emitted token — token_t[0] is the first token
        self.t_admit = t_admit
        self.t_prefill_start = None
        self.token_t = []
        # where the request asked for a replay: the experts chosen, [E
        # blocks, n, top_k] for the prompt and then [E blocks, 1, top_k]
        # a fed token; a log-probability a produced token
        self.routed = []
        self.logprobs = []
        # ahead of the host: tokens the dispatched programs produce for
        # the row, how many programs that carry it are not yet emitted,
        # whether its last token has been (future resolved), and (device
        # array, index) of the token its next decode step is fed
        self.sent = 0
        self.inflight = 0
        self.ended = False
        self.src = None


class _Step:
    """One dispatched program whose tokens are not yet emitted: the rows
    it carries, its output on the device (then on the host), the stamps
    of its timer and the spans that stay open across the loop's turns —
    ``span`` (``engine.decode`` / ``engine.prefill``) from its dispatch
    to its emit, ``device`` (``engine.<kind>.device``) while the host
    waits on it."""

    __slots__ = ("step", "kind", "program", "rows", "out", "span",
                 "device", "t0", "t1", "route")

    def __init__(self, step, kind, program, rows, span, route=None):
        self.step = step
        self.kind = kind
        self.program = program
        self.rows = rows            # [(slot index, _Slot)]
        self.out = None             # jax.Array, then its numpy copy
        self.span = span
        self.device = None
        self.t0 = self.t1 = None    # the host began waiting / fetched
        self.route = route          # a decode width's paged route


def _token_programs(batch, decode_outs, outs):
    """The two programs that keep a decode step's token ids on the
    device, by program output ``(shape, dtype)``: ``feed[shape](out,
    keep)`` for each of ``decode_outs`` — the first ``batch`` entries of a
    decode step's output where ``keep``, 0 elsewhere (the padding rows'
    id, as a host-built operand had it) — and ``put[shape](tok, out, row,
    at)`` for each of ``outs`` — ``tok`` with ``out[at]`` in ``row``.
    Neither donates."""
    import jax.numpy as jnp
    tok = jax.ShapeDtypeStruct((batch,), _np.int32)
    keep = jax.ShapeDtypeStruct((batch,), _np.bool_)
    index = jax.ShapeDtypeStruct((), _np.int32)

    def feed(out, keep):
        return jnp.where(keep, out[:batch], 0).astype(_np.int32)

    def put(tok, out, row, at):
        return tok.at[row].set(out[at].astype(_np.int32))

    def compile_each(fn, specs, outs):
        return {shape: jax.jit(fn).trace(
            *specs(jax.ShapeDtypeStruct(shape, dtype))).lower().compile()
            for shape, dtype in outs}

    return (compile_each(feed, lambda out: (out, keep), decode_outs),
            compile_each(put, lambda out: (tok, out, index, index), outs))


class GenerationEngine:
    """Per-model continuous-batching generation scheduler (one thread).

    Owned by :class:`mxnet_tpu.serving.Server` (``register(...,
    generate=True)``); drives a :class:`mxnet_tpu.deploy
    .GenerationPredictor`'s prefill/decode program families over a
    shared page pool."""

    def __init__(self, name, predictor, breaker=None, num_pages=None,
                 decode_slots=None, max_pending=None,
                 default_deadline_ms=None):
        self.name = name
        self.predictor = predictor
        self.breaker = breaker
        self.num_pages = int(num_pages if num_pages is not None
                             else _config.get("serving.kv_pages"))
        self.decode_slots = int(decode_slots if decode_slots is not None
                                else _config.get("serving.decode_slots"))
        if predictor.decode_batch is not None:
            # the artifact pinned its decode batch at export (a concrete
            # dim is what lets the Pallas paged kernel bake in) — the
            # AOT program admits exactly that many slots, knob or not
            self.decode_slots = predictor.decode_batch
        self.max_pending = int(max_pending if max_pending is not None
                               else _config.get("serving.max_pending"))
        self.default_deadline_ms = float(
            default_deadline_ms if default_deadline_ms is not None
            else _config.get("serving.default_deadline_ms"))
        psz = predictor.page_size
        # a single request may never need more pages than the pool holds
        self.max_need = min(self.num_pages,
                            _math.ceil(predictor.max_context / psz))
        if self.max_need < 1:
            raise ServingError(
                "model %r: serving.kv_pages=%d cannot hold one page"
                % (name, self.num_pages))
        #: a model of latent pages (one pool, the latent site's counters)
        self._latent = predictor.meta["kv"].get("page_layout") == "lanes"
        #: a model whose latent blocks select their tokens (the sparse
        #: site's counters)
        self._sparse = bool(predictor.meta["kv"].get("sparse"))
        #: a model whose blocks attend over the pages their compressed keys
        #: chose (the block-sparse sites' counters)
        self._block_sparse = bool(predictor.meta["kv"].get("block_sparse"))
        self._share = bool(_config.get("serving.shared_prefix"))
        if self._share and predictor.state:
            # a shared page skips the prefill that would have built the
            # sharer's recurrent state: no sharing for such a model
            self._share = False
            _telemetry.counter("serving.prefix_share_refused").inc()
            _LOG.warning(
                "serving: model %r keeps per-slot state beside its K/V "
                "pages (%d arrays); serving.shared_prefix is refused for "
                "it", name, len(predictor.state))
        # Cross-thread state (submit side vs engine thread) — the same
        # lock-discipline contract tools/mxlint.py checks on the Server.
        self._queue = deque()            # guarded-by: _cond
        self._free = list(range(self.num_pages))  # guarded-by: _cond
        # shared-prefix map: content key -> [page_id, refcount, populated]
        self._prefix = {}                # guarded-by: _cond
        self._cond = threading.Condition()
        self._started = False            # guarded-by: _cond
        self._stopping = False           # guarded-by: _cond
        self._abort = False              # guarded-by: _cond
        self._dead = None                # guarded-by: _cond — crash exc
        # last engine-loop iteration (the watchdog probe's liveness clock)
        self._last_iteration = _time.perf_counter()  # guarded-by: _cond
        self._probe_name = "serving-generate-%x" % id(self)
        # guarded-by[writes]: _cond — stop() joins outside the lock
        self._thread = None
        # Engine-thread-only state: the page pool arrays and decode slots
        # are touched exclusively by the engine loop — no lock.
        self._slots = [None] * self.decode_slots
        # the cache pytree: the page pool (2 arrays, 4 when int8), then the
        # state region's per-slot arrays where the model keeps one
        self._kv = None
        self._prefill = {}    # prompt bucket -> compiled program
        self._decode = {}     # page-table width -> compiled program
        # program -> what its ``*.dispatch`` span says of the call's host
        # operands (known from the compiled shapes, never computed a step)
        self._host_operands = {}
        self._iteration = 0   # engine.iteration spans, numbered from 1
        # programs dispatched and not yet fetched, oldest first; then
        # fetched and not yet emitted (_Step); the newest decode step's
        # output (what the next step's token ids are read from)
        self._ahead = deque()
        self._fetched = []
        self._last_out = None
        self._steps = _itertools.count(1)   # every program call, from 1
        # token ids on the device: zeros (padding rows' id) and the two
        # programs of _token_programs, by program output shape
        self._tok0 = None
        self._feed = {}
        self._put = {}
        # request ids, from 1 (next() of a count is atomic: submit runs on
        # any thread)
        self._request_ids = _itertools.count(1)

    # ----------------------------------------------------------- compile
    def _compile_programs(self):
        """AOT-compile the full program family: one prefill per prompt
        bucket (B=1) and one decode step per page-table width
        (B=decode_slots).  This is the ENTIRE compiled set — ragged
        generation traffic never adds to it (``serving.compiles`` stays
        equal to the family size, the check_generation.py gate)."""
        from . import perf as _perf
        gp = self.predictor
        params = gp._params
        pspec = jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params)
        kvspec = gp.kv_pool_specs(self.num_pages, self._state_slots())
        i32 = _np.int32
        # a prefill of a model with a state region is told its slot
        slot_spec = (jax.ShapeDtypeStruct((1,), i32),) if gp.state else ()

        def sample_specs(b):
            # the uniform program wrappers take the sampling operands in
            # every format (v4 ignores them)
            return (jax.ShapeDtypeStruct((b,), _np.float32),
                    jax.ShapeDtypeStruct((b,), i32),
                    jax.ShapeDtypeStruct((b,), _np.float32),
                    jax.ShapeDtypeStruct((b, 2), _np.uint32))

        def compile_one(fn, arg_specs, label, on_device=2):
            # parameters and pool live on the device (and a decode step's
            # token ids); the rest of a call's operands are host arrays the
            # runtime copies over each time
            host = arg_specs[on_device:]
            self._host_operands[label] = {
                "host_args": len(host),
                "host_bytes": sum(_math.prod(s.shape)
                                  * _np.dtype(s.dtype).itemsize
                                  for s in host)}
            t0 = _time.perf_counter()
            with _tracing.span("serving.compile", cat="serving",
                               model=self.name, program=label):
                traced = fn.trace(*arg_specs)
                t1 = _time.perf_counter()
                lowered = traced.lower()
                t2 = _time.perf_counter()
                program = lowered.compile()
                t3 = _time.perf_counter()
            _telemetry.counter("serving.compiles").inc()
            _telemetry.timer("serving.compile_ms").observe(
                (t3 - t0) * 1e3)
            _perf.register_compiled(
                "serving", "%s/%s" % (self.name, label), program,
                phases_ms={"trace_ms": (t1 - t0) * 1e3,
                           "lower_ms": (t2 - t1) * 1e3,
                           "compile_ms": (t3 - t2) * 1e3},
                dtype=str(gp.kv_dtype))
            return program

        for s_bucket in gp.prompt_buckets:
            if s_bucket in self._prefill:
                continue
            w_s = _math.ceil(s_bucket / gp.page_size)
            self._prefill[s_bucket] = compile_one(
                gp.prefill_fn(s_bucket),
                (pspec, kvspec,
                 jax.ShapeDtypeStruct((1, s_bucket), i32),
                 jax.ShapeDtypeStruct((1,), i32),
                 jax.ShapeDtypeStruct((1, w_s), i32))
                + slot_spec + sample_specs(1),
                "prefill-s%d" % s_bucket)
        for width in gp.decode_widths:
            if width in self._decode:
                continue
            self._decode[width] = compile_one(
                gp.decode_fn(width),
                (pspec, kvspec,
                 jax.ShapeDtypeStruct((self.decode_slots,), i32),
                 jax.ShapeDtypeStruct((self.decode_slots,), i32),
                 jax.ShapeDtypeStruct((self.decode_slots, width), i32))
                + sample_specs(self.decode_slots),
                "decode-w%d" % width, on_device=3)
        if not self._put:
            def outs(programs):
                return {(tuple(p.out_info[1].shape), p.out_info[1].dtype)
                        for p in programs}
            decode = outs(self._decode.values())
            self._feed, self._put = _token_programs(
                self.decode_slots, decode,
                decode | outs(self._prefill.values()))

    # --------------------------------------------------------- lifecycle
    def start(self):
        with self._cond:
            if self._started:
                return self
        self._compile_programs()
        self._kv = self._make_kv()
        self._tok0 = jax.device_put(_np.zeros((self.decode_slots,),
                                              _np.int32))
        with self._cond:
            self._stopping = False
            self._abort = False
            self._dead = None
            self._started = True
            self._last_iteration = _time.perf_counter()
            self._thread = threading.Thread(
                target=_tracing.wrap_context(self._supervise), daemon=True,
                name="mx-serving-generate-%s" % self.name)
        self._thread.start()
        # the serving batcher has carried a stall probe since PR-3; the
        # generation engine gets its sibling here — KV-pool occupancy,
        # decode-loop liveness and oldest in-flight request age land in
        # the watchdog hang report
        _tracing.register_stall_probe(self._probe_name, self._stall_probe)
        return self

    def stop(self, drain=True, timeout_s=30.0):
        """Stop the engine.  With ``drain`` (default) queued requests
        prefill and every in-flight sequence runs to completion; with
        ``drain=False`` queued AND active sequences fail promptly."""
        with self._cond:
            if not self._started:
                return
            self._stopping = True
            self._abort = self._abort or not drain
            self._cond.notify_all()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout_s)
            if thread.is_alive():
                _telemetry.counter("serving.stop_timeout").inc()
                _LOG.warning("serving: generation engine %r did not "
                             "drain within %.1fs", self.name, timeout_s)
        _tracing.unregister_stall_probe(self._probe_name)
        with self._cond:
            self._started = False
            self._thread = None

    # ------------------------------------------------------------ submit
    def submit(self, prompt, max_new_tokens, eos_id=None,
               deadline_ms=None, temperature=0.0, top_k=0, top_p=1.0,
               seed=None, return_replay=False):
        """Enqueue one prompt; returns a Future resolving to the
        generated token ids (np.int32, EOS included when hit).  With
        ``temperature`` 0 (default) that is the bitwise
        ``greedy_decode`` stream; ``temperature`` > 0 samples with
        optional ``top_k`` / ``top_p`` truncation under a per-request
        ``seed`` (fresh entropy when None) — v5 artifacts only.  With
        ``return_replay`` (an artifact exported with ``replay=True``) the
        future resolves to ``(ids, replay)``, what replaying the request
        elsewhere needs: ``replay["logprobs"]`` [len(ids)] float32, each
        token's log-probability, and ``replay["routed_experts"]`` [E
        blocks, tokens fed, top_k] int16, the experts every token the
        model was fed chose — the prompt, then each generated token but
        the last."""
        gp = self.predictor
        if return_replay and not gp.replay:
            raise ValueError(
                "model %r: the artifact's programs do not return what a "
                "replay needs — re-export with export_generation(..., "
                "replay=True)" % (self.name,))
        prompt = _np.asarray(prompt, _np.int32).reshape(-1)
        plen = int(prompt.shape[0])
        max_new = int(max_new_tokens)
        if plen < 1 or max_new < 1:
            raise ValueError(
                "model %r: need a non-empty prompt and max_new_tokens "
                ">= 1" % (self.name,))
        temperature = float(temperature)
        if temperature > 0.0 and not gp.sampling:
            raise ValueError(
                "model %r: temperature=%g needs a sampling-enabled "
                "artifact (format v5) — re-export with "
                "export_generation(..., sampling=True)"
                % (self.name, temperature))
        if seed is None:
            seed = _time.time_ns() if temperature > 0.0 else 0
        if plen + max_new > gp.max_context:
            raise ValueError(
                "model %r: prompt (%d) + max_new_tokens (%d) exceeds the "
                "artifact's max_context %d"
                % (self.name, plen, max_new, gp.max_context))
        gp.prefill_bucket(plen)   # raises if no bucket fits
        # (a model that keeps no page needs none: decode slots alone bound
        # its admission)
        need = _math.ceil((plen + max_new) / gp.page_size) if gp.paged else 0
        if need > self.max_need:
            raise ValueError(
                "model %r: request needs %d KV pages but the pool holds "
                "%d (serving.kv_pages) — shorten the request or grow the "
                "pool" % (self.name, need, self.num_pages))
        _telemetry.counter("serving.requests").inc()
        # the enclosing serving.submit span's trace_id (None unless a
        # causal span is open) rides the request as an extra field, so its
        # access record joins the trace
        sp = _tracing.current_span()
        trace_id = sp.trace_id if sp is not None else None
        request_id = next(self._request_ids)
        ids = _access_ids(request_id, trace_id)
        breaker = self.breaker
        if breaker is not None and breaker.rejects_submit():
            _telemetry.counter("serving.breaker_rejected").inc()
            _obs.log_access(self.name, "breaker", **ids)
            raise CircuitOpenError(
                "model %r circuit breaker is OPEN after %d consecutive "
                "dispatch failure(s); failing fast for %.0fms more"
                % (self.name, breaker.failures,
                   breaker.cooldown_remaining_ms()))
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        prefix_keys = ()
        if self._share:
            # content keys for the FULL prompt-prefix pages: key i covers
            # tokens [0, (i+1)*page_size) — causal attention makes the
            # page's K/V a pure function of those tokens, so equal keys
            # mean byte-equal pages
            psz = gp.page_size
            prefix_keys = tuple(
                (i, prompt[:(i + 1) * psz].tobytes())
                for i in range(plen // psz))
        req = _GenRequest(prompt, max_new, eos_id,
                          float(deadline_ms or 0.0), need,
                          request_id=request_id,
                          trace_id=trace_id, temperature=temperature,
                          top_k=top_k, top_p=top_p, seed=seed,
                          prefix_keys=prefix_keys,
                          want_replay=return_replay)
        with self._cond:
            if self._dead is not None:
                exc = self._dead
                raise ServingError(
                    "generation engine for model %r crashed (%s: %s) and "
                    "exhausted its restart budget; submit rejected"
                    % (self.name, type(exc).__name__, exc))
            if self._stopping or not self._started:
                raise ServingError(
                    "generation engine for model %r is %s; submit "
                    "rejected" % (self.name, "stopping" if self._stopping
                                  else "not started"))
            if self.max_pending > 0 \
                    and len(self._queue) >= self.max_pending:
                shed = True
            else:
                shed = False
                self._queue.append(req)
                self._cond.notify_all()
        if shed:
            _telemetry.counter("serving.shed_requests").inc()
            _telemetry.counter(
                "serving.shed_requests.%s" % self.name).inc()
            _obs.log_access(self.name, "shed", **ids)
            raise ServerOverloadedError(
                "generation queue for model %r is at serving.max_pending"
                "=%d; request shed — back off and retry"
                % (self.name, self.max_pending))
        return req.future

    # ----------------------------------------------------------- the loop
    def _supervise(self):
        from . import resilience as _resilience
        try:
            _resilience.call_with_retry(self._run_engine,
                                        kind="serving_batcher")
        except BaseException as exc:  # noqa: BLE001 — budget exhausted
            cause = exc.__cause__ if exc.__cause__ is not None else exc
            with self._cond:
                self._dead = cause
                queued = list(self._queue)
                self._queue.clear()
                self._cond.notify_all()
            self._fail_all(queued, cause)
            _LOG.error(
                "serving: generation engine %r crashed and exhausted its "
                "restart budget (%s: %s); submits now fail fast",
                self.name, type(cause).__name__, cause)

    def _run_engine(self):
        try:
            self._loop()
        except BaseException as exc:  # noqa: BLE001 — supervised crash
            _telemetry.counter("serving.batcher_crashes").inc()
            with self._cond:
                queued = list(self._queue)
                self._queue.clear()
            self._fail_all(queued, exc)
            self._fail_active(exc)
            _LOG.warning(
                "serving: generation engine %r crashed (%s: %s); "
                "restarting under the resilience retry budget",
                self.name, type(exc).__name__, exc)
            raise _EngineCrashError(
                "generation engine crashed: %s: %s"
                % (type(exc).__name__, exc)) from exc

    def _active(self):
        return [s for s in self._slots if s is not None]

    def _fail_all(self, reqs, exc):
        outcome = _access_outcome(exc)
        err = ("%s: %s" % (type(exc).__name__, exc)
               if outcome == "error" else None)
        for req in reqs:
            if not req.future.done():
                req.future.set_exception(exc)
                if _obs.access_log_enabled():
                    _obs.log_access(
                        self.name, outcome,
                        queue_ms=(_time.perf_counter() - req.t_submit)
                        * 1e3, error=err, **req.access_ids())

    def _fail_active(self, exc):
        """Fail every in-flight sequence and recycle its pages (the pool
        arrays were donated into the failed dispatch, so their state is
        gone — rebuild zeroed); every program in flight is forgotten, its
        spans closed."""
        for st in (*self._fetched, *self._ahead):
            _end(st.device)
            _end(st.span)
        self._ahead.clear()
        self._fetched = []
        self._last_out = None
        released = []
        outcome = _access_outcome(exc)
        err = ("%s: %s" % (type(exc).__name__, exc)
               if outcome == "error" else None)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            self._slots[i] = None
            released.append(slot)
            if not slot.req.future.done():
                slot.req.future.set_exception(exc)
                if _obs.access_log_enabled():
                    _obs.log_access(
                        self.name, outcome, ttft_ms=slot.ttft_ms,
                        tokens=len(slot.tokens), error=err,
                        **slot.req.access_ids())
        with self._cond:
            for slot in released:
                self._release_pages_locked(slot)
            # the rebuilt pool is zeroed, so any surviving shared-prefix
            # entries (refs held only by already-failed slots) are stale
            # — drop them and recycle their pages
            for entry in self._prefix.values():
                self._free.append(entry[0])
            self._prefix.clear()
            self._cond.notify_all()
        self._gauge_pages()
        # let the old cache go before the new one is made: not donated
        # into a failed call (a crash between calls), it still holds its
        # device memory, and a near-full chip has no room for both
        self._kv = None
        self._kv = self._make_kv()

    def _state_slots(self):
        """Rows of the cache's state region: one per decode slot, none
        for a model whose whole cache is pages."""
        return self.decode_slots if self.predictor.state else None

    def _make_kv(self):
        """The cache, zeroed: pages and, where the model keeps one, the
        state region (so a rebuilt cache carries no request's state)."""
        gp = self.predictor
        kv = gp.make_kv(self.num_pages, self._state_slots())
        if gp.state:
            _telemetry.gauge("serving.state_bytes").set(
                sum(int(a.nbytes) for a in kv[-len(gp.state):]))
        return kv

    def _release_pages_locked(self, slot):  # mxlint: holds(_cond)
        """Return a slot's pages to the free list — shared-prefix pages
        decref through the map and only hit the free list when the LAST
        reader exits; the trailing private pages free unconditionally.
        ``kv_pages_in_use`` therefore counts every physical page once."""
        for key in slot.prefix_keys:
            entry = self._prefix.get(key)
            if entry is None:      # pool rebuild cleared the map already
                continue
            entry[1] -= 1
            if entry[1] <= 0:
                del self._prefix[key]
                self._free.append(entry[0])
        self._free.extend(slot.pages[len(slot.prefix_keys):])
        self._cond.notify_all()

    def _gauge_pages(self):
        with self._cond:
            in_use = self.num_pages - len(self._free)
        _telemetry.gauge(
            "serving.kv_pages_in_use.%s" % self.name).set(in_use)

    def _harvest_expired_locked(self, now):  # mxlint: holds(_cond)
        dead = [r for r in self._queue if r.expired(now)]
        for req in dead:
            self._queue.remove(req)
        return dead

    def _admit_locked(self, now):  # mxlint: holds(_cond)
        """Pop queue-head requests into free slots while the page pool
        covers them.  FIFO: a head request the pool cannot cover BLOCKS
        later ones (no starvation of long requests) and counts one
        ``serving.kv_pool_exhausted`` per stall episode."""
        admitted = []
        free_slots = [i for i, s in enumerate(self._slots) if s is None]
        # stamped under the lock: a request submitted after ``now`` was
        # read is still admitted no earlier than it was submitted
        t_admit = _time.perf_counter()
        while self._queue and free_slots:
            req = self._queue[0]
            # walk the request's full-prefix pages front-to-back: each
            # key already in the map is a shared page this request can
            # reuse instead of drawing from the free list.  The walk is
            # contiguous — a sharer holding key i also holds 0..i-1, so
            # refcounts are monotone non-increasing along the prefix.
            shared = []
            for key in req.prefix_keys:
                entry = self._prefix.get(key)
                if entry is None:
                    break
                shared.append((key, entry))
            if req.need - len(shared) > len(self._free):
                if not req.stall_counted:
                    req.stall_counted = True
                    _telemetry.counter("serving.kv_pool_exhausted").inc()
                    _telemetry.counter(
                        "serving.kv_pool_exhausted.%s" % self.name).inc()
                break
            self._queue.popleft()
            pages = []
            for key, entry in shared:
                entry[1] += 1
                pages.append(entry[0])
            # the remaining FULL-prefix pages are fresh: register them so
            # later requests with the same prompt prefix share them
            for key in req.prefix_keys[len(shared):]:
                page = self._free.pop()
                self._prefix[key] = [page, 1, False]
                pages.append(page)
            while len(pages) < req.need:
                pages.append(self._free.pop())
            if shared:
                _telemetry.counter("serving.prefix_hits").inc()
                _telemetry.counter(
                    "serving.prefix_hits.%s" % self.name).inc()
                _telemetry.counter(
                    "serving.prefix_pages_shared").inc(len(shared))
            self._slots[free_slots.pop(0)] = _Slot(
                req, pages, t_admit, prefix_keys=req.prefix_keys)
            admitted.append(req)
        return admitted

    def _loop(self):
        while True:
            with self._cond:
                self._last_iteration = _time.perf_counter()
                if not self._queue and not self._active():
                    # nothing to emit, harvest, admit or decode (a row
                    # holds its slot until its last program is emitted)
                    if self._stopping:
                        return
                    with _tracing.span("engine.wait", cat="serving"):
                        self._cond.wait(timeout=0.05)
                    continue
            self._iteration += 1
            with _tracing.span("engine.iteration", cat="serving",
                               iteration=self._iteration):
                if not self._iterate():
                    return

    def _iterate(self):
        """One turn of the loop with work in sight: emit what the last
        turn fetched, harvest, admit, dispatch the admitted prefills and
        one decode step, fetch every program but that step.  False = the
        engine is done (stopped and drained, or aborted)."""
        if self._ahead:
            # the oldest program in flight is the one the host waits on
            # from the last fetch's return on
            self._wait_on(self._ahead[0])
        self._emit_fetched()
        now = _time.perf_counter()
        with self._cond:
            with _tracing.span("engine.admit", cat="serving") as sp:
                expired = self._harvest_expired_locked(now)
                admitted = self._admit_locked(now)
                sp.set(admitted=len(admitted), queued=len(self._queue),
                       free_pages=len(self._free))
            active = self._active()
            queued = None
            abort = False
            if not admitted and not active:
                # every queued request expired, or the head waits for
                # pages that only a shared-prefix holder can return
                if self._stopping and (self._abort or not self._queue):
                    queued = list(self._queue)
                    self._queue.clear()
                    abort = self._abort
                else:
                    with _tracing.span("engine.wait", cat="serving"):
                        self._cond.wait(timeout=0.05)
        self._expire(expired)
        if queued is not None:
            if abort:
                self._fail_all(queued, ServingError(
                    "generation engine stopped without drain"))
            return False
        if not admitted and not active:
            return True
        with self._cond:
            abort = self._abort
        if abort:
            with self._cond:
                queued = list(self._queue)
                self._queue.clear()
            exc = ServingError(
                "generation engine stopped without drain")
            self._fail_all(queued, exc)
            self._fail_active(exc)
            return False
        self._gauge_pages()
        for req in admitted:
            if not self._dispatch_prefill(req):
                return True
        rows = [(i, s) for i, s in enumerate(self._slots)
                if s is not None and not s.ended
                and 0 < s.sent < s.req.max_new]
        self._fetch(keep=self._dispatch_decode(rows) if rows else None)
        return True

    def _expire(self, reqs):
        for req in reqs:
            _telemetry.counter("serving.deadline_exceeded").inc()
            _telemetry.counter(
                "serving.deadline_exceeded.%s" % self.name).inc()
            if not req.future.done():
                queued_ms = (_time.perf_counter() - req.t_submit) * 1e3
                req.future.set_exception(DeadlineExceededError(
                    "generation request for model %r expired in queue "
                    "before prefill (queued %.1fms, deadline passed)"
                    % (self.name, queued_ms)))
                _obs.log_access(self.name, "deadline", queue_ms=queued_ms,
                                **req.access_ids())

    def _dispatch_failed(self, exc):
        """Shared failure path: the donated pool is poisoned, so every
        in-flight sequence fails with the causal error and the breaker
        records the failure.  Returns False for the caller to bail."""
        _telemetry.counter("serving.dispatch_errors").inc()
        if self.breaker is not None:
            self.breaker.record_failure()
        self._fail_active(exc)
        return False

    def _wait_on(self, st):
        """From now until its fetch returns, ``st`` is the program the
        host waits on: open its ``engine.<kind>.device`` span and start
        its timer (the later of its dispatch and the previous fetch's
        return)."""
        if st.device is None:
            st.device = _begin("engine.%s.device" % st.kind, step=st.step)
            st.t0 = _time.perf_counter()

    def _fetch(self, keep=None):
        """Copy out the tokens of every program in flight but ``keep``
        (the decode step this turn dispatched), oldest first: the host
        blocks on one program with the next queued behind it.  A program
        that raises fails the rows of every program in flight."""
        while self._ahead and self._ahead[0] is not keep:
            st = self._ahead[0]
            self._wait_on(st)
            try:
                # launch wait, execution and read-back all lie under the
                # fetch: no block_until_ready splits them (a host round
                # trip a step); the runtime's own events do, in a trace
                with _tracing.span("engine.%s.fetch" % st.kind,
                                   cat="serving", step=st.step):
                    st.out = _np.asarray(st.out)
            except BaseException as exc:  # noqa: BLE001 — pool donated
                # what was fetched before the fault still reaches its rows
                self._emit_fetched()
                self._dispatch_failed(exc)
                return
            st.t1 = _time.perf_counter()
            _end(st.device)
            st.device = None
            self._ahead.popleft()
            self._fetched.append(st)
            if self.breaker is not None:
                self.breaker.record_success()

    def _emit_fetched(self):
        """Hand the fetched programs' tokens to their rows, in dispatch
        order, and close each program's span."""
        fetched, self._fetched = self._fetched, []
        for st in fetched:
            if st.kind == "decode":
                self._emit_decode(st)
            else:
                self._emit_prefill(st)
            _end(st.span)

    def _dispatch_prefill(self, req):
        """Dispatch one admitted request's prompt through its bucket's
        prefill program: seeds the shared pool (scatter touches only this
        request's pages, so in-flight sequences are untouched — the
        mid-flight JOIN); its first token (TTFT) is fetched once the
        decode step that is fed it has been dispatched.  False = the
        dispatch failed."""
        gp = self.predictor
        s_bucket = gp.prefill_bucket(req.plen)
        slot_idx = next(i for i, s in enumerate(self._slots)
                        if s is not None and s.req is req)
        slot = self._slots[slot_idx]
        _telemetry.timer("serving.queue_ms").observe(
            (slot.t_admit - req.t_submit) * 1e3)
        breaker = self.breaker
        if breaker is not None and not breaker.allow_dispatch():
            self._slots[slot_idx] = None
            with self._cond:
                self._release_pages_locked(slot)
            if not req.future.done():
                req.future.set_exception(CircuitOpenError(
                    "model %r circuit breaker is OPEN; prefill failed "
                    "fast, retry after the cooldown" % (self.name,)))
                _obs.log_access(
                    self.name, "breaker",
                    queue_ms=(_time.perf_counter() - req.t_submit) * 1e3,
                    **req.access_ids())
            return True   # engine itself is fine
        step = next(self._steps)
        program = "prefill-s%d" % s_bucket
        sp = _begin("engine.prefill", request_id=req.request_id,
                    bucket=s_bucket, prompt_len=req.plen, step=step,
                    **self._sparse_prefill_args(program))
        st = _Step(step, "prefill", program, [(slot_idx, slot)], sp)
        w_s = _math.ceil(s_bucket / gp.page_size)
        sentinel = self.num_pages
        tokens = _np.zeros((1, s_bucket), _np.int32)
        tokens[0, :req.plen] = req.prompt
        table = _np.full((1, w_s), sentinel, _np.int32)
        k = min(w_s, len(slot.pages))
        table[0, :k] = slot.pages[:k]
        # shared-prefix pages another request already POPULATED must not
        # be rewritten mid-decode — sentinel them so this prefill's
        # scatter drops those rows (the bytes are already there; the
        # attention gather still reads them through slot.pages).
        # Populated-ness is decided at dispatch, in the order the device
        # runs the programs: if the registering request died before its
        # prefill was dispatched, the next sharer writes the pages itself.
        write_table = table
        if slot.prefix_keys:
            with self._cond:
                populated = [bool(self._prefix[key][2])
                             for key in slot.prefix_keys
                             if key in self._prefix]
            if any(populated):
                write_table = table.copy()
                for i, done in enumerate(populated):
                    if done and i < w_s:
                        write_table[0, i] = sentinel
        temp, tk, tp, keys = self._sample_arrays([(0, slot)], 1, sp)
        # where the cache has a state region, the prompt's final state is
        # left in this request's slot, over whatever the slot held
        where = (_np.asarray([slot_idx], _np.int32),) if gp.state else ()
        slot.t_prefill_start = _time.perf_counter()
        try:
            # the call into the exported program alone: host time inside
            # JAX and the runtime, before the device starts (with nothing
            # else in flight the host waits on this program from here)
            with _tracing.span("engine.prefill.dispatch", cat="serving",
                               step=step, **self._host_operands[program]):
                if not self._ahead:
                    self._wait_on(st)
                self._kv, st.out = self._prefill[s_bucket](
                    gp._params, self._kv, tokens,
                    _np.asarray([req.plen], _np.int32), write_table,
                    *where, temp, tk, tp, keys)
        except BaseException as exc:  # noqa: BLE001 — pool donated
            _end(st.device)
            _end(sp)
            return self._dispatch_failed(exc)
        if slot.prefix_keys:
            with self._cond:
                for key in slot.prefix_keys:
                    entry = self._prefix.get(key)
                    if entry is not None:
                        entry[2] = True
        slot.sent = slot.inflight = 1
        slot.src = (st.out, 0)
        self._ahead.append(st)
        return True

    def _emit_prefill(self, st):
        ((slot_idx, slot),) = st.rows
        req = slot.req
        nxt = st.out
        slot.inflight -= 1
        _telemetry.timer("serving.prefill_ms").observe(
            (st.t1 - st.t0) * 1e3)
        self._count_program_routes(st.program)
        if slot.ended:       # failed while its prefill was in flight
            self._retire(slot_idx)
            return
        if req.want_replay:
            slot.logprobs.append(nxt[1:2].view(_np.float32)[0])
            slot.routed.append(self._routed(
                nxt[2:], self.predictor.prefill_bucket(req.plen))[
                    :, :req.plen])
        slot.tokens.append(int(nxt[0]))
        slot.token_t.append(st.t1)
        slot.ttft_ms = (st.t1 - req.t_submit) * 1e3
        _telemetry.timer("serving.ttft_ms").observe(slot.ttft_ms)
        self._count_tokens(1)
        self._maybe_finish(slot_idx)

    def _sparse_prefill_args(self, program):
        """The ``engine.prefill`` arguments of a model with ``S`` blocks, by
        the export-time verdict on the prefill program: ``sparse_layers``
        (the blocks it has) and ``sparse_kernel_layers`` (how many of them
        attend through the masked K/V-tiled kernel); none for another
        model."""
        route = self.predictor.sparse_prefill_routes.get(program)
        if route is None:
            return {}
        return {"sparse_layers": route["sites"],
                "sparse_kernel_layers": route["sites"]
                if route.get("impl") == "masked" else 0}

    def _count_program_routes(self, program):
        """Serve-side mirror of the export-time verdict on a program's
        grouped products, once a dispatch: the Pallas kernel ran them, or
        ``lax.ragged_dot`` did while the kernel tier was on; on its
        retention updates, once an ``R`` block a dispatch
        (``kernels.retention_update`` / ``kernels.retention_fallback``);
        and on a prefill's sparse attention, once an ``S`` block a dispatch
        (``kernels.sparse_prefill`` / ``kernels.sparse_prefill_fallback``)
        or a ``B`` block's (``kernels.block_sparse_prefill`` /
        ``kernels.block_sparse_prefill_fallback``)."""
        route = self.predictor.grouped_routes.get(program)
        if route is not None:
            if route.get("impl") == "grouped":
                _telemetry.counter("kernels.grouped_matmul").inc()
            elif _kernels_enabled():
                _telemetry.counter("kernels.grouped_fallback").inc()
        route = self.predictor.retention_routes.get(program)
        if route is not None:
            if route.get("impl") == "retention":
                _telemetry.counter("kernels.retention_update").inc(
                    route["sites"])
            elif _kernels_enabled():
                _telemetry.counter("kernels.retention_fallback").inc(
                    route["sites"])
        route = self.predictor.sparse_prefill_routes.get(program)
        if route is not None:
            # (a ``B`` block's masked pass has the block-sparse names)
            took, fell_back = (
                "kernels.block_sparse_prefill",
                "kernels.block_sparse_prefill_fallback") \
                if self._block_sparse else (
                "kernels.sparse_prefill", "kernels.sparse_prefill_fallback")
            if route.get("impl") == "masked":
                _telemetry.counter(took).inc(route["sites"])
            elif _kernels_enabled():
                _telemetry.counter(fell_back).inc(route["sites"])

    def _dispatch_decode(self, rows):
        """Dispatch one decode step for ``rows``, every row with tokens
        left to make.  The page-table width buckets to the widest need
        among them; the other slots ride along on the all-sentinel row
        (writes drop, output ignored) — that is what keeps the compiled
        set flat while sequences EXIT and JOIN mid-flight.  Returns the
        step (None where none was dispatched)."""
        gp = self.predictor
        B = self.decode_slots
        breaker = self.breaker
        if breaker is not None and not breaker.allow_dispatch():
            exc = CircuitOpenError(
                "model %r circuit breaker is OPEN; in-flight decode "
                "failed fast, retry after the cooldown" % (self.name,))
            for i, s in rows:
                s.ended = True
                if not s.req.future.done():
                    s.req.future.set_exception(exc)
                    _obs.log_access(
                        self.name, "breaker", ttft_ms=s.ttft_ms,
                        tokens=len(s.tokens), **s.req.access_ids())
                self._retire(i)
            return None
        width = _io.pick_bucket(gp.decode_widths,
                                max(len(s.pages) for _, s in rows))
        step = next(self._steps)
        # an earlier program's tokens not yet fetched: this step runs
        # while the host copies them out
        ahead = int(bool(self._ahead))
        program = "decode-w%d" % width
        route = gp.paged_routes.get(str(width))
        sp = _begin("engine.decode", width=width, rows=len(rows),
                    step=step, ahead=ahead)
        st = _Step(step, "decode", program, rows, sp, route)
        with _tracing.span("engine.decode.prepare", cat="serving"):
            sentinel = self.num_pages
            positions = _np.zeros((B,), _np.int32)
            table = _np.full((B, width), sentinel, _np.int32)
            for i, s in rows:
                positions[i] = s.pos
                k = min(width, len(s.pages))
                table[i, :k] = s.pages[:k]
            temp, tk, tp, keys = self._sample_arrays(rows, B, sp)
        # tokens the rows hold against token slots the routed program
        # reads: each row's own pages where the kernel reads them in
        # place, the whole table's window where the twin gathers it
        psz = gp.page_size
        kernel = route is not None and route.get("impl") in (
            "paged", "latent", "sparse", "block_sparse")
        if kernel:
            window = sum(-(-(s.pos + 1) // psz) for _, s in rows) * psz
        else:
            window = B * width * psz
        sp.set(held_tokens=int(positions.sum()), window_tokens=window)
        if self._latent:
            # a model of latent pages: did this iteration take the kernel
            sp.set(latent_kernel=int(kernel))
        if gp.state:
            sp.set(state_rows=len(rows))
            _telemetry.gauge("serving.state_slots").set(len(rows))
        if ahead:
            _telemetry.counter("serving.decode_ahead").inc()
        try:
            token_ids = self._token_ids(rows)
            with _tracing.span("engine.decode.dispatch", cat="serving",
                               step=step, **self._host_operands[program]):
                if not self._ahead:
                    self._wait_on(st)
                self._kv, st.out = self._decode[width](
                    gp._params, self._kv, token_ids, positions, table,
                    temp, tk, tp, keys)
        except BaseException as exc:  # noqa: BLE001 — pool donated
            _end(st.device)
            _end(sp)
            self._dispatch_failed(exc)
            return None
        for i, s in rows:
            s.pos += 1
            s.sent += 1
            s.inflight += 1
            s.src = (st.out, i)
        self._last_out = st.out
        self._ahead.append(st)
        return st

    def _token_ids(self, rows):
        """The decode step's token ids, on the device: each row's last
        token where the program that made it left it — the newest decode
        step's output (one ``feed``) or a prefill's (one ``put`` a row) —
        and 0 in the padding rows.  No token id visits the host."""
        last = self._last_out
        keep = _np.zeros((self.decode_slots,), _np.bool_)
        puts = []
        for i, s in rows:
            out, at = s.src
            if out is last and at == i:
                keep[i] = True
            else:
                puts.append((i, out, at))
        tok = self._feed[last.shape](last, keep) if keep.any() \
            else self._tok0
        for i, out, at in puts:
            tok = self._put[out.shape](tok, out, _np.int32(i),
                                       _np.int32(at))
        return tok

    def _emit_decode(self, st):
        gp = self.predictor
        B = self.decode_slots
        nxt = st.out
        n_stats = len(gp.decode_stats)
        if n_stats:
            # what the model counted in this step rode behind the tokens
            counts = dict(zip(gp.decode_stats,
                              nxt[B:B + n_stats].tolist()))
            st.span.set(**counts)
            for stat, value in counts.items():
                _telemetry.counter("serving." + stat).inc(value)
        live = [(i, s) for i, s in st.rows if not s.ended]
        if any(s.req.want_replay for _, s in live):
            # ... and behind those what a replay needs
            logprobs = nxt[B + n_stats:2 * B + n_stats].view(_np.float32)
            routed = self._routed(nxt[2 * B + n_stats:], B)
            for i, s in live:
                if s.req.want_replay:
                    s.logprobs.append(logprobs[i])
                    s.routed.append(routed[:, i, None])
        _telemetry.timer("serving.decode_step_ms").observe(
            (st.t1 - st.t0) * 1e3)
        if st.route is not None:
            # serve-side mirror of the export-time routing verdict: every
            # decode iteration that ran through the Pallas paged kernel
            # (or fell back while the kernel tier was on) is counted
            # (a model of latent pages has the latent site's counters)
            took, fell_back = (
                _telemetry.counter("kernels.block_sparse"),
                _telemetry.counter("kernels.block_sparse_fallback")) \
                if self._block_sparse else (
                _telemetry.counter("kernels.sparse_latent"),
                _telemetry.counter("kernels.sparse_latent_fallback")) \
                if self._sparse else (
                _telemetry.counter("kernels.latent_paged"),
                _telemetry.counter("kernels.latent_fallback")) \
                if self._latent else (
                _telemetry.counter("kernels.paged_attention"),
                _telemetry.counter("kernels.paged_fallback"))
            if st.route.get("impl") in ("paged", "latent", "sparse",
                                        "block_sparse"):
                took.inc()
            elif _kernels_enabled():
                fell_back.inc()
        self._count_program_routes(st.program)
        with _tracing.span("engine.decode.emit", cat="serving",
                           step=st.step) as emit:
            self._count_tokens(len(live))
            gap = _telemetry.timer("serving.token_gap_ms")
            finished = 0
            for i, s in st.rows:
                s.inflight -= 1
                if s.ended:      # an EOS the host saw after this step left
                    self._retire(i)
                    continue
                s.tokens.append(int(nxt[i]))
                gap.observe((st.t1 - s.token_t[-1]) * 1e3)
                s.token_t.append(st.t1)
                finished += self._maybe_finish(i)
            emit.set(finished=finished)

    def _routed(self, flat, tokens):
        """The tail of a program's returned array as ``[E blocks, tokens,
        top_k]`` int16."""
        shape = self.predictor.replay
        return flat.astype(_np.int16).reshape(shape["layers"], tokens,
                                              shape["top_k"])

    def _sample_arrays(self, active, B, sp):
        """Per-row sampling operands for a dispatch: active rows carry
        their request's temperature / top-k / top-p / PRNG key words;
        padding rows ride greedy with a zero key (their output is
        discarded, but every operand must still be well-formed).  The
        tier of work they ask of the program's next-token choice — the
        branch it takes on the device, by the same predicate — is counted
        (``serving.sample_tier.*``) and set on the dispatch's span."""
        temp = _np.zeros((B,), _np.float32)
        tk = _np.zeros((B,), _np.int32)
        tp = _np.ones((B,), _np.float32)
        keys = _np.zeros((B, 2), _np.uint32)
        for i, s in active:
            req = s.req
            temp[i] = req.temperature
            tk[i] = req.top_k
            tp[i] = req.top_p
            keys[i] = req.key_words
        tier = SAMPLE_TIERS[int(sample_tier(temp, tk, tp))]
        _telemetry.counter("serving.sample_tier." + tier).inc()
        sp.set(sample_tier=tier)
        return temp, tk, tp, keys

    def _count_tokens(self, n):
        _telemetry.counter("serving.tokens_generated").inc(n)
        _telemetry.counter(
            "serving.tokens_generated.%s" % self.name).inc(n)

    def _retire(self, slot_idx):
        """Return an ended row's pages and slot once no dispatched
        program that carries it is left to emit: a program still queued
        may write only into pages its row holds."""
        slot = self._slots[slot_idx]
        if slot.ended and not slot.inflight:
            self._slots[slot_idx] = None
            with self._cond:
                self._release_pages_locked(slot)
            self._gauge_pages()

    def _maybe_finish(self, slot_idx):
        """Mid-flight EXIT: resolve the future the emit that brings the
        sequence's EOS or last budgeted token; the pages follow once no
        program in flight carries the row (``_retire``)."""
        slot = self._slots[slot_idx]
        req = slot.req
        done = len(slot.tokens) >= req.max_new or (
            req.eos_id is not None
            and slot.tokens[-1] == int(req.eos_id))
        if not done:
            return 0
        slot.ended = True
        self._retire(slot_idx)
        t1 = _time.perf_counter()
        wall_ms = (t1 - req.t_submit) * 1e3
        _telemetry.timer("serving.generate_request_ms").observe(wall_ms)
        if not req.future.done():
            ids = _np.asarray(slot.tokens, _np.int32)
            # the last token was never fed: its step's choice is left out
            req.future.set_result((ids, {
                "logprobs": _np.asarray(slot.logprobs, _np.float32),
                "routed_experts": _np.concatenate(slot.routed, axis=1)[
                    :, :req.plen + len(ids) - 1]})
                if req.want_replay else ids)
            if _obs.access_log_enabled():
                _obs.log_access(
                    self.name, "ok",
                    queue_ms=(slot.t_admit - req.t_submit) * 1e3,
                    dispatch_ms=wall_ms, ttft_ms=slot.ttft_ms,
                    tokens=len(slot.tokens),
                    bytes=len(slot.tokens) * 4, **req.access_ids())
        if _telemetry.enabled():
            t_sub = req.t_submit
            _telemetry.log_event(
                "serving_generate", model=self.name,
                request_id=req.request_id, trace_id=req.trace_id,
                prompt_len=req.plen, new_tokens=len(slot.tokens),
                max_new=req.max_new, pages=len(slot.pages),
                queue_ms=round((slot.t_admit - t_sub) * 1e3, 4),
                prefill_ms=round(
                    (slot.token_t[0] - slot.t_prefill_start) * 1e3, 4),
                ttft_ms=round(slot.ttft_ms, 4)
                if slot.ttft_ms is not None else None,
                # when each token left the engine, from submit: what a
                # streaming front end would feel
                token_ms=[round((t - t_sub) * 1e3, 4)
                          for t in slot.token_t],
                wall_ms=round(wall_ms, 4),
                pool_exhausted_wait=req.stall_counted,
                breaker=self.breaker.state
                if self.breaker is not None else "closed")
        return 1

    def _stall_probe(self, interval_s):
        """mx.tracing stall probe (registered in :meth:`start`): reports
        the engine wedged when work is pending but the decode loop has
        not turned over within the watchdog interval.  Mirrors the
        one-shot ``Server`` probe registered in serving.py."""
        now = _time.perf_counter()
        with self._cond:
            queued = len(self._queue)
            free = len(self._free)
            last_iter = self._last_iteration
            thread = self._thread
            oldest_q = min((r.t_submit for r in self._queue),
                           default=None)
        # advisory cross-thread read of the engine-owned slot table (the
        # same precedent stats() relies on) — staleness is acceptable here
        active = self._active()
        if queued == 0 and not active:
            return None
        if now - last_iter < interval_s:
            return None
        ages = [now - s.req.t_submit for s in active]
        if oldest_q is not None:
            ages.append(now - oldest_q)
        return {
            "model": self.name,
            "queued": queued,
            "active": len(active),
            "kv_pages": self.num_pages,
            "kv_pages_free": free,
            "since_last_iteration_s": round(now - last_iter, 3),
            "engine_alive": bool(thread is not None
                                 and thread.is_alive()),
            "oldest_request_age_s": round(max(ages), 3) if ages else 0.0,
        }

    # ------------------------------------------------------------- stats
    def stats(self):
        with self._cond:
            queued = len(self._queue)
            free = len(self._free)
            thread = self._thread
            prefix_entries = len(self._prefix)
            prefix_shared = sum(
                max(0, e[1] - 1) for e in self._prefix.values())
        _telemetry.gauge(
            "serving.prefix_shared_pages.%s" % self.name).set(
            prefix_entries)
        return {
            "queued": queued,
            "active": len(self._active()),
            "decode_slots": self.decode_slots,
            "shared_prefix": self._share,
            "prefix_entries": prefix_entries,
            "prefix_pages_shared": prefix_shared,
            "kv_pages": self.num_pages,
            "kv_pages_free": free,
            "page_size": self.predictor.page_size,
            "max_context": self.predictor.max_context,
            "prompt_buckets": list(self.predictor.prompt_buckets),
            "decode_widths": list(self.predictor.decode_widths),
            "engine_alive": bool(thread is not None
                                 and thread.is_alive()),
            "breaker": self.breaker.state
            if self.breaker is not None else "closed",
        }
