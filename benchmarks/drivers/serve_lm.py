"""Serve a ``TransformerLM`` through the generation server — a copy of
``chip_smoke.py``'s ``phase_serve`` (which has passed on the chip) without
its smoke asserts, under traffic from a generator instead of eight prompts.

Set-up: weights on the device in one jitted call from the seed, in bf16;
``export_generation`` on the chip for the traffic file's prompt buckets and
context; ``Server.register(generate=True)`` and ``start()`` (compiles or
loads every program); one short request per decode width and prompt bucket
(warm-up); then the traffic's fill.  One dispatcher (this thread) sends
requests and hears of completions through the futures' callbacks; the engine
thread is the program's.  After the drain and ``stop()``, outside set-up and
window: the checks, and the plain reference over a seeded sample of served
requests.

Time to first token is the engine's own stamp (``serving_generate``
telemetry events), because the server does not stream; the k-th request
whose callback saw a result and the k-th event are the same request (the
engine thread resolves the future, then writes the event)."""
from __future__ import annotations

import gc
import json
import math
import os
import queue
import tempfile

from benchmarks.harness import profile
from benchmarks.harness.stats import fold_seed, now

COUNTERS = ("serving.tokens_generated", "serving.compiles",
            "kernels.paged_attention", "kernels.paged_fallback",
            "kernels.gated_fallback", "serving.kv_pool_exhausted",
            "serving.shed_requests", "serving.prefix_hits")
TIMERS = ("serving.decode_step_ms", "serving.prefill_ms")


def _snapshot(telemetry):
    snap = {"t": now()}
    for name in COUNTERS:
        snap[name] = telemetry.counter(name).value
    for name in TIMERS:
        timer = telemetry.timer(name)
        snap[name] = (timer.count, timer.total)
    return snap


def _delta(a, b):
    out = {"seconds": b["t"] - a["t"]}
    for name in COUNTERS:
        out[name] = b[name] - a[name]
    for name in TIMERS:
        out[name] = {"count": b[name][0] - a[name][0],
                     "total_ms": b[name][1] - a[name][1]}
    return out


def _warm_requests(widths, buckets, page):
    """One (prompt length, new tokens) per decode width, each just over the
    width below it, so that every width and every prompt bucket runs once."""
    out = []
    for w in widths:
        total = page if w == 1 else (w // 2) * page + 8
        new = max(4, total - max(buckets))
        out.append((total - new, new))
    return out


class _Dispatcher:
    """Sends the plan's requests and records, per request, when it was due,
    when ``submit_generate`` was called and when its future resolved."""

    def __init__(self, srv, name, plan, jax):
        self.srv, self.name, self.jax = srv, name, jax
        self.requests = plan["requests"]
        self.records = []
        self.done = queue.SimpleQueue()
        self.ok_order = []           # appended by the engine thread only
        self.outstanding = 0
        self.next_index = 0

    def _on_done(self, rec, fut):
        rec["t_done"] = now()
        if fut.exception() is None:
            self.ok_order.append(rec)
        self.done.put((rec, fut))

    def submit_next(self, t_zero):
        i = self.next_index
        req = self.requests[i % len(self.requests)]
        self.next_index += 1
        rec = {"index": i, "plen": int(req["prompt"].size),
               "max_new": req["max_new"],
               "due": None if req["due_s"] is None else t_zero + req["due_s"]}
        self.records.append(rec)
        with self.jax.profiler.TraceAnnotation("bench.submit"):
            rec["t_call"] = now()
            try:
                fut = self.srv.submit_generate(self.name, req["prompt"],
                                               req["max_new"])
            except Exception as exc:  # noqa: BLE001 — shed, breaker, stopped
                rec["t_done"] = now()
                rec["error"] = "%s: %s" % (type(exc).__name__, exc)
                return
        self.outstanding += 1
        fut.add_done_callback(lambda f, rec=rec: self._on_done(rec, f))

    def next_due(self, t_zero):
        due = self.requests[self.next_index % len(self.requests)]["due_s"]
        lap = self.next_index // len(self.requests)
        if due is None:
            return None
        return t_zero + due + lap * self.requests[-1]["due_s"]

    def collect(self, timeout):
        """Handle one completion (or wait ``timeout`` for none)."""
        try:
            with self.jax.profiler.TraceAnnotation("bench.idle"):
                rec, fut = self.done.get(timeout=max(timeout, 0.0))
        except queue.Empty:
            return None
        self.outstanding -= 1
        exc = fut.exception()
        if exc is not None:
            rec["error"] = "%s: %s" % (type(exc).__name__, exc)
        else:
            rec["tokens"] = fut.result()
        return rec


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig

    sz, tp = ctx.sizes, ctx.traffic
    lm = sz["lm"]
    model = TransformerLM(TransformerLMConfig(dtype=jnp.bfloat16, **lm))
    key = jax.random.PRNGKey(fold_seed(ctx.seed) % (2 ** 31 - 1))
    make_params = jax.jit(model.init)
    params = make_params(key)
    plan = ctx.module("generators", tp["generator"]).generate(
        ctx.seed, tp, lm["vocab_size"])
    closed = plan["clients"] is not None
    slots = sz["decode_batch"]
    name = "lm"

    with tempfile.TemporaryDirectory(prefix="bench_lm_") as tmp:
        prefix = os.path.join(tmp, name)
        t0 = now()
        mx.deploy.export_generation(
            model, params, prefix, sampling=True, decode_batch=slots,
            prompt_buckets=tp["prompt_buckets"],
            max_context=tp["max_context"], page_size=sz["page_tokens"])
        export_s = now() - t0
        del params
        with open(prefix + "-meta.json") as f:
            routes = json.load(f)["paged"]
        srv = mx.serving.Server()
        srv.register(name, prefix, generate=True)
        t0 = now()
        srv.start()
        start_s = now() - t0
        stats = srv.stats()["generation"][name]
        rng = np.random.default_rng(fold_seed(ctx.seed, 2))
        t0 = now()
        for plen, new in _warm_requests(stats["decode_widths"],
                                        stats["prompt_buckets"],
                                        stats["page_size"]):
            srv.submit_generate(name, rng.integers(
                0, lm["vocab_size"], (plen,)).astype(np.int32),
                new).result(timeout=600)
        warm_s = now() - t0

        sink = os.path.join(ctx.run_dir, "telemetry.jsonl")
        telemetry.configure_sink("jsonl:" + sink)
        disp = _Dispatcher(srv, name, plan, jax)
        snap_first = _snapshot(telemetry)
        t_zero = now()

        def pump(until, open_loop_submits=True):
            """Run the dispatcher until ``until()`` is true."""
            while not until():
                if closed:
                    while disp.outstanding < plan["clients"] \
                            and open_loop_submits:
                        disp.submit_next(t_zero)
                    disp.collect(0.02)
                else:
                    due = disp.next_due(t_zero)
                    while open_loop_submits and due <= now():
                        disp.submit_next(t_zero)
                        due = disp.next_due(t_zero)
                    wait = 0.02 if not open_loop_submits else \
                        min(0.02, due - now())
                    disp.collect(wait)

        # ---- fill
        opens = tp["window_opens"]
        if opens["kind"] == "iterations_after_full":
            # rows in flight = prefills run - requests resolved; "full" is
            # every slot (or every client) busy, or admission waiting for
            # pages
            full_at = []
            want = min(slots, plan["clients"] or slots)
            t_give_up = now() + tp["drain_limit_s"]

            def filled():
                if now() > t_give_up:
                    raise RuntimeError("the decode slots never filled")
                c = telemetry.timer("serving.decode_step_ms").count
                started = telemetry.timer("serving.prefill_ms").count \
                    - snap_first["serving.prefill_ms"][0]
                resolved = len(disp.records) - disp.outstanding
                if not full_at and (
                        started - resolved >= want or telemetry.counter(
                            "serving.kv_pool_exhausted").value
                        > snap_first["serving.kv_pool_exhausted"]):
                    full_at.append(c)
                return bool(full_at) and c >= full_at[0] + opens["iterations"]
            pump(filled)
        else:
            t_fill = now() + opens["seconds"]
            pump(lambda: now() >= t_fill)

        # ---- window
        snap_open = _snapshot(telemetry)
        t_open = snap_open["t"]
        setup_s = t_open - ctx.t_start
        traced = None
        if ctx.trace:
            t_a = t_open + tp["trace_after_s"]
            pump(lambda: now() >= t_a)
            with profile.traced_window(ctx.trace_dir):
                snap_a = _snapshot(telemetry)
                t_b = snap_a["t"] + tp["trace_seconds"]
                pump(lambda: now() >= t_b)
                snap_b = _snapshot(telemetry)
            traced = _delta(snap_a, snap_b)
            traced["t"] = (snap_a["t"], snap_b["t"])
        t_end = t_open + ctx.seconds
        pump(lambda: now() >= t_end)
        snap_close = _snapshot(telemetry)
        t_close = snap_close["t"]

        # ---- drain: nothing new is sent, everything sent resolves
        t_limit = now() + tp["drain_limit_s"]
        with jax.profiler.TraceAnnotation("bench.drain"):
            pump(lambda: disp.outstanding == 0 or now() >= t_limit,
                 open_loop_submits=False)
        drained = disp.outstanding == 0
        snap_last = _snapshot(telemetry)
        drain_s = now() - t_close
        srv.stop(drain=drained, timeout_s=60.0)
        gstats = srv.stats()["generation"][name]
        telemetry.configure_sink("")
        disp.srv = None
        del srv
    gc.collect()

    with open(sink) as f:
        events = [e for e in map(json.loads, f)
                  if e.get("event") == "serving_generate"]
    window = _delta(snap_open, snap_close)
    whole = _delta(snap_first, snap_last)
    records = disp.records
    paired = len(events) == len(disp.ok_order)
    for rec, ev in zip(disp.ok_order, events):
        paired = paired and ev["prompt_len"] == rec["plen"] \
            and ev["new_tokens"] == len(rec.get("tokens", ()))
        rec["ttft_ms"] = ev["ttft_ms"]
    ok = [r for r in records if "tokens" in r
          and len(r["tokens"]) == r["max_new"] and "ttft_ms" in r]
    in_window = [r for r in records
                 if t_open <= (r["due"] if r["due"] is not None
                               else r["t_call"]) < t_close]
    ok_ids = {id(r) for r in ok}
    failed = [r for r in in_window if id(r) not in ok_ids]

    # ---- the plain reference, over a seeded sample of served requests
    params = make_params(key)
    ref = ctx.module("reference", ctx.config["reference"])
    pick = np.random.default_rng(fold_seed(ctx.seed, 3))
    sample = [ok[i] for i in sorted(pick.choice(
        len(ok), min(tp["reference_sample"], len(ok)), replace=False))] \
        if ok else []
    worst_gap = absmax = 0.0
    flips = checked = 0
    t0 = now()
    pad_rows = max(r["max_new"] for r in plan["requests"])
    for rec in sample:
        prompt = plan["requests"][rec["index"] % len(plan["requests"])][
            "prompt"]
        gaps, amax = ref.served_token_gaps(
            params, prompt, rec["tokens"], tp["max_context"], pad_rows)
        gaps = np.asarray(gaps)
        worst_gap = max(worst_gap, float(gaps.max()))
        absmax = max(absmax, float(amax))
        flips += int((gaps > 0).sum())
        checked += int(gaps.size)
    reference_s = now() - t0
    del params
    tol = ctx.config["tolerance"]["logit_gap_bf16_ulps"] * 2.0 ** -8 * absmax

    returned = sum(len(r["tokens"]) for r in records if "tokens" in r)
    checks = {
        "served_tokens_within_tolerance": bool(sample) and worst_gap <= tol,
        "tokens_returned_equal_counter":
            drained and returned == whole["serving.tokens_generated"],
        "every_request_full_length": drained and all(
            "tokens" in r and len(r["tokens"]) == r["max_new"]
            for r in records),
        "events_pair_with_requests": paired,
        "no_compile_in_window": window["serving.compiles"] == 0,
        "drained": drained,
        "stopped_clean": not gstats["engine_alive"],
    }
    lat = [r for r in in_window if id(r) in ok_ids]
    cached = [(r["plen"] + r["max_new"] / 2.0, r["max_new"]) for r in ok
              if traced and r["t_call"] < traced["t"][1]
              and r["t_done"] > traced["t"][0]]
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": len(in_window), "failed": len(failed),
        "setup_s": setup_s, "window_s": window["seconds"],
        "window": window, "traced": traced, "slots": slots,
        "compiles_in_window": window["serving.compiles"],
        "ttft_ms": [r["ttft_ms"] + ((r["t_call"] - r["due"]) * 1e3
                                    if r["due"] is not None else 0.0)
                    for r in lat],
        "tpot_ms": [((r["t_done"] - r["t_call"]) * 1e3 - r["ttft_ms"])
                    / (r["max_new"] - 1) for r in lat if r["max_new"] > 1],
        "late_ms": [(r["t_call"] - r["due"]) * 1e3 for r in in_window
                    if r["due"] is not None],
        "mean_cached_tokens": (sum(c * w for c, w in cached)
                               / sum(w for _, w in cached)) if cached
        else None,
        "lm": lm, "ops_bytes": ctx.config["ops_bytes"],
        "device_kind": jax.devices()[0].device_kind, "gap_label": "engine",
        "facts": {
            "export_s": export_s, "start_s": start_s, "warm_s": warm_s,
            "fill_s": t_open - t_zero, "setup_s": setup_s,
            "drain_s": drain_s, "reference_s": reference_s,
            "window": window, "whole": whole, "traced": traced,
            "requests_sent": len(records), "requests_ok": len(ok),
            "requests_in_window": len(in_window),
            "completed_in_window": sum(
                1 for r in ok if t_open <= r["t_done"] < t_close),
            "paged_routes": {w: r.get("impl") for w, r in routes.items()},
            "kv_pages": gstats["kv_pages"],
            "decode_widths": gstats["decode_widths"],
            "worst_logit_gap": worst_gap, "logit_gap_tolerance": tol,
            "logit_absmax": absmax, "tokens_flipped": flips,
            "tokens_checked": checked,
            "errors": sorted({r["error"] for r in records
                              if "error" in r})[:5]},
    }
