"""Serve a generation-capable model through the generation server: the
model CLASS is the one the configuration names (``sizes.model`` and
``sizes.model_config`` in ``mxnet_tpu.models``), where ``serve_lm``
builds a ``TransformerLM``.  The dispatcher, the warm-up list and the
counter snapshots are ``serve_lm``'s own (``ctx.module("drivers",
"serve_lm")``); the control flow is its ``run`` again — set-up, fill,
window, drain, checks, the plain reference over a seeded sample — with
what a model with more than K/V pages in its cache adds:

* the model is imported FIRST, so a checkout that lacks it fails at once,
  before any weight is made;
* the weights made on the device are the ones served (``export_generation(
  include_params=False)``, ``Server.register(params=...)``): 9 GB take no
  trip through a file, and the reference reads the same arrays afterwards;
* ``sizes.decode_widths`` (a subset of the page-table widths) goes to
  ``export_generation``, and one request per prompt bucket is warmed, so
  a configuration with one decode program still runs every program once
  before the window;
* the counters a decode step brings back behind its tokens
  (``serving.<name>`` for the artifact's ``decode_stats``) are read at
  the same instants as ``serve_lm``'s and land in the same ``window`` /
  ``traced`` dicts;
* with ``sizes.replay`` every request also brings back what replaying it
  needs (``submit_generate(return_replay=True)``): the experts each of
  its tokens chose and each token's log-probability.  The reference —
  handed ``sizes.reference`` (what no shape tells it) — is TOLD the
  choices: a router near-tie that bf16 flips is then no difference
  between the two, and the choices the reference's own scores would not
  have made are counted (``routing_within_tolerance``).  For each
  precision in ``tolerance.matched`` the reference is also computed THAT
  much lower, and the served log-probabilities must lie nearer the full
  reference's than the lowered one's (``nearer_full_than_<precision>``:
  the ratio of the two squared distances against the precision's limit):
  a comparison that sees a lower precision even where its effect is
  smaller than the program's own rounding;
* under ``--override traffic.reference_degrade=[...]`` each named control
  (a program with one precision or step taken away, simulated by the
  reference) goes through the SAME comparison in the served request's
  place: ``checks`` gains ``<control>:<check>`` entries and ``correct``
  is false where a control fails — which is what the tolerance is set
  for (an ``explored`` line, never a result)."""
from __future__ import annotations

import gc
import json
import os
import tempfile

from benchmarks.harness import profile
from benchmarks.harness.stats import fold_seed, now


def _replay_dispatcher(base):
    class AskReplay:
        def __init__(self, srv):
            self.srv = srv

        def submit_generate(self, *args):
            return self.srv.submit_generate(*args, return_replay=True)

    class Dispatcher(base._Dispatcher):
        """``serve_lm``'s, asking every request for its replay."""

        def __init__(self, srv, *rest):
            super().__init__(AskReplay(srv), *rest)

        def collect(self, timeout):
            rec = super().collect(timeout)
            if rec is not None and "tokens" in rec:
                rec["tokens"], rec["replay"] = rec["tokens"]
            return rec

    return Dispatcher


class _Compared:
    """The reference's readings over a sample of served (or simulated)
    requests, and the tolerance's verdict on them."""

    def __init__(self, ref, params, tol, lm, pad_to, pad_rows, control=None):
        self.ref, self.params, self.tol, self.lm = ref, params, tol, lm
        self.pad = (pad_to, pad_rows)
        self.gaps, self.absmax = [], 0.0
        self.missed = self.told = 0
        # squared distance of the served log-probabilities from the full
        # reference's (None) and from each lowered one's (a control is
        # held against its own precision alone: the one it must fail)
        matched = [m for m in tol.get("matched", ())
                   if control in (None, m)]
        self.apart = dict.fromkeys([None] + matched, 0.0)

    def add(self, np, prompt, served, tokens, replay):
        """``served`` is fed; ``tokens`` (the served ones, or a control's
        in their place) are scored, with the ``replay`` that came with
        them (None where the artifact returns none)."""
        routed = None if replay is None else replay["routed_experts"]
        for how in self.apart if replay is not None else [None]:
            got = self.ref.served_token_gaps(
                self.params, prompt, served, *self.pad, lm=self.lm,
                routed=routed, scored=tokens, degrade=how)
            if how is None:
                self.gaps.extend(np.asarray(got[0]).tolist())
                self.absmax = max(self.absmax, float(got[1]))
            if replay is not None:
                self.apart[how] += float(np.square(
                    np.asarray(got[3]) - replay["logprobs"]).sum())
                if how is None:
                    self.missed += int(got[2])
                    self.told += int(routed.size)

    def verdict(self, absmax=None):
        """``(checks, facts)``; a control is held to the served tokens'
        logit scale (``absmax``)."""
        tol, gaps = self.tol, self.gaps
        absmax = self.absmax if absmax is None else absmax
        flipped = sum(g > 0 for g in gaps)
        worst = max(gaps, default=0.0)
        gap_limit = tol["logit_gap_bf16_ulps"] * 2.0 ** -8 * absmax
        checks = {
            "served_tokens_within_tolerance":
                bool(gaps) and worst <= gap_limit,
            "flipped_tokens_within_tolerance":
                bool(gaps) and flipped <= tol["flipped_share_max"] * len(gaps)}
        facts = {
            "tokens_checked": len(gaps), "tokens_flipped": flipped,
            "tokens_flipped_limit": tol["flipped_share_max"] * len(gaps),
            "worst_logit_gap": worst, "logit_gap_tolerance": gap_limit,
            "worst_gap_bf16_ulps": worst / absmax * 256 if absmax else None}
        if self.told:
            limit = tol["routing_missed_share_max"] * self.told
            checks["routing_within_tolerance"] = self.missed <= limit
            full = self.apart[None]
            facts.update(
                routing_told=self.told, routing_missed=self.missed,
                routing_missed_limit=limit,
                logprob_rms_apart=(full / len(gaps)) ** 0.5,
                nearer_full_than={}, nearer_full_than_limits={
                    how: tol["matched"][how] for how in self.apart
                    if how is not None})
            for how, lowered in self.apart.items():
                if how is not None:
                    # > 1: nearer the full reference than the lowered one
                    ratio = lowered / full if full else None
                    facts["nearer_full_than"][how] = ratio
                    checks["nearer_full_than_" + how] = \
                        ratio is None or ratio >= tol["matched"][how]
        return checks, facts


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models, telemetry

    sz, tp = ctx.sizes, ctx.traffic
    Model = getattr(models, sz["model"])          # fails at once if absent
    Config = getattr(models, sz["model_config"])
    base = ctx.module("drivers", "serve_lm")
    lm = sz["lm"]
    model = Model(Config(dtype=jnp.bfloat16, **lm))
    stats_names = tuple("serving." + n
                        for n in getattr(model, "decode_stats", ()))

    def snapshot():
        snap = base._snapshot(telemetry)
        for name in stats_names:
            snap[name] = telemetry.counter(name).value
        return snap

    def delta(a, b):
        out = base._delta(a, b)
        for name in stats_names:
            out[name] = b[name] - a[name]
        return out

    key = jax.random.PRNGKey(fold_seed(ctx.seed) % (2 ** 31 - 1))
    make_params = jax.jit(model.init)
    params = make_params(key)
    plan = ctx.module("generators", tp["generator"]).generate(
        ctx.seed, tp, lm["vocab_size"])
    closed = plan["clients"] is not None
    slots = sz["decode_batch"]
    name = "lm"
    replay = bool(sz.get("replay"))
    Dispatcher = _replay_dispatcher(base) if replay else base._Dispatcher

    with tempfile.TemporaryDirectory(prefix="bench_lm_") as tmp:
        prefix = os.path.join(tmp, name)
        t0 = now()
        mx.deploy.export_generation(
            model, params, prefix, sampling=True, decode_batch=slots,
            prompt_buckets=tp["prompt_buckets"],
            max_context=tp["max_context"], page_size=sz["page_tokens"],
            decode_widths=sz.get("decode_widths"), include_params=False,
            replay=replay)
        export_s = now() - t0
        with open(prefix + "-meta.json") as f:
            routes = json.load(f)["paged"]
        srv = mx.serving.Server()
        srv.register(name, prefix, generate=True, params=params)
        t0 = now()
        srv.start()
        start_s = now() - t0
        stats = srv.stats()["generation"][name]
        rng = np.random.default_rng(fold_seed(ctx.seed, 2))
        t0 = now()
        warm = [(b, 4) for b in stats["prompt_buckets"]] \
            + base._warm_requests(stats["decode_widths"],
                                  stats["prompt_buckets"],
                                  stats["page_size"])
        for plen, new in warm:
            srv.submit_generate(name, rng.integers(
                0, lm["vocab_size"], (plen,)).astype(np.int32),
                new).result(timeout=600)
        warm_s = now() - t0

        sink = os.path.join(ctx.run_dir, "telemetry.jsonl")
        telemetry.configure_sink("jsonl:" + sink)
        disp = Dispatcher(srv, name, plan, jax)
        snap_first = snapshot()
        t_zero = now()

        def pump(until, submit=True):
            """Run the dispatcher until ``until()`` is true."""
            while not until():
                if closed:
                    while submit and disp.outstanding < plan["clients"]:
                        disp.submit_next(t_zero)
                    disp.collect(0.02)
                else:
                    due = disp.next_due(t_zero)
                    while submit and due <= now():
                        disp.submit_next(t_zero)
                        due = disp.next_due(t_zero)
                    disp.collect(min(0.02, due - now()) if submit else 0.02)

        # ---- fill: every slot (or client) busy, or admission waiting for
        # pages; then the traffic file's iterations more
        opens = tp["window_opens"]
        if opens["kind"] == "iterations_after_full":
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
        snap_open = snapshot()
        t_open = snap_open["t"]
        setup_s = t_open - ctx.t_start
        traced = None
        if ctx.trace:
            t_a = t_open + tp["trace_after_s"]
            pump(lambda: now() >= t_a)
            with profile.traced_window(ctx.trace_dir):
                snap_a = snapshot()
                t_b = snap_a["t"] + tp["trace_seconds"]
                pump(lambda: now() >= t_b)
                snap_b = snapshot()
            traced = delta(snap_a, snap_b)
            traced["t"] = (snap_a["t"], snap_b["t"])
        t_end = t_open + ctx.seconds
        pump(lambda: now() >= t_end)
        snap_close = snapshot()
        t_close = snap_close["t"]

        # ---- drain: nothing new is sent, everything sent resolves
        t_limit = now() + tp["drain_limit_s"]
        with jax.profiler.TraceAnnotation("bench.drain"):
            pump(lambda: disp.outstanding == 0 or now() >= t_limit,
                 submit=False)
        drained = disp.outstanding == 0
        snap_last = snapshot()
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
    window = delta(snap_open, snap_close)
    whole = delta(snap_first, snap_last)
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

    # ---- the plain reference, over a seeded sample of served requests:
    # the served tokens and, under the override, each control in their place
    ref = ctx.module("reference", ctx.config["reference"])
    pick = np.random.default_rng(fold_seed(ctx.seed, 3))
    sample = [ok[i] for i in sorted(pick.choice(
        len(ok), min(tp["reference_sample"], len(ok)), replace=False))] \
        if ok else []
    t0 = now()
    # (a rehearsal's toy widths lose nothing to int8: its own limits)
    tol = dict(ctx.config["tolerance"], **(
        ctx.config["rehearse"].get("tolerance", {}) if ctx.rehearse else {}))
    compared = {how: _Compared(
        ref, params, tol, sz.get("reference"),
        tp["max_context"], max(r["max_new"] for r in plan["requests"]), how)
        for how in [None] + list(tp.get("reference_degrade", ()))}
    for rec in sample:
        prompt = plan["requests"][rec["index"] % len(plan["requests"])][
            "prompt"]
        for how, readings in compared.items():
            tokens, came = rec["tokens"], rec.get("replay")
            if how is not None:
                tokens, routed, logprobs = ref.simulate(
                    params, prompt, rec["tokens"], *readings.pad,
                    lm=readings.lm, degrade=how)
                came = came and {"routed_experts": np.asarray(routed),
                                 "logprobs": np.asarray(logprobs)}
            readings.add(np, prompt, rec["tokens"], tokens, came)
    reference_s = now() - t0
    del params
    served = compared.pop(None)
    checks, served_facts = served.verdict()
    controls = {}
    for how, readings in compared.items():
        verdict, controls[how] = readings.verdict(served.absmax)
        checks.update({"%s:%s" % (how, k): v for k, v in verdict.items()})

    returned = sum(len(r["tokens"]) for r in records if "tokens" in r)
    checks.update({
        "tokens_returned_equal_counter":
            drained and returned == whole["serving.tokens_generated"],
        "every_request_full_length": drained and all(
            "tokens" in r and len(r["tokens"]) == r["max_new"]
            for r in records),
        "events_pair_with_requests": paired,
        "no_compile_in_window": window["serving.compiles"] == 0,
        "drained": drained,
        "stopped_clean": not gstats["engine_alive"],
    })
    lat = [r for r in in_window if id(r) in ok_ids]
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
            "logit_absmax": served.absmax, **served_facts,
            "controls": controls,
            "errors": sorted({r["error"] for r in records
                              if "error" in r})[:5]},
    }
