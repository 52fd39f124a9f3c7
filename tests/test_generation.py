"""mx.serving.generate: token-level continuous batching over a paged KV
cache — offline GenerationPredictor parity vs the eager greedy oracle,
engine admission validation, KV knob validation, shared-prefix page
refcount lifecycle (last-reader free, mid-flight sharer exit,
page-granular copy-on-write, no double-counted pages), sampling
admission gates, telemetry-report generation table + kv_pool_exhaustion
anomaly, the engine one decode step ahead of the host (every cache kind's
streams under joins, exits and EOS rides; sampled and replayed rows equal
to their solo runs; the order of dispatch and fetch, the spans' arguments,
pages held while a queued step carries their row, faults at a fetch, a
draining stop), and the tools/check_generation.py smoke (bitwise streams
under mid-flight exits/joins + flat compiles + pool exhaustion + Pallas
paged kernel routing + sampling determinism + int8 KV drift) as a
subprocess.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, deploy, generation, serving, telemetry  # noqa: F401
from mxnet_tpu.models.transformer import TransformerLM, TransformerLMConfig

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import telemetry_report  # noqa: E402

VOCAB, PAGE, CTX = 61, 4, 16


def _tiny_lm():
    """Tiny LM with host-built numpy params (model.init would burn ~1s
    compiling jax.random for no test value)."""
    import jax.numpy as jnp
    cfg = TransformerLMConfig(
        vocab_size=VOCAB, num_layers=2, d_model=16, num_heads=2,
        d_ff=32, max_len=CTX, dtype=jnp.float32)
    model = TransformerLM(cfg)
    prng = np.random.default_rng(5)
    L, D, F = 2, cfg.d_model, cfg.d_ff
    H, Dh = cfg.num_heads, cfg.head_dim

    def mk(*shape):
        return jnp.asarray(
            prng.normal(0.0, 0.02, size=shape).astype(np.float32))

    params = {
        "embed": mk(VOCAB, D),
        "pos_embed": mk(CTX, D) * 25.0,  # position-dependent streams
        "final_norm": jnp.ones((D,), jnp.float32),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "wqkv": mk(L, D, 3, H, Dh),
            "wo": mk(L, H, Dh, D),
            "ln2": jnp.ones((L, D), jnp.float32),
            "w1": mk(L, D, F),
            "w2": mk(L, F, D),
        },
    }
    return model, params


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    """One v4 generation artifact + its source model, shared module-wide."""
    prefix = str(tmp_path_factory.mktemp("generation") / "lm")
    model, params = _tiny_lm()
    deploy.export_generation(model, params, prefix, page_size=PAGE,
                             max_context=CTX, prompt_buckets=(4, 8))
    return prefix, model, params


def test_offline_generate_bitwise_matches_eager_oracle(artifact):
    """GenerationPredictor.generate (paged-cache prefill + single-token
    decode steps) reproduces the no-cache eager greedy stream bitwise."""
    prefix, model, params = artifact
    pred = deploy.load_generator(prefix)
    assert pred.format_version == 4
    rng = np.random.default_rng(3)
    for plen, max_new in ((3, 5), (7, 9), (4, 4)):
        prompt = rng.integers(0, VOCAB, size=plen).astype(np.int32)
        got = pred.generate(prompt, max_new)
        want = model.greedy_decode(params, prompt, max_new)
        assert np.array_equal(got, want), (plen, max_new)


def test_engine_submit_validation(artifact):
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    eng = generation.GenerationEngine("m", pred, num_pages=8)
    ok = np.arange(3, dtype=np.int32)
    with pytest.raises(ValueError, match="non-empty prompt"):
        eng.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError, match="non-empty prompt"):
        eng.submit(ok, 0)
    with pytest.raises(ValueError, match="max_context"):
        eng.submit(ok, CTX)  # 3 + 16 > 16
    with pytest.raises(ValueError, match="largest exported"):
        eng.submit(np.arange(9, dtype=np.int32), 2)  # buckets cap at 8
    # a pool too small for the single request, typed before queueing
    tiny = generation.GenerationEngine("m", pred, num_pages=1)
    with pytest.raises(ValueError, match="serving.kv_pages"):
        tiny.submit(ok, 9)  # needs 3 pages, pool holds 1
    # not started yet: typed ServingError, never a hang
    with pytest.raises(serving.ServingError, match="not started"):
        eng.submit(ok, 4)


def test_kv_knobs_registered_and_validated():
    for knob, default in (("serving.kv_page_size", 16),
                          ("serving.kv_pages", 256),
                          ("serving.decode_slots", 8)):
        assert knob in config.knobs()
        with pytest.raises(ValueError, match="positive integer"):
            config.set(knob, 0)
        # the failed set never sticks — reads fall back to the default
        assert config.get(knob) == default
        config.set(knob, default + 1)
        assert config.get(knob) == default + 1
        config.set(knob, default)  # restore (no unset API)


# ------------------------------------------------- shared-prefix pages

def _share_req(prompt, max_new, psz=PAGE):
    """Build a _GenRequest exactly the way submit() does when
    serving.shared_prefix is on (full-page content keys)."""
    import math
    prompt = np.asarray(prompt, np.int32)
    plen = int(prompt.shape[0])
    keys = tuple((i, prompt[:(i + 1) * psz].tobytes())
                 for i in range(plen // psz))
    need = math.ceil((plen + max_new) / psz)
    return generation._GenRequest(prompt, max_new, None, 0.0, need,
                                  prefix_keys=keys)


def test_prefix_refcount_lifecycle(artifact):
    """Admission maps equal full-page prefixes to the SAME physical
    pages (kv_pages_in_use counts them once), divergent pages go
    copy-on-write private, and pages free only with the LAST reader."""
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    eng = generation.GenerationEngine("rc", pred, num_pages=8,
                                      decode_slots=4)
    base = np.arange(8, dtype=np.int32)          # 2 full PAGE=4 pages
    fork = np.concatenate([base[:4], base[4:] + 9])  # diverges page 1
    ra, rb = _share_req(base, 3), _share_req(base, 3)   # need 3 each
    rc_ = _share_req(fork, 3)
    now = 0.0
    with eng._cond:
        eng._queue.extend([ra, rb, rc_])
        admitted = eng._admit_locked(now)
        assert admitted == [ra, rb, rc_]
        sa, sb, sc = [s for s in eng._slots if s is not None]
        # a and b share both prefix pages; c shares only page 0
        assert sa.pages[:2] == sb.pages[:2]
        assert sc.pages[0] == sa.pages[0]
        assert sc.pages[1] != sa.pages[1]       # copy-on-write page
        assert eng._prefix[ra.prefix_keys[0]][1] == 3
        assert eng._prefix[ra.prefix_keys[1]][1] == 2
        # physical accounting: 2 shared + 1 cow + 3 private = 6 pages
        assert len(eng._free) == 2
        # b exits mid-flight: shared pages survive for a, private frees
        eng._slots[eng._slots.index(sb)] = None
        eng._release_pages_locked(sb)
        assert len(eng._free) == 3
        assert eng._prefix[ra.prefix_keys[0]][1] == 2
        # c exits: its cow page was its LAST reader — freed with it
        eng._slots[eng._slots.index(sc)] = None
        eng._release_pages_locked(sc)
        assert len(eng._free) == 5
        assert rc_.prefix_keys[1] not in eng._prefix
        # a exits last: every page returns, the map drains
        eng._slots[eng._slots.index(sa)] = None
        eng._release_pages_locked(sa)
        assert len(eng._free) == 8
        assert eng._prefix == {}


def test_prefix_stall_accounts_for_shared_pages(artifact):
    """A request whose prefix is already resident admits even when the
    free list alone could not cover it — sharing IS capacity."""
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    eng = generation.GenerationEngine("cap", pred, num_pages=4,
                                      decode_slots=4)
    base = np.arange(8, dtype=np.int32)
    r1, r2 = _share_req(base, 3), _share_req(base, 3)  # need 3 each
    with eng._cond:
        eng._queue.extend([r1, r2])
        admitted = eng._admit_locked(0.0)
        # without sharing r2 would stall (3 needed, 1 free) — with it
        # r2 only draws its private page
        assert admitted == [r1, r2]
        assert len(eng._free) == 0


def test_shared_prefix_end_to_end_bitwise(artifact):
    """Concurrent sharers of one system prefix: streams stay bitwise
    equal to the eager oracle while pages are physically shared, one
    sharer exits mid-flight, and the pool drains clean."""
    prefix, model, params = artifact
    pred = deploy.load_generator(prefix)
    eng = generation.GenerationEngine(
        "share", pred, num_pages=16, decode_slots=4, max_pending=32,
        default_deadline_ms=0)
    eng.start()
    try:
        sysp = np.asarray([3, 5, 7, 2], np.int32)       # one full page
        prompts = [np.concatenate([sysp, np.asarray(t, np.int32)])
                   for t in ([7], [9], [7])]
        budgets = [6, 2, 6]   # the middle sharer EXITS mid-flight
        oracle = [model.greedy_decode(params, p, n)
                  for p, n in zip(prompts, budgets)]
        h0 = telemetry.counter("serving.prefix_hits").value
        futs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = [f.result(timeout=60) for f in futs]
        for got, want in zip(outs, oracle):
            assert np.array_equal(got, want)
        assert telemetry.counter("serving.prefix_hits").value - h0 >= 1
        st = eng.stats()
        assert st["kv_pages_free"] == 16
        assert st["prefix_entries"] == 0
    finally:
        eng.stop()


def test_shared_prefix_knob_disables_sharing(artifact):
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    assert "serving.shared_prefix" in config.knobs()
    config.set("serving.shared_prefix", False)
    try:
        eng = generation.GenerationEngine("noshare", pred, num_pages=8)
        assert eng._share is False
    finally:
        config.set("serving.shared_prefix", True)
    assert generation.GenerationEngine(
        "reshare", pred, num_pages=8)._share is True


def test_sampling_requires_v5_artifact(artifact):
    """temperature > 0 against a v4 (greedy-only) artifact fails typed
    at submit — before queueing, before the engine even starts."""
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    assert pred.sampling is False
    eng = generation.GenerationEngine("v4s", pred, num_pages=8)
    with pytest.raises(ValueError, match="sampling-enabled"):
        eng.submit(np.arange(3, dtype=np.int32), 2, temperature=0.7)


# ------------------------------------------------ telemetry report table

def _gen_rec(model="g", ttft=4.0, wall=40.0, new=8, waited=False):
    return {"event": "serving_generate", "model": model, "prompt_len": 5,
            "new_tokens": new, "max_new": new, "pages": 3,
            "ttft_ms": ttft, "wall_ms": wall,
            "pool_exhausted_wait": waited, "breaker": "closed"}


def test_report_generation_table():
    s = telemetry_report.summarize(
        [_gen_rec(ttft=1.0 * i) for i in range(12)])
    t = s["generation"]["g"]
    assert t["requests"] == 12 and t["tokens"] == 96
    assert t["prompt_tokens"] == 60
    # 96 tokens over 12 * 40ms of per-request wall time
    assert t["tokens_per_s"] == 200.0
    assert t["ttft_ms_p50"] is not None and t["pool_waits"] == 0
    assert s["other_events"] == 0 and s["anomalies"] == []


def test_report_kv_pool_exhaustion_anomaly():
    recs = [_gen_rec(waited=(i % 2 == 0)) for i in range(12)]
    s = telemetry_report.summarize(recs)
    assert "kv_pool_exhaustion" in {a["kind"] for a in s["anomalies"]}
    # waits under the ratio floor (or too few requests) never flag
    ok = telemetry_report.summarize(
        [_gen_rec(waited=(i == 0)) for i in range(12)])
    assert ok["anomalies"] == []
    few = telemetry_report.summarize([_gen_rec(waited=True)] * 3)
    assert few["anomalies"] == []


def test_report_render_includes_generation(capsys):
    out = telemetry_report.render(telemetry_report.summarize(
        [_gen_rec() for _ in range(3)]))
    assert "tokens/s" in out and "ttft_p50ms" in out


# ------------------------------------- spans, scopes, per-request record
ENGINE_SPANS = ("engine.iteration", "engine.admit", "engine.prefill",
                "engine.prefill.dispatch", "engine.prefill.device",
                "engine.prefill.fetch", "engine.decode",
                "engine.decode.prepare", "engine.decode.dispatch",
                "engine.decode.device", "engine.decode.fetch",
                "engine.decode.emit")


def _three_requests(pred, name="m"):
    """Three requests of different lengths through a fresh engine; the
    futures, resolved."""
    eng = generation.GenerationEngine(name, pred, num_pages=16,
                                      decode_slots=2).start()
    try:
        rng = np.random.default_rng(11)
        futs = [eng.submit(rng.integers(0, VOCAB, size=plen).astype(np.int32),
                           max_new)
                for plen, max_new in ((3, 5), (7, 4), (4, 6))]
        for f in futs:
            f.result(timeout=60)
    finally:
        eng.stop()
    return futs


def _by_step(spans):
    """``{step: {span name: span}}`` of the spans that carry a ``step``
    argument: one program call's dispatch, device, fetch, emit and its
    ``engine.decode`` / ``engine.prefill`` span."""
    out = {}
    for sp in spans:
        if "step" in sp[3]:
            got = out.setdefault(int(sp[3]["step"]), {})
            assert sp[0] not in got, (sp[0], "twice for one step")
            got[sp[0]] = sp
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2] \
        and inner[4] == outer[4]


def test_engine_spans_reach_a_bare_profiler_session(artifact, tmp_path):
    """A session started by ``jax.profiler.start_trace`` alone (no sink,
    no watchdog, no ``mx.profiler``) finds the engine loop's spans on the
    host plane: the turn's own work inside ``engine.iteration``, every
    span of one program call inside that call's ``engine.decode`` /
    ``engine.prefill`` span (which outlive the turn: one step is in flight
    ahead of the host), the decode span carrying the counts the per-layer
    metrics read, and as many ``engine.decode.device`` spans as the
    ``serving.decode_step_ms`` timer counted."""
    from _util import assert_spans_nest, profiled_spans
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    telemetry.reset()
    spans = profiled_spans(lambda: _three_requests(pred), tmp_path,
                           ("engine.",))
    names = {s[0] for s in spans}
    assert set(ENGINE_SPANS) <= names, sorted(names)
    for child in ("engine.admit", "engine.prefill.dispatch",
                  "engine.prefill.fetch", "engine.decode.prepare",
                  "engine.decode.dispatch", "engine.decode.fetch",
                  "engine.decode.emit"):
        assert_spans_nest(spans, child, "engine.iteration")
    assert_spans_nest(spans, "engine.decode.prepare", "engine.decode")
    for step, got in _by_step(spans).items():
        kind = "decode" if "engine.decode" in got else "prefill"
        parts = ["engine.%s.%s" % (kind, p)
                 for p in ("dispatch", "device", "fetch")]
        assert set(parts) <= set(got), (step, sorted(got))
        for part in parts + (["engine.decode.emit"] if kind == "decode"
                             else []):
            assert _inside(got[part], got["engine." + kind]), (step, part)
        assert _inside(got[parts[2]], got[parts[1]]), step
    decodes = [s[3] for s in spans if s[0] == "engine.decode"]
    for args in decodes:
        assert {"width", "rows", "held_tokens", "window_tokens", "step",
                "ahead"} <= set(args)
        assert 0 < int(args["held_tokens"]) <= int(args["window_tokens"])
        assert 1 <= int(args["rows"]) <= 2
        assert int(args["window_tokens"]) == 2 * int(args["width"]) * PAGE
    prefills = [s[3] for s in spans if s[0] == "engine.prefill"]
    assert sorted(int(a["request_id"]) for a in prefills) == [1, 2, 3]
    assert sorted(int(a["prompt_len"]) for a in prefills) == [3, 4, 7]
    assert {int(a["bucket"]) for a in prefills} == {4, 8}
    admits = [s[3] for s in spans if s[0] == "engine.admit"]
    assert sum(int(a["admitted"]) for a in admits) == 3
    emits = [s[3] for s in spans if s[0] == "engine.decode.emit"]
    assert sum(int(a["finished"]) for a in emits) == 3
    snap = telemetry.snapshot()["timers"]
    assert snap["serving.decode_step_ms"]["count"] == sum(
        s[0] == "engine.decode.device" for s in spans) == len(decodes)
    assert snap["serving.prefill_ms"]["count"] == sum(
        s[0] == "engine.prefill.device" for s in spans) == 3


def _calls(spans, kind):
    """Per program call of ``kind``, in step order: its dispatch, device
    and fetch spans."""
    return [(got["engine.%s.dispatch" % kind],
             got["engine.%s.device" % kind], got["engine.%s.fetch" % kind])
            for _, got in sorted(_by_step(spans).items())
            if "engine.%s.dispatch" % kind in got]


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_one_dispatch_span_a_device_span_before_its_fetch(artifact, tmp_path,
                                                          kind):
    """The call into the exported program alone is a span of its own,
    ``engine.<kind>.dispatch``: one a call, with its call's ``step``, over
    before that call's ``.fetch`` begins — and ``engine.<kind>.device``
    opens at the later of the dispatch and the previous fetch's return
    and holds the fetch.  It says how many host arrays the call hands
    over and how many bytes they hold (from the compiled shapes: nothing
    is worked out a step); a decode step's token ids are not among them:
    they stay on the device."""
    from _util import profiled_spans
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    spans = profiled_spans(lambda: _three_requests(pred), tmp_path,
                           ("engine.",))
    calls = _calls(spans, kind)
    assert calls
    fetches = [s for s in spans if s[0].endswith(".fetch")]
    for dispatch, device, fetch in calls:
        assert dispatch[2] <= fetch[1]
        assert _inside(fetch, device)
        assert device[1] >= dispatch[1]
        earlier = [f[2] for f in fetches if f[2] <= fetch[1]]
        assert not earlier or device[1] >= max(earlier)
        # positions / table / temperature, top-k, top-p, keys (a prefill:
        # tokens, prompt length, table and the same four)
        assert int(dispatch[3]["host_args"]) == (6 if kind == "decode"
                                                 else 7)
    sizes = {int(d[3]["host_bytes"]) for d, _, _ in calls}
    if kind == "decode":
        # 2 slots: 4 vectors of 4 B a row, key words 8 B, a table row of
        # the width's page indices
        parents = [s[3] for s in spans if s[0] == "engine.decode"]
        assert sizes == {2 * (24 + 4 * int(a["width"])) for a in parents}
    else:
        # one row: the bucket's tokens, its length, its pages' indices
        assert sizes == {4 * b + 4 + 4 * -(-b // PAGE) + 12 + 8
                         for b in (4, 8)}


def test_step_timer_keeps_the_bounds_of_the_device_span(artifact, tmp_path):
    """``serving.decode_step_ms`` / ``serving.prefill_ms`` read from inside
    ``engine.*.device`` to its end: from the later of the program's
    dispatch and the previous fetch's return (the time the program is the
    one the host waits on) over the whole of its fetch.  The device spans
    of the engine thread follow one another and never overlap."""
    from _util import profiled_spans
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    telemetry.reset()
    spans = profiled_spans(lambda: _three_requests(pred), tmp_path,
                           ("engine.",))
    snap = telemetry.snapshot()["timers"]
    for kind, timer in (("decode", "serving.decode_step_ms"),
                        ("prefill", "serving.prefill_ms")):
        calls = _calls(spans, kind)
        assert snap[timer]["count"] == len(calls)
        total_ms = snap[timer]["total"]
        outer = sum(dev[2] - dev[1] for _, dev, _ in calls) / 1e6
        inner = sum(f[2] - f[1] for _, _, f in calls) / 1e6
        assert inner <= total_ms * 1.0001 and total_ms <= outer * 1.0001
    devices = sorted((s for s in spans if s[0].endswith(".device")),
                     key=lambda s: s[1])
    assert all(a[2] <= b[1] for a, b in zip(devices, devices[1:]))


def test_dispatch_spans_leave_the_served_programs_as_they_were(
        artifact, monkeypatch):
    """The programs the engine compiles and calls are, instruction for
    instruction, those of a build whose spans are all no-ops: a span is a
    profiler annotation around a call, never part of what is called."""
    import contextlib
    prefix, _, _ = artifact

    def compiled_texts():
        pred = deploy.load_generator(prefix)
        eng = generation.GenerationEngine("m", pred, num_pages=16,
                                          decode_slots=2)
        eng._compile_programs()
        texts = {("prefill", k): p.as_text()
                 for k, p in eng._prefill.items()}
        texts.update({("decode", k): p.as_text()
                      for k, p in eng._decode.items()})
        return texts, dict(eng._host_operands)

    with_spans, operands = compiled_texts()
    assert sorted(operands) == sorted(
        ["prefill-s%d" % k[1] if k[0] == "prefill" else "decode-w%d" % k[1]
         for k in with_spans])

    class _Off:
        def set(self, **args):
            pass

    monkeypatch.setattr(
        generation._tracing, "span",
        lambda name, cat="host", **args: contextlib.nullcontext(_Off()))
    without, _ = compiled_texts()
    assert without == with_spans


def test_decode_span_counts_what_the_in_place_route_reads(tmp_path):
    """Where the decode programs took the Pallas kernel (tier on, a
    concrete decode batch), ``window_tokens`` is the pages the rows' own
    lengths reach — whole pages, one token or more past what they held —
    not slots x width x page size; and the same requests are served the
    same tokens as on the gathering route."""
    from _util import profiled_spans
    model, params = _tiny_lm()
    prefix = str(tmp_path / "lm")
    config.set("kernels.enabled", True)
    try:
        deploy.export_generation(model, params, prefix, page_size=PAGE,
                                 max_context=CTX, prompt_buckets=(4, 8),
                                 decode_batch=2)
    finally:
        config.unset("kernels.enabled")
    pred = deploy.load_generator(prefix)
    assert {r["impl"] for r in pred.paged_routes.values()} == {"paged"}
    telemetry.reset()
    futs = []
    spans = profiled_spans(lambda: futs.extend(_three_requests(pred)),
                           tmp_path, ("engine.decode",))
    decodes = [s[3] for s in spans if s[0] == "engine.decode"]
    assert decodes
    for args in decodes:
        held, window, rows = (int(args[k]) for k in
                              ("held_tokens", "window_tokens", "rows"))
        assert window % PAGE == 0
        assert held + rows <= window < held + rows + rows * PAGE
    assert telemetry.counter("kernels.paged_attention").value == len(decodes)
    rng = np.random.default_rng(11)
    for fut, (plen, max_new) in zip(futs, ((3, 5), (7, 4), (4, 6))):
        prompt = rng.integers(0, VOCAB, size=plen).astype(np.int32)
        np.testing.assert_array_equal(
            fut.result(), model.greedy_decode(params, prompt, max_new))


def test_serving_generate_event_is_the_request_record(artifact, tmp_path):
    """Tracing off: every request has the engine's own id — on the future
    and in its ``serving_generate`` event — with its queue wait, prefill
    time and one stamp per emitted token."""
    from mxnet_tpu import tracing
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    assert not tracing.enabled()
    telemetry.reset()
    sink = str(tmp_path / "events.jsonl")
    telemetry.configure_sink("jsonl:" + sink)
    try:
        futs = _three_requests(pred)
    finally:
        telemetry.configure_sink("")
    with open(sink) as f:
        events = [e for e in map(json.loads, f)
                  if e.get("event") == "serving_generate"]
    assert sorted(e["request_id"] for e in events) == [1, 2, 3]
    assert sorted(f.request_id for f in futs) == [1, 2, 3]
    by_id = {e["request_id"]: e for e in events}
    for fut in futs:
        ev = by_id[fut.request_id]
        assert ev["trace_id"] is None
        assert ev["new_tokens"] == len(fut.result())
        assert ev["queue_ms"] >= 0 and ev["prefill_ms"] > 0
        stamps = ev["token_ms"]
        assert len(stamps) == ev["new_tokens"]
        assert all(a < b for a, b in zip(stamps, stamps[1:])), stamps
        assert stamps[0] == ev["ttft_ms"]
        assert ev["queue_ms"] + ev["prefill_ms"] <= ev["ttft_ms"] + 1e-3
        assert stamps[-1] <= ev["wall_ms"] + 1e-3
    timers = telemetry.snapshot()["timers"]
    assert timers["serving.queue_ms"]["count"] == 3
    # one gap per decoded token: every token but each request's first
    assert timers["serving.token_gap_ms"]["count"] == sum(
        e["new_tokens"] - 1 for e in events)


@pytest.mark.parametrize("program, scopes", [
    ("decode", ("mx.layers", "mx.qkv", "mx.kv_write", "mx.kv_gather",
                "mx.paged_attention", "mx.attn_out", "mx.mlp",
                "mx.lm_head", "mx.sample")),
    ("decode_kernel", ("mx.layers", "mx.qkv", "mx.kv_write",
                       "mx.paged_attention", "mx.attn_out", "mx.mlp",
                       "mx.lm_head", "mx.sample")),
    ("prefill", ("mx.layers", "mx.qkv", "mx.kv_write", "mx.attention",
                 "mx.attn_out", "mx.mlp", "mx.lm_head", "mx.sample")),
])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32kv", "int8kv"])
def test_generation_programs_carry_scopes_as_metadata_only(
        program, scopes, quantized, monkeypatch):
    """The decode step and the prefill name their layers with
    ``jax.named_scope`` — and are, locations aside, the programs they were
    without the names.  ``mx.kv_gather`` is the XLA twin's: the decode
    step on the kernel's route (``decode_kernel``: the tier switched on)
    reads its pages in place and has no such scope."""
    import jax
    import jax.numpy as jnp
    from _util import lowered_with_and_without_scopes
    if program == "decode_kernel":
        program = "decode"
        mx.config.set("kernels.enabled", True)
    model, params = _tiny_lm()
    kv = model.init_kv_pages(8, PAGE, quantized=quantized)
    i32 = jnp.int32

    def lower():
        if program == "decode":
            return jax.jit(lambda p, c, t, pos, tab: model.decode_step(
                p, c, t, pos, tab, PAGE)).lower(
                    params, kv, jnp.zeros((4,), i32), jnp.zeros((4,), i32),
                    jnp.zeros((4, 2), i32))
        return jax.jit(lambda p, c, t, n, tab: model.prefill(
            p, c, t, n, tab, PAGE)).lower(
                params, kv, jnp.zeros((1, 8), i32), jnp.ones((1,), i32),
                jnp.zeros((1, 2), i32))

    try:
        text = lowered_with_and_without_scopes(lower, monkeypatch)
    finally:
        mx.config.unset("kernels.enabled")
    for scope in scopes:
        assert scope + "/" in text or scope + '"' in text, scope
    if "mx.kv_gather" not in scopes and "mx.paged_attention" in scopes:
        assert "mx.kv_gather" not in text
        assert "mx_paged_attention" in text


# ------------------------------- one decode step in flight ahead of the host
KINDS = ("transformer", "hybrid", "retention", "latent")
_ARTIFACTS = {}


def _kind(kind, tmp_path_factory, **export):
    """A toy of each cache kind, exported once a module: K/V pages
    (``TransformerLM``), pages beside per-slot state rows (``HybridLM``
    ``MEM*E``: Mamba-2, experts, attention), state rows alone (retention
    ``RFRF``), latent pages (``LFLG``).  ``(model, params, predictor,
    vocab, longest prompt, context)``."""
    key = (kind,) + tuple(sorted(export.items()))
    if key not in _ARTIFACTS:
        prefix = str(tmp_path_factory.mktemp(kind) / "lm")
        if kind == "transformer":
            model, params = _tiny_lm()
            deploy.export_generation(
                model, params, prefix, page_size=PAGE, max_context=CTX,
                prompt_buckets=(4, 8), **export)
            vocab, longest, ctx = VOCAB, 8, CTX
        else:
            import test_hybrid_lm
            import test_latent_attention
            import test_retention
            source = {"hybrid": test_hybrid_lm,
                      "retention": test_retention,
                      "latent": test_latent_attention}[kind]
            over = {"hybrid": {"pattern": "MEM*E"}}.get(kind, {})
            model, params = source._tiny(**over)
            deploy.export_generation(
                model, params, prefix, sampling=True, decode_batch=2,
                prompt_buckets=[8, 16], max_context=32, page_size=PAGE,
                **export)
            vocab, longest, ctx = 96, 16, 32
        _ARTIFACTS[key] = (model, params, deploy.load_generator(prefix),
                           vocab, longest, ctx)
    return _ARTIFACTS[key]


def _watch_releases(eng):
    """Wrap the engine's page release: every release checks that no
    program in flight or fetched-not-emitted carries the row, and
    ``rode`` counts rows that ended while a queued step still carried
    them (an EOS the host saw after the next step left)."""
    held = []
    rode = []
    release, retire = eng._release_pages_locked, eng._retire

    def checked_release(slot):
        carried = [st.step for st in (*eng._ahead, *eng._fetched)
                   if any(s is slot for _, s in st.rows)]
        held.append(carried)
        return release(slot)

    def watched_retire(i):
        slot = eng._slots[i]
        if slot.ended and slot.inflight:
            rode.append(slot.req.request_id)
        return retire(i)

    eng._release_pages_locked = checked_release
    eng._retire = watched_retire
    return held, rode


@pytest.mark.parametrize("kind", KINDS)
def test_served_streams_are_the_oracles_through_join_exit_and_eos(
        kind, tmp_path_factory):
    """Every cache kind, one step ahead of the host, serves each request
    its solo greedy stream: seven requests over two slots join and exit
    mid-flight (budgets differ, slots and pages are used again), and two
    end on an EOS while the step after it is already queued — its token
    is dropped, and no page or state slot returns to the pool while a
    program in flight carries the row."""
    model, params, pred, vocab, longest, ctx = _kind(kind, tmp_path_factory)
    pages = 2 * -(-ctx // PAGE) if pred.paged else 1
    eng = generation.GenerationEngine(kind, pred, num_pages=pages,
                                      decode_slots=2, max_pending=32,
                                      default_deadline_ms=0)
    held, rode = _watch_releases(eng)
    rng = np.random.default_rng(7)
    plans = []
    for n, plen in enumerate((3, longest, 5, 2, longest - 1, 4, 6)):
        budget = min(ctx - plen, (5, 9, 3, 8, 4, 7, 6)[n])
        prompt = rng.integers(0, vocab, size=plen).astype(np.int32)
        want = np.asarray(model.greedy_decode(params, prompt, budget))
        eos = int(want[2]) if n in (1, 3) else None
        if eos is not None:
            want = want[:list(want).index(eos) + 1]
        plans.append((prompt, budget, eos, want))
    ahead = telemetry.counter("serving.decode_ahead").value
    eng.start()
    try:
        futs = [eng.submit(p, b, eos_id=e) for p, b, e, _ in plans]
        for f, (_, _, _, want) in zip(futs, plans):
            np.testing.assert_array_equal(f.result(timeout=120), want)
    finally:
        eng.stop()
    assert eng.stats()["kv_pages_free"] == pages
    assert held and not any(held)
    assert rode, "no EOS row rode a queued step"
    assert telemetry.counter("serving.decode_ahead").value > ahead
    assert not eng._ahead and not eng._fetched


def test_a_slot_an_eos_leaves_behind_a_queued_step_serves_the_next(
        artifact):
    """One slot and a pool of one request's pages: the first request ends
    on an EOS while the step after it is queued; the second, waiting for
    those very pages, is admitted once that step is fetched and served its
    solo stream."""
    prefix, model, params = artifact
    pred = deploy.load_generator(prefix)
    eng = generation.GenerationEngine("eos", pred, num_pages=4,
                                      decode_slots=1, max_pending=8,
                                      default_deadline_ms=0)
    held, rode = _watch_releases(eng)
    rng = np.random.default_rng(8)
    first = rng.integers(0, VOCAB, size=4).astype(np.int32)
    then = rng.integers(0, VOCAB, size=6).astype(np.int32)
    solo = np.asarray(model.greedy_decode(params, first, 12))
    eos = int(solo[3])
    want_first = solo[:list(solo).index(eos) + 1]
    want_then = np.asarray(model.greedy_decode(params, then, 10))
    eng.start()
    try:
        fa = eng.submit(first, 12, eos_id=eos)
        fb = eng.submit(then, 10)
        np.testing.assert_array_equal(fa.result(timeout=60), want_first)
        np.testing.assert_array_equal(fb.result(timeout=60), want_then)
    finally:
        eng.stop()
    assert rode == [fa.request_id]
    assert held and not any(held)
    assert eng.stats()["kv_pages_free"] == 4


def test_sampled_rows_are_their_solo_streams(tmp_path):
    """A sampled request (its own seed, temperature, top-k and top-p)
    served beside greedy and other sampled rows, one step ahead of the
    host, gets the stream it gets alone, bit for bit: the same operands
    reach the program in its row, and the key is folded with the
    position inside it."""
    model, params = _tiny_lm()
    prefix = str(tmp_path / "lm")
    deploy.export_generation(model, params, prefix, page_size=PAGE,
                             max_context=CTX, prompt_buckets=(4, 8),
                             sampling=True)
    pred = deploy.load_generator(prefix)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, VOCAB, size=n).astype(np.int32)
               for n in (5, 3, 7, 4)]
    asks = [dict(temperature=0.9, top_k=7, top_p=0.9, seed=1234567),
            dict(), dict(temperature=1.3, seed=2 ** 40 + 5),
            dict(temperature=0.7, top_p=0.8, seed=99)]

    def serve(which):
        eng = generation.GenerationEngine("s", pred, num_pages=16,
                                          decode_slots=2, max_pending=8,
                                          default_deadline_ms=0).start()
        try:
            futs = {i: eng.submit(prompts[i], 8, **asks[i]) for i in which}
            return {i: f.result(timeout=60) for i, f in futs.items()}
        finally:
            eng.stop()

    together = serve(range(4))
    for i in (0, 2, 3):
        np.testing.assert_array_equal(serve([i])[i], together[i])
    assert len({tuple(together[i]) for i in (0, 2, 3)}) == 3
    np.testing.assert_array_equal(
        together[1], model.greedy_decode(params, prompts[1], 8))


@pytest.mark.parametrize("kind", ["hybrid", "retention"])
def test_the_replay_tail_is_the_solo_requests(kind, tmp_path_factory):
    """``return_replay=True`` one step ahead of the host: a request that
    ends on an EOS, served beside others, gets the replay it gets alone —
    a log-probability for each token it returns, the experts of its
    prompt and of every token but the last (none for a model without
    experts) — nothing of the step it rode after its EOS; and told those
    choices the plain reference makes every one of them and gives the
    same log-probabilities."""
    import test_hybrid_lm
    model, params, pred, vocab, _, _ = _kind(kind, tmp_path_factory,
                                            replay=True)
    experts = pred.replay["layers"], pred.replay["top_k"]
    rng = np.random.default_rng(10)
    prompts = [rng.integers(0, vocab, size=n).astype(np.int32)
               for n in (11, 5, 16)]
    solo = np.asarray(model.greedy_decode(params, prompts[0], 9))
    eos = int(solo[4])
    want = solo[:list(solo).index(eos) + 1]

    def serve(which):
        eng = generation.GenerationEngine("r", pred, num_pages=16,
                                          max_pending=8,
                                          default_deadline_ms=0).start()
        try:
            futs = [eng.submit(prompts[i], 9, eos_id=eos if i == 0 else None,
                               return_replay=True) for i in which]
            return [f.result(timeout=120) for f in futs]
        finally:
            eng.stop()

    (alone,) = serve([0])
    beside = serve([1, 0, 2])[1]
    for ids, replay in (alone, beside):
        np.testing.assert_array_equal(ids, want)
        assert replay["logprobs"].shape == (len(want),)
        assert replay["routed_experts"].shape == (
            experts[0], len(prompts[0]) + len(want) - 1, experts[1])
    np.testing.assert_array_equal(alone[1]["routed_experts"],
                                  beside[1]["routed_experts"])
    np.testing.assert_array_equal(alone[1]["logprobs"],
                                  beside[1]["logprobs"])
    if kind != "hybrid":
        return
    gaps, _, missed, logprobs = test_hybrid_lm.REF.served_token_gaps(
        params, prompts[0], want, 32, 9, lm=test_hybrid_lm.REF_LM,
        routed=beside[1]["routed_experts"])
    assert int(missed) == 0 and float(gaps.max()) == 0.0
    test_hybrid_lm._close(beside[1]["logprobs"], logprobs)


def _raw_spans(trace_dir, name):
    """Every ``name`` event of the session as ``(start, end, stats)``,
    its stats a ``[(key, value)]`` list: a key set twice shows twice."""
    import glob
    import jax
    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, list(ev.stats))
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name == name]


def test_next_step_is_dispatched_before_the_last_is_fetched(artifact,
                                                            tmp_path):
    """In steady state the decode step N+1 is dispatched before step N's
    tokens are fetched (``engine.decode.dispatch`` of N+1 begins before
    ``engine.decode.fetch`` of N ends), every step says so with ``ahead``
    1, and ``serving.decode_ahead`` counts them; the first token of a
    prefill is fetched after the decode step that is fed it has been
    dispatched."""
    import jax
    prefix, model, params = artifact
    pred = deploy.load_generator(prefix)
    prompt = np.arange(1, 5, dtype=np.int32)
    ahead = telemetry.counter("serving.decode_ahead").value
    eng = generation.GenerationEngine("o", pred, num_pages=8,
                                      decode_slots=2).start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = eng.submit(prompt, 10).result(timeout=60)
    finally:
        eng.stop()             # every span closed before the session ends
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(got, model.greedy_decode(params, prompt,
                                                           10))
    spans = [(n, s, e, dict(args), "engine")
             for n in ("engine.decode.dispatch", "engine.decode.fetch",
                       "engine.prefill.fetch", "engine.decode")
             for s, e, args in _raw_spans(tmp_path, n)]
    steps = _by_step(spans)
    decodes = sorted(k for k, v in steps.items() if "engine.decode" in v)
    assert len(decodes) == 9
    for n, m in zip(decodes, decodes[1:]):
        assert steps[m]["engine.decode.dispatch"][1] \
            < steps[n]["engine.decode.fetch"][2], (n, m)
    assert all(int(steps[k]["engine.decode"][3]["ahead"]) == 1
               for k in decodes)
    assert telemetry.counter("serving.decode_ahead").value - ahead == 9
    (prefill,) = [v for v in steps.values()
                  if "engine.prefill.fetch" in v]
    assert steps[decodes[0]]["engine.decode.dispatch"][2] \
        <= prefill["engine.prefill.fetch"][1]
    assert eng._host_operands["decode-w4"]["host_args"] == 6


@pytest.mark.parametrize("kind", ["hybrid", "latent"])
def test_every_decode_span_carries_each_roofline_argument_once(
        kind, tmp_path_factory, tmp_path):
    """What the roofline readers average over ``engine.decode`` spans is
    on every one of them exactly once — one step's rows, tokens held,
    window, sample tier, latent route and the counts that step brought
    back behind its tokens — though the step's span outlives the turn
    that dispatched it."""
    import jax
    model, params, pred, vocab, _, _ = _kind(kind, tmp_path_factory)
    eng = generation.GenerationEngine(kind, pred, num_pages=16,
                                      max_pending=8,
                                      default_deadline_ms=0).start()
    rng = np.random.default_rng(12)
    steps = telemetry.timer("serving.decode_step_ms").count
    jax.profiler.start_trace(str(tmp_path))
    try:
        futs = [eng.submit(rng.integers(0, vocab, size=n).astype(np.int32),
                           m) for n, m in ((5, 6), (9, 3), (4, 5))]
        for f in futs:
            f.result(timeout=120)
    finally:
        eng.stop()
        jax.profiler.stop_trace()
    want = {"width", "rows", "step", "ahead", "sample_tier",
            "held_tokens", "window_tokens", *model.decode_stats}
    want |= {"state_rows"} if kind == "hybrid" else {"latent_kernel"}
    decodes = _raw_spans(tmp_path, "engine.decode")
    assert decodes
    for _, _, stats in decodes:
        keys = [k for k, _ in stats]
        assert sorted(keys) == sorted(want), sorted(keys)
    assert len(decodes) == telemetry.timer(
        "serving.decode_step_ms").count - steps


class _FetchFault:
    """``generation``'s numpy, but the ``n``-th copy of a device array
    out (a fetch) raises ``exc``."""

    def __init__(self, n):
        self.n = n
        self.exc = RuntimeError("the chip lost the step")

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        import jax
        if isinstance(a, jax.Array):
            self.n -= 1
            if self.n == 0:
                raise self.exc
        return np.asarray(a, *args, **kw)


def test_a_fault_at_fetch_fails_every_row_in_flight(artifact, monkeypatch):
    """A program that raises at its fetch, with the next step queued
    behind it: every row of every program in flight fails with the causal
    error, the breaker records one failure, the pool is rebuilt, every
    page comes back — and the engine serves the request that waited."""
    prefix, model, params = artifact
    pred = deploy.load_generator(prefix)
    breaker = serving._Breaker("fault", 2, 60.0)
    eng = generation.GenerationEngine("fault", pred, num_pages=12,
                                      decode_slots=2, breaker=breaker,
                                      max_pending=8, default_deadline_ms=0)
    eng.start()
    fault = _FetchFault(4)        # two prefills, then the second decode
    monkeypatch.setattr(generation, "_np", fault)
    errors = telemetry.counter("serving.dispatch_errors").value
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, VOCAB, size=n).astype(np.int32)
               for n in (4, 6, 5)]
    try:
        futs = [eng.submit(p, 8) for p in prompts]
        for f in futs[:2]:
            with pytest.raises(RuntimeError) as got:
                f.result(timeout=60)
            assert got.value is fault.exc
        np.testing.assert_array_equal(
            futs[2].result(timeout=60),
            model.greedy_decode(params, prompts[2], 8))
    finally:
        eng.stop()
    assert telemetry.counter("serving.dispatch_errors").value == errors + 1
    assert breaker.failures == 0 and breaker.state == "closed"
    assert eng.stats()["kv_pages_free"] == 12


def test_a_fault_at_fetch_records_the_breaker(artifact, monkeypatch):
    """The same fault against a breaker that opens at the first failure:
    it opens, and the requests the engine still holds fail fast."""
    prefix, _, _ = artifact
    pred = deploy.load_generator(prefix)
    breaker = serving._Breaker("fault1", 1, 60.0)
    eng = generation.GenerationEngine("fault1", pred, num_pages=12,
                                      decode_slots=2, breaker=breaker,
                                      max_pending=8, default_deadline_ms=0)
    eng.start()
    fault = _FetchFault(4)
    monkeypatch.setattr(generation, "_np", fault)
    try:
        futs = [eng.submit(np.arange(1, 1 + n, dtype=np.int32), 8)
                for n in (4, 6, 5)]
        outcomes = []
        for f in futs:
            try:
                f.result(timeout=60)
                outcomes.append(None)
            except Exception as exc:  # noqa: BLE001
                outcomes.append(exc)
    finally:
        eng.stop()
    assert outcomes[0] is fault.exc and outcomes[1] is fault.exc
    assert isinstance(outcomes[2], serving.CircuitOpenError)
    assert breaker.state == "open" and breaker.failures == 1
    assert eng.stats()["kv_pages_free"] == 12


def test_drain_stop_resolves_every_future(artifact):
    """``stop(drain=True)`` right after the submits: every program in
    flight is fetched and every future resolves to its solo stream
    before ``stop`` returns."""
    prefix, model, params = artifact
    pred = deploy.load_generator(prefix)
    eng = generation.GenerationEngine("drain", pred, num_pages=16,
                                      decode_slots=2, max_pending=8,
                                      default_deadline_ms=0).start()
    rng = np.random.default_rng(14)
    prompts = [rng.integers(0, VOCAB, size=n).astype(np.int32)
               for n in (3, 7, 5, 4, 8)]
    futs = [eng.submit(p, 6) for p in prompts]
    eng.stop(drain=True, timeout_s=60)
    assert all(f.done() for f in futs)
    for f, p in zip(futs, prompts):
        np.testing.assert_array_equal(f.result(timeout=0),
                                      model.greedy_decode(params, p, 6))
    assert not eng._ahead and not eng._fetched
    assert eng.stats()["kv_pages_free"] == 16


# ------------------------------------------- the pool as the scan's carry

def _eager_kv(model, params, tokens):
    """K and V rows of whole sequences, layer by layer in Python on the
    plain causal stack: two ``[L, B, S, H*Dh]`` arrays."""
    import jax
    B, S = tokens.shape
    x = params["embed"][tokens] + params["pos_embed"][:S][None]
    ks, vs = [], []
    for i in range(model.cfg.num_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        _, k, v = model._qkv(x, lp)
        ks.append(np.asarray(k).transpose(0, 2, 1, 3).reshape(B, S, -1))
        vs.append(np.asarray(v).transpose(0, 2, 1, 3).reshape(B, S, -1))
        x = model._layer(x, lp)
    return np.stack(ks), np.stack(vs)


@pytest.mark.parametrize("case", ["all_rows", "inactive_slot"])
@pytest.mark.parametrize("stack_mode", ["scan", "unroll"])
@pytest.mark.parametrize("route", ["twin", "kernel"])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32kv", "int8kv"])
def test_prefill_and_decode_steps_carry_the_pool_to_the_oracles(
        quantized, route, stack_mode, case):
    """``prefill`` then N x ``decode_step`` (fed the oracle's tokens)
    with the whole ``[L, P, psz, W]`` pools as the layer scan's carry:
    every step's token is the eager greedy oracle's (an int8 pool: its
    logits stay near the full forward's), the pages hold the K/V rows of
    the eager stack at the positions the tables name, and nothing else
    was written — padded prompt positions, table entries past a row's
    pages (the sentinel id) and a slot with no request (sentinel pages,
    position 0) all drop."""
    import jax
    import jax.numpy as jnp
    model, params = _tiny_lm()
    L, N, P, rows = model.cfg.num_layers, 5, 12, 3
    rng = np.random.default_rng(11)
    lens = np.array([3, 7, 5], np.int32)
    prompts = [rng.integers(0, VOCAB, size=n).astype(np.int32) for n in lens]
    want = [model.greedy_decode(params, p, N + 1) for p in prompts]
    full = np.zeros((rows, CTX), np.int32)
    for b, (p, w) in enumerate(zip(prompts, want)):
        full[b, :len(p) + N] = np.concatenate([p, w[:N]])
    ref_logits = np.asarray(model.apply(params, jnp.asarray(full)))
    ref_k, ref_v = _eager_kv(model, params, jnp.asarray(full))
    # each row owns the pages it needs and no more; the rest of its table
    # is the sentinel
    table = np.full((rows, CTX // PAGE), P, np.int32)
    free = list(rng.permutation(P))
    for b in range(rows):
        for w in range(-(-(lens[b] + N) // PAGE)):
            table[b, w] = free.pop()
    idle = 1 if case == "inactive_slot" else None   # after its prefill

    config.set("runtime.stack_mode", stack_mode)
    if route == "kernel":
        config.set("kernels.enabled", True)
    try:
        prefill = jax.jit(lambda ps, kv, t, n, tab: model.prefill(
            ps, kv, t, n, tab, PAGE, return_logits=True))
        decode = jax.jit(lambda ps, kv, t, pos, tab: model.decode_step(
            ps, kv, t, pos, tab, PAGE, return_logits=True))
        kv = model.init_kv_pages(P, PAGE, quantized=quantized)
        kv, ids, _ = prefill(params, kv, jnp.asarray(full[:, :8]),
                             jnp.asarray(lens), jnp.asarray(table[:, :2]))
        # (the first token never sees the pages, whatever they hold)
        assert np.array_equal(ids, [w[0] for w in want])
        held = lens.copy()
        for j in range(N):
            pos, tab = lens + j, table.copy()
            tok = full[np.arange(rows), pos]
            if idle is not None:
                pos[idle], tok[idle], tab[idle] = 0, 0, P
            kv, ids, logits = decode(params, kv, jnp.asarray(tok),
                                     jnp.asarray(pos), jnp.asarray(tab))
            for b in range(rows):
                if b == idle:
                    continue
                held[b] = pos[b] + 1
                ref = ref_logits[b, pos[b]]
                if quantized:
                    assert np.abs(np.asarray(logits[b]) - ref).max() \
                        <= 0.05 * np.abs(ref).max(), (j, b)
                else:
                    assert int(ids[b]) == want[b][j + 1], (j, b)
    finally:
        config.unset("runtime.stack_mode")
        config.unset("kernels.enabled")

    for key, ref in (("k", ref_k), ("v", ref_v)):
        pool = np.asarray(kv[key], np.float32)
        if quantized:
            pool = (pool.reshape(pool.shape[:3] + (model.cfg.num_heads, -1))
                    * np.asarray(kv[key + "_scale"])[..., None]
                    ).reshape(pool.shape)
        written = np.zeros(pool.shape[:3], bool)
        for b in range(rows):
            for t in range(held[b]):
                at = (slice(None), table[b, t // PAGE], t % PAGE)
                written[at] = True
                tol = (np.abs(ref[:, b, t]).max() / 100 if quantized
                       else 1e-6)
                assert np.abs(pool[at] - ref[:, b, t]).max() <= tol, \
                    (key, b, t)
        assert written.sum() == L * held.sum()
        assert not pool[~written].any(), key
        if quantized:
            assert not np.asarray(kv[key + "_scale"])[~written].any()


# ------------------------------------------------------- smoke wrapper

def test_check_generation_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(root, "tools", "check_generation.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["bitwise"]["mismatches"] == 0
    assert report["compiles"]["compiled"] == \
        len(report["compiles"]["prompt_buckets"]) + \
        len(report["compiles"]["decode_widths"])
    assert report["kv_pool"]["exhausted_waits"] > 0
    assert all(impl == "paged"
               for impl in report["paged_kernel"]["routes"].values())
    assert report["paged_kernel"]["decode_iterations"] > 0
    assert report["sampling"]["replay_ok"]
    assert report["sampling"]["distinct_of_8"] >= 2
    assert report["int8_kv"]["logit_drift"] <= \
        report["int8_kv"]["error_budget"]
    # time is judged by the tool's own CPU seconds (its budget, held
    # inside it): a wall clock here measures the other xdist workers
    assert 0 < report["cpu_s"] < (90.0 if (os.cpu_count() or 1) >= 2
                                  else 180.0), report
