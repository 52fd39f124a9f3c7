"""Fast CPU smoke for the mx.obs operational plane (about 5s on an idle
>=2-core box; ``elapsed_s`` is reported, not asserted).

Proves the exporter + access log + SLO tracker end-to-end on the host
backend, with one parseable JSON line on stdout:

  1. metrics — ``/metrics`` scraped DURING concurrent one-shot serving
               and generation traffic parses as Prometheus text
               exposition (every sample under a declared family, no
               duplicate families), and every counter is monotonic
               across scrapes;
  2. healthz — 200 with per-engine detail while healthy; opening a real
               circuit breaker (injected dispatch faults) flips it to
               503 naming ``breaker_open:<model>``;
  3. varz    — knob provenance: the overridden obs knobs report
               ``override``, untouched knobs report ``default``;
  4. access  — exactly ONE schema-valid JSONL record per completed
               request (ok + injected-error outcomes tally), and every
               ``request_id`` joins a ``serving.submit`` span id in the
               Chrome trace written by ``tracing.sink``;
  5. slo     — SLOTracker burn-rate math on a synthetic sample stream
               with explicit timestamps (window bases, fast/slow alert
               pairing, zero-traffic burn);
  6. overhead — the measured SERIAL per-record access-log cost (the
               hot enqueue on the dispatch thread — the only piece
               that cannot overlap anything) against the measured
               per-request service time: added cost <= 2%.  The
               writer-thread drain (serialization + file write) is
               measured and reported per record but priced separately:
               it overlaps GIL-released dispatch and IO, and a
               falling-behind writer sheds into ``obs.access_dropped``
               instead of backpressuring serving.

The overhead gate is DETERMINISTIC by construction: end-to-end A/B
throughput on a noisy CPU box cannot resolve a 2% bound (A/A spread is
an order of magnitude wider), so the gate decomposes into the two
directly-measurable factors instead — serial cost added per record,
divided by the time a request takes anyway.

Usage: JAX_PLATFORMS=cpu python tools/check_obs.py
Wired as a `not slow` test in tests/test_obs.py.
"""
from __future__ import annotations

import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

MAX_BATCH = 8
FEATURES = 6
N_THREADS = 4
REQS_PER_THREAD = 6
GEN_REQUESTS = 3
VOCAB = 89
MAX_CONTEXT = 16
OVERHEAD_RECORDS = 20000
OVERHEAD_LIMIT_PCT = 2.0
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})? (\S+)$")


def parse_prometheus(text):
    """Strict-enough exposition parse: ``{family: {"type": t,
    "samples": {(name, labels): float}}}``.  Raises AssertionError on a
    sample without a family, a duplicate family, or a bad value."""
    families = {}
    current = None
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            fam, _, typ = rest.partition(" ")
            assert fam not in families, \
                "line %d: duplicate family %s" % (ln, fam)
            assert typ in ("counter", "gauge", "summary"), \
                "line %d: family %s has type %r" % (ln, fam, typ)
            families[fam] = {"type": typ, "samples": {}}
            current = fam
            continue
        assert not line.startswith("#"), "line %d: stray comment" % ln
        m = _SAMPLE_RE.match(line)
        assert m, "line %d: unparsable sample %r" % (ln, line)
        name, labels, value = m.groups()
        base = name
        for suffix in ("_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in families:
                base = name[:-len(suffix)]
        assert base in families, \
            "line %d: sample %s outside any # TYPE family" % (ln, name)
        assert current == base, \
            "line %d: sample %s outside its family block" % (ln, name)
        families[base]["samples"][(name, labels or "")] = float(value)
    return families


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_obs_")
    access_path = os.path.join(tmpdir, "access.jsonl")
    trace_path = os.path.join(tmpdir, "trace.json")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import jax.numpy as jnp
        import mxnet_tpu as mx
        from mxnet_tpu import config, obs, telemetry, tracing
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.models.transformer import (TransformerLM,
                                                  TransformerLMConfig)
        result["backend"] = jax.default_backend()

        config.set("obs.listen", "127.0.0.1:0")
        config.set("obs.access_log", "jsonl:" + access_path)
        config.set("obs.slo", "availability=99.9,latency_p99_ms=5000")
        config.set("tracing.sink", "chrome:" + trace_path)
        host, port = obs.exporter_address()
        base_url = "http://%s:%d" % (host, port)

        def fetch(path):
            try:
                with urllib.request.urlopen(base_url + path,
                                            timeout=5) as resp:
                    return resp.status, resp.read().decode("utf-8")
            except urllib.error.HTTPError as err:
                return err.code, err.read().decode("utf-8")

        # --- model zoo: a one-shot MLP and a tiny generation LM on ONE
        # server, so the scrape happens over genuinely mixed traffic
        mx.random.seed(7)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        example = mx.nd.random.uniform(shape=(MAX_BATCH, FEATURES))
        net(example)
        prefix = os.path.join(tmpdir, "mlp")
        mx.deploy.export_model(net, prefix, example)

        cfg = TransformerLMConfig(
            vocab_size=VOCAB, num_layers=1, d_model=16, num_heads=2,
            d_ff=32, max_len=MAX_CONTEXT, dtype=jnp.float32)
        model = TransformerLM(cfg)
        prng = np.random.default_rng(0)

        def mk(*shape):
            return jnp.asarray(
                prng.normal(0.0, 0.02, size=shape).astype(np.float32))

        params = {
            "embed": mk(VOCAB, cfg.d_model),
            "pos_embed": mk(MAX_CONTEXT, cfg.d_model) * 25.0,
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "layers": {
                "ln1": jnp.ones((1, cfg.d_model), jnp.float32),
                "wqkv": mk(1, cfg.d_model, 3, cfg.num_heads, cfg.head_dim),
                "wo": mk(1, cfg.num_heads, cfg.head_dim, cfg.d_model),
                "ln2": jnp.ones((1, cfg.d_model), jnp.float32),
                "w1": mk(1, cfg.d_model, cfg.d_ff),
                "w2": mk(1, cfg.d_ff, cfg.d_model),
            },
        }
        gprefix = os.path.join(tmpdir, "lm")
        mx.deploy.export_generation(model, params, gprefix,
                                    page_size=8, max_context=MAX_CONTEXT,
                                    prompt_buckets=(4,))

        config.set("serving.kv_pages", 8)
        config.set("serving.decode_slots", 4)
        srv = mx.serving.Server(max_batch=MAX_BATCH,
                                max_queue_delay_ms=2.0,
                                breaker_threshold=2,
                                breaker_cooldown_ms=60000.0)
        srv.register("mlp", prefix)
        srv.register("lm", gprefix, generate=True)
        srv.start()

        # 2: healthy while everything runs — engine detail present
        code, body = fetch("/healthz")
        health = json.loads(body)
        assert code == 200 and health["healthy"], body
        gen_info = None
        for src in health["sources"].values():
            gen_info = (src.get("generation") or {}).get("lm", gen_info)
        assert gen_info is not None and gen_info["engine_alive"], health
        result["healthz"] = {"healthy_code": code,
                             "kv_pages": gen_info["kv_pages"]}

        # 1: concurrent one-shot + generation traffic, scraped mid-flight
        rng = np.random.RandomState(0)
        xs = rng.uniform(size=(1, FEATURES)).astype(np.float32)
        prompts = [rng.randint(0, VOCAB, size=3).astype(np.int32)
                   for _ in range(GEN_REQUESTS)]
        errors = []
        pass_times = []

        def one_shot_worker():
            try:
                for _ in range(REQS_PER_THREAD):
                    srv.submit("mlp", xs).result(timeout=30)
            except BaseException as exc:  # noqa: BLE001
                errors.append("%s: %s" % (type(exc).__name__, exc))

        srv.submit("mlp", xs).result(timeout=30)  # warm the dispatch path
        gen_futs = [srv.submit_generate("lm", p, 4) for p in prompts]
        threads = [threading.Thread(target=one_shot_worker)
                   for _ in range(N_THREADS)]
        t_pass = time.perf_counter()
        for t in threads:
            t.start()
        scrape1 = fetch("/metrics")  # mid-flight, traffic still running
        for t in threads:
            t.join()
        pass_times.append(time.perf_counter() - t_pass)
        streams = [f.result(timeout=30) for f in gen_futs]
        assert not errors, errors[0]
        assert all(len(s) == 4 for s in streams), \
            [len(s) for s in streams]
        scrape2 = fetch("/metrics")

        assert scrape1[0] == 200 and scrape2[0] == 200
        fams1 = parse_prometheus(scrape1[1])
        fams2 = parse_prometheus(scrape2[1])
        for fam in ("mxnet_tpu_serving_requests",
                    "mxnet_tpu_obs_scrapes",
                    "mxnet_tpu_slo_error_budget",
                    "mxnet_tpu_slo_burn_rate"):
            assert fam in fams2, "scrape missing family %s" % fam
        assert any(key[1] == 'quantile="0.99"'
                   for fam in fams2.values()
                   for key in fam["samples"]), "no summary quantiles"
        regressions = [
            key for fam, entry in fams1.items()
            if entry["type"] == "counter" and fam in fams2
            for key, val in entry["samples"].items()
            if fams2[fam]["samples"].get(key, val) < val]
        assert not regressions, \
            "counters moved backwards: %s" % regressions
        result["metrics"] = {
            "families": len(fams2),
            "counters": sum(1 for entry in fams2.values()
                            if entry["type"] == "counter")}

        # 3: knob provenance on /varz
        code, body = fetch("/varz")
        assert code == 200
        knobs = json.loads(body)
        assert knobs["obs.listen"]["source"] == "override", \
            knobs["obs.listen"]
        assert knobs["obs.listen"]["env"] == "MXNET_TPU_OBS_LISTEN"
        assert knobs["serving.max_pending"]["source"] == "default", \
            knobs["serving.max_pending"]
        result["varz"] = {"knobs": len(knobs)}

        # 6: overhead gate (deterministic decomposition — see module
        # docstring).  Denominator: a second measured one-shot pass;
        # numerator: the serial hot enqueue per record, with the
        # writer's drain cost measured alongside for the report.
        threads = [threading.Thread(target=one_shot_worker)
                   for _ in range(N_THREADS)]
        t_pass = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        pass_times.append(time.perf_counter() - t_pass)
        assert not errors, errors[0]
        per_request_us = min(pass_times) / (N_THREADS * REQS_PER_THREAD) \
            * 1e6
        obs.flush_access_log()
        t0 = time.perf_counter()
        for i in range(OVERHEAD_RECORDS):
            obs.log_access("bench", "ok", request_id=str(i),
                           queue_ms=0.5, dispatch_ms=1.0, bytes=64)
        hot_us = (time.perf_counter() - t0) / OVERHEAD_RECORDS * 1e6
        t0 = time.perf_counter()
        obs.flush_access_log()
        drain_us = (time.perf_counter() - t0) / OVERHEAD_RECORDS * 1e6
        overhead_pct = hot_us / per_request_us * 100.0
        result["overhead"] = {
            "per_request_us": round(per_request_us, 1),
            "hot_enqueue_us": round(hot_us, 3),
            "writer_drain_us": round(drain_us, 3),
            "overhead_pct": round(overhead_pct, 3)}
        assert overhead_pct <= OVERHEAD_LIMIT_PCT, \
            "access log adds %.2f%% (%.2fus/record over %.0fus/request)" \
            % (overhead_pct, hot_us, per_request_us)

        # 4: exactly one schema-valid record per completed request, and
        # the injected-breaker phase below adds its error records — so
        # the access assertions run after the breaker flip.
        config.set("resilience.faults", "serving_dispatch:2@step=1")
        for i in range(2):
            exc = srv.submit("mlp", xs).exception(timeout=30)
            assert exc is not None, "injected dispatch fault vanished"
        assert srv.stats()["breakers"]["mlp"] == "open", srv.stats()
        code, body = fetch("/healthz")
        health = json.loads(body)
        assert code == 503 and not health["healthy"], (code, body)
        reasons = [r for src in health["sources"].values()
                   for r in src.get("reasons", ())]
        assert "breaker_open:mlp" in reasons, reasons
        result["healthz"]["breaker_code"] = code

        obs.flush_access_log()
        tracing.flush()
        with open(access_path) as fh:
            records = [json.loads(line) for line in fh]
        for rec in records:
            obs.validate_access_record(rec)
        served = [r for r in records if r["model"] != "bench"]
        tally = {}
        for rec in served:
            tally[rec["outcome"]] = tally.get(rec["outcome"], 0) + 1
        expect_ok = 1 + 2 * N_THREADS * REQS_PER_THREAD + GEN_REQUESTS
        assert tally.get("ok") == expect_ok, \
            "expected %d ok records, got %s" % (expect_ok, tally)
        assert tally.get("error") == 2, tally
        assert len(records) == expect_ok + 2 + OVERHEAD_RECORDS, \
            len(records)
        gen_recs = [r for r in served if r["model"] == "lm"]
        assert all(r["tokens"] == 4 and r["ttft_ms"] is not None
                   for r in gen_recs), gen_recs

        events = tracing.load_trace(trace_path)
        span_ids = {str(e["args"]["trace_id"])
                    for e in events
                    if isinstance(e.get("args"), dict)
                    and "trace_id" in e["args"]}
        assert len(served) == expect_ok + 2, len(served)
        # a one-shot record's request_id IS its submit span's trace_id; a
        # generation record carries the engine's own request number and
        # the span's trace_id beside it
        assert all(r["request_id"].isdigit() and "trace_id" in r
                   for r in gen_recs), gen_recs
        assert len({r["request_id"] for r in gen_recs}) == len(gen_recs)
        orphans = [r["request_id"] for r in served
                   if r.get("trace_id", r["request_id"]) not in span_ids]
        assert not orphans, \
            "access records with no Chrome-trace span: %s" % orphans[:5]
        result["access"] = {"records": len(records), "outcomes": tally,
                            "trace_joined": len(served)}

        # 5: SLO burn-rate math on a synthetic stream (budget 1%)
        trk = obs.SLOTracker(availability=99.0)
        burn = trk.burn_rates(now=0.0)  # zero traffic spends no budget
        assert burn and all(v == 0.0 for v in burn.values()), burn
        trk.observe(0, 0, now=0.0)
        burn = trk.burn_rates(now=0.0)
        assert all(v == 0.0 for v in burn.values()), burn
        trk.observe(1000, 200, now=300.0)
        burn = trk.burn_rates()
        assert all(abs(v - 20.0) < 1e-9 for v in burn.values()), burn
        assert trk.alerts(burn) == ["fast", "slow"], trk.alerts(burn)
        slow = obs.SLOTracker(availability=99.0)
        slow.observe(0, 0, now=0.0)
        slow.observe(1000, 100, now=300.0)  # burn 10: ticket, no page
        assert slow.alerts() == ["slow"], slow.alerts()
        # window bases differ once the stream outlives the short window
        win = obs.SLOTracker(availability=99.0)
        win.observe(0, 0, now=0.0)
        win.observe(1000, 0, now=2000.0)
        win.observe(2000, 130, now=2300.0)
        burn = win.burn_rates()
        assert abs(burn["5m"] - 13.0) < 1e-9, burn   # base = t=2000
        assert abs(burn["30m"] - 6.5) < 1e-9, burn   # base = t=0
        assert win.alerts(burn) == ["slow"], win.alerts(burn)
        result["slo"] = {"fast_page_burn": 20.0, "window_split": burn}

        srv.stop()
        # reported, not asserted: a loaded machine is not a fault of the
        # pipeline (ROADMAP D9)
        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config
            for knob in ("obs.listen", "obs.access_log", "obs.slo",
                         "tracing.sink", "resilience.faults"):
                config.set(knob, "")
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
