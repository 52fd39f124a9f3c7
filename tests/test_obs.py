"""mx.obs operational plane: exporter, access log, SLO tracker.

Covers the obs PR: Prometheus rendering (family folding, labeled
per-model twins, no duplicate families), the /metrics-/healthz-/varz
exporter under concurrent registry traffic, health-source aggregation,
the async bounded access log (schema round-trip, escape handling, drop
accounting, reconfigure drain), SLOTracker burn-rate math and the
obs.slo knob, and the tools/check_obs.py smoke (real serving +
generation traffic, breaker-driven 503, trace join, overhead gate) as a
subprocess.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from mxnet_tpu import config, obs, telemetry

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import check_obs  # noqa: E402


@pytest.fixture(autouse=True)
def _obs_off():
    """Each test starts with the whole plane off and a zeroed registry."""
    for knob in ("obs.listen", "obs.access_log", "obs.slo"):
        config.set(knob, "")
    telemetry.reset()
    yield
    for knob in ("obs.listen", "obs.access_log", "obs.slo"):
        config.set(knob, "")
    telemetry.reset()


def _fetch(path, timeout=30):
    # generous timeout: on a single-core box the GIL parcels the handler
    # thread ~1/9th of the time under the 8-thread hammer test
    host, port = obs.exporter_address()
    try:
        with urllib.request.urlopen(
                "http://%s:%d%s" % (host, port, path),
                timeout=timeout) as resp:
            return resp.status, resp.read().decode("utf-8")
    except urllib.error.HTTPError as err:
        return err.code, err.read().decode("utf-8")


# ------------------------------------------------------- prometheus text
def test_render_prometheus_families_and_quantiles():
    telemetry.counter("serving.requests").inc(5)
    telemetry.gauge("serving.queue_depth").set(3)
    t = telemetry.timer("serving.request_ms")
    for v in (1.0, 2.0, 3.0):
        t.observe(v)
    fams = check_obs.parse_prometheus(obs.render_prometheus())
    assert fams["mxnet_tpu_serving_requests"]["type"] == "counter"
    assert fams["mxnet_tpu_serving_requests"]["samples"][
        ("mxnet_tpu_serving_requests", "")] == 5.0
    assert fams["mxnet_tpu_serving_queue_depth"]["type"] == "gauge"
    summary = fams["mxnet_tpu_serving_request_ms"]
    assert summary["type"] == "summary"
    assert summary["samples"][
        ("mxnet_tpu_serving_request_ms", 'quantile="0.5"')] == 2.0
    assert summary["samples"][
        ("mxnet_tpu_serving_request_ms_count", "")] == 3.0
    assert summary["samples"][
        ("mxnet_tpu_serving_request_ms_sum", "")] == 6.0


def test_render_prometheus_folds_per_model_twins():
    """serving emits base + ``<base>.<model>`` counter twins; the twins
    must fold into ONE labeled family, not duplicate-family spellings."""
    telemetry.counter("serving.shed_requests").inc(4)
    telemetry.counter("serving.shed_requests.mlp").inc(3)
    telemetry.counter("serving.shed_requests.lm").inc(1)
    fams = check_obs.parse_prometheus(obs.render_prometheus())
    samples = fams["mxnet_tpu_serving_shed_requests"]["samples"]
    assert samples[("mxnet_tpu_serving_shed_requests", "")] == 4.0
    assert samples[
        ("mxnet_tpu_serving_shed_requests", 'model="mlp"')] == 3.0
    assert samples[
        ("mxnet_tpu_serving_shed_requests", 'model="lm"')] == 1.0


def test_render_prometheus_label_escaping():
    telemetry.counter('serving.shed_requests.we"ird\\name').inc()
    text = obs.render_prometheus()
    assert 'model="we\\"ird\\\\name"' in text
    check_obs.parse_prometheus(text)  # still structurally valid


def test_render_prometheus_windowed_quantiles_go_live():
    """Scraped quantiles come from the rotating window once it has
    samples — scraped latency is LIVE latency, not lifetime latency."""
    t = telemetry.timer("serving.request_ms")
    base = t._win_start
    t.observe(100.0, now=base)          # warmup spike
    t.observe(1.0, now=base + 61.0)     # rotates the spike out
    snap = {"counters": {}, "gauges": {},
            "timers": {t.name: t.stats(now=base + 61.0)}}
    fams = check_obs.parse_prometheus(obs.render_prometheus(snap))
    samples = fams["mxnet_tpu_serving_request_ms"]["samples"]
    assert samples[
        ("mxnet_tpu_serving_request_ms", 'quantile="0.99"')] == 1.0
    # lifetime accumulators still carry the spike
    assert samples[("mxnet_tpu_serving_request_ms_sum", "")] == 101.0


# --------------------------------------------------------------- exporter
def test_exporter_concurrent_traffic_parses_and_counts_monotonic():
    config.set("obs.listen", "127.0.0.1:0")
    assert obs.exporter_address() is not None
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            telemetry.counter("serving.requests").inc()
            telemetry.timer("serving.request_ms").observe(0.5)
            # yield: 8 spinning CPU-bound threads convoy the GIL on a
            # small box and starve the exporter's accept/handler thread
            time.sleep(0.0002)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    try:
        scrapes = []
        for _ in range(5):
            code, body = _fetch("/metrics")
            assert code == 200
            scrapes.append(check_obs.parse_prometheus(body))
    finally:
        stop.set()
        for t in threads:
            t.join()
    for prev, cur in zip(scrapes, scrapes[1:]):
        for fam, entry in prev.items():
            if entry["type"] != "counter" or fam not in cur:
                continue
            for key, val in entry["samples"].items():
                assert cur[fam]["samples"].get(key, val) >= val, \
                    (fam, key)
    req_key = ("mxnet_tpu_serving_requests", "")
    assert scrapes[-1]["mxnet_tpu_serving_requests"]["samples"][req_key] \
        > scrapes[0]["mxnet_tpu_serving_requests"]["samples"][req_key]
    assert telemetry.counter("obs.scrapes").value >= 5


def test_exporter_rebind_and_disable():
    config.set("obs.listen", "127.0.0.1:0")
    first = obs.exporter_address()
    config.set("obs.listen", "127.0.0.1:0")  # idempotent spec: same server
    assert obs.exporter_address() == first
    config.set("obs.listen", "")
    assert obs.exporter_address() is None


def test_exporter_unknown_path_404():
    config.set("obs.listen", "127.0.0.1:0")
    code, body = _fetch("/nope")
    assert code == 404 and "/nope" in body


def test_listen_knob_rejects_malformed_spec():
    with pytest.raises(ValueError):
        config.set("obs.listen", "no-port-here")
    assert config.get("obs.listen") == ""  # hook reverted the override


# ---------------------------------------------------------------- healthz
def test_healthz_aggregates_sources_and_flips():
    config.set("obs.listen", "127.0.0.1:0")
    state = {"healthy": True}
    obs.register_health_source("unit", lambda: dict(state))
    try:
        code, body = _fetch("/healthz")
        report = json.loads(body)
        assert code == 200 and report["healthy"]
        assert report["sources"]["unit"]["healthy"]
        assert "last_step_age_s" in report
        state["healthy"] = False
        state["reasons"] = ["breaker_open:mlp"]
        code, body = _fetch("/healthz")
        report = json.loads(body)
        assert code == 503 and not report["healthy"]
        assert report["sources"]["unit"]["reasons"] == ["breaker_open:mlp"]
    finally:
        obs.unregister_health_source("unit")
    code, _ = _fetch("/healthz")
    assert code == 200  # unregistered source no longer taints health


def test_healthz_raising_source_reported_not_fatal():
    def bad():
        raise RuntimeError("probe exploded")

    obs.register_health_source("bad", bad)
    try:
        ok, report = obs.healthz()
        assert not ok
        assert "probe exploded" in report["sources"]["bad"]["error"]
    finally:
        obs.unregister_health_source("bad")


# ------------------------------------------------------------------- varz
def test_varz_provenance():
    config.set("obs.slo", "availability=99.9")
    out = obs.varz()
    assert out["obs.slo"]["value"] == "availability=99.9"
    assert out["obs.slo"]["source"] == "override"
    assert out["obs.slo"]["env"] == "MXNET_TPU_OBS_SLO"
    assert out["serving.max_pending"]["source"] == "default"


# ------------------------------------------------------------- access log
def test_access_log_roundtrip_and_escaping(tmp_path):
    path = tmp_path / "access.jsonl"
    config.set("obs.access_log", "jsonl:%s" % path)
    assert obs.access_log_enabled() and obs.access_log_path() == str(path)
    obs.log_access("mlp", "ok", request_id="41", queue_ms=0.25,
                   dispatch_ms=1.5, tokens=4, bytes=16)
    obs.log_access('m"x\\y', "error", error='Boom: "quote"\nnewline')
    obs.log_access("lm", "shed")
    obs.flush_access_log()
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(recs) == 3
    for rec in recs:
        obs.validate_access_record(rec)
    assert recs[0]["request_id"] == "41" and recs[0]["tokens"] == 4
    assert recs[1]["model"] == 'm"x\\y'
    assert recs[1]["error"] == 'Boom: "quote"\nnewline'
    assert recs[2]["outcome"] == "shed" and "queue_ms" not in recs[2]
    assert telemetry.counter("obs.access_records").value == 3


def test_access_log_off_is_noop(tmp_path):
    obs.log_access("mlp", "ok")  # no sink: must not queue or raise
    assert len(obs._ACCESS_QUEUE) == 0
    obs.flush_access_log()


def test_access_log_bounded_queue_drops_and_counts(tmp_path, monkeypatch):
    config.set("obs.access_log", "jsonl:%s" % (tmp_path / "a.jsonl"))
    # suspend the writer so the queue bound is hit deterministically
    obs._ACCESS_STOP.set()
    obs._ACCESS_THREAD.join(timeout=5)
    monkeypatch.setattr(obs, "_ACCESS_QUEUE_MAX", 16)
    for i in range(21):
        obs.log_access("mlp", "ok", request_id=str(i))
    assert len(obs._ACCESS_QUEUE) == 16
    assert telemetry.counter("obs.access_dropped").value == 5
    monkeypatch.undo()
    obs.flush_access_log()
    recs = [json.loads(line)
            for line in (tmp_path / "a.jsonl").read_text().splitlines()]
    assert [r["request_id"] for r in recs] == [str(i) for i in range(16)]


def test_access_log_reconfigure_drains_to_old_sink(tmp_path):
    old = tmp_path / "old.jsonl"
    new = tmp_path / "new.jsonl"
    config.set("obs.access_log", "jsonl:%s" % old)
    obs.log_access("mlp", "ok", request_id="1")
    config.set("obs.access_log", "jsonl:%s" % new)
    obs.log_access("mlp", "ok", request_id="2")
    obs.flush_access_log()
    config.set("obs.access_log", "")
    assert [json.loads(l)["request_id"]
            for l in old.read_text().splitlines()] == ["1"]
    assert [json.loads(l)["request_id"]
            for l in new.read_text().splitlines()] == ["2"]


def test_validate_access_record_rejects():
    good = {"event": "access", "ts": 1.0, "model": "m", "outcome": "ok"}
    obs.validate_access_record(good)
    for bad in (
            {**good, "outcome": "exploded"},        # unknown outcome
            {**good, "event": "step"},              # wrong event
            {**good, "request_id": 41},             # int id (must be str)
            {**good, "tokens": -1},                 # negative count
            {**good, "queue_ms": "fast"},           # non-numeric
            {k: v for k, v in good.items() if k != "model"},
            "not a dict"):
        with pytest.raises(ValueError):
            obs.validate_access_record(bad)


# ------------------------------------------------------------ slo tracker
def test_slo_burn_rate_windows_and_alert_pairing():
    trk = obs.SLOTracker(availability=99.0)  # budget: 1%
    trk.observe(0, 0, now=0.0)
    trk.observe(1000, 0, now=2000.0)
    trk.observe(2000, 130, now=2300.0)
    burn = trk.burn_rates()
    # 5m window base = the t=2000 sample: 130/1000 errors over 1% budget
    assert abs(burn["5m"] - 13.0) < 1e-9
    # the long windows reach back to t=0: 130/2000 over 1% budget
    assert abs(burn["6h"] - 6.5) < 1e-9
    assert trk.alerts(burn) == ["slow"]  # 6 < slow burn < 14.4 fast burn
    trk.observe(2100, 430, now=2310.0)   # page-rate burst
    burn = trk.burn_rates()
    assert burn["5m"] > 14.4 and burn["1h"] > 14.4
    assert trk.alerts(burn) == ["fast", "slow"]


def test_slo_no_traffic_spends_no_budget():
    trk = obs.SLOTracker(availability=99.9)
    assert all(v == 0.0 for v in trk.burn_rates(now=10.0).values())
    trk.observe(100, 0, now=0.0)
    trk.observe(100, 0, now=400.0)  # idle stretch, zero new requests
    assert all(v == 0.0 for v in trk.burn_rates().values())
    assert trk.alerts() == []


def test_slo_out_of_order_observations_stay_monotonic():
    trk = obs.SLOTracker(availability=99.0)
    trk.observe(10, 0, now=100.0)
    trk.observe(20, 1, now=50.0)  # racing scrape: must not go backwards
    pts = list(trk._points)
    assert pts[1][0] > pts[0][0]
    trk.burn_rates()  # and the math still runs


def test_slo_knob_validation_and_status():
    with pytest.raises(ValueError):
        config.set("obs.slo", "availability=101")
    with pytest.raises(ValueError):
        config.set("obs.slo", "frobnication=3")
    with pytest.raises(ValueError):
        config.set("obs.slo", "timer=serving.request_ms")  # no objective
    assert obs.slo_status() is None  # bad specs never armed the tracker
    config.set("obs.slo", "availability=99.9,latency_p99_ms=50")
    telemetry.counter("serving.requests").inc(100)
    telemetry.counter("serving.shed_requests").inc(2)
    telemetry.timer("serving.request_ms").observe(75.0)
    status = obs.slo_status()
    assert status["requests"] == 100 and status["errors"] == 2
    assert status["latency"]["breach"]  # 75ms p99_1m over a 50ms target
    fams = check_obs.parse_prometheus(obs.render_prometheus())
    assert "mxnet_tpu_slo_burn_rate" in fams
    assert fams["mxnet_tpu_slo_latency_breach"]["samples"][
        ("mxnet_tpu_slo_latency_breach",
         'timer="serving.request_ms"')] == 1.0
    config.set("obs.slo", "")
    assert obs.slo_status() is None


# ------------------------------------------------------- smoke wrapper
def test_check_obs_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "check_obs.py")],
        capture_output=True, text=True, timeout=180,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["ok"], report
    assert report["healthz"]["healthy_code"] == 200
    assert report["healthz"]["breaker_code"] == 503
    assert report["access"]["outcomes"]["ok"] \
        == report["access"]["trace_joined"] - 2
    assert report["overhead"]["overhead_pct"] <= 2.0
    assert report["elapsed_s"] > 0, report   # reported, no budget (D9)
