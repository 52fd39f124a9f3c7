"""(f) The gap between two serving programs in legs on one clock
(``harness/gap_trace.py``): exact numbers from made-up events with a known
clock offset, and the properties the seven ``gap_*`` / ``dispatch_ms``
readers rest on from one small trace recorded on a TPU v5e —
``fixture_gap.xplane.pb`` with ``fixture_gap.scopes.json``, by
``record_gap_trace_fixture.py`` (its docstring says what runs)."""
import json
import os

import pytest

from benchmarks.harness import gap_trace as gt, manifest, program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_gap.xplane.pb")
SCOPES = os.path.join(HERE, "fixture_gap.scopes.json")
MS = 1e6    # ns
T = "engine/1"


def _made_up(steps, delta=0.7, call=0.2, kinds=None, window=None,
             extra_ops=(), waits=()):
    """A serial engine on a true (host) clock; the device's events are
    written ``delta`` ms early.  ``steps``: per call ``(launch, execution,
    read-back, host time before the next call)`` in ms."""
    spans, modules, ops = [], [], []
    t = 5.0
    for j, (launch, run, back, host) in enumerate(steps):
        kind = (kinds or {}).get(j, "decode")
        begin, done = t + launch, t + launch + run
        fetch_end = done + back
        spans += [("engine.%s.dispatch" % kind, t * MS, (t + call) * MS,
                   {"host_args": 7}, T),
                  ("engine.%s.fetch" % kind, (t + call) * MS,
                   fetch_end * MS, {}, T)]
        modules.append(("jit_%s(1)" % kind, (begin - delta) * MS,
                        (done - delta) * MS))
        ops.append(("%%fusion.%d = f32[] fusion()" % j, (begin - delta) * MS,
                    (done - delta) * MS))
        t = fetch_end + host
    modules.append(("jit_decode(1)", (t + 1 - delta) * MS,
                    (t + 2 - delta) * MS))     # the one the trace cuts
    lo, hi = window or (0.0, t + 3)
    spans.append(("bench.window", lo * MS, hi * MS, {}, "bench"))
    spans += [("engine.wait", a * MS, b * MS, {}, T) for a, b in waits]
    ops += [(n, (a - delta) * MS, (b - delta) * MS) for n, a, b in extra_ops]
    return {"spans": spans, "modules": modules, "ops": ops}


STEPS = [(0.9, 10.0, 1.3, 0.6), (0.8, 10.0, 1.2, 0.5), (1.1, 10.0, 1.0, 0.7),
         (0.8, 10.0, 1.6, 0.6), (1.0, 10.0, 1.1, 0.4), (0.9, 10.0, 1.0, 0.6)]


def test_a_known_offset_floor_and_excesses_come_back_exactly():
    out = gt.legs(_made_up(STEPS, delta=0.7))
    assert out["fault"] is None and out["executions"] == 6
    assert out["gaps_counted"] == 5
    mean = out["mean_ms"]
    # least launch 0.8 + least read-back 1.0, whatever delta is
    assert mean["floor"] == pytest.approx(1.8)
    assert mean["host"] == pytest.approx((0.6 + 0.5 + 0.7 + 0.6 + 0.4) / 5)
    # launch of the NEXT call over 0.8, read-back of THIS one over 1.0
    assert mean["launch_var"] == pytest.approx((0 + 0.3 + 0 + 0.2 + 0.1) / 5)
    assert mean["readback_var"] == pytest.approx(
        (0.3 + 0.2 + 0 + 0.6 + 0.1) / 5)
    assert mean["gap"] == pytest.approx(
        mean["host"] + mean["floor"] + mean["launch_var"]
        + mean["readback_var"])
    clock = out["clock"]
    # delta lies in [L, U] = [0.7 - 0.8, 0.7 + 1.0]
    assert clock["blocks"][0][1:] == pytest.approx([-0.1, 1.7])
    assert clock["slack_ms"] == pytest.approx(1.8)
    assert out["call_ms"] == pytest.approx(0.2)
    assert out["outlier_share"] == 0.0 and out["outliers"] == []


@pytest.mark.parametrize("delta", [-1.5, 0.0, 0.4, 2.0])
def test_no_leg_depends_on_where_the_offset_lies(delta):
    ref = gt.legs(_made_up(STEPS, delta=0.7))["mean_ms"]
    got = gt.legs(_made_up(STEPS, delta=delta))["mean_ms"]
    assert got == pytest.approx(ref)


def test_the_four_legs_add_up_gap_by_gap_across_blocks():
    steps = [(0.8 + 0.01 * (j % 7), 9.0 + j % 3, 1.0 + 0.02 * (j % 5),
              0.3 + 0.05 * (j % 4)) for j in range(23)]
    out = gt.legs(_made_up(steps), block=5)
    assert out["fault"] is None and len(out["clock"]["blocks"]) == 5
    mean = out["mean_ms"]
    assert mean["gap"] == pytest.approx(
        mean["host"] + mean["floor"] + mean["launch_var"]
        + mean["readback_var"], abs=1e-9)
    for legs in out["by_kind"].values():
        assert legs["gap"] == pytest.approx(
            legs["host"] + legs["floor"] + legs["launch_var"]
            + legs["readback_var"], abs=1e-9)
    assert min(mean[k] for k in ("launch_var", "readback_var")) >= 0
    # the gap is the device's own: execution start less the last one's end
    assert mean["gap"] == pytest.approx(sum(
        steps[j][2] + steps[j][3] + steps[j + 1][0]
        for j in range(22)) / 22)


def test_a_drift_shows_as_a_slope_of_the_mid_point():
    steps = [(0.8, 10.0, 1.0, 0.5)] * 40
    events = _made_up(steps, delta=0.0)
    # the device's clock loses 100 us a second against the host's
    for key in ("modules", "ops"):
        events[key] = [(n, s - 1e-4 * s, e - 1e-4 * e)
                       for n, s, e in events[key]]
    clock = gt.legs(events, block=10)["clock"]
    assert clock["drift_us_per_s"] == pytest.approx(100.0, rel=0.02)


def test_the_cut_last_execution_and_calls_outside_the_window_are_left():
    out = gt.legs(_made_up(STEPS, window=(16.0, 69.0)))
    # calls 0 (begins before the window) and 5 (returns after it) are out
    assert out["fault"] is None and out["executions"] == 4
    assert out["calls_in_trace"] == 6 and out["executions_in_trace"] == 6


def test_the_slice_behind_a_prefill_counts_as_busy():
    steps = list(STEPS)
    # after call 2 (a prefill) a small program runs 0.05 ms, 0.2 ms in
    t2_end = 5.0 + sum(sum(s) for s in steps[:2]) + 1.1 + 10.0
    extra = [("%slice.1 = s32[1] slice()", t2_end + 0.2, t2_end + 0.25)]
    plain = gt.legs(_made_up(steps, kinds={2: "prefill"}))
    out = gt.legs(_made_up(steps, kinds={2: "prefill"}, extra_ops=extra))
    assert out["by_kind"]["prefill>decode"]["gap"] == pytest.approx(
        plain["by_kind"]["prefill>decode"]["gap"] - 0.05)
    assert out["mean_ms"]["gap"] == pytest.approx(
        sum(out["mean_ms"][k] for k in gt.LEGS[1:]))
    assert sorted(out["by_kind"]) == ["decode>decode", "decode>prefill",
                                      "prefill>decode"]


def test_a_gap_across_a_wait_is_left_out():
    steps = list(STEPS)
    steps[2] = (1.1, 10.0, 1.0, 50.0)        # the engine waited for work
    t = 5.0 + sum(sum(s) for s in steps[:2]) + 1.1 + 10.0 + 1.0
    out = gt.legs(_made_up(steps, waits=[(t + 0.1, t + 49.9)]))
    assert out["gaps_across_wait"] == 1 and out["gaps_counted"] == 4
    assert out["mean_ms"]["host"] == pytest.approx((0.6 + 0.5 + 0.6 + 0.4) / 4)
    assert out["outliers"] == []


def test_a_gap_over_ten_medians_is_an_outlier_with_its_cover():
    steps = [(0.8, 10.0, 1.0, 0.5)] * 12
    steps[5] = (0.8, 10.0, 100.0, 0.5)       # the host heard 99 ms late
    events = _made_up(steps)
    a = 5.0 + sum(sum(s) for s in steps[:5])
    runtime = [("CompleteCallbacks", (a + 110.0) * MS, (a + 110.5) * MS,
                "pjrt-tpu-tasks/77"),
               ("elsewhere", 1.0 * MS, 2.0 * MS, "123")]
    out = gt.legs(events, runtime=runtime)
    assert out["gaps_counted"] == 10 and len(out["outliers"]) == 1
    rec = out["outliers"][0]
    assert rec["ms"]["gap"] == pytest.approx(100.0 + 0.5 + 0.8)
    assert rec["ms"]["readback_var"] == pytest.approx(99.0)
    assert max(rec["spans_ms"], key=rec["spans_ms"].get) == \
        "engine.decode.fetch"
    # the longest stretch in which no thread began or ended anything:
    # from the fetch's begin to the runtime's first sign of life
    assert rec["quiet_ms"] == pytest.approx(110.0 - 0.2)
    assert rec["quiet_after_ms"] == pytest.approx(0.2)
    assert [r[:2] for r in rec["across"]] == [
        ["engine", "engine.decode.fetch"]]
    assert rec["resumed"] == [("pjrt-tpu-tasks", "CompleteCallbacks")]
    assert out["outlier_share"] == pytest.approx(
        101.3 / (out["window_s"] * 1e3) * 100.0)
    # the outlier is in no mean
    assert out["mean_ms"]["readback_var"] == pytest.approx(0.0)
    assert out["mean_ms"]["gap"] == pytest.approx(2.3)


def test_unequal_counts_are_a_fault_not_a_number():
    events = _made_up(STEPS)
    del events["modules"][3]                 # a call with no execution
    out = gt.legs(events)
    assert out["fault"].startswith("matching: call 3")
    assert "mean_ms" not in out
    # registered programs only: one of them under no call is a fault too
    events = _made_up(STEPS)
    events["modules"].insert(3, ("jit_decode(1)", 38.1 * MS, 38.3 * MS))
    out = gt.legs(events, serving={"jit_decode"})
    assert out["fault"].startswith("matching:")
    # ... and another program's executions are not looked at
    events["modules"][3] = ("jit_slice(9)", 38.1 * MS, 38.3 * MS)
    assert gt.legs(events, serving={"jit_decode"})["fault"] is None


def test_bounds_that_cross_are_a_fault_that_says_which_block():
    events = _made_up(STEPS)
    # execution 4 reads as ending after its fetch returned
    n, s, e = events["modules"][4]
    events["modules"][4] = (n, s, e + 3.0 * MS)
    out = gt.legs(events)
    assert out["fault"].startswith("clock: executions 0-5 of 6")
    assert "mean_ms" not in out


def test_a_program_without_the_dispatch_spans_reads_nothing():
    events = _made_up(STEPS)
    events["spans"] = [s for s in events["spans"]
                       if not s[0].endswith(".dispatch")]
    assert gt.legs(events) is None
    assert gt.legs({"spans": [], "modules": [], "ops": []}) is None
    assert gt.leg_ms(None, "gap") is None      # an untraced run
    assert gt.field(None, "call_ms") is None


def test_runtime_under_a_span_by_thread_kind_and_name():
    spans = [("engine.decode.device", 10 * MS, 20 * MS, {}, T),
             ("engine.decode.device", 30 * MS, 40 * MS, {}, T),
             ("engine.decode.device", 90 * MS, 99 * MS, {}, T)]   # outside
    runtime = [("TransferToDevice", 10.1 * MS, 10.2 * MS, "pjrt-tpu-tasks/5"),
               ("TransferToDevice", 10.3 * MS, 10.4 * MS, "pjrt-tpu-tasks/6"),
               ("TransferToDevice", 30.1 * MS, 30.3 * MS, "pjrt-tpu-tasks/5"),
               ("ReadSyncFlag", 38.0 * MS, 39.0 * MS, "4242"),
               ("between", 25.0 * MS, 26.0 * MS, "main/1")]
    got = gt.runtime_under(runtime, spans, "engine.decode.device",
                           0.0, 50 * MS)
    assert [(r["thread"], r["event"]) for r in got] == [
        ("unnamed", "ReadSyncFlag"), ("pjrt-tpu-tasks", "TransferToDevice")]
    assert got[1]["per_span"] == 1.5
    assert got[1]["mean_ms"] == pytest.approx(0.4 / 3)
    assert got[0]["after_begin_ms"] == pytest.approx(8.0)
    assert got[0]["before_end_ms"] == pytest.approx(1.0)


def test_the_runtimes_marks_narrow_the_offset():
    events = _made_up(STEPS, delta=0.7, call=0.3)
    runtime = []
    t = 5.0
    for launch, run, back, host in STEPS:
        # handed over 0.25 ms into the call; completion seen 0.4 ms
        # after the chip ended
        runtime.append((gt.HAND_OVER, (t + 0.25) * MS, (t + 0.29) * MS,
                        "main/1"))
        runtime.append((gt.COMPLETION, (t + launch + run + 0.2) * MS,
                        (t + launch + run + 0.4) * MS, "4242"))
        t += launch + run + back + host
    out = gt.legs(events, runtime=runtime)
    got = out["clock"]["narrowed"]
    # launch at least 0.8: 0.25 of it before the hand-over; read-back at
    # least 1.0: at most 0.4 of it before the completion was seen
    assert got["slack_ms"] == pytest.approx((0.8 - 0.25) + 0.4)
    assert got["launch_floor_ms"] == pytest.approx([0.25, 0.25 + 0.95])
    assert got["readback_floor_ms"] == pytest.approx([1.0 - 0.4, 1.55])
    # on the host's clock alone: call begin to hand-over, completion seen
    # to the fetch's return
    assert got["hand_over_ms"] == pytest.approx(0.25)
    assert got["return_ms"] == pytest.approx(1.2 - 0.4)
    assert got["offset_ms"] == pytest.approx((0.15 + 1.1) / 2)
    # a call that shows no mark: nothing is narrowed
    assert gt.legs(events, runtime=runtime[2:])["clock"]["narrowed"] is None


# ------------------------------------------------ the readers' own files
NEW = ("gap_ms", "gap_host_ms", "gap_floor_ms", "gap_launch_var_ms",
       "gap_readback_var_ms", "dispatch_ms", "gap_outlier_share")


def test_the_manifest_lists_the_seven_for_the_serving_cells_alone():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    serving = [w["name"] for w in m["workloads"]
               if any(w["name"] in e["workloads"] for e in m["end_to_end"]
                      if e["name"] == "serve_tok_per_s")]
    entries = {e["name"]: e for e in m["per_layer"]}
    assert [e["name"] for e in m["per_layer"]][-7:] == [
        n + ".serve" for n in NEW]
    for name in NEW:
        entry = entries[name + ".serve"]
        assert entry["workloads"] == serving
        assert entry["moves"] == "serve_tok_per_s"
        assert entry["better"] == "lower" and entry["layer"] == "scheduling"
        reader = manifest.load_module("layer_metrics", name + ".serve",
                                      fallback_to_base=True)
        assert reader.read({}, None) is None


# --------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.skip("no fixture_gap.xplane.pb recorded yet")
    with open(SCOPES) as f:
        tables = json.load(f)
    events = pt.read_events(FIXTURE)
    runtime = gt.read_runtime(FIXTURE)
    return events, tables, runtime


def test_recorded_trace_is_small_and_holds_both_sides(recorded):
    events, tables, runtime = recorded
    assert os.path.getsize(FIXTURE) < 200 * 1024
    names = {s[0] for s in events["spans"]}
    assert {"engine.decode.dispatch", "engine.decode.fetch",
            "engine.prefill.dispatch", "engine.prefill.fetch",
            "engine.decode.device", "bench.window"} <= names
    assert gt.serving_modules(tables)
    # the runtime's own threads are there, and none of their events is a
    # span of the program
    assert runtime and not any(pt.SPAN_NAME.match(r[0]) for r in runtime)
    assert {gt.HAND_OVER, gt.COMPLETION} <= {r[0] for r in runtime}


def test_recorded_trace_gives_legs_that_add_up(recorded):
    events, tables, runtime = recorded
    out = gt.legs(events, gt.serving_modules(tables), runtime=runtime)
    assert out["fault"] is None, out
    calls = [c for c in gt.engine_calls(events["spans"])]
    assert out["calls_in_trace"] == len(calls)
    assert 2 <= out["executions"] <= len(calls)
    mean = out["mean_ms"]
    assert mean["gap"] == pytest.approx(
        mean["host"] + mean["floor"] + mean["launch_var"]
        + mean["readback_var"], abs=1e-6)
    # the recorder sleeps 3 ms between a fetch and the next call
    assert mean["host"] > 3.0
    assert mean["floor"] > 0 and mean["launch_var"] >= 0 \
        and mean["readback_var"] >= 0
    assert out["clock"]["slack_ms"] == pytest.approx(mean["floor"], rel=0.5)
    # the same with every module allowed (a bare capture, no table): the
    # slice behind the prefill's first token is the shorter of two
    bare = gt.legs(events, None, runtime=runtime)
    assert bare["fault"] is None
    assert bare["mean_ms"] == pytest.approx(mean)
    # the runtime's marks lie inside the spans' bounds
    got = out["clock"]["narrowed"]
    assert got is not None and "fault" not in got
    assert 0 <= got["slack_ms"] <= out["clock"]["slack_ms"]
    under = gt.runtime_under(
        runtime, events["spans"], "engine.decode.device",
        *max(((s, e) for n, s, e, _, _ in events["spans"]
              if n == "bench.window"), key=lambda w: w[1] - w[0]))
    assert {gt.HAND_OVER, gt.COMPLETION} <= {r["event"] for r in under}
