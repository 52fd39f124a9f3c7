"""(e) The program's own names in the trace (``harness/program_trace.py``):
exact numbers from made-up events, and the properties the per-layer readers
rest on from one small recorded trace — ``fixture_program.xplane.pb`` with
``fixture_program.scopes.json``, recorded on a TPU v5e by
``record_program_trace_fixture.py`` (its docstring says what runs)."""
import json
import os

import pytest

from benchmarks.harness import manifest, program_trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_program.xplane.pb")
SCOPES = os.path.join(HERE, "fixture_program.scopes.json")
MS = 1e6    # ns


# ------------------------------------------------------- made-up events
def test_self_time_leaves_a_container_what_its_children_do_not_cover():
    events = [("while", 0.0, 100.0), ("a", 10.0, 30.0), ("b", 30.0, 70.0),
              ("inner", 40.0, 50.0),          # nested in b
              ("after", 100.0, 120.0), ("cut", 190.0, 260.0)]
    got = {t: d for t, d, _ in pt.self_times(events, 0.0, 200.0)}
    assert got == {"while": 40.0, "a": 20.0, "b": 30.0, "inner": 10.0,
                   "after": 20.0, "cut": 10.0}
    assert sum(got.values()) == 130.0          # = the union of the events


def _made_up():
    table = {"module": "jit_step", "family": "t", "key": "k", "ops": {
        "while.2": ["(s32[])", "jit(step)/mx.layers/while"],
        "fusion.1": ["bf16[8]", "jit(step)/mx.layers/while/body/"
                                "mx.kv_gather/gather"],
        "mx_paged_attention.4": [
            "bf16[8]", "jit(step)/mx.layers/while/body/mx.paged_attention/"
                       "mx_paged_attention/pallas_call"],
        "copy.7": ["bf16[8]", "jit(step)/mx.layers/while/body/dynamic_slice"],
        "fusion.9": ["f32[]", "jit(step)/transpose(jvp(mx.forward))/mul;"
                              "jit(step)/mx.opt_update/mul"],
        "fusion.10": ["f32[]", "jit(step)/jvp(mx.forward)/mul"],
        "sort.3": ["f32[8]", "sort"]}}
    call = ('%mx_paged_attention.4 = bf16[8] custom-call(bf16[8] %x), '
            'custom_call_target="tpu_custom_call"')
    ops = [("%while.2 = (s32[]) while(...)", 0.0, 10 * MS),
           ("%fusion.1 = bf16[8] fusion(...)", 1 * MS, 4 * MS),
           (call, 4 * MS, 6 * MS),
           ("%copy.7 = bf16[8] copy(...)", 6 * MS, 9 * MS),
           ("%fusion.10 = f32[] fusion(...)", 10 * MS, 11 * MS),
           ("%fusion.9 = f32[] fusion(...)", 11 * MS, 13 * MS),
           ("%sort.3 = f32[8] sort(...)", 13 * MS, 14 * MS),
           ("%unknown.1 = f32[] add(...)", 16 * MS, 17 * MS)]
    # a second execution, which the end of the trace may have cut
    modules = [("jit_step(123)", 0.0, 18 * MS),
               ("jit_step(123)", 18 * MS, 19 * MS)]
    return ops, modules, [table]


def test_device_scopes_of_made_up_events():
    ops, modules, tables = _made_up()
    dev = pt.device_scopes(ops, modules, tables, 0.0, 20 * MS)
    ms = {k: round(v * 1e3, 9) for k, v in dev["scopes_s"].items()}
    # the while keeps its own 2 ms (0-1, 9-10) under the stack's scope,
    # which also takes the copy no inner scope names
    assert ms == {"mx.kv_gather": 3.0, "mx.paged_attention": 2.0,
                  "mx.layers": 5.0, "mx.forward": 3.0}
    assert {k: round(v * 1e3, 9) for k, v in dev["paths_s"].items()} == {
        "mx.layers/while": 2.0, "mx.layers/while/body": 8.0,
        "transpose(jvp(mx.forward))/mul": 2.0, "jvp(mx.forward)/mul": 1.0}
    assert dev["busy_s"] * 1e3 == pytest.approx(15.0)
    assert dev["union_s"] * 1e3 == pytest.approx(15.0)
    assert dev["unscoped_s"] * 1e3 == pytest.approx(2.0)   # sort, unknown
    assert sum(dev["scopes_s"].values()) + dev["unscoped_s"] == \
        pytest.approx(dev["busy_s"])
    # backward = through transpose(; a fusion of several names: the first
    assert dev["backward_of_forward_s"] * 1e3 == pytest.approx(2.0)
    assert dev["tpu_custom_call_s"] * 1e3 == pytest.approx(2.0)
    assert dev["named_s"] * 1e3 == pytest.approx(14.0)
    assert [k[:7] for k, _ in dev["top_unscoped"]] == ["%sort.3", "%unknow"]
    # the one execution of the program lies wholly in the window
    (prog,) = dev["programs"].values()
    assert list(dev["programs"]) == ["t/k"]
    assert prog["executions"] == 1 and prog["module"] == "jit_step(123)"
    assert prog["busy_s"] == pytest.approx(dev["busy_s"])
    assert prog["scopes_s"] == pytest.approx(dev["scopes_s"])
    # clipped to the window: half of the kernel, nothing after it; an
    # execution the window cuts is no execution to take a mean over
    half = pt.device_scopes(ops, modules, tables, 0.0, 5 * MS)
    assert half["scopes_s"]["mx.paged_attention"] * 1e3 == pytest.approx(1.0)
    assert half["busy_s"] * 1e3 == pytest.approx(5.0)
    assert half["programs"] == {}


def test_the_table_of_the_program_that_ran_is_picked_by_shape():
    ops, modules, tables = _made_up()
    other = json.loads(json.dumps(tables[0]))
    other["key"] = "wider"
    other["ops"] = {n: ["bf16[16]", "jit(step)/mx.mlp/dot_general"]
                    for n in other["ops"]}
    dev = pt.device_scopes(ops, modules, [other] + tables, 0.0, 20 * MS)
    assert "mx.mlp" not in dev["scopes_s"]
    assert dev["scopes_s"]["mx.kv_gather"] * 1e3 == pytest.approx(3.0)


def _spans():
    t = "engine-thread"
    return [("bench.window", 0.0, 100 * MS, {}, "main"),
            ("bench.idle", 0.0, 100 * MS, {}, "main"),
            ("engine.iteration", 10 * MS, 60 * MS, {"iteration": 1}, t),
            ("engine.admit", 10 * MS, 12 * MS, {"admitted": 1}, t),
            ("engine.decode", 12 * MS, 59 * MS,
             {"held_tokens": 30, "window_tokens": 120}, t),
            ("engine.decode.prepare", 12 * MS, 15 * MS, {}, t),
            ("engine.decode.device", 15 * MS, 50 * MS, {}, t),
            ("engine.decode.emit", 50 * MS, 59 * MS, {"finished": 1}, t),
            ("engine.iteration", 60 * MS, 120 * MS, {"iteration": 2}, t),
            ("engine.decode", 62 * MS, 118 * MS,
             {"held_tokens": 10, "window_tokens": 40}, t),
            ("engine.decode.device", 70 * MS, 110 * MS, {}, t)]


def test_gaps_go_to_the_innermost_program_span():
    ops = [("%a = f32[] add()", 16 * MS, 49 * MS),
           ("%a = f32[] add()", 71 * MS, 99.5 * MS)]
    gaps = pt.idle_gaps(ops, _spans(), 0.0, 100 * MS)
    assert [(round(g["ms"], 6), g["label"]) for g in gaps] == [
        (16.0, None), (22.0, "engine.decode.emit")]
    # the first gap: 10 ms of nothing, then admit 2, prepare 3, device 1;
    # most of it is covered by no span of the program, and says so
    first, second = gaps
    assert first["cover_ms"] == {"engine.decode.prepare": 3.0,
                                 "engine.admit": 2.0,
                                 "engine.decode.device": 1.0}
    assert first["uncovered_ms"] == pytest.approx(10.0)
    # emit 50-59; the second decode before its device call, 62-70; what
    # the iterations hold outside their decodes, 59-62; the ends of the
    # two device calls, 49-50 and 70-71
    assert second["cover_ms"] == {
        "engine.decode.emit": 9.0, "engine.decode": 8.0,
        "engine.iteration": 3.0, "engine.decode.device": 2.0}
    assert second["uncovered_ms"] == pytest.approx(0.0)
    # a gap under a millisecond is not listed
    assert all(g["ms"] >= 1.0 for g in gaps)


def test_span_statistics_and_what_the_readers_ask(monkeypatch):
    spans = _spans()
    stats = pt.span_stats(spans, 0.0, 100 * MS)
    assert stats["engine.decode"]["count"] == 1     # wholly inside only
    assert stats["engine.decode"]["args"] == {"held_tokens": 30.0,
                                              "window_tokens": 120.0}
    assert stats["engine.decode.device"]["mean_ms"] == 35.0
    assert "bench.window" not in stats
    assert pt.parents_less_children(spans, "engine.iteration", ".device",
                                    0.0, 100 * MS) == pytest.approx(15.0)
    assert pt.parents_less_children(spans, "spmd.step", ".device", 0.0,
                                    100 * MS) is None
    out = pt.reduce_events({"ops": [], "modules": [], "spans": spans}, None)
    assert out["device"] is None and out["gaps"] == []
    monkeypatch.setattr(pt, "load", lambda: out)
    assert pt.span_args_ratio({}, "engine.decode", "held_tokens",
                              "window_tokens") == pytest.approx(25.0)
    assert pt.host_ms({}, "engine.iteration", ".device") == \
        pytest.approx(15.0)
    assert pt.span_mean_ms({}, "spmd.dispatch") is None
    assert pt.scope_ms({}, "mx.kv_gather", "serving", "/decode-") is None
    assert pt.busy_share({}, "unscoped_s") is None
    # an untraced run hands the readers no trace: nothing is opened
    monkeypatch.setattr(pt, "load", lambda: pytest.fail("opened a trace"))
    assert pt.host_ms(None, "engine.iteration", ".device") is None


def test_a_program_without_names_or_spans_reads_nothing(tmp_path):
    """The parent of the PR that added the names: no table, no program
    span.  Every reader returns None and none raises."""
    assert pt.newest_xplane(str(tmp_path)) is None
    out = pt.reduce_events({"ops": [("%a = f32[] add()", 0.0, MS)],
                            "modules": [], "spans": [
                                ("bench.window", 0.0, 2 * MS, {}, "main")]},
                           None)
    assert out["device"] is None and out["spans"] == {}
    assert pt.reduce_events({"ops": [], "modules": [], "spans": []},
                            None) is None


# -------------------------------------------------- the recorded fixture
@pytest.fixture(scope="module")
def recorded():
    with open(SCOPES) as f:
        tables = json.load(f)
    return pt.load(FIXTURE, tables=tables, report=False), tables


def test_recorded_scopes_add_up_to_busy(recorded):
    out, _ = recorded
    dev = out["device"]
    assert dev["busy_s"] > 0
    scoped = sum(dev["scopes_s"].values())
    assert scoped + dev["unscoped_s"] == pytest.approx(dev["busy_s"],
                                                       rel=1e-6)
    assert dev["union_s"] == pytest.approx(dev["busy_s"], rel=1e-6)
    # nearly every operation is found in the program's table
    assert dev["named_s"] > 0.95 * dev["busy_s"]
    assert set(dev["scopes_s"]) == {
        "mx.layers", "mx.kv_gather", "mx.paged_attention", "mx.mlp",
        "mx.sample", "mx.forward", "mx.opt_update"}
    assert all(v > 0 for v in dev["scopes_s"].values())
    # something ran under no scope (the sums after the sort), not much
    assert 0 < dev["unscoped_s"] < 0.2 * dev["busy_s"]


def test_recorded_while_is_not_counted_twice(recorded):
    """The scanned stack is one ``while`` on the device that encloses its
    body's operations: by duration it alone would be most of the decode
    program, by self time it is the little its body does not cover."""
    out, _ = recorded
    lo, hi = out["window"]
    events = pt.read_events(FIXTURE)
    whiles = [(t, s, e) for t, s, e in events["ops"]
              if " while(" in t and lo <= s and e <= hi]
    assert len(whiles) >= 6         # 3 decode + 3 step (forward, backward)
    by_duration = sum(e - s for _, s, e in whiles)
    self_ns = sum(d for t, d, _ in pt.self_times(events["ops"], lo, hi)
                  if " while(" in t)
    assert by_duration > 0.5 * out["device"]["busy_s"] * 1e9
    assert self_ns < 0.05 * by_duration


def test_recorded_kernel_by_scope_equals_kernel_by_custom_call(recorded):
    out, _ = recorded
    dev = out["device"]
    assert dev["tpu_custom_call_s"] > 0
    assert dev["scopes_s"]["mx.paged_attention"] == pytest.approx(
        dev["tpu_custom_call_s"], rel=1e-9)


def test_recorded_backward_is_told_from_forward(recorded):
    out, _ = recorded
    dev = out["device"]
    assert 0 < dev["backward_of_forward_s"] < dev["scopes_s"]["mx.forward"]
    assert dev["backward_s"] == dev["backward_of_forward_s"]
    # the backward of tanh(c @ w) is two matmuls to the forward's one
    forward = dev["scopes_s"]["mx.forward"] - dev["backward_of_forward_s"]
    assert 1.2 < dev["backward_of_forward_s"] / forward < 3.0


def test_recorded_absent_scope_reads_zero_and_spans_give_counts(
        recorded, monkeypatch):
    out, _ = recorded
    monkeypatch.setattr(pt, "load", lambda: out)
    trace = {}      # what run.py hands a reader in a traced run
    # three executions each; the first decode lies before the window (the
    # profile places device events about a millisecond early against the
    # host's clock here) and the last step may be cut by the trace's end
    progs = out["device"]["programs"]
    assert {k: p["executions"] for k, p in progs.items()} == {
        "fixture/decode": 2, "fixture/step": 2}
    assert pt.scope_ms(trace, "mx.kv_write", "fixture", "decode") == 0.0
    gather = pt.scope_ms(trace, "mx.kv_gather", "fixture", "decode")
    assert gather == pytest.approx(
        progs["fixture/decode"]["scopes_s"]["mx.kv_gather"] * 1e3 / 2)
    assert out["device"]["scopes_s"]["mx.kv_gather"] * 1e3 == \
        pytest.approx(2 * gather)
    assert pt.scope_ms(trace, "mx.opt_update", "fixture", "step") > 0
    assert pt.scope_ms(trace, "mx.opt_update", "fixture", "decode") == 0.0
    assert pt.scope_ms(trace, "mx.opt_update", "serving") is None
    assert out["spans"]["engine.decode"]["count"] == 3
    assert out["spans"]["engine.decode"]["args"] == {
        "width": 12.0, "rows": 9.0, "held_tokens": 54.0,
        "window_tokens": 192.0}
    assert pt.span_args_ratio(trace, "engine.decode", "held_tokens",
                              "window_tokens") == pytest.approx(28.125)
    assert out["spans"]["engine.admit"]["args"]["admitted"] == 3.0
    assert out["spans"]["spmd.step"]["args"]["step"] == 6.0
    assert pt.span_mean_ms(trace, "spmd.dispatch") > 0
    host = pt.host_ms(trace, "engine.iteration", ".device")
    assert 3.0 < host < 6.0         # the 3 ms asleep in emit, and a little
    assert 0 < pt.busy_share(trace, "unscoped_s") < 20.0


def test_recorded_gaps_carry_their_cause(recorded):
    """3 ms of sleep inside ``engine.decode.emit`` and ``spmd.post``: the
    device's gaps over a millisecond, three of each."""
    out, _ = recorded
    labels = [g["label"] for g in out["gaps"] if g["ms"] >= 2.5]
    assert labels.count("engine.decode.emit") == 3
    assert labels.count("spmd.post") == 3
    for gap in out["gaps"]:
        if gap["label"] == "engine.decode.emit":
            assert gap["cover_ms"]["engine.decode.emit"] >= 2.4
            assert gap["uncovered_ms"] < 0.1


def test_every_new_metric_names_a_reader_that_exists():
    with open(os.path.join(pt.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, layer, moves in (
            ("kv_gather_device_ms.longgen", "kernels", "serve_tok_per_s"),
            ("paged_attention_device_ms.longgen", "kernels",
             "serve_tok_per_s"),
            ("kv_write_device_ms.longgen", "kernels", "serve_tok_per_s"),
            ("unscoped_device_share.longgen", "kernels", "serve_tok_per_s"),
            ("engine_host_ms.longgen", "scheduling", "serve_tok_per_s"),
            ("kv_window_fill.longgen", "scheduling", "serve_tok_per_s"),
            ("spmd_dispatch_ms.train", "entry points", "train_img_per_s"),
            ("opt_update_device_ms.train", "kernels", "train_img_per_s"),
            ("bwd_device_share.train", "kernels", "train_img_per_s"),
            ("unscoped_device_share.train", "kernels", "train_img_per_s")):
        assert entries[name]["layer"] == layer
        assert entries[name]["moves"] == moves
        reader = manifest.load_module("layer_metrics", name,
                                      fallback_to_base=True)
        assert reader.read({}, None) is None    # untraced: nothing read
