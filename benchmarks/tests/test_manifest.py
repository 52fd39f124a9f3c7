"""``BENCHMARK.json`` against the limits its contract states, so that a
manifest a later PR extends is refused here and not by the driver."""
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_and_limits():
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and len(m["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in m["command"])
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    assert 1 <= len(m["end_to_end"]) <= 16 and 1 <= len(m["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmarks/") and os.path.exists(
            os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert "source" in body and "assumed" in body
        assert isinstance(body["knobs"]["kernels.enabled"], bool)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert w["config"] in {c["name"] for c in m["configs"]}
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
    assert {c["name"] for c in m["configs"]} == \
        {w["config"] for w in m["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(1, len(m["workloads"]) // 4)
    names = [e["name"] for g in ("configs", "workloads") for e in m[g]]
    metrics = [e["name"] for g in ("end_to_end", "per_layer") for e in m[g]]
    assert len(set(metrics)) == len(metrics)
    assert len(set(names)) == len(names)


def test_metrics_are_consistent_with_their_cells():
    m = _manifest()
    cells = [w["name"] for w in m["workloads"]]

    def where(entry):
        assert set(entry.get("workloads", cells)) <= set(cells)
        return set(entry.get("workloads", cells))

    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "end_to_end", e["name"] + ".py"))
    for p in m["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        assert p["better"] in ("lower", "higher") and p["source"] in SOURCES
        assert _line(p["layer"]) and p["moves"] in e2e
        # the metric it moves is reported wherever this one is
        assert where(p) <= where(e2e[p["moves"]]), p["name"]
        base = p["name"].split(".")[0]
        assert any(os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", n + ".py"))
            for n in (p["name"], base)), p["name"]
    for cell in cells:
        own = [e for e in m["end_to_end"] if cell in where(e)]
        assert len(own) >= 2, cell            # setup_s and one more
        assert any(cell in where(p) for p in m["per_layer"]), cell


def test_files_under_paths_have_admitted_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(os.path.join(ROOT, "benchmarks")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel
