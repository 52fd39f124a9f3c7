"""(c) A later PR adds a cell by adding files and manifest entries only: in a
temporary copy, a new configuration file, a new traffic file and a new
per-layer metric file make a new cell runnable under ``--rehearse`` with no
edit to any existing file of the benchmark."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _digests(top):
    out = {}
    for base, _, files in os.walk(top):
        if "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(copy / "benchmarks")
    bench = copy / "benchmarks"

    config = json.loads((bench / "configs" / "opt_1p3b.json").read_text())
    config["name"] = "tiny_lm"
    (bench / "configs" / "tiny_lm.json").write_text(json.dumps(config))
    traffic = json.loads((bench / "traffic" / "chat_short.json").read_text())
    traffic["rehearse"]["arrivals"]["rate"] = 6.0
    (bench / "traffic" / "chat_fast.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "requests_in_window.py").write_text(
        "def read(obs, trace):\n    return obs['attempted']\n")

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "tiny_lm", "source": "a test", "reduced": [],
        "file": "benchmarks/configs/tiny_lm.json", "why": "a test"})
    manifest["workloads"].append({
        "name": "tiny-chat-fast", "config": "tiny_lm",
        "traffic": "chat_fast", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "requests_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "scheduling",
        "moves": "setup_s", "workloads": ["tiny-chat-fast"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload",
         "tiny-chat-fast", "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=copy, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsed"] == "tiny-chat-fast" and last["correct"]
    # the new metric, and only the metrics that list this cell
    assert last["metric_names"] == ["requests_in_window"]
    after = _digests(copy / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/tiny_lm.json", "layer_metrics/requests_in_window.py",
        "traffic/chat_fast.json"]


def test_no_branch_on_a_name_in_the_harness():
    """No cell, configuration, mix or metric is named in ``run.py`` or
    ``harness/``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = {e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in manifest[group]}
    names |= {w["traffic"] for w in manifest["workloads"]}
    names |= {n.split(".")[0] for n in names}
    names -= {"setup_s"}    # the contract's own metric, named in a docstring
    texts = [os.path.join(ROOT, "benchmarks", "run.py")] + [
        os.path.join(ROOT, "benchmarks", "harness", f)
        for f in os.listdir(os.path.join(ROOT, "benchmarks", "harness"))
        if f.endswith(".py")]
    for path in texts:
        with open(path) as f:
            text = f.read()
        hit = [n for n in names if n in text]
        assert not hit, "%s names %s" % (path, hit)


def test_run_fails_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", json.load(open(os.path.join(
             ROOT, "BENCHMARK.json")))["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
