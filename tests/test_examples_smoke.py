"""Example-script smoke tests: every `examples/*.py` entry point runs to
completion as a real CLI process (reference CI runs example scripts the
same way, ci/docker/runtime_functions.sh).  Tiny configs, on the cpu
backend via each script's --cpu flag."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=600, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # never run with cwd=repo-root: scripts export checkpoints into cwd
    import tempfile
    r = subprocess.run([sys.executable, os.path.join(ROOT, script),
                        "--cpu", *args],
                       capture_output=True, text=True, timeout=timeout,
                       cwd=cwd or tempfile.mkdtemp(), env=env)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
    return r.stdout


def test_gluon_mnist_example():
    out = _run("examples/gluon_mnist.py", "--epochs", "1",
               "--samples", "256", "--batch-size", "64")
    assert "accuracy" in out.lower() or "epoch" in out.lower()


def test_rnn_lm_example():
    out = _run("examples/rnn_lm.py", "--epochs", "1")
    assert "ppl" in out.lower() or "perplexity" in out.lower() \
        or "epoch" in out.lower()


def test_rnn_bucketing_example():
    out = _run("examples/rnn_bucketing.py", "--epochs", "1",
               "--sentences", "128", "--batch-size", "16",
               "--hidden", "32", "--embed", "16", "--layers", "1")
    assert "buckets trained" in out.lower()


def test_bert_pretrain_example():
    out = _run("examples/bert_pretrain.py", "--layers", "1", "--steps", "2")
    assert "sequences/s" in out


@pytest.mark.slow
def test_ssd_train_example():
    out = _run("examples/ssd_train.py", "--steps", "1", "--size", "128",
               timeout=900)
    assert "img/s" in out and "NMS" in out


def test_benchmark_score_example():
    out = _run("examples/benchmark_score.py", "--networks", "resnet18_v1",
               "--batch-sizes", "2", "--iters", "2",
               "--image-shape", "3,32,32", timeout=900)
    assert "img/s" in out and "resnet18_v1" in out


def test_bandwidth_tool():
    out = _run("tools/bandwidth.py", "--network", "squeezenet1.0",
               "--num-batches", "2")
    assert "result check OK" in out


def test_bandwidth_tool_2bit():
    out = _run("tools/bandwidth.py", "--network", "squeezenet1.0",
               "--num-batches", "1", "--gc-type", "2bit")
    assert "result check OK" in out
