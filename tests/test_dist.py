"""Multi-process distributed kvstore tests.

Forks real worker processes (via tools/launch.py, the reference's
``tools/launch.py`` local-launcher analog) that rendezvous through
``jax.distributed`` on the CPU backend and assert the value-exact dist_sync
contract from ``tests/nightly/dist_sync_kvstore.py:26-60``.  This is the
multi-node test strategy SURVEY.md §4 prescribes: N workers as local
processes on one host.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env():
    env = dict(os.environ)
    # Each worker is its own single-device CPU process: drop the test
    # process's 8-virtual-device flag.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.parametrize("nworker", [2, 3])
def test_dist_sync_kvstore_value_exact(nworker):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", str(nworker), sys.executable,
         os.path.join(ROOT, "tests", "dist_worker.py")],
        env=_worker_env(), capture_output=True, text=True, timeout=300)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, proc.stderr[-2000:]
    for rank in range(nworker):
        assert "WORKER_OK rank=%d/%d" % (rank, nworker) in proc.stdout


def test_dist_worker_death_aborts_job_cleanly():
    """A worker dying mid-job must fail the whole launch promptly — the
    launcher SIGTERMs survivors instead of leaving them hung in a barrier
    (reference: dmlc tracker failure propagation; SURVEY §5.3 failure
    detection)."""
    import time
    env = _worker_env()
    env["MXTPU_TEST_DIE_RANK"] = "1"
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "2", sys.executable,
         os.path.join(ROOT, "tests", "dist_worker.py")],
        env=env, capture_output=True, text=True, timeout=300)
    elapsed = time.time() - t0
    assert proc.returncode != 0, "worker death must fail the job"
    assert "WORKER_DYING rank=1" in proc.stdout
    assert "WORKER_OK rank=1/2" not in proc.stdout
    # promptly: well under the suite timeout — no hung-barrier wait
    assert elapsed < 240, "job abort took %.0fs (hung barrier?)" % elapsed


def test_dist_async_warns_sync_semantics():
    """dist_async is a documented alias: accepted, but runs synchronously
    with a one-time warning (docs/MIGRATION.md; no parameter server on a
    TPU pod, sync collectives are strictly faster)."""
    import warnings
    import mxnet_tpu as mx
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        kv = mx.kv.create("dist_async")
    assert any("SYNCHRONOUS" in str(w.message) for w in rec)
    assert kv.type == "dist_async"
