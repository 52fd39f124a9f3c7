#!/usr/bin/env python
"""Fast CPU chaos smoke for mx.elastic — distributed edition (seconds on an
idle box; ``elapsed_s`` is reported, not asserted).

Proves the multi-host elasticity story end-to-end with real processes
(2 ranks over the jax.distributed rendezvous, CPU backend), one parseable
JSON line on stdout:

  1. baseline   — 2-process dist_sync training, 10 steps, no faults;
  2. chaos      — the SAME job under ``tools/launch.py --elastic``: rank 1
                  draws an injected ``peer_preempt`` at step 5, the cluster
                  agreement preempts BOTH ranks at the same step boundary,
                  they write one coordinated checkpoint (rank-0-writes /
                  all-ranks-barrier, world-stamped manifest) and exit 0;
                  the launcher re-forms the world (generation 1), which
                  resumes from the snapshot and finishes — final loss
                  curve and params must match the baseline BITWISE;
  3. compressed — the same job with 2-bit DCN gradient compression plus an
                  injected ``dcn_push`` wire fault (retried, value-exact):
                  asserts >= 8x wire reduction and convergence inside the
                  error budget, and records step time with/without
                  compression (the MULTICHIP bench evidence).

Usage: python tools/check_dist_chaos.py
Wired as a `not slow` test in tests/test_dist_chaos.py.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import launch  # noqa: E402  (tools/launch.py — the elastic launcher)

STEPS = 10
PREEMPT_STEP = 5
NWORKER = 2
WORKER = os.path.join(ROOT, "tools", "dist_chaos_worker.py")


def _worker_env(out_path, **extra):
    """Env for one launch: single-device CPU workers, isolated from the
    test process's own JAX/plugin configuration."""
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "MXTPU_CHAOS_OUT": out_path,
        "MXTPU_CHAOS_STEPS": str(STEPS),
    }
    env.update(extra)
    return env


def _read(out_path):
    with open(out_path) as f:
        return json.load(f)


def main():
    t_main = time.perf_counter()
    result = {"ok": False}
    tdir = tempfile.mkdtemp(prefix="mxtpu_dist_chaos_")
    try:
        # ---- leg 1: uninterrupted baseline --------------------------------
        o1 = os.path.join(tdir, "baseline.json")
        rc = launch.launch_local(
            NWORKER, [sys.executable, WORKER], extra_env=_worker_env(o1))
        assert rc == 0, "baseline launch rc=%d" % rc
        base = _read(o1)
        assert base["generation"] == 0 and base["resumed_step"] is None
        assert len(base["losses"]) == STEPS
        assert base["losses"][-1] < 0.5 * base["losses"][0], \
            "baseline failed to converge: %r" % (base["losses"],)
        result["baseline_loss"] = base["losses"][-1]

        # ---- leg 2: peer_preempt -> coordinated ckpt -> elastic restart ---
        o2 = os.path.join(tdir, "chaos.json")
        edir = os.path.join(tdir, "elastic")
        ckpt = os.path.join(edir, "ckpt")
        rc = launch.launch_elastic(
            NWORKER, [sys.executable, WORKER], max_restarts=1,
            elastic_dir=edir,
            extra_env=_worker_env(
                o2, MXTPU_CHAOS_CKPT=ckpt,
                MXTPU_CHAOS_PREEMPT_RANK="1",
                MXTPU_CHAOS_PREEMPT_STEP=str(PREEMPT_STEP),
                MXNET_TPU_ON_PREEMPT="save_and_exit"))
        assert rc == 0, "elastic launch rc=%d" % rc
        chaos = _read(o2)
        assert chaos["generation"] == 1, \
            "no elastic restart happened: %r" % (chaos,)
        assert chaos["resumed_step"] == PREEMPT_STEP - 1, chaos
        # the coordinated snapshot must carry the world stamp
        mans = sorted(f for f in os.listdir(ckpt)
                      if f.endswith(".manifest.json"))
        assert mans, "no checkpoint manifests in %s" % ckpt
        with open(os.path.join(ckpt, mans[-1])) as f:
            man = json.load(f)
        assert man["world"]["process_count"] == NWORKER, man
        # bitwise survival: restarted run == uninterrupted run
        assert chaos["losses"] == base["losses"], \
            "loss curve diverged after elastic restart"
        assert chaos["w"] == base["w"], \
            "params diverged after elastic restart"
        result["resumed_step"] = chaos["resumed_step"]

        # ---- leg 3: compressed DCN sync + injected wire fault -------------
        o3 = os.path.join(tdir, "compressed.json")
        rc = launch.launch_local(
            NWORKER, [sys.executable, WORKER],
            extra_env=_worker_env(
                o3, MXNET_TPU_GRAD_COMPRESS="2bit",
                MXTPU_GRAD_COMPRESSION_THRESHOLD="0.5",
                MXNET_TPU_FAULTS="dcn_push:1@step=2"))
        assert rc == 0, "compressed launch rc=%d" % rc
        comp = _read(o3)
        assert comp["compressed_bytes"] > 0, comp
        assert comp["compression_ratio"] >= 8.0, \
            "wire reduction %.2fx < 8x" % comp["compression_ratio"]
        assert comp["injected_dcn_push"] >= 1, \
            "dcn_push fault never fired: %r" % (comp,)
        # error budget: 2-bit + error feedback lands near the uncompressed
        # optimum — within 0.35 * initial loss after 10 steps (measured
        # headroom ~2x: simulation gives 1.67 vs budget 1.82)
        budget = base["losses"][-1] + 0.35 * base["losses"][0]
        assert comp["losses"][-1] < budget, \
            "compressed loss %.4f outside error budget %.4f" % \
            (comp["losses"][-1], budget)
        result.update({
            "compressed_loss": comp["losses"][-1],
            "error_budget": budget,
            "compression_ratio": comp["compression_ratio"],
            "dcn_push_retried": comp["injected_dcn_push"],
            # MULTICHIP bench evidence: per-step wall time for the same
            # job with and without DCN gradient compression
            "step_s_uncompressed": base["elapsed_s"] / STEPS,
            "step_s_compressed": comp["elapsed_s"] / STEPS,
        })

        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except BaseException as exc:  # noqa: BLE001 — smoke must print JSON
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    print(json.dumps(result))
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
