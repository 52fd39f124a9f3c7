"""Fast CPU chaos smoke for mx.resilience (seconds on an idle
box; ``elapsed_s`` is reported, not asserted).

Proves the fault-tolerance story end-to-end on the host backend, with one
parseable JSON line on stdout:

  1. baseline — SPMD train loop (10 steps) with the nanguard in ``skip``
                mode and a deterministic injected NaN at step 5: the bad
                step's update is dropped on-device, training continues;
  2. chaos    — the SAME run under injected I/O faults (retried with
                backoff), an injected checkpoint-write fault (retried,
                checkpoint still lands atomically), and a real SIGTERM
                mid-training (MXNET_TPU_ON_PREEMPT=save_and_exit): the
                in-flight step finishes, a checkpoint is saved, sinks
                flush, and the process "exits" cleanly (SystemExit 0);
  3. resume   — the newest checkpoint is then truncated to simulate
                external corruption; auto-resume detects it via the CRC
                manifest, falls back to the previous checkpoint, and
                replays the remaining steps — final params and the full
                loss curve match the unfaulted baseline BITWISE.

Usage: JAX_PLATFORMS=cpu python tools/check_resilience.py
Wired as a `not slow` test in tests/test_resilience.py.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STEPS = 10
NAN_STEP = 5
PREEMPT_AFTER = 7  # SIGTERM lands before this step; exit happens after it
CKPT_EVERY = 2


def make_batches(np):
    rng = np.random.RandomState(1)
    return [(rng.randn(8, 6).astype("f4"), rng.randn(8, 4).astype("f4"))
            for _ in range(STEPS)]


def make_trainer(mx):
    from mxnet_tpu.gluon import nn
    import mxnet_tpu.gluon.loss as gloss
    from mxnet_tpu.parallel.trainer import SPMDTrainer
    mx.random.seed(0)
    net = nn.Dense(4, in_units=6, prefix="chaos_")
    net.initialize()
    return SPMDTrainer(net, gloss.L2Loss(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})


def params_of(trainer, np):
    return {n: np.asarray(v) for n, v in sorted(trainer.params.items())}


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tdir = tempfile.mkdtemp(prefix="mxtpu_resilience_")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import config, resilience, telemetry
        result["backend"] = jax.default_backend()

        config.set("resilience.nanguard", "skip")
        config.set("resilience.fault_seed", 11)
        config.set("resilience.retry_base_s", 0.001)
        batches = make_batches(np)

        # 1. baseline: only the deterministic NaN at step 5 (the guard
        # skips its update); this is the curve chaos+resume must match
        config.set("resilience.faults", "nan:1@step=%d" % NAN_STEP)
        resilience.reset_nanguard()
        tr = make_trainer(mx)
        base_losses = [float(tr.step(x, y)) for x, y in batches]
        resilience.poll_streaks(block=True)
        base_params = params_of(tr, np)
        assert np.isnan(base_losses[NAN_STEP - 1]), base_losses
        assert telemetry.counter("spmd.nonfinite_steps").value >= 1
        result["baseline"] = {
            "losses": ["%.6g" % l for l in base_losses],
            "nan_step_skipped": True}

        # 2. chaos: same NaN + probabilistic io faults (retried) + one
        # injected ckpt-write fault (retried) + SIGTERM preemption
        config.set("resilience.faults",
                   "nan:1@step=%d,io:0.3,ckpt_write:1@step=1" % NAN_STEP)
        config.set("resilience.on_preempt", "save_and_exit")
        resilience.reset_nanguard()
        mgr = resilience.CheckpointManager(tdir, every_n_steps=CKPT_EVERY,
                                           keep=3)
        tr2 = make_trainer(mx)
        assert tr2.attach_checkpoint_manager(mgr) is None  # nothing yet
        it = mx.io.NDArrayIter(
            np.stack([x for x, _ in batches]).reshape(-1, 6),
            np.stack([y for _, y in batches]).reshape(-1, 4),
            batch_size=8, shuffle=False)
        chaos_losses = []
        exited = False
        try:
            for i, batch in enumerate(it):  # io faults hit __next__ here
                x = batch.data[0].asnumpy()
                y = batch.label[0].asnumpy()
                if i + 1 == PREEMPT_AFTER:
                    os.kill(os.getpid(), signal.SIGTERM)  # preempt notice
                chaos_losses.append(float(tr2.step(x, y)))
        except SystemExit as e:
            exited = True
            assert e.code == 0, "preemption exit code %r" % (e.code,)
        assert exited, "SIGTERM did not trigger a clean preemption exit"
        # the preempted step's loss is never returned (step() exits at its
        # end), so only PREEMPT_AFTER-1 losses were observed ...
        assert len(chaos_losses) == PREEMPT_AFTER - 1, len(chaos_losses)
        io_injected = telemetry.counter("resilience.injected.io").value
        assert io_injected > 0, "io fault never fired at p=0.3"
        assert telemetry.counter("resilience.injected.ckpt_write").value >= 1
        assert telemetry.counter("resilience.retries").value >= io_injected
        assert telemetry.counter("resilience.preemptions").value == 1
        steps_saved = [s for s, _ in mgr.checkpoints()]
        # ... but the step DID finish before the exit: the preemption
        # checkpoint carries its step number
        assert PREEMPT_AFTER in steps_saved, steps_saved
        result["chaos"] = {
            "steps_before_preempt": len(chaos_losses),
            "io_injected": int(io_injected),
            "retries": int(telemetry.counter("resilience.retries").value),
            "checkpoints": steps_saved}

        # 3. resume past a corrupt checkpoint: truncate the newest, then
        # auto-resume must fall back and replay to a bitwise-equal end
        newest = mgr.checkpoints()[-1][1]
        with open(newest, "r+b") as f:
            f.truncate(32)
        config.set("resilience.on_preempt", "")
        config.set("resilience.faults", "nan:1@step=%d" % NAN_STEP)
        resilience.reset_nanguard()
        mgr2 = resilience.CheckpointManager(tdir, every_n_steps=CKPT_EVERY,
                                            keep=3)
        tr3 = make_trainer(mx)
        resumed_at = tr3.attach_checkpoint_manager(mgr2)
        assert resumed_at == PREEMPT_AFTER - 1, resumed_at  # fell back
        assert telemetry.counter("resilience.ckpt_fallbacks").value == 1
        resume_losses = [float(tr3.step(x, y))
                         for x, y in batches[resumed_at:]]
        resilience.poll_streaks(block=True)
        full = chaos_losses[:resumed_at] + resume_losses
        assert np.array_equal(np.asarray(full), np.asarray(base_losses),
                              equal_nan=True), (full, base_losses)
        resume_params = params_of(tr3, np)
        assert set(resume_params) == set(base_params)
        assert all(np.array_equal(resume_params[n], base_params[n])
                   for n in base_params), "resumed params diverged"
        result["resume"] = {
            "resumed_at_step": int(resumed_at),
            "fallbacks": 1,
            "loss_curve_bitwise": True,
            "params_bitwise": True}

        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except (Exception, SystemExit) as exc:  # noqa: BLE001 — JSON IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config as _cfg
            from mxnet_tpu import resilience as _rs
            _cfg.set("resilience.faults", "")
            _cfg.set("resilience.nanguard", "")
            _cfg.set("resilience.on_preempt", "")
            _cfg.set("resilience.retry_base_s", 0.05)
            _rs.reset_nanguard()
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
