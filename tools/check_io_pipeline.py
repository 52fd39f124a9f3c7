"""Fast CPU smoke for the device-resident input pipeline (seconds on an idle
box; ``elapsed_s`` is reported, not asserted).

Proves the mx.io device-side prefetch end-to-end on the host backend, with
one parseable JSON line on stdout:

  1. overlap — an SPMDTrainer epoch fed by ``io.DevicePrefetcher``
               (bucketed padding + sharded staging on the background
               thread) performs ZERO synchronous caller-thread H2D
               transfers (io.h2d_sync flat) and its losses match the
               host-side-prefetch baseline (``io.device_prefetch`` off)
               bitwise — staging changes placement, never numerics;
  2. drain   — early consumer exit (2 of 7 batches) then ``reset()``
               joins the staging worker inside the hard deadline
               (io.prefetch_thread_leaked stays 0) and the next epoch
               yields the full batch count;
  3. decode  — ``io.decode_workers`` fans ImageIter decode over a thread
               pool with bitwise-identical batches, and deterministic
               injected 'io' faults (MXNET_TPU_FAULTS) are retried on the
               workers without changing the output.

Usage: JAX_PLATFORMS=cpu python tools/check_io_pipeline.py
Wired as a `not slow` test in tests/test_io_pipeline.py.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BATCH = 8
ROWS = 28          # 3 full batches + a 4-row ragged tail
FEATURES = 6
SEED = 11


def make_raw_iter(mio, np):
    """A host iterator emitting raw numpy with a RAGGED final batch — the
    shape-churn case bucketed padding exists for."""
    rng = np.random.RandomState(0)
    X = rng.randn(ROWS, FEATURES).astype(np.float32)
    Y = rng.randn(ROWS).astype(np.float32)

    class RawIter(mio.DataIter):
        def __init__(self):
            super().__init__(BATCH)
            self.pos = 0

        def reset(self):
            self.pos = 0

        def next(self):
            if self.pos >= ROWS:
                raise StopIteration
            d = X[self.pos:self.pos + BATCH]
            l = Y[self.pos:self.pos + BATCH]
            self.pos += BATCH
            return mio.DataBatch([d], [l], pad=0)

    return RawIter()


def train_epochs(mx, mio, np, device_prefetch, epochs=2):
    """Train a tiny seeded MLP over the ragged dataset; returns (losses,
    sync_h2d_per_step)."""
    from mxnet_tpu import config, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import SPMDTrainer

    config.set("io.device_prefetch", device_prefetch)
    mx.random.seed(SEED)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()

    def l2(out, label):
        return ((out - label.reshape((-1, 1))) ** 2).mean(axis=1)

    tr = SPMDTrainer(net, l2, "sgd", {"learning_rate": 0.05})
    dp = mio.DevicePrefetcher(make_raw_iter(mio, np),
                              placement=lambda: tr.batch_sharding,
                              buckets="full")
    mx.random.seed(SEED)
    losses, syncs = [], []
    for epoch in range(epochs):
        if epoch:
            dp.reset()
        for b in dp:
            before = telemetry.counter("io.h2d_sync").value
            loss = tr.step(b.data[0], b.label[0], pad=b.pad)
            losses.append(float(loss))
            syncs.append(telemetry.counter("io.h2d_sync").value - before)
    return losses, syncs


def write_image_dataset(np, tmpdir, count=7, size=16):
    """PNG files + a .lst imglist for ImageIter (needs PIL, like the image
    tests)."""
    from PIL import Image
    rng = np.random.RandomState(3)
    lines = []
    for i in range(count):
        arr = (rng.rand(size, size, 3) * 255).astype(np.uint8)
        fname = "img_%d.png" % i
        Image.fromarray(arr).save(os.path.join(tmpdir, fname))
        lines.append("%d\t%d\t%s" % (i, i % 3, fname))
    lst = os.path.join(tmpdir, "data.lst")
    with open(lst, "w") as f:
        f.write("\n".join(lines) + "\n")
    return lst


def collect_batches(it, np):
    out = []
    for b in it:
        d = b.data[0]
        l = b.label[0]
        out.append((np.asarray(d._data if hasattr(d, "_data") else d),
                    np.asarray(l._data if hasattr(l, "_data") else l),
                    b.pad))
    return out


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_io_")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import config, telemetry
        from mxnet_tpu import io as mio
        result["backend"] = jax.default_backend()

        # 1. overlap: device prefetch does zero caller-thread H2D and is
        # bitwise-equal to the host-prefetch baseline
        losses_on, syncs_on = train_epochs(mx, mio, np, True)
        losses_off, syncs_off = train_epochs(mx, mio, np, False)
        config.set("io.device_prefetch", True)
        assert all(s == 0 for s in syncs_on), \
            "caller-thread H2D with device prefetch on: %s" % syncs_on
        assert all(s > 0 for s in syncs_off), \
            "host baseline should sync-stage every step: %s" % syncs_off
        as_bits = lambda xs: [np.float32(x).tobytes() for x in xs]
        assert as_bits(losses_on) == as_bits(losses_off), \
            "device staging changed numerics: %s vs %s" % (losses_on,
                                                           losses_off)
        assert telemetry.counter("io.h2d_async").value > 0
        result["overlap"] = {
            "steps": len(losses_on),
            "sync_h2d_on": sum(syncs_on), "sync_h2d_off": sum(syncs_off),
            "h2d_async": telemetry.counter("io.h2d_async").value,
            "pad_recompiles_avoided":
                telemetry.counter("io.pad_recompiles_avoided").value}

        # 2. ring drain: early exit + reset joins the worker cleanly
        leaked0 = telemetry.counter("io.prefetch_thread_leaked").value
        dp = mio.DevicePrefetcher(make_raw_iter(mio, np), buckets="full")
        seen = 0
        for b in dp:          # early StopIteration from the consumer side
            seen += 1
            if seen == 2:
                break
        dp.reset()
        full = sum(1 for _ in dp)
        assert full == 4, "expected 4 batches after reset, got %d" % full
        leaked = telemetry.counter("io.prefetch_thread_leaked").value \
            - leaked0
        assert leaked == 0, "prefetch worker leaked %d time(s)" % leaked
        result["drain"] = {"consumed_before_reset": seen,
                           "epoch_after_reset": full, "leaked": leaked}

        # 3. decode workers: pooled decode is bitwise-identical, injected
        # io faults are retried on the workers transparently
        from mxnet_tpu.image import ImageIter
        lst = write_image_dataset(np, tmpdir)

        def fresh_iter():
            return ImageIter(batch_size=4, data_shape=(3, 16, 16),
                             path_imglist=lst, path_root=tmpdir,
                             shuffle=False)

        config.set("io.decode_workers", 0)
        base = collect_batches(fresh_iter(), np)
        config.set("io.decode_workers", 3)
        pooled = collect_batches(fresh_iter(), np)
        assert len(base) == len(pooled) == 2
        for (bd, bl, bp), (pd, pl, pp) in zip(base, pooled):
            assert bd.tobytes() == pd.tobytes() and \
                bl.tobytes() == pl.tobytes() and bp == pp, \
                "pooled decode diverged from serial"

        retries0 = telemetry.counter("resilience.retries.io").value
        config.set("resilience.faults", "io:2@step=3")  # deterministic
        faulted = collect_batches(fresh_iter(), np)
        config.set("resilience.faults", "")
        retried = telemetry.counter("resilience.retries.io").value - retries0
        assert retried == 2, "expected 2 injected-fault retries, got %d" \
            % retried
        for (bd, bl, bp), (fd, fl, fp) in zip(base, faulted):
            assert bd.tobytes() == fd.tobytes() and \
                bl.tobytes() == fl.tobytes() and bp == fp, \
                "fault retry changed decoded output"
        result["decode"] = {"batches": len(pooled), "retries": retried}

        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config as _cfg
            _cfg.set("io.device_prefetch", True)
            _cfg.set("io.decode_workers", 0)
            _cfg.set("resilience.faults", "")
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
