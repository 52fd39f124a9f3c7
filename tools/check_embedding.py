"""Fast CPU smoke for mesh-sharded embeddings (seconds on an idle
box; ``elapsed_s`` is reported, not asserted).

Proves the mx.parallel.embedding path end-to-end on a 2-shard host mesh,
with one parseable JSON line on stdout:

  1. sharded — ShardedEmbedding lookup + update on a vocab-sharded table
               (shard_map gather/scatter + psum) are BITWISE-equal to the
               single-device path on the same ids, including repeated ids
               and sentinel-padded rows, and untouched rows keep their
               exact bytes;
  2. trainer — an SPMDTrainer step with Embedding(sparse_grad=True)
               routed through the deduplicated row-sparse path produces
               bitwise-identical losses to the dense-gradient baseline
               (``embedding.sharded`` off);
  3. compiles — ragged id batches padded to one bucket reuse ONE fused
               program (``fused_compiles`` flat) and the dedup ratio of a
               Zipf-like batch is reported.

Usage: JAX_PLATFORMS=cpu python tools/check_embedding.py
Wired as a `not slow` test in tests/test_embedding.py.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS",
    (os.environ.get("XLA_FLAGS", "") +
     " --xla_force_host_platform_device_count=2").strip())

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

VOCAB, DIM, B = 32, 4, 8
SEED = 7


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    try:
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import config, gluon, profiler, telemetry
        from mxnet_tpu.parallel import (ShardedEmbedding, SPMDTrainer,
                                        make_mesh)
        result["backend"] = jax.default_backend()
        assert len(jax.devices()) >= 2, \
            "need 2 host devices, got %d" % len(jax.devices())
        mesh2 = make_mesh({"dp": 2}, jax.devices()[:2])
        mesh1 = make_mesh({"dp": 1}, jax.devices()[:1])

        # 1. sharded: primitive lookup+update bitwise vs single device
        rng = np.random.RandomState(0)
        ids = rng.randint(0, VOCAB, (B, 3)).astype(np.int32)
        ids[3, :] = 9            # repeated row
        ids[-2:, :] = VOCAB      # sentinel-padded tail
        grad = rng.randn(B, 3, DIM).astype(np.float32)
        kw = dict(optimizer="adam", seed=3, init_scale=0.5)
        e2 = ShardedEmbedding(VOCAB, DIM, mesh=mesh2, **kw)
        e1 = ShardedEmbedding(VOCAB, DIM, mesh=mesh1, **kw)
        t0 = np.asarray(e2.table)
        out2 = np.asarray(e2.lookup(ids))
        out1 = np.asarray(e1.lookup(ids))
        assert out2.tobytes() == out1.tobytes(), "sharded lookup diverged"
        assert (out2[ids == VOCAB] == 0).all(), "sentinel rows not zero"
        e2.update(ids, grad, lr=0.1)
        e1.update(ids, grad, lr=0.1)
        t2, t1 = np.asarray(e2.table), np.asarray(e1.table)
        assert t2.tobytes() == t1.tobytes(), "sharded update diverged"
        touched = np.unique(ids[ids < VOCAB])
        untouched = np.setdiff1d(np.arange(VOCAB), touched)
        assert t2[untouched].tobytes() == t0[untouched].tobytes(), \
            "update touched rows outside the batch"
        result["sharded"] = {"bitwise": True, "axis": e2.axis,
                             "rows_touched": int(touched.size)}

        # 2. trainer: sparse routing vs dense baseline, bitwise losses
        def run(sharded):
            config.set("embedding.sharded", sharded)
            try:
                mx.random.seed(SEED)
                net = gluon.nn.HybridSequential()
                with net.name_scope():
                    net.add(gluon.nn.Embedding(VOCAB, DIM,
                                               sparse_grad=True))
                    net.add(gluon.nn.Flatten())
                    net.add(gluon.nn.Dense(1))
                net.initialize(mx.init.Xavier())
                tr = SPMDTrainer(net, gluon.loss.L2Loss(), "sgd",
                                 {"learning_rate": 0.1}, mesh=mesh2)
                rng = np.random.RandomState(1)
                losses = []
                profiler.reset_counters()
                for _ in range(3):
                    d = rng.randint(0, VOCAB, (B, 3)).astype(np.int32)
                    l = rng.randn(B, 1).astype(np.float32)
                    losses.append(float(tr.step(d, l)))
                return losses, profiler.counters()["fused_compiles"]
            finally:
                config.set("embedding.sharded", True)

        sparse_losses, sparse_compiles = run(True)
        dense_losses, _ = run(False)
        bits = lambda xs: [np.float32(x).tobytes() for x in xs]
        assert bits(sparse_losses) == bits(dense_losses), \
            "sparse routing changed losses: %s vs %s" % (sparse_losses,
                                                         dense_losses)
        result["trainer"] = {"bitwise": True, "steps": len(sparse_losses),
                             "loss": sparse_losses[-1]}

        # 3. compiles flat across ragged batches + dedup ratio
        assert sparse_compiles == 1, \
            "expected 1 fused compile over ragged ids, got %d" \
            % sparse_compiles
        zipf = np.minimum(
            np.random.RandomState(2).zipf(1.5, (B, 8)), VOCAB) - 1
        emb = ShardedEmbedding(VOCAB, DIM, mesh=mesh2, optimizer="sgd")
        emb.lookup(zipf.astype(np.int32))
        ratio = telemetry.gauge("embedding.unique_ratio").value
        assert 0.0 < ratio < 1.0, "Zipf batch should contain duplicates"
        result["compiles"] = {"flat": True, "fused": sparse_compiles}
        result["dedup"] = {"unique_ratio": round(ratio, 4),
                           "ids": int(zipf.size)}

        result["elapsed_s"] = round(time.perf_counter() - t_main, 3)
        result["ok"] = True
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    finally:
        try:
            from mxnet_tpu import config as _cfg
            _cfg.set("embedding.sharded", True)
            _cfg.set("embedding.unique_size", 0)
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
