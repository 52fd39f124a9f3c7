"""KVStore bandwidth measurement — the reference's tools/bandwidth/
measure.py analog.

Pushes/pulls gradient-shaped arrays for a model-zoo network through the
mx.kv facade (the path a Module/Trainer sync takes), verifies the merged
values, and reports per-round bandwidth.  On TPU meshes the same sync is
a compiled psum over ICI (see tools/scaling_bench.py for the raw
collective bus numbers); this harness measures the FACADE path the
reference's tool measured for its kvstores.

  python tools/bandwidth.py --cpu --network resnet50_v1 --num-batches 5
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse_args():
    ap = argparse.ArgumentParser(
        description="benchmark kv-store push/pull bandwidth")
    ap.add_argument("--network", type=str, default="resnet50_v1",
                    help="model-zoo name whose parameter shapes are the "
                         "workload")
    ap.add_argument("--kv-store", type=str, default="local",
                    help="kvstore type (local | device | dist_*)")
    ap.add_argument("--num-batches", type=int, default=5)
    ap.add_argument("--test-results", type=int, default=1,
                    help="verify pulled values equal the pushed ones")
    ap.add_argument("--gc-type", type=str, default="none",
                    help="gradient compression: none | 2bit")
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the host cpu backend")
    return ap.parse_args()


def main():
    args = parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model(args.network, classes=args.num_classes)
    net.initialize(mx.init.Zero())
    net(mx.nd.array(np.zeros((1, 3, 224, 224), np.float32)))
    shapes = [tuple(p.shape) for p in net.collect_params().values()]
    total_mb = sum(int(np.prod(s)) for s in shapes) * 4 / 1e6

    kv = mx.kv.create(args.kv_store)
    if args.gc_type != "none":
        kv.set_gradient_compression({"type": args.gc_type})
    rng = np.random.RandomState(0)
    grads = [mx.nd.array(rng.uniform(-1, 1, s).astype(np.float32))
             for s in shapes]
    for i, g in enumerate(grads):
        kv.init(i, g)

    print("network %s: %d params, %.1f MB/round, kvstore=%s gc=%s"
          % (args.network, len(shapes), total_mb, args.kv_store,
             args.gc_type))
    outs = [mx.nd.zeros(s) for s in shapes]
    for batch in range(args.num_batches):
        t0 = time.perf_counter()
        for i, g in enumerate(grads):
            kv.push(i, g)
        for i, o in enumerate(outs):
            kv.pull(i, out=o)
        outs[-1].wait_to_read()
        dt = time.perf_counter() - t0
        print("batch %d: %.1f ms, %.2f GB/s (push+pull)"
              % (batch, dt * 1e3, 2 * total_mb / 1e3 / dt))

    if args.test_results:
        # local single-worker semantics: pull returns the pushed value
        # (2-bit compression is lossy; bound the error by the threshold)
        for g, o in zip(grads, outs):
            err = np.abs(g.asnumpy() - o.asnumpy()).max()
            tol = 0.0 if args.gc_type == "none" else 1.0
            assert err <= tol, "pull mismatch: max err %.4f" % err
        print("result check OK")


if __name__ == "__main__":
    main()
