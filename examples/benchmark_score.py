"""Inference throughput across the model zoo.

Reference analog: example/image-classification/benchmark_score.py — for
each network and batch size, time the forward pass and print img/s (the
corpus behind the reference's perf.md inference tables).

TPU-native: each (model, batch) pair is one jitted forward with
device-resident inputs and forced-fetch timing.  --dtype bfloat16 casts
params+inputs for the MXU rate.
"""
from __future__ import annotations

import os as _os
import sys as _sys
_sys.path.insert(
    0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), "..")))

import argparse
import time

import _common
import numpy as np


def score(model_name, batch, dtype, iters, image_shape=(3, 224, 224)):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import functionalize

    net = vision.get_model(model_name, classes=1000)
    net.initialize(mx.init.Xavier())
    rng = np.random.RandomState(0)
    seed = rng.uniform(size=(1,) + image_shape).astype(np.float32)
    net(mx.nd.array(seed))  # resolve deferred shapes
    fn = functionalize(net)
    params = {n: jnp.asarray(v) for n, v in fn.init_values().items()}
    cdt = jnp.bfloat16 if dtype == "bfloat16" else None
    if cdt is not None:
        params = {n: v.astype(cdt) if v.dtype == jnp.float32 else v
                  for n, v in params.items()}

    def fwd(pm, data):
        if cdt is not None:
            data = data.astype(cdt)
        (out,), _ = fn.apply(pm, (data,), key=None, training=False)
        return out.astype(jnp.float32)

    jfwd = jax.jit(fwd)
    data = jnp.asarray(rng.uniform(size=(batch,) + image_shape), jnp.float32)
    np.asarray(jfwd(params, data)[0, 0])   # compile + warm
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = jfwd(params, data)
    np.asarray(out[0, 0])                  # forced fetch ends the timing
    dt = time.perf_counter() - t0
    return batch * iters / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--networks", default="resnet18_v1,resnet50_v1",
                    help="comma-separated model-zoo names (reference "
                         "default set: alexnet/vgg/inception/resnet)")
    ap.add_argument("--batch-sizes", default="1,16,32")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--image-shape", default="3,224,224")
    _common.add_device_flag(ap)
    args = ap.parse_args()
    _common.apply_device_flag(args)
    shape = tuple(int(s) for s in args.image_shape.split(","))

    for name in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            img_s = score(name, bs, args.dtype, args.iters, shape)
            print("network: %s, batch: %d, dtype: %s, %.1f img/s"
                  % (name, bs, args.dtype, img_s), flush=True)


if __name__ == "__main__":
    main()
