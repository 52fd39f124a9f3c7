"""Step-trace capture for the headline ResNet-50 training config.

Profiles the bf16 BS128 NHWC_HWIO train step on the chip through
`mx.profiler` (jax trace capture underneath), classifies the per-device-op
time into convolution / batchnorm-stats / layout-copy / other buckets, and
writes the breakdown as JSON.  A device trace needs the device: without an
accelerator the tool fails, unless --cpu asks for a dry run of its control
flow (whose output has no device plane and no MFU).

Usage: python tools/profile_step.py [--out chiprun_out/profile_step.json]
                                    [--iters 10]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import bench  # noqa: E402  (TRAIN_FLOPS_PER_IMG lives there)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "profile_step.json"))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--layout", default="NHWC_HWIO")
    ap.add_argument("--cpu", action="store_true",
                    help="dry-run the control flow on the cpu backend")
    args = ap.parse_args()
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    import mxnet_tpu.config as _cfg
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    if not args.cpu:
        mx.context.require_accelerator("tools/profile_step.py")
    mx.runtime.configure_compile_cache()
    dev = jax.devices()[0]
    result = {"config": {"dtype": "bfloat16", "batch": args.batch,
                         "conv_layout": args.layout,
                         "iters_profiled": args.iters},
              "platform": dev.platform, "device_kind": dev.device_kind}

    _cfg.set("conv.internal_layout",
             "NHWC" if args.layout.startswith("NHWC") else "native")
    _cfg.set("conv.weights_layout",
             "HWIO" if args.layout.endswith("HWIO") else "ref")

    rng = np.random.RandomState(0)
    mesh = make_mesh({"dp": -1})
    net = vision.get_model("resnet50_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(rng.uniform(
        size=(16, 3, 224, 224)).astype(np.float32)))
    tr = SPMDTrainer(net, SoftmaxCrossEntropyLoss(), "sgd",
                     {"learning_rate": 0.1, "momentum": 0.9,
                      "wd": 1e-4}, mesh=mesh, dtype="bfloat16")
    data = rng.uniform(size=(args.batch, 3, 224, 224)).astype(np.float32)
    label = rng.randint(0, 1000, (args.batch,)).astype(np.float32)

    loss = tr.step(data, label)              # compile
    jax.block_until_ready(loss)
    ddev = jax.device_put(jnp.asarray(data), tr._batch_sharding)
    ldev = jax.device_put(jnp.asarray(label), tr._batch_sharding)
    for _ in range(3):                       # warm
        loss = tr.step(ddev, ldev)
    jax.block_until_ready(loss)

    trace_dir = tempfile.mkdtemp(prefix="mxtpu_profile_")
    mx.profiler.set_config(trace_dir=trace_dir)
    t0 = time.perf_counter()
    mx.profiler.start()
    for _ in range(args.iters):
        loss = tr.step(ddev, ldev)
    jax.block_until_ready(loss)
    mx.profiler.stop()
    wall = time.perf_counter() - t0
    step_ms = wall / args.iters * 1e3
    img_s = args.batch * args.iters / wall
    result["measured"] = {
        "step_ms": round(step_ms, 2),
        "img_s": round(img_s, 2),
        # raises UnknownDeviceError on a chip the peak table does not list
        "mfu_vs_bf16_peak": None if args.cpu else round(
            img_s * bench.TRAIN_FLOPS_PER_IMG
            / mx.perf.peak_flops(dev.device_kind, "bfloat16"), 4),
        "note": "profiled steps include trace overhead; take throughput "
                "with the profiler off",
    }

    ops = mx.profiler.device_op_events(trace_dir)
    if not ops:
        result["device_ops"] = None
        result["note"] = "no device plane in trace (cpu backend)"
    else:
        from mxnet_tpu.perf import classify_op
        per_class = {}
        rows = []
        for name, durs in ops.items():
            total = sum(durs)
            cls = classify_op(name)
            per_class[cls] = per_class.get(cls, 0.0) + total
            rows.append((total, len(durs), name))
        rows.sort(reverse=True)
        total_all = sum(per_class.values()) or 1.0
        result["per_class_ms_per_step"] = {
            k: round(v / args.iters * 1e3, 3) for k, v in
            sorted(per_class.items(), key=lambda kv: -kv[1])}
        result["per_class_fraction"] = {
            k: round(v / total_all, 4) for k, v in
            sorted(per_class.items(), key=lambda kv: -kv[1])}
        result["device_busy_ms_per_step"] = round(
            total_all / args.iters * 1e3, 3)
        result["top_ops"] = [
            {"op": name[:120], "calls": calls,
             "ms_per_step": round(total / args.iters * 1e3, 3)}
            for total, calls, name in rows[:25]]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"profile": "ok", "step_ms": result["measured"][
        "step_ms"], "out": args.out}))


if __name__ == "__main__":
    main()
