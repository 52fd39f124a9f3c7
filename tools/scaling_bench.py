"""Multi-chip scaling harness — ready to run the day real chips show up.

Reference analogs: example/image-classification/train_imagenet.py
--benchmark 1 run across GPU counts (README.md:290-320, the 90.1%% 256-GPU
scaling table) and tools/bandwidth/ (kvstore allreduce bandwidth
measurement).

Two measurements over a dp mesh of 1..N devices:
  * ResNet-50 synthetic-data training throughput per device count, with
    scaling efficiency vs the 1-device number;
  * gradient-allreduce (psum) bus bandwidth, the tools/bandwidth analog.

On a CPU host, validate the harness with virtual devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python tools/scaling_bench.py --model dense --iters 3
On TPU hardware it runs as-is on every visible chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _devices_sweep(max_devices):
    import jax
    n = len(jax.devices())
    if max_devices:
        n = min(n, max_devices)
    sweep = []
    d = 1
    while d <= n:
        sweep.append(d)
        d *= 2
    if sweep[-1] != n:
        sweep.append(n)
    return sweep


def _build_net(model):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    if model == "resnet50":
        from mxnet_tpu.gluon.model_zoo import vision
        net = vision.get_model("resnet50_v1", classes=1000)
        shape = (3, 224, 224)
    else:  # small dense model for CPU harness validation
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            net.add(gluon.nn.Dense(256, activation="relu"),
                    gluon.nn.Dense(10))
        shape = (64,)
    net.initialize(mx.init.Xavier())
    return net, shape


def bench_training_scaling(model="resnet50", per_device_batch=32, iters=20,
                           max_devices=None):
    """Compute-normalized weak scaling.

    On an oversubscribed host (N virtual devices sharing few cores) raw
    weak-scaling throughput measures the oversubscription, not the
    harness.  So each device count runs the SAME global batch twice:

      * sharded — dp mesh of n devices, gradients psum'd (the real path);
      * unsharded — one device, identical math, no collectives.

    Both runs execute the same total FLOPs on the same silicon, so their
    ratio cancels the compute and isolates what sharding adds:
    ``collective_overhead_fraction = 1 - t_unsharded / t_sharded``.
    On real multi-chip hardware the sharded run is also a true
    throughput measurement (img_s is reported either way).
    """
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel import SPMDTrainer
    from jax.sharding import Mesh

    results = []
    net, shape = _build_net(model)
    rng = np.random.RandomState(0)

    def timed_step(nd_, batch, data, label):
        mesh = Mesh(np.asarray(jax.devices()[:nd_]), ("dp",))
        tr = SPMDTrainer(net, SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.05, "momentum": 0.9},
                         mesh=mesh)
        tr._materialize(data)
        loss = tr.step(data, label)
        np.asarray(loss)          # compile + settle
        ddev = jax.device_put(jnp.asarray(data), tr._batch_sharding)
        ldev = jax.device_put(jnp.asarray(label), tr._batch_sharding)
        loss = tr.step(ddev, ldev)
        np.asarray(loss)          # warm with device-resident data
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = tr.step(ddev, ldev)
        np.asarray(loss)
        return (time.perf_counter() - t0) / iters

    # the unsharded control only makes sense on an oversubscribed virtual
    # mesh: real chips measure true weak scaling directly, and one chip
    # could not hold (or fairly time) the n-device global batch anyway
    normalize = jax.devices()[0].platform == "cpu"
    base_img_s = None
    for nd_ in _devices_sweep(max_devices):
        batch = per_device_batch * nd_
        data = rng.uniform(size=(batch,) + shape).astype(np.float32)
        label = rng.randint(0, 10, (batch,)).astype(np.float32)
        t_sharded = timed_step(nd_, batch, data, label)
        img_s = batch / t_sharded
        if base_img_s is None:
            base_img_s = img_s
        row = {
            "devices": nd_,
            "global_batch": batch,
            "img_s": round(img_s, 2),
            "t_sharded_ms": round(t_sharded * 1e3, 2),
        }
        if normalize:
            t_single = timed_step(1, batch, data, label) if nd_ > 1 \
                else t_sharded
            overhead = max(0.0, 1.0 - t_single / t_sharded)
            row["t_unsharded_same_flops_ms"] = round(t_single * 1e3, 2)
            row["collective_overhead_fraction"] = round(overhead, 4)
            print("devices=%d batch=%d: %.1f samples/s, sharding overhead "
                  "%.1f%% (%.1fms vs %.1fms unsharded)"
                  % (nd_, batch, row["img_s"], 100 * overhead,
                     t_sharded * 1e3, t_single * 1e3), flush=True)
        else:
            row["scaling_efficiency"] = round(
                img_s / (base_img_s * nd_), 4)
            print("devices=%d batch=%d: %.1f samples/s (eff %.1f%%)"
                  % (nd_, batch, row["img_s"],
                     100 * row["scaling_efficiency"]), flush=True)
        results.append(row)
    return results


def bench_allreduce_bandwidth(sizes_mb=(1, 16, 64), max_devices=None):
    """psum bus bandwidth over the dp mesh (tools/bandwidth analog)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    n = len(jax.devices()) if not max_devices else \
        min(len(jax.devices()), max_devices)
    mesh = Mesh(np.asarray(jax.devices()[:n]), ("dp",))
    results = []
    for mb in sizes_mb:
        elems = int(mb * 1024 * 1024 / 4)
        x = jnp.ones((n, elems), jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P("dp")))

        @jax.jit
        def allreduce(v):
            return jax.shard_map(
                lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
                in_specs=P("dp"), out_specs=P("dp"))(v)

        np.asarray(allreduce(x)[0, 0])   # 4-byte forced fetch, not full D2H
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            out = allreduce(x)
        np.asarray(out[0, 0])   # sync without timing a full D2H copy
        dt = (time.perf_counter() - t0) / reps
        # ring-allreduce moves 2*(n-1)/n of the payload per device
        algo_bytes = mb * 1024 * 1024 * 2 * (n - 1) / max(n, 1)
        results.append({"size_mb": mb, "devices": n,
                        "time_ms": round(dt * 1e3, 3),
                        "bus_gb_s": round(algo_bytes / dt / 1e9, 2)})
        print("allreduce %dMB on %d devices: %.2fms (%.1f GB/s bus)"
              % (mb, n, dt * 1e3, results[-1]["bus_gb_s"]), flush=True)
    return results


def bench_dcn_compression(model="dense", per_device_batch=8, iters=10,
                          max_devices=None):
    """Fused-step time with vs without 2-bit compressed DCN gradient sync.

    Splits the visible devices into a {'dcn': 2, 'dp': n/2} mesh — the
    two dcn slices stand in for two pods — and times the same training
    step with ``kvstore.grad_compress`` off and '2bit'.  Also reports the
    wire bytes the compressed DCN hop moved (from the kvstore telemetry
    the fused step feeds) so the ratio is a measured number, not the
    nominal 16x.  On a virtual CPU mesh the *time* delta mostly prices
    the pack/unpack compute (host DCN is simulated); on real multi-pod
    hardware the same row measures the actual wire win.
    """
    import jax
    from jax.sharding import Mesh
    from mxnet_tpu import config, telemetry
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel import SPMDTrainer

    n = len(jax.devices())
    if max_devices:
        n = min(n, max_devices)
    n -= n % 2
    if n < 2:
        return None
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(2, n // 2),
                ("dcn", "dp"))
    net, shape = _build_net(model)
    batch = per_device_batch * n
    rng = np.random.RandomState(0)
    data = rng.uniform(size=(batch,) + shape).astype(np.float32)
    label = rng.randint(0, 10, (batch,)).astype(np.float32)

    def timed(codec):
        config.set("kvstore.grad_compress", codec)
        try:
            tr = SPMDTrainer(net, SoftmaxCrossEntropyLoss(), "sgd",
                             {"learning_rate": 0.05}, mesh=mesh)
            np.asarray(tr.step(data, label))     # compile + settle
            np.asarray(tr.step(data, label))     # warm
            t0 = time.perf_counter()
            for _ in range(iters):
                loss = tr.step(data, label)
            np.asarray(loss)
            return (time.perf_counter() - t0) / iters
        finally:
            config.set("kvstore.grad_compress", "")

    before = telemetry.snapshot()["counters"]
    t_plain = timed("")
    t_comp = timed("2bit")
    after = telemetry.snapshot()["counters"]
    wire = after.get("kvstore.compressed_bytes", 0) - \
        before.get("kvstore.compressed_bytes", 0)
    raw = after.get("kvstore.compressed_raw_bytes", 0) - \
        before.get("kvstore.compressed_raw_bytes", 0)
    row = {
        "devices": n, "dcn_shards": 2, "global_batch": batch,
        "t_step_ms": round(t_plain * 1e3, 2),
        "t_step_compressed_ms": round(t_comp * 1e3, 2),
        "dcn_wire_bytes_per_step": wire // max(iters + 2, 1),
        "dcn_wire_bytes_f32_equiv": raw // max(iters + 2, 1),
        "measured_compression_ratio": round(raw / wire, 2) if wire else 0.0,
    }
    print("dcn 2-bit sync on %d devices (2 dcn shards): %.2fms -> %.2fms "
          "per step, wire %.1fx smaller"
          % (n, t_plain * 1e3, t_comp * 1e3,
             row["measured_compression_ratio"]), flush=True)
    return row


def _measured_single_chip():
    """Best measured **bf16** train img/s, sourced from committed bench
    artifacts with provenance.  Priority: driver-captured beats
    session-measured beats the session-claimed constant; within one
    provenance tier the higher throughput wins.  Artifacts whose headline
    is a different dtype (e.g. the fp32 early-harness BENCH_r02) are
    excluded — t_comp here explicitly models the bf16 step."""
    import glob
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tiers = {"driver-captured": 0, "session-measured": 1}
    best = None
    for path in sorted(glob.glob(os.path.join(root, "BENCH*.json"))):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = rec.get("parsed") or rec  # driver writes parsed: null on rc!=0
        val = parsed.get("value", 0) or 0
        if parsed.get("platform") == "cpu" or val <= 0:
            continue
        if parsed.get("dtype") != "bfloat16":
            continue  # fp32 or dtype-less early-schema artifacts don't model bf16 t_comp
        prov = ("session-measured" if "SESSION" in path
                else "driver-captured")
        cand = {"img_s": val, "provenance": prov,
                "source": os.path.basename(path)}
        if best is None or (tiers[prov], -val) < \
                (tiers[best["provenance"]], -best["img_s"]):
            best = cand
    if best is None:
        best = {"img_s": 2560.0, "provenance": "session-claimed",
                "source": "docs/PERF_NOTES.md round-3 measurement "
                          "(no bf16 bench artifact with a nonzero value)"}
    return best


def analytic_projection():
    """Project dp weak-scaling efficiency to chip counts this host cannot
    hold, against the reference's published north star (90.1%% at 256
    GPUs, example/image-classification/README.md:290-320).

    Model: one ResNet-50 bf16 train step is t_comp of pure device math
    plus a ring allreduce of the gradient bytes that overlaps with the
    backward pass; efficiency = t_comp / max(t_comp, exposed_comm + t_comp)
    where exposed_comm = (1 - overlap) * t_ring.  Every constant is an
    explicit, auditable assumption in the emitted record:

    * grad_bytes — 25.6M ResNet-50 params in bf16 (2 bytes);
    * t_comp — from the best committed bench artifact (BENCH*.json); the
      emitted img_s_provenance names the file and whether it was
      driver-captured, session-measured, or a session-claimed fallback;
    * ICI — 4 links x 100 GB/s/dir per v5e chip, ring uses 2 concurrent
      directions => 200 GB/s bus per chip pair (public v5e figure);
    * DCN — 25 GB/s per host (8 chips share it), the cross-pod fallback;
    * overlap — 0.7: XLA overlaps most of the allreduce with the tail of
      the backward pass (reducescatter starts as soon as layer grads are
      ready); a deliberately conservative figure.
    """
    grad_bytes = 25.6e6 * 2
    measured = _measured_single_chip()
    img_s_1chip = measured["img_s"]
    t_comp = 128.0 / img_s_1chip          # s/step at BS128/chip
    ici_bus = 200e9
    dcn_bus_per_chip = 25e9 / 8
    overlap = 0.7
    rows = []
    for n in (8, 64, 256):
        t_ring_ici = 2 * (n - 1) / n * grad_bytes / ici_bus
        # beyond one pod (256 v5e chips = 1 pod) DCN would carry the
        # inter-pod hop; inside a pod everything rides ICI
        t_ring_dcn = 2 * (n - 1) / n * grad_bytes / dcn_bus_per_chip
        eff_ici = t_comp / (t_comp + (1 - overlap) * t_ring_ici)
        eff_dcn = t_comp / (t_comp + (1 - overlap) * t_ring_dcn)
        rows.append({
            "devices": n,
            "t_comp_ms": round(t_comp * 1e3, 2),
            "t_ring_ici_ms": round(t_ring_ici * 1e3, 3),
            "efficiency_ici": round(eff_ici, 4),
            "efficiency_dcn_fallback": round(eff_dcn, 4),
        })
    return {
        "assumptions": {
            "grad_bytes": grad_bytes,
            "img_s_1chip_bf16_bs128": img_s_1chip,
            "img_s_provenance": measured,
            "ici_bus_gb_s": ici_bus / 1e9,
            "dcn_bus_per_chip_gb_s": dcn_bus_per_chip / 1e9,
            "overlap": overlap,
            "model": "eff = t_comp / (t_comp + (1-overlap) * "
                     "t_ring(n)); ring moves 2(n-1)/n of grad_bytes",
        },
        "reference_north_star": {
            "efficiency": 0.901, "devices": 256,
            "source": "example/image-classification/README.md:290-320"},
        "projection": rows,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "dense"])
    ap.add_argument("--per-device-batch", type=int, default=32)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--max-devices", type=int, default=None)
    ap.add_argument("--skip-bandwidth", action="store_true")
    ap.add_argument("--skip-dcn-compression", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the host cpu backend; combine with "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N for a virtual mesh")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import mxnet_tpu as mx
    mx.runtime.configure_compile_cache()
    platform = jax.devices()[0].platform
    out = {
        "platform": platform,
        "model": args.model,
        "per_device_batch": args.per_device_batch,
        "iters": args.iters,
        "virtual_mesh": platform == "cpu",
        "note": ("CPU virtual-mesh run: the training table is "
                 "COMPUTE-NORMALIZED — each row times the same global "
                 "batch sharded vs unsharded on the same silicon, so "
                 "collective_overhead_fraction is the harness+collective "
                 "cost, not CPU oversubscription; the analytic projection "
                 "carries the multi-chip efficiency claim until real "
                 "chips are attached" if platform == "cpu" else
                 "real-device measurement"),
        "training": bench_training_scaling(
            args.model, args.per_device_batch, args.iters,
            args.max_devices),
    }
    if not args.skip_bandwidth:
        out["allreduce"] = bench_allreduce_bandwidth(
            max_devices=args.max_devices)
    if not args.skip_dcn_compression:
        out["dcn_compression"] = bench_dcn_compression(
            args.model, max(args.per_device_batch // 4, 1), args.iters,
            args.max_devices)
    out["analytic"] = analytic_projection()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
        print("wrote", args.json)


if __name__ == "__main__":
    main()
