"""Headline benchmark: ResNet-50 training throughput (synthetic data).

Mirrors the reference harness `example/image-classification/train_imagenet.py
--benchmark 1` (synthetic-data training throughput); baseline is the
reference's published 363.69 img/s fp32 @BS128 on 1xV100
(docs/static_site/src/pages/api/faq/perf.md:247-256, see BASELINE.md).

Sweep: fp32 @BS128 (baseline-comparable config) plus bf16 mixed precision
@BS{128,256} — the TPU-native policy (MXU runs bf16 natively; f32 master
weights, see mxnet_tpu/parallel/trainer.py dtype=).  The headline value is
the best bf16 number; every config is reported in "runs" with its own MFU.

Methodology notes (both match the reference benchmark semantics):
  * Synthetic data lives ON DEVICE and is reused each step — the
    reference's --benchmark 1 likewise generates its batch once on the GPU.
  * Every timing region ends in ``jax.block_until_ready`` on the last
    result: dispatch is asynchronous, and chip_smoke.py's train phase
    checks on the chip that this wait really waits.

MFU denominators are explicit per dtype (peak_tflops in each run record):
bf16 vs the chip's MXU peak from the one table, ``mx.perf.DEVICE_PEAKS``;
fp32 has no MXU path on TPU so its utilization is quoted against the same
bf16 peak and labeled accordingly.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The measurement needs the chip: without an accelerator it fails, unless
MXTPU_BENCH_CPU asks for the cpu backend (a dry run of the control flow,
whose records say platform "cpu" and carry no MFU).  A phase that raises
fails the run — there is no record-and-carry-on.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax

BASELINE_IMG_S = 363.69  # ResNet-50 fp32 train, 1xV100, BS128

# ResNet-50 fwd FLOPs/image at 224x224 ~ 4.1e9; a train step ~ 3x fwd
# (forward + grad-wrt-activations + grad-wrt-weights).
TRAIN_FLOPS_PER_IMG = 3 * 4.1e9


def _mfu(tflops, peak):
    """Utilization against the device's published peak, or None on a
    device that has none (the cpu dry run)."""
    return round(tflops / peak, 4) if peak else None


def run_bench(runs_out):
    import mxnet_tpu as mx

    if not os.environ.get("MXTPU_BENCH_CPU"):
        mx.context.require_accelerator("bench.py")
    on_tpu = mx.context.accelerator_is_real()
    mx.runtime.configure_compile_cache()
    for phase, tpu_args, cpu_args in PHASES:
        phase(runs_out, *(tpu_args if on_tpu else cpu_args))
    result = _summarize(runs_out)
    dev = jax.devices()[0]
    result.update(platform=dev.platform, device_kind=dev.device_kind,
                  device_count=len(jax.devices()))
    return result


def resnet_config(runs_out, on_tpu):
    """The headline: ResNet-50 SPMDTrainer sweep (dtype x batch x conv
    layout) plus one bf16 inference config."""
    import numpy as np
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    platform = jax.devices()[0].platform
    kind = jax.devices()[0].device_kind
    # raises UnknownDeviceError on a chip the peak table does not list
    peak = mx.perf.peak_flops(kind, "bfloat16") / 1e12 if on_tpu else None
    mesh = make_mesh({"dp": -1})  # 1 chip under the driver; dp-scales as-is
    rng = np.random.RandomState(0)

    seed_batch = rng.uniform(size=(16, 3, 224, 224)).astype(np.float32)
    net = vision.get_model("resnet50_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    net(mx.nd.array(seed_batch))  # resolve deferred shapes once

    def infer_config(batch, dtype, iters):
        """Inference throughput (reference comparison: 1233 img/s fp32 /
        2355 img/s fp16 @BS128 on V100, perf.md:196,210)."""
        from mxnet_tpu.parallel import functionalize
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

        # registry-wrapped so the run record carries cost_analysis-derived
        # FLOPs next to the analytic 4.1e9/img estimate
        jfwd = mx.perf.wrap(jax.jit(fwd), "bench",
                            "infer/b%d/%s" % (batch, dtype or "float32"))
        data = jnp.asarray(rng.uniform(size=(batch, 3, 224, 224)),
                           jnp.float32)
        out = jfwd(params, data)
        jax.block_until_ready(out)     # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jfwd(params, data)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        img_s = batch * iters / dt
        fwd_tflops = img_s * 4.1e9 / 1e12  # fwd-only FLOPs
        rec = {
            "dtype": dtype or "float32", "batch": batch, "iters": iters,
            "mode": "inference", "img_s": round(img_s, 2),
            "tflops": round(fwd_tflops, 2), "peak_tflops": peak,
            "peak_basis": "bf16 MXU peak for %s" % (kind or platform),
            "mfu": _mfu(fwd_tflops, peak),
            "ref_note": "reference inference: 1233 img/s fp32 / 2355 "
                        "img/s fp16 @BS128 V100 (perf.md:196,210)",
        }
        _measured_cost(rec, "bench", batch, img_s, 4.1e9, peak)
        runs_out.append(rec)

    def _measured_cost(rec, family, batch, img_s, analytic_per_img, peak):
        """flops_measured/mfu_measured from the newest mx.perf program in
        ``family`` (XLA cost_analysis, captured at compile); a >10%
        analytic-vs-measured gap is flagged in the run note.  None fields
        when no hooked program registered (backend without cost data)."""
        progs = mx.perf.programs(family)
        prog = progs[-1] if progs else None
        if not prog or not prog.get("flops"):
            rec["flops_measured"] = None
            rec["mfu_measured"] = None
            return
        per_img = prog["flops"] / batch
        rec["flops_measured"] = round(per_img, 1)
        rec["mfu_measured"] = _mfu(img_s * per_img / 1e12, peak)
        gap = abs(per_img - analytic_per_img) / analytic_per_img
        if gap > 0.10:
            note = ("analytic %.3g vs measured %.3g FLOPs/img: %.0f%% "
                    "discrepancy — trust mfu_measured"
                    % (analytic_per_img, per_img, 100 * gap))
            rec["note"] = ("%s; %s" % (rec["note"], note)
                           if rec.get("note") else note)

    def one_config(batch, dtype, iters, layout="native"):
        # layout: "native" | "NHWC" | "NHWC_HWIO" (channels-last weights
        # end-to-end — conv.weights_layout=HWIO, docs/PERF_NOTES.md)
        import mxnet_tpu.config as _cfg
        _cfg.set("conv.internal_layout",
                 "NHWC" if layout.startswith("NHWC") else "native")
        _cfg.set("conv.weights_layout",
                 "HWIO" if layout.endswith("HWIO") else "ref")
        data = rng.uniform(size=(batch, 3, 224, 224)).astype(np.float32)
        label = rng.randint(0, 1000, (batch,)).astype(np.float32)
        tr = SPMDTrainer(net, SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1, "momentum": 0.9,
                          "wd": 1e-4},
                         mesh=mesh, dtype=dtype)
        loss = tr.step(data, label)          # compile
        jax.block_until_ready(loss)
        ddev = jax.device_put(jnp.asarray(data), tr._batch_sharding)
        ldev = jax.device_put(jnp.asarray(label), tr._batch_sharding)
        loss = tr.step(ddev, ldev)           # warm with device-resident data
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = tr.step(ddev, ldev)
        jax.block_until_ready(loss)          # the wait ends the timing
        dt = time.perf_counter() - t0
        lv = float(loss)
        img_s = batch * iters / dt
        tflops = img_s * TRAIN_FLOPS_PER_IMG / 1e12
        rec = {
            "dtype": dtype or "float32",
            "batch": batch,
            "iters": iters,
            "conv_layout": layout,
            "img_s": round(img_s, 2),
            "tflops": round(tflops, 2),
            "peak_tflops": peak,
            "peak_basis": "bf16 MXU peak for %s" % (kind or platform),
            "mfu": _mfu(tflops, peak),
            "loss": round(lv, 4),
        }
        if dtype is None:
            rec["note"] = ("fp32 has no MXU path on TPU; mfu is vs the "
                           "bf16 peak for comparability")
        _measured_cost(rec, "spmd", batch, img_s, TRAIN_FLOPS_PER_IMG, peak)
        runs_out.append(rec)
        return rec

    iters = 50 if on_tpu else 3
    # the NHWC internal-layout experiment (docs/PERF_NOTES.md) runs as an
    # extra bf16 candidate; if it wins it becomes the headline (a real,
    # honest measurement — the layout is recorded per run)
    cfgs = [("bfloat16", 128, "native"), ("bfloat16", 128, "NHWC"),
            ("bfloat16", 128, "NHWC_HWIO"), ("bfloat16", 256, "native"),
            (None, 128, "native")] \
        if on_tpu else [("bfloat16", 16, "native"), ("bfloat16", 16, "NHWC"),
                        ("bfloat16", 16, "NHWC_HWIO"), (None, 16, "native")]
    for dtype, batch, layout in cfgs:
        try:
            one_config(batch, dtype, iters, layout)
        finally:
            import mxnet_tpu.config as _cfg
            _cfg.set("conv.internal_layout", "native")
            _cfg.set("conv.weights_layout", "ref")
    infer_config(128 if on_tpu else 16, "bfloat16", 100 if on_tpu else 3)


def module_train_config(runs_out, fused_iters, eager_iters):
    """Secondary: symbolic Module.fit step throughput, fused vs eager.

    The benchmark MLP (8x128, batch 64, adam) is dispatch-bound, which is
    exactly what the fused train step eliminates — one jitted
    fwd+bwd+update program per step vs two stage programs plus a
    per-parameter updater loop.  PR acceptance pins fused >= 3x eager on
    CPU; the measured pair is recorded under runs[] with mode
    "module_train" and surfaced as module_mlp_train_throughput."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import config as _cfg

    layers, width, batch, feat = 8, 128, 64, 64
    rng = np.random.RandomState(0)
    X = mx.nd.array(rng.randn(batch, feat).astype(np.float32))
    Y = mx.nd.array((rng.rand(batch) * 10).astype(np.float32))
    batch_obj = mx.io.DataBatch([X], [Y])

    def build_sym():
        h = mx.sym.Variable("data")
        for i in range(layers):
            h = mx.sym.FullyConnected(h, num_hidden=width, name="fc%d" % i)
            h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="head")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    def one_path(mode, iters, label=None):
        _cfg.set("module.fused_step", "auto" if mode == "fused" else "off")
        mod = mx.mod.Module(build_sym())
        mod.bind([("data", (batch, feat))], [("softmax_label", (batch,))])
        mod.init_params(mx.init.Uniform(0.05))
        mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 1e-3})
        for _ in range(3):                     # compile + warm
            mod.train_step(batch_obj)
        sync = mod._exec.arg_dict["fc0_weight"]
        jax.block_until_ready(sync._data)
        t0 = time.perf_counter()
        for _ in range(iters):
            mod.train_step(batch_obj)
        jax.block_until_ready(sync._data)
        dt = time.perf_counter() - t0
        runs_out.append({
            "mode": "module_train", "path": label or mode, "batch": batch,
            "iters": iters, "mlp": "%dx%d" % (layers, width),
            "optimizer": "adam",
            "steps_s": round(iters / dt, 2),
            "samples_s": round(batch * iters / dt, 2),
        })
        return iters / dt

    try:
        fused = one_path("fused", fused_iters)
        eager = one_path("eager", eager_iters)
        if eager > 0:
            runs_out.append({"mode": "module_train", "path": "speedup",
                             "fused_over_eager": round(fused / eager, 2)})
        # telemetry-overhead guard: the same fused workload with the JSONL
        # step log ON must stay within a few % of the instrumented-off
        # number (ISSUE acceptance: <= 2% on the TPU target; CPU µs-steps
        # are recorded informationally)
        import tempfile
        log_path = os.path.join(tempfile.mkdtemp(prefix="mxtpu_bench_tel_"),
                                "steps.jsonl")
        try:
            _cfg.set("telemetry.sink", "jsonl:" + log_path)
            fused_tel = one_path("fused", fused_iters,
                                 label="fused_telemetry")
        finally:
            _cfg.set("telemetry.sink", "")
        if fused > 0 and fused_tel > 0:
            runs_out.append({
                "mode": "module_train", "path": "telemetry_overhead",
                "overhead_pct": round((fused - fused_tel) / fused * 100, 2)})
        # tracing-overhead guard: same contract for the causal-span chrome
        # sink (MXNET_TPU_TRACE) — span enter/exit plus one JSON line per
        # span must stay in the same few-% envelope
        trace_path = os.path.join(
            tempfile.mkdtemp(prefix="mxtpu_bench_trace_"), "run.trace.json")
        try:
            _cfg.set("tracing.sink", "chrome:" + trace_path)
            fused_trace = one_path("fused", fused_iters,
                                   label="fused_tracing")
        finally:
            _cfg.set("tracing.sink", "")
        if fused > 0 and fused_trace > 0:
            runs_out.append({
                "mode": "module_train", "path": "tracing_overhead",
                "overhead_pct":
                    round((fused - fused_trace) / fused * 100, 2)})
        # resilience-overhead guard: the same fused workload with the
        # non-finite step guard armed (the all-finite check and the
        # keep-or-skip select fold into the fused program — no host sync on
        # the happy path) plus a periodic CheckpointManager in the loop.
        # ISSUE acceptance: <= 1% on the TPU target, where the extra
        # elementwise ops vanish next to the matmuls; on CPU µs-steps the
        # same ops are a visible fraction of the step and the number is
        # recorded informationally (same caveat as the telemetry/tracing
        # guards above).  Knobs off costs ~0% since the guard-off program
        # is byte-identical.
        from mxnet_tpu import resilience as _resilience
        ck_dir = tempfile.mkdtemp(prefix="mxtpu_bench_res_")
        mgr = _resilience.CheckpointManager(
            ck_dir, every_n_steps=10 ** 9, keep=1)  # cadence check only
        try:
            _cfg.set("resilience.nanguard", "skip")
            fused_res = one_path("fused", fused_iters,
                                 label="fused_resilience")
            mgr.maybe_save(1, lambda p: None)  # prove the hook is live
        finally:
            _cfg.set("resilience.nanguard", "")
            _resilience.reset_nanguard()
        if fused > 0 and fused_res > 0:
            runs_out.append({
                "mode": "module_train", "path": "resilience_overhead",
                "overhead_pct":
                    round((fused - fused_res) / fused * 100, 2)})
    finally:
        _cfg.set("module.fused_step", "auto")


def input_pipeline_config(runs_out, steps):
    """Secondary: device-resident vs host-side input pipeline throughput.

    The same seeded MLP + SPMDTrainer consumes the same host-prep iterator
    (per-batch normalize + cast — the decode/augment stand-in) two ways:
    ``PrefetchingIter`` hands the step host numpy (the trainer pays a
    synchronous sharded device_put per step), ``DevicePrefetcher`` stages
    batches on its background thread so the caller thread dispatches
    immediately.  samples/s for both paths land under runs[] with mode
    "input_pipeline" and surface as the input_pipeline_overlap secondary
    (docs/PERF_NOTES.md input-pipeline section)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import config as _cfg
    from mxnet_tpu import io as mio
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import SPMDTrainer

    BATCH, FEAT = 64, 256
    rng = np.random.RandomState(5)
    X = rng.randn(BATCH * 8, FEAT).astype(np.float32)
    Y = rng.randn(BATCH * 8).astype(np.float32)

    class HostPrepIter(mio.DataIter):
        def __init__(self):
            super().__init__(BATCH)
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self):
            if self.i + BATCH > len(X):
                raise StopIteration
            lo = self.i
            self.i += BATCH
            d = X[lo:lo + BATCH]
            d = (d - d.mean(axis=0)) / (d.std(axis=0) + 1e-6)
            return mio.DataBatch([d.astype(np.float32)],
                                 [Y[lo:lo + BATCH]], pad=0)

    def l2(out, label):
        return ((out - label.reshape((-1, 1))) ** 2).mean(axis=1)

    def run(device_prefetch):
        _cfg.set("io.device_prefetch", device_prefetch)
        mx.random.seed(9)
        net = nn.HybridSequential()
        net.add(nn.Dense(256, activation="relu"), nn.Dense(1))
        net.initialize()
        tr = SPMDTrainer(net, l2, "sgd", {"learning_rate": 0.01})
        if device_prefetch:
            feed = mio.DevicePrefetcher(
                HostPrepIter(), placement=lambda: tr.batch_sharding,
                buckets="full")
        else:
            feed = mio.PrefetchingIter(HostPrepIter())
        loss = None
        for b in feed:                       # warm epoch: compile + ring
            loss = tr.step(b.data[0], b.label[0], pad=b.pad)
        jax.block_until_ready(loss)
        done = 0
        t0 = time.perf_counter()
        while done < steps:
            feed.reset()
            for b in feed:
                loss = tr.step(b.data[0], b.label[0], pad=b.pad)
                done += 1
                if done >= steps:
                    break
        jax.block_until_ready(loss)          # the wait ends the timing
        return BATCH * done / (time.perf_counter() - t0)

    try:
        sps_host = run(False)
        sps_dev = run(True)
    finally:
        _cfg.set("io.device_prefetch", True)
    runs_out.append({"mode": "input_pipeline", "path": "host_prefetch",
                     "samples_s": round(sps_host, 1), "batch": BATCH,
                     "steps": steps})
    runs_out.append({"mode": "input_pipeline", "path": "device_prefetch",
                     "samples_s": round(sps_dev, 1), "batch": BATCH,
                     "steps": steps})
    runs_out.append({"mode": "input_pipeline", "path": "overlap",
                     "device_over_host": round(sps_dev / sps_host, 3)})


def dlrm_embedding_config(runs_out, steps):
    """Secondary headline: recommendation-style embedding training — the
    deduplicated row-sparse path vs the dense-gradient baseline.

    The same seeded model (a >=100k-row ``Embedding(sparse_grad=True)``
    feeding a small MLP) trains on the same Zipf-distributed id batches
    two ways: ``embedding.sharded`` ON routes the table through
    mx.parallel.embedding (dedup + ``step_rows``, O(rows-touched) per
    step), OFF takes the dense path (full-table cotangent + full-table
    optimizer step).  samples/s for both land under runs[] with mode
    "dlrm_embedding" and surface as the dlrm_embedding_throughput
    secondary; target is >=3x sparse-over-dense on tables >=100k rows
    (docs/PERF_NOTES.md sharded-embedding section)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import config as _cfg
    from mxnet_tpu.gluon import nn, loss as gloss
    from mxnet_tpu.parallel import SPMDTrainer

    VOCAB, DIM, BATCH, SLOTS = 1_000_000, 32, 256, 8
    rng = np.random.RandomState(5)
    # Zipf traffic: heavy head, long tail — the dedup-friendly real shape
    batches = [np.minimum(rng.zipf(1.5, (BATCH, SLOTS)), VOCAB)
                 .astype(np.int32) - 1 for _ in range(8)]
    labels = [rng.randn(BATCH, 1).astype(np.float32) for _ in range(8)]
    unique_ratio = float(np.mean(
        [np.unique(b).size / b.size for b in batches]))

    def run(sparse):
        _cfg.set("embedding.sharded", sparse)
        mx.random.seed(9)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Embedding(VOCAB, DIM, sparse_grad=True))
            net.add(nn.Flatten())
            net.add(nn.Dense(64, activation="relu"))
            net.add(nn.Dense(1))
        net.initialize(mx.init.Xavier())
        tr = SPMDTrainer(net, gloss.L2Loss(), "sgd",
                         {"learning_rate": 0.05})
        loss = tr.step(batches[0], labels[0])     # compile
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for i in range(steps):
            loss = tr.step(batches[i % len(batches)],
                           labels[i % len(batches)])
        jax.block_until_ready(loss)      # the wait ends the timing
        return BATCH * steps / (time.perf_counter() - t0)

    try:
        sps_sparse = run(True)
        sps_dense = run(False)
    finally:
        _cfg.set("embedding.sharded", True)
    common = {"mode": "dlrm_embedding", "vocab": VOCAB, "dim": DIM,
              "batch": BATCH, "slots": SLOTS, "steps": steps,
              "unique_ratio": round(unique_ratio, 4)}
    runs_out.append(dict(common, path="sparse",
                         samples_s=round(sps_sparse, 1)))
    runs_out.append(dict(common, path="dense",
                         samples_s=round(sps_dense, 1)))
    runs_out.append({"mode": "dlrm_embedding", "path": "speedup",
                     "sparse_over_dense":
                         round(sps_sparse / sps_dense, 3)})


def serving_config(runs_out, requests):
    """Secondary: mx.serving continuous batching vs sequential batch-1
    predict, requests/s under concurrent load.

    The same exported MLP artifact serves the same single-row request
    stream two ways: one thread calling ``StableHLOPredictor.predict``
    per request (every request pays its own dispatch), and N caller
    threads submitting into a :class:`serving.Server` whose batcher
    coalesces them into bucketed batches (many requests amortize one
    dispatch).  requests/s for both paths land under runs[] with mode
    "serving" plus the server-side queue-delay p99, and surface as the
    serving_throughput secondary (docs/SERVING.md).  PR acceptance pins
    continuous >= 2x sequential on CPU."""
    import tempfile
    import threading
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import deploy, serving, telemetry
    from mxnet_tpu.gluon import nn

    FEAT, MAX_BATCH, THREADS = 64, 16, 8
    mx.random.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"), nn.Dense(16))
    net.initialize()
    example = mx.nd.random.uniform(shape=(MAX_BATCH, FEAT))
    net(example)
    prefix = os.path.join(tempfile.mkdtemp(prefix="mxtpu_bench_srv_"),
                          "mlp")
    deploy.export_model(net, prefix, example)

    rng = np.random.RandomState(2)
    reqs = [rng.uniform(size=(1, FEAT)).astype(np.float32)
            for _ in range(requests)]

    # sequential batch-1: every request is its own synchronous dispatch
    pred = deploy.StableHLOPredictor(prefix)
    pred.predict(reqs[0])                       # compile the batch-1 shape
    t0 = time.perf_counter()
    for r in reqs:
        pred.predict(r)
    seq_rps = requests / (time.perf_counter() - t0)

    # continuous batching: THREADS submitters share one batcher
    srv = serving.Server(max_batch=MAX_BATCH, max_queue_delay_ms=2.0)
    srv.register("mlp", prefix)
    srv.start()
    try:
        srv.predict("mlp", reqs[0])             # warm the dispatch path
        telemetry.timer("serving.queue_delay_ms").reset()
        telemetry.timer("serving.batch_fill").reset()
        shards = [reqs[i::THREADS] for i in range(THREADS)]

        def worker(shard):
            for f in [srv.submit("mlp", r) for r in shard]:
                f.result(timeout=60)

        threads = [threading.Thread(target=worker, args=(s,))
                   for s in shards]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        cont_rps = requests / (time.perf_counter() - t0)
        qd_p99 = telemetry.timer("serving.queue_delay_ms").stats()["p99"]
        fill = telemetry.timer("serving.batch_fill").stats()
    finally:
        srv.stop()
    runs_out.append({"mode": "serving", "path": "sequential_batch1",
                     "requests": requests,
                     "requests_s": round(seq_rps, 1)})
    runs_out.append({"mode": "serving", "path": "continuous",
                     "requests": requests, "threads": THREADS,
                     "max_batch": MAX_BATCH,
                     "requests_s": round(cont_rps, 1),
                     "queue_delay_p99_ms": round(qd_p99, 3),
                     "batch_fill_mean": round(
                         fill["total"] / fill["count"], 3)
                     if fill["count"] else None})
    runs_out.append({"mode": "serving", "path": "speedup",
                     "continuous_over_sequential":
                         round(cont_rps / seq_rps, 2)})


def quantized_serving_config(runs_out, requests):
    """Secondary: INT8 quantized serving vs fp32 serving, requests/s.

    One MLP is exported twice from the same weights — the fp32 v2
    artifact and the int8-recolored v3 artifact
    (``mx.quantization.export_quantized``) — and each serves the same
    ragged request stream through its own continuous-batching Server.
    requests/s for both land under runs[] with mode "quantized_serving"
    and surface as the quantized_serving_throughput secondary.  On CPU
    the throughput delta is INFORMATIONAL (no int8 MXU path; XLA may
    even emulate int8 slower) — the structural win asserted by the tests
    is the int8 dot_general in the exported HLO, which on TPU engages
    the MXU's double-rate int8 path (docs/QUANTIZATION.md)."""
    import tempfile
    import threading
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import deploy, quantization, serving, telemetry
    from mxnet_tpu.gluon import nn

    FEAT, MAX_BATCH, THREADS = 64, 16, 8
    mx.random.seed(13)
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"), nn.Dense(16))
    net.initialize()
    rng = np.random.RandomState(3)
    calib = [rng.uniform(-1, 1, size=(MAX_BATCH, FEAT)).astype(np.float32)
             for _ in range(4)]
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_bench_q_")
    fp32_prefix = os.path.join(tmpdir, "fp32")
    int8_prefix = os.path.join(tmpdir, "int8")
    deploy.export_model(net, fp32_prefix, calib[0])
    cal = quantization.calibrate(net, calib)
    quantization.export_quantized(net, int8_prefix, cal)
    measured = deploy.load_model(int8_prefix,
                                 quantized=True).meta["measured_error"]

    reqs = [rng.uniform(-1, 1, size=(1, FEAT)).astype(np.float32)
            for _ in range(requests)]

    def drive(prefix, quantized):
        srv = serving.Server(max_batch=MAX_BATCH, max_queue_delay_ms=2.0)
        srv.register("mlp", prefix, quantized=quantized)
        srv.start()
        try:
            srv.predict("mlp", reqs[0])         # warm the dispatch path
            telemetry.timer("serving.queue_delay_ms").reset()
            shards = [reqs[i::THREADS] for i in range(THREADS)]

            def worker(shard):
                for f in [srv.submit("mlp", r) for r in shard]:
                    f.result(timeout=60)

            threads = [threading.Thread(target=worker, args=(s,))
                       for s in shards]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            rps = requests / (time.perf_counter() - t0)
            qd = telemetry.timer("serving.queue_delay_ms").stats()["p99"]
        finally:
            srv.stop()
        return rps, qd

    fp32_rps, fp32_qd = drive(fp32_prefix, quantized=False)
    int8_rps, int8_qd = drive(int8_prefix, quantized=True)
    runs_out.append({"mode": "quantized_serving", "path": "fp32",
                     "requests": requests, "threads": THREADS,
                     "requests_s": round(fp32_rps, 1),
                     "queue_delay_p99_ms": round(fp32_qd, 3)})
    runs_out.append({"mode": "quantized_serving", "path": "int8",
                     "requests": requests, "threads": THREADS,
                     "requests_s": round(int8_rps, 1),
                     "queue_delay_p99_ms": round(int8_qd, 3),
                     "measured_error": measured})
    runs_out.append({"mode": "quantized_serving", "path": "speedup",
                     "int8_over_fp32": round(int8_rps / fp32_rps, 2)})


def obs_overhead_config(runs_out, requests):
    """Secondary: the mx.obs operational plane's serving-path cost.

    ONE continuous-batching Server serves the same ragged request
    stream with the plane toggled per pass — OFF, then the full plane
    ON (/metrics exporter with a live scraper polling it mid-run, plus
    the JSONL access log writing one record per request) — interleaved
    off/on pairs so machine drift hits both sides equally, and the
    MEDIAN of the per-pair on/off ratios lands as the informational
    paired_median_pct (on a noisy shared box even the paired-median
    A/A control swings several percent — wider than the bound under
    test, so end-to-end A/B cannot BE the gate).  The headline
    overhead_pct is deterministic by decomposition, the same method
    tools/check_obs.py gates on: the measured SERIAL per-record cost —
    the hot enqueue that runs on the batcher's dispatch path, the only
    piece that cannot overlap anything — divided by the plane-off
    per-request service time.  The writer thread's drain cost
    (serialization + file write) is priced separately per record: it
    overlaps the GIL-released XLA dispatch and file IO, and if it ever
    fell behind the bounded queue sheds into ``obs.access_dropped``
    rather than backpressuring serving.  PR acceptance bounds
    overhead_pct at <= 2%."""
    import tempfile
    import threading
    import urllib.request
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import config as _cfg
    from mxnet_tpu import deploy, obs, serving
    from mxnet_tpu.gluon import nn

    FEAT, MAX_BATCH, THREADS, PASSES = 128, 16, 8, 5
    mx.random.seed(17)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu"),
            nn.Dense(256, activation="relu"),
            nn.Dense(256, activation="relu"), nn.Dense(16))
    net.initialize()
    rng = np.random.RandomState(5)
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_bench_obs_")
    prefix = os.path.join(tmpdir, "mlp")
    deploy.export_model(
        net, prefix,
        rng.uniform(-1, 1, size=(MAX_BATCH, FEAT)).astype(np.float32))
    reqs = [rng.uniform(-1, 1, size=(1, FEAT)).astype(np.float32)
            for _ in range(requests)]
    shards = [reqs[i::THREADS] for i in range(THREADS)]

    srv = serving.Server(max_batch=MAX_BATCH, max_queue_delay_ms=2.0)
    srv.register("mlp", prefix)
    srv.start()
    stop_scrape = threading.Event()

    def scraper():
        # 4 scrapes/s is already ~60x denser than a production Prometheus
        # interval; denser polling benchmarks the scrape handler's GIL
        # share, not the serving hot path
        while not stop_scrape.wait(0.25):
            addr = obs.exporter_address()
            if addr is None:
                continue
            try:
                urllib.request.urlopen(
                    "http://%s:%d/metrics" % addr, timeout=5).read()
            except OSError:
                pass

    def worker(shard):
        for f in [srv.submit("mlp", r) for r in shard]:
            f.result(timeout=60)

    def one_pass():
        threads = [threading.Thread(target=worker, args=(s,))
                   for s in shards]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return requests / (time.perf_counter() - t0)

    import statistics
    ratios, off_rps, on_rps = [], 0.0, 0.0
    try:
        srv.predict("mlp", reqs[0])             # warm the dispatch path
        scrape_thread = threading.Thread(target=scraper, daemon=True)
        scrape_thread.start()
        for i in range(PASSES):
            _cfg.set("obs.listen", "")
            _cfg.set("obs.access_log", "")
            off = max(one_pass(), one_pass())
            _cfg.set("obs.listen", "127.0.0.1:0")
            _cfg.set("obs.access_log",
                     "jsonl:" + os.path.join(tmpdir,
                                             "access%d.jsonl" % i))
            on = max(one_pass(), one_pass())
            ratios.append(on / off)
            off_rps = max(off_rps, off)
            on_rps = max(on_rps, on)
        # deterministic decomposition: price the serial hot-path
        # enqueue (what one record adds to the dispatch thread) and
        # the concurrent writer drain separately, against the
        # per-request service time measured above
        _cfg.set("obs.access_log",
                 "jsonl:" + os.path.join(tmpdir, "access_cost.jsonl"))
        obs.flush_access_log()
        n_rec = 20000
        t0 = time.perf_counter()
        for i in range(n_rec):
            obs.log_access("mlp", "ok", request_id=str(i),
                           queue_ms=0.5, dispatch_ms=1.0, bytes=64)
        hot_us = (time.perf_counter() - t0) / n_rec * 1e6
        t0 = time.perf_counter()
        obs.flush_access_log()
        drain_us = (time.perf_counter() - t0) / n_rec * 1e6
    finally:
        stop_scrape.set()
        srv.stop()
        _cfg.set("obs.listen", "")
        _cfg.set("obs.access_log", "")
    per_request_us = 1e6 / off_rps
    overhead = hot_us / per_request_us * 100.0
    paired = 100.0 * (1.0 - statistics.median(ratios)) \
        if ratios else 0.0
    runs_out.append({"mode": "obs", "path": "plane_off",
                     "requests": requests, "threads": THREADS,
                     "passes": PASSES, "requests_s": round(off_rps, 1)})
    runs_out.append({"mode": "obs", "path": "plane_on",
                     "requests": requests, "threads": THREADS,
                     "passes": PASSES, "requests_s": round(on_rps, 1)})
    runs_out.append({"mode": "obs", "path": "obs_overhead",
                     "hot_enqueue_us": round(hot_us, 3),
                     "writer_drain_us": round(drain_us, 3),
                     "per_request_us": round(per_request_us, 1),
                     "overhead_pct": round(overhead, 3),
                     "pair_ratios": [round(r, 4) for r in ratios],
                     "paired_median_pct": round(paired, 2)})


def numerics_overhead_config(runs_out, iters):
    """Secondary: mx.numerics in-program capture cost on the fused
    Module train step.

    The benchmark MLP (8x128, batch 64 — the dispatch-bound workload
    whose µs-scale steps make host-side costs loudest) trains with
    ``numerics.capture`` toggled per pass: OFF, then ``step:10`` (the
    documented production cadence) — interleaved off/on pairs, median
    of the per-pair ratios recorded as the informational
    paired_median_pct (same caveat as obs_overhead: paired end-to-end
    A/B on a noisy box cannot resolve a 2% bound).  The headline
    overhead_pct is deterministic by the PR-17 serial-cost
    decomposition: the only piece of a captured step that runs ON the
    dispatch thread and cannot overlap anything is the publish/poll
    host seam (enqueue the device stats pytree, drain the ready ones
    to host), microbenched per captured step over a
    representative-width stats dict and amortized over the cadence —
    overhead = publish_us / (10 * off_step_us).  The stats reductions
    themselves execute on-device INSIDE the async step program, where
    they overlap the dispatch pipeline and are matmul-dwarfed on the
    TPU target; on CPU the same core pays them serially, so the full
    marginal cost of a captured step (step1_ms - off_ms, a ``step:1``
    pass against the off pass) and the end-to-end pair ratios are
    recorded as the informational cross-check, the same split as the
    telemetry/tracing/resilience guards.  PR acceptance bounds
    overhead_pct at <= 2%."""
    import statistics
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import config as _cfg
    from mxnet_tpu import numerics as _numerics

    layers, width, batch, feat, PASSES = 8, 128, 64, 64, 4
    rng = np.random.RandomState(0)
    X = mx.nd.array(rng.randn(batch, feat).astype(np.float32))
    Y = mx.nd.array((rng.rand(batch) * 10).astype(np.float32))
    batch_obj = mx.io.DataBatch([X], [Y])

    def build_sym():
        h = mx.sym.Variable("data")
        for i in range(layers):
            h = mx.sym.FullyConnected(h, num_hidden=width, name="fc%d" % i)
            h = mx.sym.Activation(h, act_type="relu")
        h = mx.sym.FullyConnected(h, num_hidden=10, name="head")
        return mx.sym.SoftmaxOutput(h, name="softmax")

    _cfg.set("module.fused_step", "auto")
    mod = mx.mod.Module(build_sym())
    mod.bind([("data", (batch, feat))], [("softmax_label", (batch,))])
    mod.init_params(mx.init.Uniform(0.05))
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 1e-3})
    sync = mod._exec.arg_dict["fc0_weight"]

    def one_pass(spec, n):
        _cfg.set("numerics.capture", spec)
        jax.block_until_ready(sync._data)
        t0 = time.perf_counter()
        for _ in range(n):
            mod.train_step(batch_obj)
        jax.block_until_ready(sync._data)
        dt = time.perf_counter() - t0
        _numerics.poll("module", block=True)   # drain off the clock
        return n / dt                          # steps/s

    try:
        # warm BOTH program variants before any timed pass
        _cfg.set("numerics.capture", "step:1")
        for _ in range(3):
            mod.train_step(batch_obj)
        _cfg.set("numerics.capture", "")
        for _ in range(3):
            mod.train_step(batch_obj)
        jax.block_until_ready(sync._data)

        ratios, off_best, on10_best = [], 0.0, 0.0
        for _ in range(PASSES):
            off = max(one_pass("", iters), one_pass("", iters))
            on10 = max(one_pass("step:10", iters),
                       one_pass("step:10", iters))
            ratios.append(on10 / off)
            off_best = max(off_best, off)
            on10_best = max(on10_best, on10)
        on1_best = max(one_pass("step:1", iters),
                       one_pass("step:1", iters))

        # microbench the publish/poll host seam with ready stats at the
        # real fused-MLP site count (~17 op outputs + 18 grads + 18
        # updates)
        import jax.numpy as jnp
        stats = {"site%d" % i: _numerics.summarize(jnp.ones((4,)))
                 for i in range(53)}
        for v in stats.values():
            v.block_until_ready()
        n_pub = 2000
        t0 = time.perf_counter()
        for i in range(n_pub):
            _numerics.publish("bench_numerics", i, stats)
            _numerics.poll("bench_numerics")
        publish_us = (time.perf_counter() - t0) / n_pub * 1e6
    finally:
        _cfg.set("numerics.capture", "")
        _cfg.set("module.fused_step", "auto")
        _numerics.reset()

    off_ms = 1000.0 / off_best
    step1_ms = 1000.0 / on1_best
    captured_extra_ms = max(step1_ms - off_ms, 0.0)
    overhead = publish_us / (10.0 * off_ms * 1000.0) * 100.0
    paired = 100.0 * (1.0 - statistics.median(ratios)) if ratios else 0.0
    runs_out.append({"mode": "numerics", "path": "capture_off",
                     "mlp": "%dx%d" % (layers, width), "batch": batch,
                     "iters": iters, "passes": PASSES,
                     "steps_s": round(off_best, 2)})
    runs_out.append({"mode": "numerics", "path": "capture_step10",
                     "mlp": "%dx%d" % (layers, width), "batch": batch,
                     "iters": iters, "passes": PASSES,
                     "steps_s": round(on10_best, 2)})
    runs_out.append({"mode": "numerics", "path": "numerics_overhead",
                     "step_off_ms": round(off_ms, 4),
                     "step_captured_ms": round(step1_ms, 4),
                     "captured_extra_ms": round(captured_extra_ms, 4),
                     "publish_us": round(publish_us, 2),
                     "overhead_pct": round(overhead, 3),
                     "pair_ratios": [round(r, 4) for r in ratios],
                     "paired_median_pct": round(paired, 2)})


def generation_config(runs_out, requests):
    """Secondary: token-level continuous batching vs static batch-1
    generation, tokens/s and time-to-first-token under mixed lengths.

    One v4 generation artifact (tiny TransformerLM, paged KV cache)
    serves the same mixed-prompt-length request stream two ways: a
    static batch-1 loop calling ``GenerationPredictor.generate`` per
    request (every request decodes alone and every later request waits
    for the WHOLE earlier one), and a burst of ``submit_generate`` into
    a :class:`serving.Server` whose per-iteration scheduler packs up to
    ``serving.decode_slots`` sequences into each single-token decode
    dispatch, admitting queued prefills and exiting finished sequences
    mid-flight.  tokens/s for both paths land under runs[] with mode
    "generation" plus the continuous path's server-side TTFT p50/p99
    (``serving.ttft_ms``); the static path's TTFT p99 is the queue-
    serialization lower bound (elapsed time before a request's generate
    call even STARTS — its own prefill would only add to it).  Surfaces
    as the generation_throughput secondary (docs/SERVING.md).  PR
    acceptance pins continuous > static on tokens/s.

    A second scenario (shared_sysprompt_* rows) holds pool BYTES
    constant and pits the f32-KV no-sharing baseline against int8 KV
    pages (serving.kv_pages doubled) + shared-prefix page reuse + the
    Pallas paged-attention decode kernel under high concurrency with
    one common system prompt; acceptance pins the optimized stack
    >= 1.5x baseline tokens/s with the kernels.paged_attention counter
    proving the kernel served every decode iteration."""
    import math
    import tempfile
    import numpy as np
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import deploy, serving, telemetry
    from mxnet_tpu.models.transformer import (TransformerLM,
                                              TransformerLMConfig)

    VOCAB, PAGE, CTX, SLOTS = 89, 8, 32, 4
    cfg = TransformerLMConfig(
        vocab_size=VOCAB, num_layers=2, d_model=32, num_heads=2,
        d_ff=64, max_len=CTX, dtype=jnp.float32)
    model = TransformerLM(cfg)
    # host-side numpy param init (model.init would spend ~1s compiling
    # jax.random); amplified pos_embed keeps greedy streams position-
    # dependent so decode steps do real work
    prng = np.random.default_rng(0)
    L, D, F = 2, cfg.d_model, cfg.d_ff
    H, Dh = cfg.num_heads, cfg.head_dim

    def mk(*shape):
        return jnp.asarray(
            prng.normal(0.0, 0.02, size=shape).astype(np.float32))

    params = {
        "embed": mk(VOCAB, D),
        "pos_embed": mk(CTX, D) * 25.0,
        "final_norm": jnp.ones((D,), jnp.float32),
        "layers": {
            "ln1": jnp.ones((L, D), jnp.float32),
            "wqkv": mk(L, D, 3, H, Dh),
            "wo": mk(L, H, Dh, D),
            "ln2": jnp.ones((L, D), jnp.float32),
            "w1": mk(L, D, F),
            "w2": mk(L, F, D),
        },
    }
    prefix = os.path.join(tempfile.mkdtemp(prefix="mxtpu_bench_gen_"),
                          "lm")
    deploy.export_generation(model, params, prefix, page_size=PAGE,
                             max_context=CTX, prompt_buckets=(8, 16))

    # mixed lengths across both prefill buckets, budgets that finish at
    # different decode iterations (mid-flight exits + joins)
    mix = [(3, 9), (7, 6), (12, 12), (5, 8), (9, 10), (14, 7)]
    traffic = [mix[i % len(mix)] for i in range(requests)]
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, VOCAB, size=p).astype(np.int32)
               for p, _ in traffic]
    total_new = sum(n for _, n in traffic)

    # static batch-1: each request decodes alone, strictly in turn.
    # One full untimed pass first — the offline predictor jit-caches per
    # (prompt bucket, pool size, table width), so a partial warm would
    # bill compiles to the timed pass.
    pred = deploy.load_generator(prefix)
    for pr, (_, n) in zip(prompts, traffic):
        pred.generate(pr, n)
    starts_ms = []
    t0 = time.perf_counter()
    for pr, (_, n) in zip(prompts, traffic):
        starts_ms.append((time.perf_counter() - t0) * 1000.0)
        pred.generate(pr, n)
    static_wall = time.perf_counter() - t0
    static_tps = total_new / static_wall
    static_ttft_p99 = float(np.percentile(np.asarray(starts_ms), 99))

    # continuous: burst everything, the engine packs the decode batch
    mx.config.set("serving.kv_page_size", PAGE)
    mx.config.set("serving.kv_pages",
                  2 * SLOTS * math.ceil(CTX / PAGE))  # pages never bind
    mx.config.set("serving.decode_slots", SLOTS)
    srv = serving.Server()
    srv.register("lm", prefix, generate=True)
    srv.start()
    try:
        srv.generate("lm", prompts[0], 2)       # warm the dispatch path
        telemetry.timer("serving.ttft_ms").reset()
        t0 = time.perf_counter()
        futs = [srv.submit_generate("lm", pr, n)
                for pr, (_, n) in zip(prompts, traffic)]
        for f in futs:
            f.result(timeout=300)
        cont_wall = time.perf_counter() - t0
        ttft = telemetry.timer("serving.ttft_ms").stats()
    finally:
        srv.stop()
    cont_tps = total_new / cont_wall

    runs_out.append({"mode": "generation", "path": "static_batch1",
                     "requests": requests, "new_tokens": total_new,
                     "tokens_s": round(static_tps, 1),
                     "ttft_p99_ms": round(static_ttft_p99, 1)})
    runs_out.append({"mode": "generation", "path": "continuous",
                     "requests": requests, "new_tokens": total_new,
                     "decode_slots": SLOTS,
                     "tokens_s": round(cont_tps, 1),
                     "ttft_p50_ms": round(ttft["p50"], 1),
                     "ttft_p99_ms": round(ttft["p99"], 1)})
    runs_out.append({"mode": "generation", "path": "speedup",
                     "continuous_over_static":
                         round(cont_tps / static_tps, 2)})

    # --- shared-prefix + int8 KV at CONSTANT pool bytes (PR 20) ------
    # High concurrency with one common system prompt, the page pool
    # deliberately the binding resource.  Baseline: the f32-KV artifact
    # with serving.shared_prefix off at a fixed pool byte budget.
    # Optimized: int8 KV pages DOUBLE serving.kv_pages inside the same
    # byte budget (half-size pages + per-row scales) and shared-prefix
    # page reuse maps every sharer's system-prompt pages to one physical
    # copy — so admissions that stalled on pages now run concurrently
    # and the decode batch stays full.  The optimized artifact exports
    # with the kernel tier explicitly ON and a concrete decode batch, so
    # its decode steps run the Pallas paged-attention kernel
    # (kernels.paged_attention counts every served iteration).
    # PR acceptance pins optimized >= 1.5x baseline tokens/s.
    # 24-token system prompt = 3 full shared pages; 1 divergent prompt
    # token + 7 generated = exactly ONE private page per sharer, so the
    # doubled int8 pool admits 5 sharers where the f32 pool fits one
    SLOTS2, SYS_LEN, DIVERGE, NEW2 = 8, 24, 1, 7
    requests2 = 8 * requests       # long enough to swamp poll jitter
    sys_prompt = rng.randint(0, VOCAB, size=SYS_LEN).astype(np.int32)
    traffic2 = [np.concatenate([sys_prompt,
                                np.asarray([(i + 1) % VOCAB], np.int32)])
                for i in range(requests2)]
    plen2 = SYS_LEN + DIVERGE
    spec = model.kv_spec()
    row = spec["num_layers"] * spec["num_heads"] * spec["head_dim"]
    page_bytes_f32 = 2 * row * PAGE * np.dtype(spec["dtype"]).itemsize
    page_bytes_int8 = (2 * row * PAGE
                       + 2 * spec["num_layers"] * spec["num_heads"]
                       * PAGE * 4)
    # byte budget = exactly ONE f32 request resident: the pool-bound
    # regime the scenario is about (baseline decodes serially)
    pages_f32 = math.ceil((plen2 + NEW2) / PAGE)
    pages_int8 = 2 * pages_f32                         # same byte budget
    assert pages_int8 * page_bytes_int8 <= pages_f32 * page_bytes_f32
    total_new2 = requests2 * NEW2

    gen_dir = tempfile.mkdtemp(prefix="mxtpu_bench_gen2_")
    base_prefix = os.path.join(gen_dir, "base")
    deploy.export_generation(model, params, base_prefix,
                             page_size=PAGE, max_context=CTX,
                             prompt_buckets=(32,))
    opt_prefix = os.path.join(gen_dir, "opt")
    mx.config.set("kernels.enabled", True)
    try:
        deploy.export_generation(model, params, opt_prefix,
                                 page_size=PAGE, max_context=CTX,
                                 prompt_buckets=(32,), sampling=True,
                                 kv_quantized=True, decode_batch=SLOTS2)
    finally:
        mx.config.unset("kernels.enabled")

    def shared_run(prefix, pages, share, label):
        mx.config.set("serving.kv_pages", pages)
        mx.config.set("serving.decode_slots", SLOTS2)
        mx.config.set("serving.shared_prefix", share)
        srv2 = serving.Server()
        try:
            srv2.register(label, prefix, generate=True)
            srv2.start()
            srv2.generate(label, traffic2[0], 2)   # warm dispatch
            telemetry.timer("serving.ttft_ms").reset()
            gauge = telemetry.gauge("serving.kv_pages_in_use.%s" % label)
            paged0 = telemetry.counter("kernels.paged_attention").value
            t0 = time.perf_counter()
            futs = [srv2.submit_generate(label, pr, NEW2)
                    for pr in traffic2]
            # sample the in-use gauge only until the pool proves full —
            # polling past that point just steals cycles from the
            # single-core engine thread and skews the measurement
            peak = 0
            while peak < pages and not all(f.done() for f in futs):
                peak = max(peak, int(gauge.value))
                time.sleep(0.005)
            for f in futs:
                f.result(timeout=300)
            wall = time.perf_counter() - t0
            ttft2 = telemetry.timer("serving.ttft_ms").stats()
            paged_iters = telemetry.counter(
                "kernels.paged_attention").value - paged0
        finally:
            srv2.stop()
            mx.config.unset("serving.shared_prefix")
        return {"tokens_s": total_new2 / wall,
                "ttft_p99_ms": ttft2["p99"],
                "kv_pages_in_use_peak": peak,
                "paged_kernel_iterations": int(paged_iters)}

    base = shared_run(base_prefix, pages_f32, False, "lm_base")
    opt = shared_run(opt_prefix, pages_int8, True, "lm_int8_shared")
    runs_out.append({
        "mode": "generation", "path": "shared_sysprompt_f32_baseline",
        "requests": requests2, "new_tokens": total_new2,
        "decode_slots": SLOTS2, "kv_pages": pages_f32,
        "pool_bytes": pages_f32 * page_bytes_f32,
        "shared_prefix": False,
        "tokens_s": round(base["tokens_s"], 1),
        "ttft_p99_ms": round(base["ttft_p99_ms"], 1),
        "kv_pages_in_use_peak": base["kv_pages_in_use_peak"]})
    runs_out.append({
        "mode": "generation", "path": "shared_sysprompt_int8_shared",
        "requests": requests2, "new_tokens": total_new2,
        "decode_slots": SLOTS2, "kv_pages": pages_int8,
        "pool_bytes": pages_int8 * page_bytes_int8,
        "shared_prefix": True,
        "tokens_s": round(opt["tokens_s"], 1),
        "ttft_p99_ms": round(opt["ttft_p99_ms"], 1),
        "kv_pages_in_use_peak": opt["kv_pages_in_use_peak"],
        "paged_kernel_iterations": opt["paged_kernel_iterations"]})
    runs_out.append({
        "mode": "generation", "path": "shared_int8_speedup",
        "pages_ratio": round(pages_int8 / pages_f32, 2),
        "int8_shared_over_f32_baseline":
            round(opt["tokens_s"] / base["tokens_s"], 2)})


def transformer_kernels_config(runs_out, on_tpu):
    """Secondary: the mx.kernels tier on the transformer hot path.

    Three paired measurements, every program registered with mx.perf
    under the "kernels" family so achieved FLOPs come from the
    compiler's own cost analysis, not hand math:

    * attention — the fused Pallas flash kernel vs the XLA lowering on
      the same [B,H,S,D] problem, per-op wall ms + achieved GFLOP/s
      (on CPU the kernel runs in the Pallas interpreter: numerics
      proven, speed meaningless — the deltas only bind on TPU);
    * train step — a small TransformerLM Adam step with the tier off
      vs on (flash attention + fused optimizer epilogue), same seed;
    * stack tuning — trace+compile ms of the SAME loss program built
      with runtime.stack_mode=unroll vs scan (perf phases_ms), equal
      loss required.
    """
    import numpy as np
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import config as _cfg
    from mxnet_tpu import kernels as _kernels
    from mxnet_tpu import perf as _perf
    from mxnet_tpu.models.transformer import (TransformerLM,
                                              TransformerLMConfig)

    B, H, S, D = (4, 8, 1024, 64) if on_tpu else (1, 2, 128, 32)
    iters = 20 if on_tpu else 3
    rng = np.random.RandomState(7)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D), dt) for _ in range(3))

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    def attn_row(path, enabled):
        _cfg.set("kernels.enabled", enabled)
        key = "attention/%s/b%dh%ds%dd%d" % (path, B, H, S, D)
        fn = _perf.wrap(
            jax.jit(lambda q, k, v: _kernels.attention(q, k, v,
                                                       causal=True)),
            "kernels", key)
        ms = timed(fn, q, k, v)
        rec = _perf.program("kernels", key) or {}
        row = {"mode": "transformer_kernels", "path": "attention_" + path,
               "shape": [B, H, S, D], "wall_ms": round(ms, 3)}
        if rec.get("flops"):
            row["flops"] = rec["flops"]
            row["achieved_gflops"] = round(rec["flops"] / (ms / 1e3) / 1e9,
                                           3)
        return row

    try:
        xla_row = attn_row("xla", False)
        flash_row = attn_row("flash", True)
        runs_out.append(xla_row)
        runs_out.append(flash_row)

        # ---- train step, tier off vs on (same seed, Adam)
        cfg = TransformerLMConfig(vocab_size=256, num_layers=2,
                                  d_model=4 * D, num_heads=H, d_ff=8 * D,
                                  max_len=S, dtype=jnp.float32)
        model = TransformerLM(cfg)
        tok = jnp.asarray(rng.randint(0, 256, (B, S)), jnp.int32)
        opt = mx.optimizer.create("adam", learning_rate=1e-3)

        def train_row(path, enabled):
            _cfg.set("kernels.enabled", enabled)
            params = model.init(jax.random.PRNGKey(11))
            leaves, treedef = jax.tree_util.tree_flatten(params)
            state = [(jnp.zeros_like(w), jnp.zeros_like(w))
                     for w in leaves]
            fused = _kernels.fused_step_enabled(opt)

            def step(leaves, state, t):
                loss, grads = jax.value_and_grad(
                    lambda lv: model.loss(
                        jax.tree_util.tree_unflatten(treedef, lv),
                        tok, tok))(leaves)
                new_l, new_s = [], []
                for w, g, s in zip(leaves, grads, state):
                    if fused and w.dtype == jnp.float32:
                        nw, _m, ns = opt.step_fused(
                            w, g, s, 1e-3, 0.0, t, out_dtype=w.dtype)
                    else:
                        nw, ns = opt.step(w, g, s, 1e-3, 0.0, t)
                        nw = nw.astype(w.dtype)
                    new_l.append(nw)
                    new_s.append(ns)
                return new_l, new_s, loss

            key = "train/kernels=%s" % ("on" if enabled else "off")
            fn = _perf.wrap(jax.jit(step), "kernels", key)
            loss = None
            t0 = time.perf_counter()
            for i in range(iters):
                leaves, state, loss = fn(leaves, state, i + 1)
            jax.block_until_ready(loss)
            ms = (time.perf_counter() - t0) / iters * 1e3
            rec = _perf.program("kernels", key) or {}
            row = {"mode": "transformer_kernels", "path": "train_" + path,
                   "steps": iters, "step_ms": round(ms, 3),
                   "loss": float(loss)}
            if rec.get("flops"):
                row["flops"] = rec["flops"]
                row["achieved_gflops"] = round(
                    rec["flops"] / (ms / 1e3) / 1e9, 3)
            return row

        t_off = train_row("off", False)
        t_on = train_row("on", True)
        runs_out.append(t_off)
        runs_out.append(t_on)
        runs_out.append({"mode": "transformer_kernels",
                         "path": "train_loss_delta",
                         "abs_delta": round(
                             abs(t_on["loss"] - t_off["loss"]), 8)})

        # ---- scan vs unroll: trace+compile ms at equal loss
        _cfg.set("kernels.enabled", False)
        deep = TransformerLMConfig(vocab_size=256, num_layers=8,
                                   d_model=64, num_heads=4, d_ff=128,
                                   max_len=64, dtype=jnp.float32)
        dmodel = TransformerLM(deep)
        dparams = dmodel.init(jax.random.PRNGKey(3))
        dtok = jnp.asarray(rng.randint(0, 256, (2, 64)), jnp.int32)
        stack = {}
        for mode in ("unroll", "scan"):
            _cfg.set("runtime.stack_mode", mode)
            key = "stack/%s" % mode
            fn = _perf.wrap(jax.jit(dmodel.loss), "kernels", key)
            loss = fn(dparams, dtok, dtok)
            jax.block_until_ready(loss)
            rec = _perf.program("kernels", key) or {}
            ph = rec.get("phases_ms", {})
            build_ms = round(ph.get("trace_ms", 0.0) +
                             ph.get("compile_ms", 0.0) +
                             ph.get("lower_ms", 0.0), 1)
            stack[mode] = {"loss": float(loss), "build_ms": build_ms}
            runs_out.append({"mode": "transformer_kernels",
                             "path": "stack_" + mode,
                             "layers": deep.num_layers,
                             "build_ms": build_ms,
                             "phases_ms": ph, "loss": float(loss)})
        _cfg.set("runtime.stack_mode", "scan")
        runs_out.append({
            "mode": "transformer_kernels", "path": "stack_speedup",
            "unroll_over_scan_build":
                round(stack["unroll"]["build_ms"] /
                      max(stack["scan"]["build_ms"], 1e-9), 3),
            "loss_delta": round(abs(stack["scan"]["loss"] -
                                    stack["unroll"]["loss"]), 8)})
    finally:
        _cfg.set("kernels.enabled", False)
        _cfg.set("runtime.stack_mode", "scan")


def autotune_config(runs_out, on_tpu):
    """Secondary: mx.perf.autotune tuned-vs-untuned on the attention hot
    path (BENCH_r06).  Three legs against one [B,H,S,D] problem:

    * untuned — ``perf.autotune=off``: the tier's legacy routing (flash
      wherever feasible, default block_q), no measured picks anywhere;
    * search — the one-time measured block_q sweep in ``measure`` mode,
      winner persisted to a private cache; its wall cost is the price a
      cold site pays exactly once per (config-fingerprint, device);
    * tuned — a fresh program traced AFTER the search: the cached
      winner applies at trace time with zero re-measurement (the
      ``autotune.measure`` counter delta across the timed leg is
      asserted into the row, not assumed).
    """
    import tempfile
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu import autotune as _autotune
    from mxnet_tpu import config as _cfg
    from mxnet_tpu import kernels as _kernels
    from mxnet_tpu import telemetry as _tel

    B, H, S, D = (4, 8, 1024, 64) if on_tpu else (1, 2, 128, 32)
    iters = 20 if on_tpu else 3
    rng = np.random.RandomState(9)
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    q, k, v = (jnp.asarray(rng.randn(B, H, S, D), dt) for _ in range(3))

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    def attn(q, k, v):
        return _kernels.attention(q, k, v, causal=True)

    cache = os.path.join(tempfile.mkdtemp(prefix="mxtpu_bench_at_"),
                         "autotune.json")
    try:
        _cfg.set("perf.autotune", "off")
        _autotune.reset()
        ms_off = timed(jax.jit(attn), q, k, v)
        runs_out.append({"mode": "autotune", "path": "untuned",
                         "shape": [B, H, S, D],
                         "wall_ms": round(ms_off, 3)})

        _cfg.set("perf.autotune_cache", cache)
        _cfg.set("perf.autotune", "measure")
        _autotune.reset()
        t0 = time.perf_counter()
        entry = _autotune.search_attention(
            (B, H, S, D), (B, H, S, D), str(q.dtype), True)
        search_ms = (time.perf_counter() - t0) * 1e3
        runs_out.append({"mode": "autotune", "path": "search",
                         "search_ms": round(search_ms, 1),
                         "impl": entry.get("impl"),
                         "block_q": entry.get("block_q"),
                         "parity": entry.get("parity"),
                         "speedup": entry.get("speedup"),
                         "candidates": entry.get("candidates")})

        m0 = _tel.counter("autotune.measure").value
        ms_tuned = timed(jax.jit(attn), q, k, v)  # fresh trace: pick applies
        re_measure = _tel.counter("autotune.measure").value - m0
        runs_out.append({"mode": "autotune", "path": "tuned",
                         "wall_ms": round(ms_tuned, 3),
                         "impl": entry.get("impl"),
                         "re_measure": re_measure})
        runs_out.append({"mode": "autotune", "path": "delta",
                         "tuned_over_untuned":
                             round(ms_off / max(ms_tuned, 1e-9), 3),
                         "search_ms": round(search_ms, 1)})
    finally:
        _cfg.unset("perf.autotune")
        _cfg.unset("perf.autotune_cache")
        _autotune.reset()


def _summarize(runs):
    """One JSON result from the completed sweep configs (best bf16 TRAIN
    run wins — inference runs are reported in `runs` but never headline,
    since vs_baseline compares training against the training baseline)."""
    timed = [r for r in runs if "img_s" in r]
    train = [r for r in timed if r.get("mode") != "inference"]
    bf16 = [r for r in train if r["dtype"] == "bfloat16"]
    best = max(bf16 or train or timed, key=lambda r: r["img_s"])
    secondary = {}
    mod_runs = {r.get("path"): r for r in runs
                if r.get("mode") == "module_train"}
    if "fused" in mod_runs:
        secondary["module_mlp_train_throughput"] = {
            "value": mod_runs["fused"]["samples_s"],
            "unit": "samples/s",
            "mlp": mod_runs["fused"]["mlp"],
            "batch": mod_runs["fused"]["batch"],
        }
        if "speedup" in mod_runs:
            secondary["module_mlp_train_throughput"]["fused_over_eager"] = \
                mod_runs["speedup"]["fused_over_eager"]
        if "telemetry_overhead" in mod_runs:
            secondary["module_mlp_train_throughput"][
                "telemetry_overhead_pct"] = \
                mod_runs["telemetry_overhead"]["overhead_pct"]
        if "tracing_overhead" in mod_runs:
            secondary["module_mlp_train_throughput"][
                "tracing_overhead_pct"] = \
                mod_runs["tracing_overhead"]["overhead_pct"]
        if "resilience_overhead" in mod_runs:
            secondary["module_mlp_train_throughput"][
                "resilience_overhead_pct"] = \
                mod_runs["resilience_overhead"]["overhead_pct"]
    ip_runs = {r.get("path"): r for r in runs
               if r.get("mode") == "input_pipeline"}
    if "device_prefetch" in ip_runs and "host_prefetch" in ip_runs:
        secondary["input_pipeline_overlap"] = {
            "device_prefetch_samples_s":
                ip_runs["device_prefetch"]["samples_s"],
            "host_prefetch_samples_s":
                ip_runs["host_prefetch"]["samples_s"],
            "unit": "samples/s",
            "device_over_host":
                ip_runs.get("overlap", {}).get("device_over_host"),
        }
    emb_runs = {r.get("path"): r for r in runs
                if r.get("mode") == "dlrm_embedding"}
    if "sparse" in emb_runs and "dense" in emb_runs:
        secondary["dlrm_embedding_throughput"] = {
            "sparse_samples_s": emb_runs["sparse"]["samples_s"],
            "dense_samples_s": emb_runs["dense"]["samples_s"],
            "unit": "samples/s",
            "sparse_over_dense":
                emb_runs.get("speedup", {}).get("sparse_over_dense"),
            "unique_ratio": emb_runs["sparse"].get("unique_ratio"),
            "vocab": emb_runs["sparse"].get("vocab"),
        }
    srv_runs = {r.get("path"): r for r in runs
                if r.get("mode") == "serving"}
    if "continuous" in srv_runs and "sequential_batch1" in srv_runs:
        secondary["serving_throughput"] = {
            "continuous_requests_s":
                srv_runs["continuous"]["requests_s"],
            "sequential_batch1_requests_s":
                srv_runs["sequential_batch1"]["requests_s"],
            "unit": "requests/s",
            "continuous_over_sequential":
                srv_runs.get("speedup", {}).get(
                    "continuous_over_sequential"),
            "queue_delay_p99_ms":
                srv_runs["continuous"].get("queue_delay_p99_ms"),
            "batch_fill_mean":
                srv_runs["continuous"].get("batch_fill_mean"),
        }
    q_runs = {r.get("path"): r for r in runs
              if r.get("mode") == "quantized_serving"}
    if "int8" in q_runs and "fp32" in q_runs:
        secondary["quantized_serving_throughput"] = {
            "int8_requests_s": q_runs["int8"]["requests_s"],
            "fp32_requests_s": q_runs["fp32"]["requests_s"],
            "unit": "requests/s",
            "int8_over_fp32":
                q_runs.get("speedup", {}).get("int8_over_fp32"),
            "measured_error": q_runs["int8"].get("measured_error"),
        }
    o_runs = {r.get("path"): r for r in runs
              if r.get("mode") == "obs"}
    if "plane_on" in o_runs and "plane_off" in o_runs:
        secondary["obs_overhead"] = {
            "plane_off_requests_s": o_runs["plane_off"]["requests_s"],
            "plane_on_requests_s": o_runs["plane_on"]["requests_s"],
            "unit": "requests/s",
            "overhead_pct":
                o_runs.get("obs_overhead", {}).get("overhead_pct"),
            "paired_median_pct":
                o_runs.get("obs_overhead", {}).get("paired_median_pct"),
        }
    n_runs = {r.get("path"): r for r in runs
              if r.get("mode") == "numerics"}
    if "capture_off" in n_runs and "capture_step10" in n_runs:
        secondary["numerics_overhead"] = {
            "capture_off_steps_s": n_runs["capture_off"]["steps_s"],
            "capture_step10_steps_s":
                n_runs["capture_step10"]["steps_s"],
            "unit": "steps/s",
            "overhead_pct":
                n_runs.get("numerics_overhead", {}).get("overhead_pct"),
            "captured_extra_ms":
                n_runs.get("numerics_overhead", {}).get(
                    "captured_extra_ms"),
            "paired_median_pct":
                n_runs.get("numerics_overhead", {}).get(
                    "paired_median_pct"),
        }
    g_runs = {r.get("path"): r for r in runs
              if r.get("mode") == "generation"}
    if "continuous" in g_runs and "static_batch1" in g_runs:
        secondary["generation_throughput"] = {
            "continuous_tokens_s": g_runs["continuous"]["tokens_s"],
            "static_batch1_tokens_s": g_runs["static_batch1"]["tokens_s"],
            "unit": "tokens/s",
            "continuous_over_static":
                g_runs.get("speedup", {}).get("continuous_over_static"),
            "ttft_p50_ms": g_runs["continuous"].get("ttft_p50_ms"),
            "ttft_p99_ms": g_runs["continuous"].get("ttft_p99_ms"),
            "static_ttft_p99_ms":
                g_runs["static_batch1"].get("ttft_p99_ms"),
            "decode_slots": g_runs["continuous"].get("decode_slots"),
        }
    k_runs = {r.get("path"): r for r in runs
              if r.get("mode") == "transformer_kernels"}
    if "attention_flash" in k_runs:
        secondary["transformer_kernels"] = {
            "attention_flash_gflops":
                k_runs["attention_flash"].get("achieved_gflops"),
            "attention_xla_gflops":
                k_runs["attention_xla"].get("achieved_gflops"),
            "attention_shape": k_runs["attention_flash"].get("shape"),
            "train_on_step_ms": k_runs.get("train_on", {}).get("step_ms"),
            "train_off_step_ms":
                k_runs.get("train_off", {}).get("step_ms"),
            "train_loss_delta":
                k_runs.get("train_loss_delta", {}).get("abs_delta"),
            "scan_build_ms": k_runs.get("stack_scan", {}).get("build_ms"),
            "unroll_build_ms":
                k_runs.get("stack_unroll", {}).get("build_ms"),
            "unroll_over_scan_build":
                k_runs.get("stack_speedup", {}).get(
                    "unroll_over_scan_build"),
        }
    a_runs = {r.get("path"): r for r in runs
              if r.get("mode") == "autotune"}
    if "tuned" in a_runs and "untuned" in a_runs:
        secondary["autotune_delta"] = {
            "untuned_ms": a_runs["untuned"]["wall_ms"],
            "tuned_ms": a_runs["tuned"]["wall_ms"],
            "unit": "ms",
            "tuned_over_untuned":
                a_runs.get("delta", {}).get("tuned_over_untuned"),
            "winner": a_runs["tuned"].get("impl"),
            "search_ms": a_runs.get("delta", {}).get("search_ms"),
            "re_measure": a_runs["tuned"].get("re_measure"),
        }
    return dict(secondary, **{
        "metric": "resnet50_train_throughput",
        "value": best["img_s"],
        "unit": "img/s",
        "vs_baseline": round(best["img_s"] / BASELINE_IMG_S, 3),
        "batch": best["batch"],
        "dtype": best["dtype"],
        "tflops": best["tflops"],
        "mfu": best["mfu"],
        "peak_tflops_assumed": best["peak_tflops"],
        "runs": list(runs),
        "baseline_note": "baseline 363.69 img/s = fp32 V100 BS128 "
                         "(reference perf.md:254)",
    })


def _lint_preflight():
    """Refuse to burn a bench sweep on a tree with open mxlint findings
    (docs/ANALYSIS.md): a tracer leak or an unguarded cross-thread write
    discovered AFTER a long run invalidates the numbers it produced.
    Returns the findings text, or None when clean."""
    import subprocess
    mxlint = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "mxlint.py")
    proc = subprocess.run([sys.executable, mxlint],
                          capture_output=True, text=True, timeout=120)
    if proc.returncode != 0:
        return (proc.stdout.strip() or proc.stderr.strip())[-2000:]
    return None


# (phase, args on the chip, args for the MXTPU_BENCH_CPU dry run).  The
# ResNet sweep is the headline; the rest are the secondaries _summarize
# folds in.  Each raises on its own error and so fails the run.
PHASES = (
    (resnet_config, (True,), (False,)),
    (module_train_config, (40, 10), (20, 5)),
    (input_pipeline_config, (96,), (48,)),
    (dlrm_embedding_config, (24,), (8,)),
    (serving_config, (512,), (256,)),
    (quantized_serving_config, (512,), (128,)),
    (obs_overhead_config, (512,), (256,)),
    (numerics_overhead_config, (60,), (30,)),
    (generation_config, (24,), (12,)),
    (transformer_kernels_config, (True,), (False,)),
    (autotune_config, (True,), (False,)),
)


def main():
    # the lint runs in a child: start it before this process touches jax,
    # and so the chip
    findings = _lint_preflight()
    if findings is not None:
        sys.exit("bench: mxlint preflight failed — fix or baseline the "
                 "findings (tools/mxlint.py):\n%s" % findings)
    if os.environ.get("MXTPU_BENCH_CPU"):
        # dry run of the control flow on the cpu backend, asked for by name
        jax.config.update("jax_platforms", "cpu")
    print(json.dumps(run_bench([])), flush=True)


if __name__ == "__main__":
    main()
