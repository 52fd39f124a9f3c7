"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of models the repo ships, in ONE process (a chip belongs to
one process; nothing here starts a child once jax is touched):

  device   the backend is a TPU, ``mx.tpu(0)`` is a TPU device, the device
           kind is in the one peak table (``mx.perf.DEVICE_PEAKS``)
  train    the README quick start: ResNet-50 v1, ``SPMDTrainer`` bf16 on a
           one-device mesh, batch 128 x 3x224x224, a few timed steps
  serve    the generation server at the default ``TransformerLMConfig``:
           ``export_generation`` ON THE CHIP, ``Server.register(...,
           generate=True)``, ragged prompts some sharing a prefix, streams
           checked against ``TransformerLM.greedy_decode``
  serve_hybrid  the same server over the second kind of cache: a two-
           period ``HybridLM`` (Mamba-2, held experts, grouped-query
           attention), K/V pages beside per-slot recurrent state, more
           requests than slots, the paged kernel's route counted
  retention  the third kind of cache row: one power-retention block at
           toy widths with real lanes (heads of 128), its chunked prefill
           and its decode update, compiled, against the quadratic form
           that defines the layer; the update's kernel against its twin,
           and behind the server, its route counted
  kernels  every Pallas kernel on those paths, compiled, against its XLA twin

``--chips 4`` runs ``device`` and, instead of the phases above, the
paths that exist only across chips: ResNet-50 on a dp=4 mesh and one
TransformerLM loss+grad step on a dp=2 x tp=2 mesh, each against the same
computation on a one-device mesh in the same process.

Every phase prints one JSON line of set-up facts (compile seconds, step ms,
counters, peak bytes, tolerances found — NOT benchmark results) and fails
the run, non-zero exit, on its own error.  The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without an accelerator the script fails in ``device`` and prints no such
line.  ``--rehearse`` walks the same control flow at tiny sizes wherever jax
runs (cpu, Pallas interpreter) to find wrong paths before a chip run; it
never prints the success line.

Weights and data come from ``--seed``; nothing is read from outside the
checkout, and the compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says or to the checkout's ``.jax_cache``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import tempfile
import time


class SmokeFailure(AssertionError):
    """A phase's own check failed."""


def _require(cond, what):
    if not cond:
        raise SmokeFailure(what)


def _emit(phase, **facts):
    print(json.dumps(dict(phase=phase, **facts)), flush=True)


def _scaled_err(got, want):
    """(max abs error, that error over the twin's largest magnitude) —
    a relative error that a zero in the twin cannot blow up."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    _require(got.shape == want.shape,
             "shape %s != twin's %s" % (got.shape, want.shape))
    _require(bool(np.isfinite(got).all()), "non-finite values")
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    return err, err / max(float(np.max(np.abs(want))) if want.size else 0.0,
                          1e-30)


@contextlib.contextmanager
def _knobs(**values):
    """Set config knobs by name (dots as ``__``) for a block, then put
    them back to their default source."""
    from mxnet_tpu import config
    names = [k.replace("__", ".") for k in values]
    for name, value in zip(names, values.values()):
        config.set(name, value)
    try:
        yield
    finally:
        for name in names:
            config.unset(name)


def _peak_bytes(dev):
    stats = dev.memory_stats()  # None where the backend keeps no stats
    return stats.get("peak_bytes_in_use") if stats else None


# ------------------------------------------------------------------ sizes
def _sizes(rehearse):
    """The real sizes, or the tiny ones of a rehearsal.  Same keys, same
    code paths."""
    if rehearse:
        return dict(
            vision="resnet18_v1", classes=10, image=32, batch=4,
            warm=1, timed=2, settle=2,
            lm=dict(vocab_size=256, num_layers=2, d_model=64, num_heads=4,
                    d_ff=128, max_len=128),
            prompts=(4, 9, 20, 33, 40, 47, 64, 90), shared=(2, 3, 4),
            prompt_buckets=(8, 32, 64, 128),
            prefix=16, new_tokens=4, decode_batch=8, kv_pages=96,
            hybrid=dict(vocab_size=256, pattern="MEM*EM*E", d_model=64,
                        num_heads=4, num_kv_heads=2, head_dim=16,
                        ssm_heads=8, ssm_head_dim=8, ssm_groups=2,
                        ssm_state=16, chunk=8, num_experts=16, top_k=3,
                        # (experts of whole lanes: the grouped kernel's)
                        moe_latent=128, expert_ff=128, shared_ff=96,
                        route_scale=5.0, experts_held=8, expert_offset=4,
                        max_len=128),
            hybrid_serve=dict(prompts=(4, 9, 20, 30, 7, 13),
                              prompt_buckets=(8, 32), new_tokens=6,
                              decode_batch=4, page=16, kv_pages=32),
            retention=dict(
                lm=dict(vocab_size=64, pattern="RF", d_model=64, num_heads=4,
                        num_kv_heads=2, head_dim=16, mlp_ff=96, chunk=8,
                        max_len=64),
                rows=2, prompt=11, steps=3,
                # (the update's kernel wants whole lanes: rows, K/V heads,
                # query heads a K/V head, head width)
                update=(1, 1, 2, 128),
                serve=dict(prompts=(5, 11, 3), bucket=16, new_tokens=3,
                           page=4)),
            flash=((2, 4, 32, 16), (1, 2, 24, 16)), paged_k=(32, 64),
            grouped=(200, 8, 128, 256), softmax=(16, 48), sbr=(16, 40),
            dp_batch=16, lm_batch=4, lm_seq=32)
    return dict(
        vision="resnet50_v1", classes=1000, image=224, batch=128,
        warm=2, timed=8, settle=21,
        lm={},  # the default TransformerLMConfig: 12L/768/12H/3072/32000/2048
        prompts=(32, 96, 200, 384, 512, 640, 800, 1024), shared=(2, 3, 4),
        # the buckets these prompts need, not the whole pow2 family up to
        # max_len: every exported program sorts the vocabulary for top-p,
        # and that sort alone is ~20 s of TPU compile per program
        prompt_buckets=(32, 128, 256, 512, 1024),
        prefix=128, new_tokens=32, decode_batch=8, kv_pages=512,
        # two periods of a Mamba-2 / experts / attention pattern, toy
        # widths with the lanes of real ones (heads of 128, state 128)
        hybrid=dict(vocab_size=8192, pattern="MEM*EM*E", d_model=1024,
                    num_heads=16, num_kv_heads=2, head_dim=128,
                    ssm_heads=32, ssm_head_dim=64, ssm_groups=4,
                    ssm_state=128, chunk=128, num_experts=32, top_k=4,
                    moe_latent=256, expert_ff=512, shared_ff=1024,
                    route_scale=5.0, experts_held=16, expert_offset=8,
                    max_len=1024),
        hybrid_serve=dict(prompts=(40, 100, 200, 384, 130, 61, 250, 17,
                                   300, 90),
                          prompt_buckets=(128, 512), new_tokens=24,
                          decode_batch=8, page=128, kv_pages=64),
        # one retention block at toy widths with the lanes of real ones
        # (heads of 128: a state of 8,256 x 128 a K/V head), a prompt off
        # the chunk's edge
        retention=dict(
            lm=dict(vocab_size=1024, pattern="RF", d_model=1024, num_heads=10,
                    num_kv_heads=2, head_dim=128, mlp_ff=2048, chunk=128,
                    max_len=512),
            rows=4, prompt=300, steps=12, update=(4, 2, 5, 128),
            serve=dict(prompts=(40, 300, 130, 7, 61), bucket=512,
                       new_tokens=12, page=128)),
        flash=((8, 12, 1024, 64), (2, 12, 200, 64)), paged_k=(1024, 2048),
        # rows, groups, K, N: a decode step's pairs over a quarter of the
        # hybrid cell's held experts, at their widths
        grouped=(2816, 32, 1024, 2688), softmax=(4096, 1024),
        sbr=(4096, 768),
        dp_batch=512, lm_batch=4, lm_seq=1024)


# ----------------------------------------------------------------- device
def phase_device(args):
    import jax
    import mxnet_tpu as mx

    devs = jax.devices()
    kind = devs[0].device_kind
    facts = dict(platform=devs[0].platform, device_kind=kind,
                 count=len(devs), backend=jax.default_backend(),
                 jax=jax.__version__, accelerator_is_real=
                 mx.context.accelerator_is_real(),
                 peaks_bf16_tflops_hbm_gbps=mx.perf.DEVICE_PEAKS.get(kind),
                 # record files are the only native code; neither path
                 # below reads one, so libmxtpu_native.so is never built
                 native_io="not used")
    facts["compile_cache_dir"] = mx.runtime.configure_compile_cache()
    facts["compile_cache_entries_at_start"] = _cache_entries(
        facts["compile_cache_dir"])
    _emit("device", **facts)
    _require(len(devs) >= args.chips,
             "--chips %d, but jax reports %d device(s)"
             % (args.chips, len(devs)))
    if args.rehearse:
        return
    _require(jax.default_backend() == "tpu",
             "jax found no TPU: the default backend is %r"
             % jax.default_backend())
    mx.context.require_accelerator("chip_smoke.py")
    for ctx in (mx.current_context(), mx.tpu(0)):
        _require(ctx.jax_device.platform == "tpu",
                 "%r resolves to a %s device" % (ctx, ctx.jax_device.platform))
    n_tpu = sum(d.platform == "tpu" for d in devs)
    _require(mx.num_tpus() == n_tpu, "mx.num_tpus()=%d but jax has %d TPU "
             "device(s)" % (mx.num_tpus(), n_tpu))
    mx.perf.peak_flops(kind)  # UnknownDeviceError: not in the peak table


def _cache_entries(cache_dir):
    import glob
    return len(glob.glob(os.path.join(cache_dir, "*-cache")))


class _CacheEvents:
    """Counts jax's persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("/compilation_cache/cache_misses"):
            self.misses += 1


# ------------------------------------------------------------------ train
def _trainer(net, mesh):
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import SPMDTrainer
    return SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
                       mesh=mesh, dtype="bfloat16")


def _vision_net(sz):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    net = vision.get_model(sz["vision"], classes=sz["classes"])
    net.initialize(mx.init.Xavier())
    return net


def _image_batch(sz, batch, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    data = rng.uniform(size=(batch, 3, sz["image"], sz["image"])).astype(
        np.float32)
    label = rng.randint(0, sz["classes"], (batch,)).astype(np.float32)
    return data, label


def phase_train(args, sz):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel import make_mesh

    dev = jax.devices()[0]
    mx.random.seed(args.seed)
    net = _vision_net(sz)
    tr = _trainer(net, make_mesh({"dp": 1}, jax.devices()[:1]))
    data, label = _image_batch(sz, sz["batch"], args.seed)

    t0 = time.perf_counter()
    losses = [tr.step(data, label)]          # materialize + compile
    jax.block_until_ready(losses[0])
    first_step_s = time.perf_counter() - t0
    ddev = jax.device_put(jnp.asarray(data), tr._batch_sharding)
    ldev = jax.device_put(jnp.asarray(label), tr._batch_sharding)
    for _ in range(sz["warm"]):
        losses.append(tr.step(ddev, ldev))
    jax.block_until_ready(losses[-1])

    compiles0 = profiler.counters()["fused_compiles"]
    programs0 = len(mx.perf.programs("spmd"))

    def timed(finish):
        t0 = time.perf_counter()
        for _ in range(sz["timed"]):
            losses.append(tr.step(ddev, ldev))
        enqueued = time.perf_counter() - t0
        finish(losses[-1])
        return enqueued, time.perf_counter() - t0

    # Does block_until_ready wait on this backend?  End one window with
    # it and then fetch the same loss: if the wait waited, the fetch of a
    # finished scalar is a fraction of one step.  A second window ends in
    # the fetch alone, for the same number of steps.
    enq_b, wall_b = timed(jax.block_until_ready)
    t0 = time.perf_counter()
    np.asarray(losses[-1])
    fetch_after_block_s = time.perf_counter() - t0
    enq_f, wall_f = timed(np.asarray)
    # SGD at lr 0.1 with momentum, no warm-up, one repeated batch: on the
    # chip the loss swings between 6 and 10 for its first ~16 steps, then
    # falls — and spikes again for a dozen steps now and then (to 25 at
    # step 23 in one run, at step 48 in another; my chip runs, PR 21).  So
    # run past the swings, and ask whether the loss has been below its
    # start in the second half of the run, not whether one step sits low.
    for _ in range(sz["settle"]):
        losses.append(tr.step(ddev, ldev))
    step_ms = wall_b / sz["timed"] * 1e3
    waits = fetch_after_block_s < 0.25 * step_ms / 1e3

    host = [float(v) for v in losses]
    on_dev = all(set(v.devices()) == {dev}
                 for v in jax.tree_util.tree_leaves((tr.params, tr.opt_state)))
    _emit("train", model=sz["vision"], batch=sz["batch"], dtype="bfloat16",
          first_step_s=round(first_step_s, 2), step_ms=round(step_ms, 3),
          steps_timed=sz["timed"], losses=[round(v, 4) for v in host],
          timing_end=dict(
              block_until_ready_s=round(wall_b, 4),
              enqueue_only_s=round(enq_b, 4),
              fetch_after_block_s=round(fetch_after_block_s, 5),
              fetch_window_s=round(wall_f, 4),
              fetch_window_enqueue_s=round(enq_f, 4),
              block_until_ready_waits=waits),
          compiles_after_warmup=profiler.counters()["fused_compiles"]
          - compiles0,
          programs_after_warmup=len(mx.perf.programs("spmd")) - programs0,
          params_on_device=on_dev, peak_bytes=_peak_bytes(dev))
    _require(all(np.isfinite(host)), "non-finite loss: %s" % host)
    recent = min(host[len(host) // 2:])
    _require(recent < host[0], "loss did not fall: first %.4f, best of the "
             "second half of the run %.4f" % (host[0], recent))
    _require(on_dev, "parameters or optimizer state are not on %s" % dev)
    _require(profiler.counters()["fused_compiles"] == compiles0
             and len(mx.perf.programs("spmd")) == programs0,
             "the step recompiled after warm-up")
    _require(waits, "block_until_ready returned before the device was done "
             "(fetch after it took %.4fs, a step %.4fs): timing regions "
             "that end in it do not end" % (fetch_after_block_s, step_ms / 1e3))


# ------------------------------------------------------------------ serve
def _lm(sz, mesh=None):
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig
    return TransformerLM(TransformerLMConfig(**sz["lm"]), mesh=mesh)


def _prompts(sz, vocab, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, (sz["prefix"],))
    prompts = []
    for i, n in enumerate(sz["prompts"]):
        p = rng.randint(0, vocab, (n,))
        if i in sz["shared"]:    # the system-prompt case: full shared pages
            p[:sz["prefix"]] = prefix
        prompts.append(p.astype(np.int32))
    return prompts


def phase_serve(args, sz):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    dev = jax.devices()[0]
    model = _lm(sz)
    cfg = model.cfg
    params = model.init(jax.random.PRNGKey(args.seed))
    prompts = _prompts(sz, cfg.vocab_size, args.seed)
    new = sz["new_tokens"]

    def count(name):
        return telemetry.counter(name).value

    # the Pallas route, asked for by name: an explicit knob is not gated
    with _knobs(kernels__enabled=True, serving__kv_pages=sz["kv_pages"]), \
            tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        prefix = os.path.join(tmp, "lm")
        t0 = time.perf_counter()
        mx.deploy.export_generation(
            model, params, prefix, sampling=True,
            decode_batch=sz["decode_batch"],
            prompt_buckets=sz["prompt_buckets"])
        export_s = time.perf_counter() - t0
        with open(prefix + "-meta.json") as f:
            routes = json.load(f)["paged"]   # per decode width
        srv = mx.serving.Server()
        srv.register("lm", prefix, generate=True)
        compiles_at_register = count("serving.compiles")
        t0 = time.perf_counter()
        srv.start()
        start_s = time.perf_counter() - t0
        before = {n: count(n) for n in (
            "serving.compiles", "kernels.paged_attention",
            "kernels.paged_fallback", "kernels.gated_fallback",
            "serving.prefix_hits")}
        steps0 = telemetry.timer("serving.decode_step_ms").count
        t0 = time.perf_counter()
        futs = [srv.submit_generate("lm", p, new) for p in prompts]
        served = [np.asarray(f.result(timeout=900)) for f in futs]
        traffic_s = time.perf_counter() - t0
        decode_steps = telemetry.timer(
            "serving.decode_step_ms").count - steps0
        delta = {n: count(n) - v for n, v in before.items()}
        # stop() drains: requests accepted just before it still resolve
        late = [srv.submit_generate("lm", p, new) for p in prompts[:2]]
        srv.stop()
        late_out = [np.asarray(f.result(timeout=0)) for f in late]
        gstats = srv.stats()["generation"]["lm"]
    # the oracle is plain XLA: the cache-free full re-forward with the
    # kernel tier off, independent of every kernel under test
    with _knobs(kernels__enabled=False):
        t0 = time.perf_counter()
        oracle = [model.greedy_decode(params, p, new) for p in prompts]
        oracle_s = time.perf_counter() - t0
        gaps = _teacher_forced_gaps(model, params, prompts, served)

    equal = [bool(np.array_equal(s, o)) for s, o in zip(served, oracle)]
    # a served token is right when the oracle, fed the SAME served prefix,
    # scores it within `tol` of its own best token.  bf16 carries 8 bits:
    # two paths that round a [.., d_model] activation differently disagree
    # by a few bf16 ulps of the logits' magnitude, which flips an argmax
    # between near-tied tokens — and only between those.
    tol = 4 * 2.0 ** -8 * max(g["logit_absmax"] for g in gaps)
    worst = max(g["max_gap"] for g in gaps)
    _emit("serve", config=dict(layers=cfg.num_layers, d_model=cfg.d_model,
                               heads=cfg.num_heads, d_ff=cfg.d_ff,
                               vocab=cfg.vocab_size, max_len=cfg.max_len,
                               dtype=jnp.dtype(cfg.dtype).name),
          prompt_lens=[int(p.size) for p in prompts], new_tokens=new,
          prompt_buckets=list(sz["prompt_buckets"]),
          programs_compiled_at_start=before["serving.compiles"]
          - compiles_at_register,
          export_s=round(export_s, 2), start_s=round(start_s, 2),
          traffic_s=round(traffic_s, 3), oracle_s=round(oracle_s, 2),
          decode_iterations=decode_steps, counters=delta,
          paged_routes={w: r.get("impl") for w, r in sorted(
              routes.items(), key=lambda kv: int(kv[0]))},
          prefill_attention="xla (the prefill programs keep batch and pool "
                            "symbolic, which the flash kernel cannot take)",
          streams_equal_to_greedy_decode="%d/%d" % (sum(equal), len(equal)),
          tokens_flipped=sum(g["flips"] for g in gaps),
          tokens_total=new * len(prompts),
          worst_logit_gap=round(worst, 6), logit_gap_tolerance=round(tol, 6),
          engine=dict(alive_after_stop=gstats["engine_alive"],
                      kv_pages=gstats["kv_pages"],
                      kv_pages_free=gstats["kv_pages_free"],
                      decode_slots=gstats["decode_slots"]),
          peak_bytes=_peak_bytes(dev))
    _require(all(s.shape == (new,) for s in served + late_out),
             "a request did not return %d tokens" % new)
    _require(all(equal) or worst <= tol,
             "served tokens leave the oracle: worst logit gap %.5f > "
             "tolerance %.5f" % (worst, tol))
    _require(decode_steps > 0 and
             delta["kernels.paged_attention"] == decode_steps,
             "kernels.paged_attention counted %d of %d decode iterations"
             % (delta["kernels.paged_attention"], decode_steps))
    _require(delta["kernels.paged_fallback"] == 0
             and delta["kernels.gated_fallback"] == 0,
             "a decode iteration fell back to XLA: %s" % delta)
    _require(all(r.get("impl") == "paged" for r in routes.values()),
             "an exported decode program is not kernel-routed: %s" % routes)
    _require(delta["serving.compiles"] == 0,
             "traffic compiled %d program(s) after start()"
             % delta["serving.compiles"])
    _require(delta["serving.prefix_hits"] > 0,
             "no shared-prefix page was reused")
    _require(not gstats["engine_alive"]
             and gstats["kv_pages_free"] == gstats["kv_pages"],
             "stop() left the engine running or pages in use: %s" % gstats)


def phase_serve_hybrid(args, sz):
    """The second kind of cache: a hybrid decoder (Mamba-2, held experts,
    grouped-query attention) behind the same server — K/V pages AND
    per-slot recurrent state, more requests than slots so that slots are
    used again, the paged kernel at several queries a K/V head."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    hs = sz["hybrid_serve"]
    model = HybridLM(HybridLMConfig(**sz["hybrid"]))
    params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(0, model.cfg.vocab_size, n).astype(np.int32)
               for n in hs["prompts"]]
    new = hs["new_tokens"]
    names = ("kernels.paged_attention", "kernels.paged_fallback",
             "kernels.grouped_matmul", "kernels.grouped_fallback",
             "serving.compiles", "serving.prefix_share_refused") \
        + tuple("serving." + n for n in model.decode_stats)

    def counts():
        return {n: telemetry.counter(n).value for n in names}

    with _knobs(kernels__enabled=True, serving__kv_pages=hs["kv_pages"]), \
            tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        prefix = os.path.join(tmp, "hybrid")
        width = model.cfg.max_len // hs["page"]
        mx.deploy.export_generation(
            model, params, prefix, sampling=True,
            decode_batch=hs["decode_batch"], page_size=hs["page"],
            prompt_buckets=hs["prompt_buckets"], decode_widths=[width])
        with open(prefix + "-meta.json") as f:
            meta = json.load(f)
        before = counts()
        srv = mx.serving.Server()
        engine = srv.register("hybrid", prefix, generate=True)
        srv.start()
        started = counts()
        steps0 = telemetry.timer("serving.decode_step_ms").count
        t0 = time.perf_counter()
        futs = [srv.submit_generate("hybrid", p, new) for p in prompts]
        served = [np.asarray(f.result(timeout=900)) for f in futs]
        traffic_s = time.perf_counter() - t0
        steps = telemetry.timer("serving.decode_step_ms").count - steps0
        after = counts()
        state_touched = [bool(np.asarray(a).any()) for a in engine._kv[2:]]
        srv.stop()
    with _knobs(kernels__enabled=False):
        gaps = _teacher_forced_gaps(model, params, prompts, served)
    delta = {n: after[n] - started[n] for n in names}
    # wider than the dense model's 4 ulps: bf16 activations move the
    # router's scores, a token whose last chosen expert swaps with the next
    # one moves by more than rounding, and only such tokens do (PERF.md
    # section 6, PR 27) — so few tokens may flip, and none by much
    tol = 16 * 2.0 ** -8 * max(g["logit_absmax"] for g in gaps)
    worst = max(g["max_gap"] for g in gaps)
    flipped = sum(g["flips"] for g in gaps)
    _emit("serve_hybrid", pattern=model.cfg.pattern,
          prompt_lens=list(hs["prompts"]),
          new_tokens=new, decode_slots=hs["decode_batch"],
          paged_routes={w: r.get("impl") for w, r in meta["paged"].items()},
          grouped_routes={p: r.get("impl")
                          for p, r in meta["grouped"].items()},
          state_arrays=[s["name"] for s in meta["kv"]["state"]],
          traffic_s=round(traffic_s, 3), decode_iterations=steps,
          counters=delta, tokens_flipped=flipped,
          tokens_total=new * len(prompts), worst_logit_gap=round(worst, 6),
          logit_gap_tolerance=round(tol, 6),
          peak_bytes=_peak_bytes(jax.devices()[0]))
    _require(all(s.shape == (new,) for s in served),
             "a request did not return %d tokens" % new)
    _require(worst <= tol and flipped <= 0.25 * new * len(prompts),
             "served tokens leave the oracle: worst logit gap %.5f > "
             "tolerance %.5f, or %d tokens flipped" % (worst, tol, flipped))
    _require(steps > 0 and delta["kernels.paged_attention"] == steps
             and delta["kernels.paged_fallback"] == 0,
             "the paged kernel ran %d of %d decode iterations"
             % (delta["kernels.paged_attention"], steps))
    _require(delta["kernels.grouped_matmul"] == steps + len(prompts)
             and delta["kernels.grouped_fallback"] == 0,
             "the grouped kernel ran %d of %d prefill and decode dispatches"
             % (delta["kernels.grouped_matmul"], steps + len(prompts)))
    _require(all(state_touched) and len(state_touched)
             == 2 * model.cfg.pattern.count("M"),
             "a state array was never written: %s" % state_touched)
    _require(delta["serving.moe_pairs"] > 0
             and delta["serving.moe_experts_hit"] > 0,
             "no expert pair was counted: %s" % delta)
    _require(delta["serving.compiles"] == 0,
             "traffic compiled %d program(s) after start()"
             % delta["serving.compiles"])
    _require(started["serving.prefix_share_refused"]
             == before["serving.prefix_share_refused"] + 1,
             "prefix sharing was not refused for a model with state")


def phase_retention(args, sz):
    """The third kind of cache row: a power-retention block's chunked
    prefill and then its decode update, compiled, the float32 state donated
    and rewritten in place, against the QUADRATIC form that defines the
    layer (``A[t,s] = (q_t . k_s)^2 prod g``, ``y = A v / sum A``) computed
    in float32 at full precision from the same q, k, v and gates; the
    update's Pallas kernel against its XLA twin at heads of whole lanes;
    and the block behind the server, the kernel's counter held to the
    decode dispatches."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kernels, telemetry
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    rs = sz["retention"]
    model = HybridLM(HybridLMConfig(**rs["lm"]))
    lp = model.init(jax.random.PRNGKey(args.seed))["layers"]["00"]
    B, S, T = rs["rows"], rs["prompt"], rs["steps"]
    x = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                          (B, S + T, model.cfg.d_model), model.cfg.dtype)

    @jax.jit
    def quadratic(lp, x):
        positions = jnp.broadcast_to(jnp.arange(S + T), (B, S + T))
        q, k, v, logg = model._ret_parts(x, lp, positions)
        with jax.default_matmul_precision("highest"):
            q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
            cum = jnp.cumsum(logg, axis=1)
            decay = jnp.exp(jnp.where(
                jnp.tril(jnp.ones((S + T, S + T), bool))[None, :, :, None],
                cum[:, :, None] - cum[:, None, :], -jnp.inf))  # [B,t,s,KV]
            a = jnp.square(jnp.einsum("bthre,bshe->bhrts", q, k)) \
                * jnp.moveaxis(decay, -1, 1)[:, :, None]
            y = jnp.einsum("bhrts,bshe->bthre", a, v) \
                / jnp.moveaxis(a.sum(-1), -1, 1)[..., None]
        return model._ret_out(y.astype(x.dtype), lp)

    prefill = jax.jit(lambda lp, x: model._ret_sequence(x, lp))
    step = jax.jit(lambda lp, x, pos, state, z: model._ret_step(
        x, lp, pos, state, z), donate_argnums=(3, 4))
    want = np.asarray(quadratic(lp, x), np.float32)
    names = ("kernels.retention_update", "kernels.retention_fallback")

    def counts():
        return {n: telemetry.counter(n).value for n in names}

    # heads of whole lanes take the kernel, narrower ones its twin
    route = "xla" if model.cfg.head_dim % 128 else "retention"
    with _knobs(kernels__enabled=True):
        out, state, z = prefill(lp, x[:, :S])
        got = [np.asarray(out, np.float32)]
        for t in range(S, S + T):
            out, state, z = step(lp, x[:, t], jnp.full((B,), t, jnp.int32),
                                 state, z)
            got.append(np.asarray(out, np.float32)[:, None])
        got = np.concatenate(got, axis=1)
        err = _scaled_err(got[:, :S], want[:, :S])[1], \
            _scaled_err(got[:, S:], want[:, S:])[1]

        # the kernel against its twin: random state, one update
        b, kvh, r, dh = rs["update"]
        n = dh * (dh + 1) // 2
        keys = jax.random.split(jax.random.PRNGKey(args.seed + 2), 6)
        case = (jax.random.normal(keys[0], (b, kvh, n, dh)),
                1.0 + jax.random.uniform(keys[1], (b, kvh, n)),
                jnp.square(jax.random.normal(keys[2], (b, kvh, n))),
                jnp.square(jax.random.normal(keys[3], (b, kvh, r, n))),
                jax.random.uniform(keys[4], (b, kvh), minval=0.9,
                                   maxval=0.999),
                jax.random.normal(keys[5], (b, kvh, dh), jnp.bfloat16))
        twin = jax.jit(kernels._retention_update_xla)(*case)
        with kernels.record_retention_routes() as routes:
            ours = jax.jit(kernels.retention_update)(*case)
        kernel_err = max(_scaled_err(a, w)[1] for a, w in zip(ours, twin))

        # the block behind the server: one update an ``R`` block a dispatch
        sv = rs["serve"]
        rng = np.random.RandomState(args.seed)
        prompts = [rng.randint(0, model.cfg.vocab_size, p).astype(np.int32)
                   for p in sv["prompts"]]
        params = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
        with _knobs(serving__kv_pages=1), \
                tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
            prefix = os.path.join(tmp, "retention")
            mx.deploy.export_generation(
                model, params, prefix, sampling=True, decode_batch=B,
                prompt_buckets=[sv["bucket"]], page_size=sv["page"],
                max_context=model.cfg.max_len)
            with open(prefix + "-meta.json") as f:
                meta = json.load(f)
            srv = mx.serving.Server()
            srv.register("retention", prefix, generate=True)
            srv.start()
            started = counts()
            steps0 = telemetry.timer("serving.decode_step_ms").count
            futs = [srv.submit_generate("retention", p, sv["new_tokens"])
                    for p in prompts]
            served = [np.asarray(f.result(timeout=900)) for f in futs]
            steps = telemetry.timer("serving.decode_step_ms").count - steps0
            delta = {n: v - started[n] for n, v in counts().items()}
            srv.stop()
    blocks = model.cfg.pattern.count("R")
    taken, other = names if route == "retention" else names[::-1]
    # an MXU product rounds its inputs to bf16 whatever their dtype; the
    # update's kernel keeps float32 products and sums, as its twin does
    tol, f32_tol = 2.0 ** -6, 1e-5
    _emit("retention", rows=B, prompt=S, steps=T,
          state_shape=list(state.shape), scan_err=err[0], update_err=err[1],
          tolerance=tol, update_shape=list(rs["update"]),
          kernel_route=routes[0]["impl"], kernel_err=kernel_err,
          kernel_tolerance=f32_tol,
          served_routes={p: r.get("impl")
                         for p, r in meta["retention"].items()},
          decode_iterations=steps, counters=delta)
    _require(np.isfinite(got).all(), "retention output is not finite")
    _require(max(err) <= tol, "retention against the quadratic form: "
             "scan %.3g, update %.3g over %.3g" % (err + (tol,)))
    _require(routes[0]["impl"] == "retention" and kernel_err <= f32_tol,
             "the update's kernel against its twin: route %s, error %.3g "
             "over %.3g" % (routes[0], kernel_err, f32_tol))
    _require(all(s.shape == (sv["new_tokens"],) for s in served),
             "a request did not return %d tokens" % sv["new_tokens"])
    _require(set(r["impl"] for r in meta["retention"].values()) == {route},
             "the served decode program's update is %s, want %s"
             % (meta["retention"], route))
    _require(steps > 0 and delta[taken] == steps * blocks
             and delta[other] == 0,
             "%s counted %d over %d decode iterations of %d R block(s), "
             "%s %d" % (taken, delta[taken], steps, blocks, other,
                        delta[other]))


def _teacher_forced_gaps(model, params, prompts, served):
    """For each request, one oracle forward over prompt + served tokens:
    per generated position, how far the oracle's logit for the served
    token sits below the oracle's best (0.0 wherever they agree)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    S = model.cfg.max_len

    @jax.jit
    def gaps_of(ps, toks, start, chosen):
        logits = model.apply(ps, toks)[0]                      # [S, V]
        rows = jax.lax.dynamic_slice_in_dim(logits, start, chosen.shape[0])
        picked = jnp.take_along_axis(rows, chosen[:, None], axis=1)[:, 0]
        return rows.max(axis=1) - picked, jnp.abs(rows).max()

    out = []
    for prompt, toks in zip(prompts, served):
        buf = np.zeros((1, S), np.int32)
        n = int(prompt.size)
        buf[0, :n] = prompt
        buf[0, n:n + toks.size - 1] = toks[:-1]
        gap, absmax = gaps_of(params, jnp.asarray(buf), n - 1,
                              jnp.asarray(toks, jnp.int32))
        gap = np.asarray(gap)
        out.append(dict(max_gap=float(gap.max()), flips=int((gap > 0).sum()),
                        logit_absmax=float(absmax)))
    return out


# ---------------------------------------------------------------- kernels
def phase_kernels(args, sz):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kernels
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.parallel.ring_attention import attention as xla_attention

    rng = np.random.RandomState(args.seed)
    bf16, f32 = jnp.bfloat16, jnp.float32
    # tolerances, set from the dtype before any number is seen, as error
    # over the twin's largest magnitude: an MXU product rounds its inputs
    # to bf16 (8 bits) whatever the storage dtype, and kernel and twin
    # round at different places; pointwise f32 math agrees to a few ulps
    MXU, POINTWISE = 2.0 ** -6, 1e-5
    results = {}

    def rand(shape, dtype, lo=None):
        a = rng.standard_normal(shape) if lo is None \
            else rng.uniform(lo, 1.0, shape)
        return jnp.asarray(a, dtype)

    def check(name, kernel_fn, twin_fn, operands, tol):
        jitted = jax.jit(kernel_fn)
        compiled = "tpu_custom_call" in jitted.lower(*operands).as_text()
        got = jax.tree_util.tree_leaves(jitted(*operands))
        want = jax.tree_util.tree_leaves(jax.jit(twin_fn)(*operands))
        _require(len(got) == len(want), "%s: output count differs" % name)
        errs = [_scaled_err(g, w) for g, w in zip(got, want)]
        results[name] = dict(
            max_abs=float("%.3g" % max(e[0] for e in errs)),
            scaled=float("%.3g" % max(e[1] for e in errs)),
            tol=tol, compiled=compiled)

    for shape in sz["flash"]:
        q, k, v, cot = (rand(shape, bf16) for _ in range(4))
        tag = "x".join(map(str, shape))
        check("flash_fwd/%s" % tag,
              lambda q, k, v: pk.flash_attention(q, k, v, causal=True),
              lambda q, k, v: xla_attention(q, k, v, causal=True),
              (q, k, v), MXU)

        def grads(attn):
            return jax.grad(lambda q, k, v: jnp.sum(
                attn(q, k, v, causal=True).astype(f32) * cot.astype(f32)),
                argnums=(0, 1, 2))
        check("flash_bwd/%s" % tag, grads(pk.flash_attention),
              grads(xla_attention), (q, k, v), 2 * MXU)

    cfg = _lm(sz).cfg
    B, H, D = sz["decode_batch"], cfg.num_heads, cfg.head_dim
    psz = 16
    for K in sz["paged_k"]:
        # rows of ragged lengths up to K tokens, their pages scattered
        # through a pool half again as large; one row is an idle slot
        # (all sentinel, one position)
        W = K // psz
        pool = B * W * 3 // 2
        lens = rng.randint(1, K + 1, (B,))
        table = rng.permutation(pool)[:B * W].reshape(B, W)
        table[np.arange(W)[None, :] * psz >= lens[:, None]] = pool
        table[-1], lens[-1] = pool, 1
        table, lens = jnp.asarray(table, jnp.int32), \
            jnp.asarray(lens, jnp.int32)
        wide = (pool, psz, H * D)
        for dt in (f32, bf16):
            check("paged_%s/K=%d" % (jnp.dtype(dt).name, K),
                  pk.pallas_paged_attention, kernels._paged_attention_xla,
                  (rand((B, H, 1, D), dt), rand(wide, dt), rand(wide, dt),
                   table, lens), MXU)
        k8, v8 = (jnp.asarray(rng.randint(-127, 128, wide), jnp.int8)
                  for _ in range(2))
        ks, vs = (jnp.asarray(rng.uniform(1e-3, 2e-2, wide[:2] + (H,)), f32)
                  for _ in range(2))
        check("paged_int8/K=%d" % K,
              lambda q, k, v, t, n, ks, vs: pk.pallas_paged_attention(
                  q, k, v, t, n, k_scale=ks, v_scale=vs),
              lambda q, k, v, t, n, ks, vs: kernels._paged_attention_xla(
                  q, k, v, t, n, k_scale=ks, v_scale=vs),
              (rand((B, H, 1, D), bf16), k8, v8, table, lens, ks, vs), MXU)

    # rows sorted by group: a third of them behind the last group, some
    # groups empty, most lying across a row tile's edge
    m, e, k, n = sz["grouped"]
    sizes = rng.multinomial(2 * m // 3, rng.dirichlet(np.ones(e)))
    sizes[rng.randint(0, e, e // 4)] = 0
    held = int(sizes.sum())
    for dt in (f32, bf16):
        check("grouped_%s" % jnp.dtype(dt).name,
              lambda x, w, s: pk.pallas_grouped_matmul(x, w, s)[:held],
              lambda x, w, s: kernels._grouped_matmul_xla(
                  x, w, s, None, f32)[:held],
              (rand((m, k), dt), rand((e, k, n), dt),
               jnp.asarray(sizes, jnp.int32)), MXU)

    x = rand(sz["softmax"], f32)
    check("pallas_row_softmax", pk.pallas_row_softmax,
          lambda x: jax.nn.softmax(x, axis=-1), (x,), POINTWISE)
    x, s, b = rand(sz["sbr"], f32), rand(sz["sbr"][-1:], f32), \
        rand(sz["sbr"][-1:], f32)
    check("pallas_scale_bias_relu", pk.pallas_scale_bias_relu,
          lambda x, s, b: jnp.maximum(x * s + b, 0.0), (x, s, b), POINTWISE)

    _emit("kernels", kernels=results,
          interpreted=mx.rtc.interpret_mode())
    bad = {n: r for n, r in results.items() if r["scaled"] > r["tol"]}
    _require(not bad, "kernels off their XLA twins: %s" % bad)
    if not args.rehearse:
        _require(all(r["compiled"] for r in results.values()),
                 "no tpu_custom_call in the lowered text of: %s"
                 % [n for n, r in results.items() if not r["compiled"]])


# ------------------------------------------------------------ four chips
def _on_devices(x):
    return sorted(s.device.id for s in x.addressable_shards)


def _rel_l2(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) /
                 max(np.linalg.norm(want), 1e-30))


def phase_four_chips(args, sz):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh

    devs = jax.devices()[:4]
    want = sorted(d.id for d in devs)
    one = make_mesh({"dp": 1}, devs[:1])

    # (a) ResNet-50, data parallel over four chips, against one chip: the
    # same global batch, the same start (each trainer snapshots the
    # Block's values).  The one-chip run goes first, while memory is
    # clean: its batch is the whole global batch.
    mx.random.seed(args.seed)
    net = _vision_net(sz)
    data, label = _image_batch(sz, sz["dp_batch"], args.seed)
    runs = {}
    for name, mesh in (("one", one), ("dp4", make_mesh({"dp": 4}, devs))):
        tr = _trainer(net, mesh)
        t0 = time.perf_counter()
        losses = [float(tr.step(data, label))]
        first = time.perf_counter() - t0
        losses += [float(tr.step(data, label)) for _ in range(2)]
        runs[name] = dict(
            losses=losses, first_step_s=round(first, 2),
            batch_on=_on_devices(jax.device_put(label, tr._batch_sharding)))
        del tr
    # Step 1 runs from identical weights: only the forward's rounding
    # differs.  Steps 2-3 add the rounding of the gradient path (bf16
    # weight gradients reduced in another order) in SGD's unsettled first
    # phase; on the chip they still agree to 0.3% (my chip run, PR 21).
    # The losses are the check: the first UPDATE's norm is not — the
    # cold-start gradient of a BatchNorm ResNet is so badly conditioned
    # that in bf16 it differs by more than its own norm between the two
    # layouts while the losses agree to a part in a thousand.
    first_tol, later_tol = 2e-2, 0.25
    rel = [abs(a - b) / abs(b) for a, b in
           zip(runs["dp4"]["losses"], runs["one"]["losses"])]

    # (b) TransformerLM loss + grad, dp=2 x tp=2, against one chip
    mesh = make_mesh({"dp": 2, "tp": 2}, devs)
    model, model1 = _lm(sz, mesh), _lm(sz, one)
    host = jax.tree_util.tree_map(
        np.asarray, model1.init(jax.random.PRNGKey(args.seed)))
    rng = np.random.RandomState(args.seed)
    vocab = model.cfg.vocab_size
    tok, tgt = (rng.randint(0, vocab, (sz["lm_batch"], sz["lm_seq"])).astype(
        np.int32) for _ in range(2))
    sharded = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)), host,
        model.param_specs())
    bsh = NamedSharding(mesh, P("dp", None))
    tok4, tgt4 = jax.device_put(tok, bsh), jax.device_put(tgt, bsh)
    t0 = time.perf_counter()
    loss4, grads4 = jax.jit(jax.value_and_grad(model.loss))(
        sharded, tok4, tgt4)
    jax.block_until_ready(grads4)
    lm_first = time.perf_counter() - t0
    put1 = lambda v: jax.device_put(v, devs[0])  # noqa: E731
    loss1, grads1 = jax.jit(jax.value_and_grad(model1.loss))(
        jax.tree_util.tree_map(put1, host), put1(tok), put1(tgt))
    # bf16 grads (8 bits, an ulp is 2^-8) through the whole depth, with
    # contractions split over tp and partial sums rounded before they are
    # added: up to 16 ulps per leaf in the L2 norm (the norm scales, sums
    # of bf16 products over every token, sit highest); a missing
    # reduction would be off by tens of percent
    lm_tol = 2.0 ** -4
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(grads1)]
    grad_err = {n: round(_rel_l2(a, b), 6) for n, a, b in zip(
        names, jax.tree_util.tree_leaves(grads4),
        jax.tree_util.tree_leaves(grads1))}
    loss_err = abs(float(loss4) - float(loss1)) / abs(float(loss1))
    tp_leaves = {n: _on_devices(sharded["layers"][n])
                 for n in ("wqkv", "wo", "w1", "w2")}

    _emit("four_chips", resnet=runs,
          resnet_loss_rel_err=[round(r, 6) for r in rel],
          resnet_loss_tolerance=[first_tol, later_tol, later_tol],
          lm=dict(batch=sz["lm_batch"], seq=sz["lm_seq"],
                  loss_dp2_tp2=float(loss4), loss_one=float(loss1),
                  loss_rel_err=round(loss_err, 6), grad_rel_l2=grad_err,
                  tolerance=lm_tol, first_step_s=round(lm_first, 2),
                  batch_on=_on_devices(tok4), tp_leaves_on=tp_leaves,
                  tp_shard_shapes={
                      n: list(sharded["layers"][n].addressable_shards[0]
                              .data.shape) for n in tp_leaves}),
          peak_bytes=[_peak_bytes(d) for d in devs])
    _require(runs["dp4"]["batch_on"] == want and _on_devices(tok4) == want
             and all(v == want for v in tp_leaves.values()),
             "shards do not sit on four distinct devices")
    _require(all(np.isfinite(r["losses"]).all() for r in runs.values())
             and rel[0] <= first_tol and max(rel) <= later_tol,
             "dp=4 ResNet leaves the one-device run: loss rel err %s" % rel)
    _require(np.isfinite(float(loss4)) and loss_err <= lm_tol
             and max(grad_err.values()) <= lm_tol,
             "dp2 x tp2 TransformerLM leaves the one-device run: loss "
             "%.5f, grads %s > %.4f" % (loss_err, grad_err, lm_tol))


# ------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the four-chip paths instead of "
                         "train/serve/kernels")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of all weights, data and prompts")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend; never prints the "
                         "success line")
    args = ap.parse_args(argv)
    if args.rehearse and args.chips > 1 and \
            "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # the cpu backend reads its virtual device count at start-up
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=%d" % args.chips).strip()

    import jax
    cache = _CacheEvents()
    sz = _sizes(args.rehearse)
    t0 = time.perf_counter()
    phase_device(args)
    phases = [phase_four_chips] if args.chips == 4 else \
        [phase_train, phase_serve, phase_serve_hybrid, phase_retention,
         phase_kernels]
    for phase in phases:
        phase(args, sz)
    import mxnet_tpu as mx
    _emit("done", seconds=round(time.perf_counter() - t0, 1),
          compile_cache=dict(hits=cache.hits, misses=cache.misses,
                             entries_now=_cache_entries(
                                 mx.runtime.configure_compile_cache())))
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.rehearse:
        print(json.dumps({"rehearsed": [p.__name__ for p in phases],
                          "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
