"""Train a model-zoo vision network through ``SPMDTrainer`` — a copy of
``chip_smoke.py``'s ``phase_train`` (which has passed on the chip) without
its smoke asserts, with a window in place of a fixed step count.

Set-up: the net, the trainer on a ``{"dp": chips}`` mesh, the seeded batch
placed on the device once under the trainer's batch sharding, the first
step (materialize + compile) and a few warm steps.  Window: steps enqueued
back to back with a fixed small number in flight — enqueue one, wait for
the loss of the step ``in_flight`` back — so the host can neither starve
the chip nor run ahead of it; the window ends in ``block_until_ready`` on
the last loss.  After the window, outside both: the checks and the plain
reference's forward (``reference/<name>.py``)."""
from __future__ import annotations

import collections

from benchmarks.harness import profile
from benchmarks.harness.stats import fold_seed, now


def _strip_prefix(values):
    """Gluon names carry the net's instance prefix (``resnetv10_``)."""
    names = list(values)
    cut = len(names[0].split("_", 1)[0]) + 1
    assert all(n[:cut] == names[0][:cut] for n in names)
    return {n[cut:]: v for n, v in values.items()}


def run(ctx):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, profiler
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    sz, chips = ctx.sizes, ctx.chips
    plan = ctx.module("generators", ctx.traffic["generator"]).generate(
        ctx.seed, ctx.traffic, sz, chips)
    devs = jax.devices()[:chips]

    mx.random.seed(fold_seed(ctx.seed) % (2 ** 31 - 1))
    net = vision.get_model(sz["model"], classes=sz["classes"])
    net.initialize(mx.init.Xavier())
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                     dict(sz["optimizer"]),
                     mesh=make_mesh({"dp": chips}, devs), dtype=sz["dtype"])
    data, label = plan["data"], plan["label"]

    t0 = now()
    losses = [tr.step(data, label)]           # materialize + compile
    jax.block_until_ready(losses[0])
    first_step_s = now() - t0
    ddev = jax.device_put(jnp.asarray(data), tr.batch_sharding)
    ldev = jax.device_put(jnp.asarray(label), tr.batch_sharding)
    for _ in range(plan["warm_steps"]):
        losses.append(tr.step(ddev, ldev))
    jax.block_until_ready(losses[-1])

    def compiles():
        return (profiler.counters()["fused_compiles"],
                len(mx.perf.programs("spmd")))

    compiles0 = compiles()
    depth = plan["in_flight"]
    pending = collections.deque()
    enqueue_s = 0.0

    def step():
        nonlocal enqueue_s
        with jax.profiler.TraceAnnotation("bench.step"):
            t = now()
            losses.append(tr.step(ddev, ldev))
            enqueue_s += now() - t
        pending.append(losses[-1])
        if len(pending) > depth:
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(pending.popleft())

    def drain():
        with jax.profiler.TraceAnnotation("bench.wait"):
            while pending:
                jax.block_until_ready(pending.popleft())

    traced_steps = 0
    trace_at = ctx.traffic["trace_after_s"] if ctx.trace else None
    n_before = len(losses)
    t_open = now()
    setup_s = t_open - ctx.t_start
    while now() - t_open < ctx.seconds:
        if trace_at is not None and now() - t_open >= trace_at:
            trace_at = None
            drain()
            with profile.traced_window(ctx.trace_dir):
                for _ in range(ctx.traffic["trace_steps"]):
                    step()
                drain()
            traced_steps = ctx.traffic["trace_steps"]
            continue
        step()
    drain()
    window_s = now() - t_open
    steps = len(losses) - n_before
    compiles1 = compiles()

    # ---- after the window, outside set-up and window: the checks
    host = np.asarray(jnp.stack(losses), np.float32)
    ref = ctx.module("reference", ctx.config["reference"])
    layers = tuple(sz["layers"])
    ref_loss = jax.jit(lambda p, x, y: ref.loss(p, x, y, layers))
    x0 = jax.device_put(jnp.asarray(data), devs[0])
    y0 = jax.device_put(jnp.asarray(label), devs[0])
    # the Block still holds the initial values: the trainer copied them
    init = _strip_prefix({n: jax.device_put(p.data()._data, devs[0])
                          for n, p in net.collect_params().items()})
    ref_first = float(ref_loss(init, x0, y0))
    del init
    held = _strip_prefix({n: jax.device_put(v, devs[0])
                          for n, v in tr.params.items()})
    ref_last = float(ref_loss(held, x0, y0))
    del held
    got_last = float(tr.step(ddev, ldev))   # the loss AT the values held
    tol = ctx.config["tolerance"]["loss_rel"]

    def rel(got, want):
        # of the loss, or of one nat where the loss has fallen under it
        return abs(got - want) / max(abs(want), 1.0)

    half = host[len(host) // 2:]
    checks = {
        "losses_finite": bool(np.isfinite(host).all()),
        "loss_fell": bool(half.min() < host[0]),
        "no_compile_in_window": compiles1 == compiles0,
        "first_loss_vs_reference": rel(float(host[0]), ref_first) <= tol,
        "last_loss_vs_reference": rel(got_last, ref_last) <= tol,
        "batch_on_every_chip": len({s.device for s in
                                    ddev.addressable_shards}) == chips,
    }
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": steps, "failed": 0,
        "setup_s": setup_s, "window_s": window_s, "steps": steps,
        "global_batch": plan["batch"], "per_chip_batch": plan["batch"] // chips,
        "enqueue_s": enqueue_s, "traced_steps": traced_steps,
        "compiles_in_window": (compiles1[0] - compiles0[0])
        + (compiles1[1] - compiles0[1]),
        "sizes": sz, "ops_bytes": ctx.config["ops_bytes"],
        "device_kind": devs[0].device_kind, "gap_label": "host",
        "facts": {
            "first_step_s": first_step_s, "steps": steps,
            "window_s": window_s, "setup_s": setup_s,
            "loss_first": float(host[0]), "loss_ref_first": ref_first,
            "loss_last": got_last, "loss_ref_last": ref_last,
            "loss_rel_err": [rel(float(host[0]), ref_first),
                             rel(got_last, ref_last)],
            "loss_min_second_half": float(half.min()),
            "loss_max": float(host.max())},
    }
