"""Fast CPU smoke for the fused Module train step (< 30s).

Proves the three load-bearing properties of the fused path end-to-end on
the host backend, with one parseable JSON line on stdout:

  1. routing   — N fixed-shape train_step calls dispatch N fused steps
                 through exactly ONE compiled program, zero eager steps;
  2. numerics  — fused weights match an eager twin trained from the same
                 init/data (the stage-at-a-time reference path);
  3. speed     — fused step throughput beats eager on the benchmark MLP
                 (informational: a CPU timing).

Usage: JAX_PLATFORMS=cpu python tools/check_fused_step.py
Wired as a `not slow` test in tests/test_fused_step.py.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STEPS = 8
RTOL, ATOL = 1e-4, 1e-5


def build_module(mx, init_params, mode):
    from mxnet_tpu import config
    config.set("module.fused_step", mode)
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    h = data
    for i, width in enumerate((64, 64)):
        h = mx.sym.FullyConnected(h, num_hidden=width, name="fc%d" % i)
        h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=5, name="head")
    out = mx.sym.SoftmaxOutput(h, label, name="softmax")
    mod = mx.mod.Module(out)
    mod.bind([("data", (32, 16))], [("softmax_label", (32,))])
    mod.init_params(initializer=None, arg_params=init_params)
    mod.init_optimizer(optimizer="adam",
                       optimizer_params={"learning_rate": 0.01})
    return mod


def train(mod, mx, X, Y, steps=STEPS):
    batch = mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(Y)])
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.train_step(batch)
    ws = mod.get_params()[0]
    import jax
    jax.block_until_ready([w._data for w in ws.values()])
    return ws, steps / (time.perf_counter() - t0)


def main():
    import numpy as np
    result = {"ok": False}
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import profiler
        result["backend"] = jax.default_backend()

        rng = np.random.RandomState(0)
        X = rng.randn(32, 16).astype(np.float32)
        Y = (rng.rand(32) * 5).astype(np.float32)
        shapes = {"fc0_weight": (64, 16), "fc0_bias": (64,),
                  "fc1_weight": (64, 64), "fc1_bias": (64,),
                  "head_weight": (5, 64), "head_bias": (5,)}
        init = {n: mx.nd.array(rng.randn(*s).astype(np.float32) * 0.1)
                for n, s in shapes.items()}

        profiler.reset_counters()
        fused, fused_sps = train(build_module(mx, init, "auto"), mx, X, Y)
        c = dict(profiler.counters())
        result["counters"] = c
        assert c["fused_steps"] == STEPS, c
        assert c["fused_compiles"] == 1, c
        assert c["eager_steps"] == 0, c

        profiler.reset_counters()
        eager, eager_sps = train(build_module(mx, init, "off"), mx, X, Y)
        assert profiler.counters()["eager_steps"] == STEPS

        max_diff = 0.0
        for n in fused:
            d = float(np.abs(fused[n].asnumpy()
                             - eager[n].asnumpy()).max())
            max_diff = max(max_diff, d)
            np.testing.assert_allclose(fused[n].asnumpy(),
                                       eager[n].asnumpy(),
                                       rtol=RTOL, atol=ATOL, err_msg=n)
        result.update(ok=True, steps=STEPS, max_param_diff=max_diff,
                      fused_steps_s=round(fused_sps, 1),
                      eager_steps_s=round(eager_sps, 1),
                      speedup=round(fused_sps / eager_sps, 2))
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
