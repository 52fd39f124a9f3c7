"""Per-operator micro-benchmark runner.

Reference: benchmark/opperf/opperf.py — runs every (or a selected set of)
operator(s) on standard small/large inputs, timing forward and backward, and
emits a markdown/JSON table (results corpus:
benchmark/opperf/results/mxnet_operator_benchmark_results_cpu.md).

TPU-native: each op is timed as a JITTED function with device-resident
inputs, the timed region ending in ``jax.block_until_ready``, so the number
is kernel time + dispatch — not host tracing overhead.  Backward timing
uses jax.grad of sum(op(x)).

Usage:
    python tools/opperf.py                      # curated default op set
    python tools/opperf.py --ops relu,dot      # specific ops
    python tools/opperf.py --json out.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _inputs_for(name, large=False):
    """Standard inputs per op family (opperf's default shapes)."""
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    big = (1024, 1024) if large else (256, 256)

    def t(*s):
        return jnp.asarray(rng.uniform(0.5, 1.5, s).astype(np.float32))

    TABLE = {
        "dot": lambda: (t(*big), t(*big)),
        "batch_dot": lambda: (t(32, 128, 128), t(32, 128, 128)),
        "FullyConnected": lambda: (t(64, 512), t(256, 512), t(256)),
        "Convolution": lambda: (t(8, 32, 32, 32), t(64, 32, 3, 3), t(64)),
        "Pooling": lambda: (t(8, 32, 64, 64),),
        "BatchNorm": lambda: (t(8, 32, 32, 32), t(32), t(32), t(32), t(32)),
        "LayerNorm": lambda: (t(64, 512), t(512), t(512)),
        "softmax": lambda: (t(64, 1000),),
        "log_softmax": lambda: (t(64, 1000),),
        "Activation": lambda: (t(*big),),
        "LeakyReLU": lambda: (t(*big),),
        "Embedding": lambda: (jnp.asarray(
            rng.randint(0, 1000, (64, 32)).astype(np.float32)),
            t(1000, 128)),
        "pallas_flash_attention": lambda: (t(2, 4, 256, 64),
                                           t(2, 4, 256, 64),
                                           t(2, 4, 256, 64)),
        "transpose": lambda: (t(*big),),
        "sum": lambda: (t(*big),),
        "mean": lambda: (t(*big),),
        "broadcast_add": lambda: (t(*big), t(*big)),
        "broadcast_mul": lambda: (t(*big), t(*big)),
        "elemwise chain": None,
    }
    if name in TABLE and TABLE[name] is not None:
        return TABLE[name]()
    return (t(*big),)


_ATTRS = {
    "FullyConnected": {"num_hidden": 256},
    "Convolution": {"kernel": (3, 3), "num_filter": 64, "pad": (1, 1)},
    "Pooling": {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
    "BatchNorm": {"fix_gamma": False, "training": True},
    "Activation": {"act_type": "relu"},
    "Embedding": {"input_dim": 1000, "output_dim": 128},
    "sum": {"axis": 1},
    "mean": {"axis": 1},
}

DEFAULT_OPS = ["dot", "batch_dot", "FullyConnected", "Convolution",
               "Pooling", "BatchNorm", "LayerNorm", "softmax", "log_softmax",
               "Activation", "LeakyReLU", "Embedding", "transpose", "sum",
               "mean", "broadcast_add", "broadcast_mul", "sigmoid", "tanh",
               "exp", "sqrt"]


def _time_fn(fn, args, warmup=2, runs=10):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(runs):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / runs


def run_performance_test(ops=None, large=False, runs=10):
    """Benchmark the given op names; returns a list of result dicts
    (the opperf.run_performance_test analog)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import _REGISTRY

    results = []
    for name in (ops or DEFAULT_OPS):
        if name not in _REGISTRY:
            results.append({"op": name, "error": "not registered"})
            continue
        op = _REGISTRY[name]
        attrs = _ATTRS.get(name, {})
        args = _inputs_for(name, large)
        fwd = jax.jit(lambda *xs, _f=op.fn, _a=attrs: _f(*xs, **_a))
        rec = {"op": name,
               "shapes": [tuple(a.shape) for a in args]}
        try:
            rec["fwd_ms"] = round(_time_fn(fwd, args, runs=runs) * 1e3, 4)
        except Exception as e:  # noqa: BLE001
            rec["error"] = "fwd: %s" % e
            results.append(rec)
            continue
        # compiler-attributed work for the same program: flops plus the
        # achieved rate at the measured wall time.  Older result files
        # simply lack these keys — all readers go through .get()
        from mxnet_tpu import perf as _perf
        ca = _perf.cost_analysis(fwd, *args)
        if ca and ca["flops"] > 0 and rec["fwd_ms"] > 0:
            rec["flops"] = ca["flops"]
            rec["achieved_gflops"] = round(
                ca["flops"] / (rec["fwd_ms"] / 1e3) / 1e9, 3)
        if op.differentiable:
            def loss(*xs, _f=op.fn, _a=attrs):
                out = _f(*xs, **_a)
                leaves = jax.tree_util.tree_leaves(out)
                return sum(jnp.sum(l.astype(jnp.float32)) for l in leaves
                           if jnp.issubdtype(l.dtype, jnp.inexact))
            try:
                bwd = jax.jit(jax.grad(loss))
                rec["fwd_bwd_ms"] = round(
                    _time_fn(bwd, args, runs=runs) * 1e3, 4)
            except Exception as e:  # noqa: BLE001
                rec["bwd_error"] = str(e)[:120]
        results.append(rec)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None,
                    help="comma-separated op names (default: curated set)")
    ap.add_argument("--large", action="store_true",
                    help="use opperf's larger tensor shapes")
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--json", default=None, help="also write JSON here")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the cpu backend (each row is stamped "
                         "with its platform)")
    args = ap.parse_args()
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    ops = args.ops.split(",") if args.ops else None
    results = run_performance_test(ops, large=args.large, runs=args.runs)
    for r in results:
        r["platform"] = platform
    print("%-24s %-28s %12s %12s %12s" % ("Op", "Shapes", "Fwd(ms)",
                                          "Fwd+Bwd(ms)", "GFLOP/s"))
    for r in results:
        print("%-24s %-28s %12s %12s %12s"
              % (r["op"], str(r.get("shapes", ""))[:28],
                 r.get("fwd_ms", r.get("error", "-")),
                 r.get("fwd_bwd_ms", r.get("bwd_error", "-")),
                 r.get("achieved_gflops", "-")))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
