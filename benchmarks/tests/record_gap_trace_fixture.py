"""How ``fixture_gap.xplane.pb`` and ``fixture_gap.scopes.json`` were
recorded: a serial engine's calls into two toy programs on one TPU chip,
under the benchmark's own profile options, with the spans the generation
engine writes around a call (``mxnet_tpu/generation.py``).

    chiprun -- python3 benchmarks/tests/record_gap_trace_fixture.py

``decode`` / ``prefill``  a scanned stack of eight 4096-wide matmuls (about
            5 ms on a v5e: long enough for the profiler's alignment, good
            to a millisecond or two, to say which call an execution
            belongs to), a donated "pool" and, as the engine's programs,
            seven small host arrays a call (token ids, positions, table,
            four sampling arrays), which the runtime copies over each time.
            Both are in the table under family ``serving``.

Fourteen calls, a prefill first and again at the eighth: ``engine.<kind>.
dispatch`` around the call alone, ``engine.<kind>.fetch`` around the copy
back (``np.asarray``; after a prefill ``int(nxt[0])``, whose slice is a
small program of its own behind the prefill), both inside ``engine.<kind>.
device``; then 3 ms of sleep inside ``engine.decode.emit``: a host leg with
a known least.  Recorded at ``host_tracer_level`` 2, so the runtime's own
threads are in the file.  Writes both files to ``chiprun_out/``;
``test_gap_trace.py`` reads copies kept beside it.
"""
import glob
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.harness import profile  # noqa: E402
from mxnet_tpu import perf  # noqa: E402
from mxnet_tpu.tracing import span  # noqa: E402

ROWS = 32
WIDTH = 4096


def _program(ws, pool, token_ids, positions, table, temp, tk, tp, keys):
    def layer(c, w):
        with jax.named_scope("mx.mlp"):
            return jnp.tanh(c @ w), None
    x = pool + (token_ids + positions + table.sum(-1)).astype(
        pool.dtype)[:, None]
    with jax.named_scope("mx.layers"):
        y, _ = jax.lax.scan(layer, jnp.tile(x, (WIDTH // ROWS, 1)), ws)
    with jax.named_scope("mx.sample"):
        scaled = y[:ROWS].astype(jnp.float32) * (1.0 + temp * tp)[:, None] \
            + (tk + keys.sum(-1).astype(jnp.int32))[:, None]
        nxt = jnp.argmax(scaled, axis=-1).astype(jnp.int32)
    return pool + 1, nxt


def decode(*args):
    return _program(*args)


def prefill(*args):
    return _program(*args)


def _host_arrays(i):
    return (np.full((ROWS,), i, np.int32), np.arange(ROWS, dtype=np.int32),
            np.zeros((ROWS, 4), np.int32), np.zeros((ROWS,), np.float32),
            np.zeros((ROWS,), np.int32), np.ones((ROWS,), np.float32),
            np.zeros((ROWS, 2), np.uint32))


def main():
    ws = jnp.ones((8, WIDTH, WIDTH), jnp.bfloat16) * 0.01
    pool = jnp.ones((ROWS, WIDTH), jnp.bfloat16)
    tables, programs = [], {}
    for fn in (decode, prefill):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            ws, pool, *_host_arrays(0)).compile()
        pool, nxt = compiled(ws, pool, *_host_arrays(0))
        int(nxt[0])                      # the slice compiles outside
        programs[fn.__name__] = compiled
        tables.append(dict(perf.hlo_op_names(compiled.as_text()),
                           family="serving", key="fixture/" + fn.__name__))
    trace_dir = os.path.join(ROOT, ".bench_runs", "fixture_gap", "trace")
    shutil.rmtree(os.path.dirname(trace_dir), ignore_errors=True)
    with profile.traced_window(trace_dir):
        for i in range(14):
            kind = "prefill" if i in (0, 7) else "decode"
            host = _host_arrays(i)
            with span("engine.iteration", iteration=i + 1):
                with span("engine.%s" % kind):
                    with span("engine.%s.device" % kind):
                        with span("engine.%s.dispatch" % kind,
                                  host_args=len(host),
                                  host_bytes=sum(a.nbytes for a in host)):
                            pool, nxt = programs[kind](ws, pool, *host)
                        with span("engine.%s.fetch" % kind):
                            if kind == "prefill":
                                int(nxt[0])
                            else:
                                np.asarray(nxt)
                    with span("engine.decode.emit", finished=0):
                        time.sleep(0.003)
    (xplane,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(xplane, os.path.join(out_dir, "fixture_gap.xplane.pb"))
    with open(os.path.join(out_dir, "fixture_gap.scopes.json"), "w") as f:
        json.dump(tables, f)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "xplane_bytes": os.path.getsize(xplane),
                      "instructions": [len(t["ops"]) for t in tables]}))


if __name__ == "__main__":
    main()
