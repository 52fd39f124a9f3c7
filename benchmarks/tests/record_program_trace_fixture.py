"""How ``fixture_program.xplane.pb`` and ``fixture_program.scopes.json`` were
recorded: two toy programs on one TPU chip under the benchmark's own profile
options, with the program's names in them.

    chiprun -- python3 benchmarks/tests/record_program_trace_fixture.py

``decode``  a scanned stack (a ``while`` on the device) of a gather under
            ``mx.kv_gather``, a Pallas kernel named ``mx_paged_attention``
            under ``mx.paged_attention`` and a matmul under ``mx.mlp``; a
            sort under ``mx.sample``; a sum under no scope at all.
``step``    loss and gradient under ``mx.forward``, update under
            ``mx.opt_update``.  No operation anywhere is under
            ``mx.kv_write``.

Around them the host spans the engine and the trainer write, three turns,
with 3 ms of sleep inside ``engine.decode.emit`` and inside ``spmd.post``:
idle gaps on the device with a known cause.  Writes both files to
``chiprun_out/``; ``test_program_trace.py`` reads copies kept beside it.

Two things the committed recording shows besides: the profile places the
device's events about a millisecond early against the host's clock (the
first ``decode`` execution reads as over before its dispatch began, so it
lies outside the window); and, recorded without
``mx.runtime.configure_compile_cache()``, the ``step`` executable came out
of the machine's compile cache with the names an earlier probe had compiled
the same operations under (a ``blk`` scope inside ``mx.forward``): a cache
key blind to metadata hands back stale names, which is why the program's
own cache set-up keys on them.
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
from jax.experimental import pallas as pl  # noqa: E402

from benchmarks.harness import profile  # noqa: E402
from mxnet_tpu import perf  # noqa: E402
from mxnet_tpu.tracing import span  # noqa: E402


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _layer(c, w):
    with jax.named_scope("mx.kv_gather"):
        idx = (jnp.arange(c.shape[0]) * 7) % c.shape[0]
        g = jnp.transpose(c[idx].reshape(8, -1, c.shape[1]),
                          (1, 0, 2)).reshape(c.shape)
    with jax.named_scope("mx.paged_attention"):
        g = pl.pallas_call(
            _double, out_shape=jax.ShapeDtypeStruct(g.shape, g.dtype),
            name="mx_paged_attention")(g)
    with jax.named_scope("mx.mlp"):
        return jnp.tanh(g @ w), None


def decode(x, ws):
    with jax.named_scope("mx.layers"):
        y, _ = jax.lax.scan(_layer, x, ws)
    with jax.named_scope("mx.sample"):
        s = jnp.sort(y, axis=-1)[:, ::-1]
    return s.sum() + y.sum()


@jax.named_scope("mx.forward")
def _loss(ws, x):
    def layer(c, w):
        return jnp.tanh(c @ w), None
    y, _ = jax.lax.scan(layer, x, ws)
    return (y * y).mean()


def step(ws, x):
    loss, grads = jax.value_and_grad(_loss)(ws, x)
    with jax.named_scope("mx.opt_update"):
        ws = ws - 0.1 * grads
    return ws, loss


def main():
    x = jnp.ones((2048, 1024), jnp.bfloat16)
    ws = jnp.ones((6, 1024, 1024), jnp.bfloat16) * 0.01
    tables = []
    programs = {}
    for fn in (decode, step):
        args = (x, ws) if fn is decode else (ws, x)
        compiled = jax.jit(fn).lower(*args).compile()
        jax.block_until_ready(compiled(*args))
        programs[fn.__name__] = compiled
        tables.append(dict(perf.hlo_op_names(compiled.as_text()),
                           family="fixture", key=fn.__name__))
    trace_dir = os.path.join(ROOT, ".bench_runs", "fixture_program", "trace")
    shutil.rmtree(os.path.dirname(trace_dir), ignore_errors=True)
    with profile.traced_window(trace_dir):
        for i in range(3):
            with span("engine.iteration", iteration=i + 1):
                with span("engine.admit") as sp:
                    sp.set(admitted=i, queued=2 - i, free_pages=40)
                with span("engine.decode", width=4, rows=3) as sp:
                    with span("engine.decode.prepare"):
                        pass
                    sp.set(held_tokens=17 + i, window_tokens=64)
                    with span("engine.decode.device"):
                        jax.block_until_ready(programs["decode"](x, ws))
                    with span("engine.decode.emit", finished=1):
                        time.sleep(0.003)
            with span("spmd.step", step=i + 1):
                with span("spmd.dispatch"):
                    out = programs["step"](ws, x)
                jax.block_until_ready(out)
                with span("spmd.post"):
                    time.sleep(0.003)
    (xplane,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(xplane, os.path.join(out_dir, "fixture_program.xplane.pb"))
    with open(os.path.join(out_dir, "fixture_program.scopes.json"),
              "w") as f:
        json.dump(tables, f)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "xplane_bytes": os.path.getsize(xplane),
                      "instructions": [len(t["ops"]) for t in tables]}))


if __name__ == "__main__":
    main()
