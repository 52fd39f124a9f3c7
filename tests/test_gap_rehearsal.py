"""The benchmark's gap readers (``benchmarks/harness/gap_trace.py``; seven
``per_layer`` entries on the four serving cells) against the program they
read: a traced rehearsal of one serving cell on the cpu prints its line
whatever they find, and a profile of a served toy model holds the spans
they match — one ``(dispatch, fetch)`` pair a program call, by its ``step``.
Exact numbers from made-up events and a chip-recorded trace are the
benchmark's own tests (``benchmarks/tests/test_gap_trace.py``)."""
import json
import os
import subprocess
import sys

import numpy as np

from benchmarks.harness import gap_trace
from mxnet_tpu import deploy, generation
from mxnet_tpu.models.transformer import TransformerLM, TransformerLMConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = {"gap_ms.serve", "gap_host_ms.serve", "gap_floor_ms.serve",
       "gap_launch_var_ms.serve", "gap_readback_var_ms.serve",
       "dispatch_ms.serve", "gap_outlier_share.serve"}


def test_traced_rehearsal_prints_its_line_with_the_gap_readers(tmp_path):
    """``run.py --rehearse --trace 1`` walks every per-layer reader of the
    cell; the cpu backend has no device plane, so the gap readers find no
    execution to match and leave their metrics out — none of them raises,
    and the readers that were there read what they read."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = "opt1p3b-serve-longgen"
    listed = {e["name"] for e in manifest["per_layer"]
              if cell in e.get("workloads", [cell])}
    assert NEW <= listed
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert "Traceback" not in proc.stderr
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsed"] == cell and last["correct"]
    names = set(last["metric_names"])
    assert names <= listed
    assert {"decode_step_ms.longgen", "engine_host_ms.longgen",
            "idle_share.longgen"} <= names
    # a number or nothing: a reader that found executions reports all
    # four legs and their sum together
    legs = {"gap_ms.serve", "gap_host_ms.serve", "gap_floor_ms.serve",
            "gap_launch_var_ms.serve", "gap_readback_var_ms.serve"}
    assert not (names & legs) or legs <= names


def test_a_served_models_profile_holds_the_calls_the_readers_match(tmp_path):
    """From a bare profiler session around a running engine, every program
    call has one ``*.dispatch`` and one ``*.fetch`` span of one kind that
    carry the call's ``step``, the dispatch over before its own fetch
    begins — one step in flight ahead of the host, so a decode step's
    dispatch comes before the fetch of the step before it, which is what
    ``gap_trace.engine_calls`` (written for a serial engine) pairs it with
    instead: it still reads the spans without a fault, and with no device
    plane (the cpu backend) ``legs`` reads nothing and says so with None.
    Pairing by ``step`` is left to the next benchmark change."""
    from _util import profiled_spans
    import jax
    import jax.numpy as jnp
    cfg = TransformerLMConfig(vocab_size=64, d_model=32, num_heads=2,
                              num_layers=2, d_ff=64, max_len=16,
                              dtype=jnp.float32)
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prefix = str(tmp_path / "lm")
    deploy.export_generation(model, params, prefix, page_size=4,
                             max_context=16, prompt_buckets=(4, 8))
    pred = deploy.load_generator(prefix)

    def serve():
        eng = generation.GenerationEngine("m", pred, num_pages=16,
                                          decode_slots=2).start()
        try:
            futs = [eng.submit(np.arange(1, 1 + plen, dtype=np.int32), new)
                    for plen, new in ((3, 5), (7, 4))]
            for f in futs:
                f.result(timeout=60)
        finally:
            eng.stop()

    spans = profiled_spans(serve, tmp_path / "trace", ("engine.",))
    steps = {}
    for name, s, e, args, _ in spans:
        if name.endswith((".dispatch", ".fetch")):
            steps.setdefault(int(args["step"]), []).append((s, e, name))
    assert len(steps) >= 5
    for step, calls in steps.items():
        (d0, d1, dn), (f0, f1, fn) = sorted(calls)
        assert dn.endswith(".dispatch") and fn.endswith(".fetch"), step
        assert dn.rsplit(".", 1)[0] == fn.rsplit(".", 1)[0]
        assert d0 <= d1 <= f0 <= f1
    decodes = sorted(k for k, v in steps.items()
                     if v[0][2].startswith("engine.decode"))
    ahead = [(a, b) for a, b in zip(decodes, decodes[1:])
             if min(steps[b])[0] < max(steps[a])[1]]
    assert ahead
    calls = gap_trace.engine_calls(spans)
    assert calls == sorted(calls, key=lambda c: c[1])
    window = [("bench.window", spans[0][1], spans[-1][2], {}, "bench")]
    assert gap_trace.legs({"spans": spans + window, "modules": [],
                           "ops": []}) is None
