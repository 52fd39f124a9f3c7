"""Fast CPU smoke for mx.perf cost attribution (< 5s).

Proves the compiled-program registry end-to-end on the host backend,
with one parseable JSON line on stdout:

  1. module   — fused Module MLP steps register a "module" program whose
                cost_analysis FLOPs agree with the hand-computed analytic
                matmul count within 10%, and the per-step ``mfu`` JSONL
                field / ``perf.mfu.module`` gauge are exactly
                flops / (wall x dtype-aware peak);
  2. families — all five compile-site families (module, spmd, gluon,
                serving, embedding) appear in the registry with
                non-empty cost AND memory analysis and a phase
                breakdown;
  3. serving  — per-model ``serving.flops_per_request`` /
                ``bytes_per_request`` gauges are set and consistent with
                the registered program / bucket;
  4. report   — ``perf.export()`` + a TRUNCATED copy of the step JSONL
                render through tools/perf_report.py (malformed tail
                tolerated), and telemetry_report's per-source table
                carries the mfu column.

Usage: JAX_PLATFORMS=cpu python tools/check_perf.py
Wired as a `not slow` test in tests/test_perf.py.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

STEPS = 6
B, IN, H, OUT = 32, 16, 64, 5
# train step ~ 3x the forward matmul work (fwd + grad-wrt-activations +
# grad-wrt-weights); sgd keeps the elementwise tail small
ANALYTIC_FLOPS = 3 * 2 * B * (IN * H + H * H + H * OUT)


def build_module(mx):
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    h = data
    for i, width in enumerate((H, H)):
        h = mx.sym.FullyConnected(h, num_hidden=width, name="fc%d" % i)
        h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=OUT, name="head")
    out = mx.sym.SoftmaxOutput(h, label, name="softmax")
    mod = mx.mod.Module(out)
    mod.bind([("data", (B, IN))], [("softmax_label", (B,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01})
    return mod


def main():
    t_main = time.perf_counter()
    import numpy as np
    result = {"ok": False}
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_perf_")
    log_path = os.path.join(tmpdir, "steps.jsonl")
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        import mxnet_tpu as mx
        from mxnet_tpu import config, gluon, perf, telemetry
        from mxnet_tpu.gluon import nn
        from mxnet_tpu.parallel import (ShardedEmbedding, SPMDTrainer,
                                        make_mesh)
        import perf_report
        import telemetry_report
        result["backend"] = jax.default_backend()
        # this smoke checks the ARITHMETIC of the MFU path (flops / (wall
        # x peak) through the step log, the gauges and both reports) on
        # the cpu backend, which has no published peak and so no MFU of
        # its own: name the table row to do the arithmetic with
        perf._KIND_CACHE[0] = result["peaks_of"] = "TPU v5 lite"

        config.set("module.fused_step", "auto")
        config.set("telemetry.sink", "jsonl:" + log_path)
        telemetry.reset()
        perf.reset()

        # 1. module: fused MLP steps, MFU vs hand-computed FLOPs
        rng = np.random.RandomState(0)
        X = rng.randn(B, IN).astype(np.float32)
        Y = (rng.rand(B) * OUT).astype(np.float32)
        batch = mx.io.DataBatch([mx.nd.array(X)], [mx.nd.array(Y)])
        mod = build_module(mx)
        for _ in range(STEPS):
            mod.train_step(batch)
            jax.block_until_ready(
                [w._data for w in mod.get_params()[0].values()])
        mod_progs = perf.programs("module")
        assert len(mod_progs) == 1, \
            "expected 1 module program, got %d" % len(mod_progs)
        prog = mod_progs[0]
        assert prog["flops"] > 0 and prog["memory"], prog
        gap = abs(prog["flops"] - ANALYTIC_FLOPS) / ANALYTIC_FLOPS
        assert gap < 0.10, \
            "measured %.0f vs analytic %d FLOPs/step: %.1f%% gap" \
            % (prog["flops"], ANALYTIC_FLOPS, 100 * gap)
        records, bad = telemetry_report.load_records(log_path)
        steps = [r for r in records if r.get("event") == "step"]
        assert len(steps) == STEPS and bad == 0, (len(steps), bad)
        last = steps[-1]
        assert last.get("flops") and last.get("mfu"), last
        telemetry.validate_step_record(last)
        # the gauge IS flops / (wall x dtype-aware peak), one divide
        # (snapshot access: the parametrized gauge names are documented
        # as perf.mfu.<source> in the metric index)
        pk = perf.peak_flops(dtype=prog["dtype"])
        want = last["flops"] / (last["wall_ms"] / 1e3 * pk)
        got = telemetry.snapshot()["gauges"]["perf.mfu.module"]
        assert abs(got - want) / want < 0.02, (got, want)
        assert telemetry.gauge("perf.mfu").value > 0
        result["module"] = {
            "flops_measured": prog["flops"],
            "flops_analytic": ANALYTIC_FLOPS,
            "gap_pct": round(100 * gap, 2),
            "mfu_gauge": got,
            "bound": prog["roofline"]["bound"],
        }

        # 2a. spmd: two SPMDTrainer steps on a 1-device mesh
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
        net.initialize()
        net(mx.nd.array(X[:, :IN]))
        tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                         {"learning_rate": 0.1},
                         mesh=make_mesh({"dp": 1}, jax.devices()[:1]))
        lbl = (rng.rand(B) * 4).astype(np.float32)
        for _ in range(2):
            loss = tr.step(X, lbl)
        np.asarray(loss)

        # 2b. gluon: hybridized concrete forward
        gnet = nn.HybridSequential()
        gnet.add(nn.Dense(8, activation="relu"), nn.Dense(4))
        gnet.initialize()
        gnet.hybridize()
        out = gnet(mx.nd.array(X))   # first call resolves deferred shapes
        out = gnet(mx.nd.array(X))   # second call hits the cached graph
        np.asarray(out._data)

        # 2c. embedding: sharded lookup + update programs
        emb = ShardedEmbedding(32, 4, mesh=make_mesh(
            {"dp": 1}, jax.devices()[:1]), optimizer="sgd", seed=3)
        ids = rng.randint(0, 32, (B, 2)).astype(np.int32)
        emb.lookup(ids)
        emb.update(ids, rng.randn(B, 2, 4).astype(np.float32), lr=0.1)

        # 2d+3. serving: exported model, per-bucket AOT programs + gauges
        snet = nn.HybridSequential()
        snet.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        snet.initialize()
        example = mx.nd.random.uniform(shape=(4, 6))
        snet(example)
        prefix = os.path.join(tmpdir, "mlp")
        mx.deploy.export_model(snet, prefix, example)
        srv = mx.serving.Server(max_batch=4, max_queue_delay_ms=2.0)
        srv.register("mlp", prefix)
        srv.start()
        try:
            np.asarray(srv.submit("mlp",
                                  rng.uniform(size=(2, 6)).astype(
                                      np.float32)).result(timeout=30))
            st = srv.stats()
            cost = st["cost_per_item"]["mlp"]
            assert cost and cost["flops"] > 0, st["cost_per_item"]
            gauges = telemetry.snapshot()["gauges"]
            g = gauges["serving.flops_per_request.mlp"]
            sprog = perf.program("serving",
                                 "mlp/b%d" % cost["bucket"])
            assert sprog is not None and \
                abs(g - sprog["flops"] / cost["bucket"]) < 0.1, (g, sprog)
            assert gauges["serving.bytes_per_request.mlp"] > 0
            result["serving"] = {"flops_per_request": g,
                                 "bucket": cost["bucket"]}
        finally:
            srv.stop()

        fams = {p["family"] for p in perf.programs()}
        missing = set(perf.FAMILIES) - fams
        assert not missing, "families missing from registry: %s" % missing
        for p in perf.programs():
            assert p["flops"] > 0, p
            assert p["memory"], p
            assert p["phases_ms"].get("compile_ms", 0) > 0, p
        result["families"] = sorted(fams)
        result["programs"] = len(perf.programs())

        # 4. report renders from the export + a TRUNCATED jsonl copy
        prog_path = os.path.join(tmpdir, "programs.json")
        perf.export(prog_path)
        trunc = os.path.join(tmpdir, "trunc.jsonl")
        raw = open(log_path, "rb").read()
        open(trunc, "wb").write(raw[:int(len(raw) * 0.8)])
        import contextlib
        import io as _io
        buf = _io.StringIO()   # stdout stays one JSON line
        with contextlib.redirect_stdout(buf):
            rc = perf_report.main(["--programs", prog_path, trunc])
        assert rc == 0, "perf_report exit %d" % rc
        assert "family" in buf.getvalue(), buf.getvalue()[:200]
        summary = perf_report.summarize(
            *([json.load(open(prog_path))["programs"]] +
              [telemetry_report.load_records(trunc)[0]]))
        assert summary["mfu"].get("module", {}).get("steps", 0) > 0, \
            summary["mfu"]
        tsum = telemetry_report.summarize(records)
        assert tsum["sources"]["module"]["mfu_mean"] > 0, \
            tsum["sources"]["module"]
        result.update(ok=True,
                      elapsed_s=round(time.perf_counter() - t_main, 2))
    except Exception as exc:  # noqa: BLE001 — the JSON line IS the report
        import traceback
        result["error"] = "%s: %s" % (type(exc).__name__, exc)
        result["trace"] = traceback.format_exc()[-1500:]
    finally:
        try:
            from mxnet_tpu import config as _cfg
            _cfg.set("telemetry.sink", "")
        except Exception:  # noqa: BLE001
            pass
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
