"""The ``nemotron3_super_ep4`` configuration's own pieces: its plain
reference against hand-sized cases written out in numpy, its ``ops_bytes``
against a count by hand at the published sizes, its file against the
catalog's keys, and its cell under ``--rehearse``."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest

ROOT = manifest.ROOT
REF = manifest.load_module("reference", "nemotron3_super_ep4")
OPS = manifest.load_module("ops_bytes", "nemotron3_super_ep4")
CFG = manifest.load_json("configs", "nemotron3_super_ep4.json")
LM = CFG["sizes"]["lm"]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _rms(x, g, eps=1e-5):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


# ------------------------------------------------- the reference, by hand
def test_mamba_block_is_the_recurrence_written_out():
    """Two heads of two, one group, state three, three taps, five tokens:
    every step of the published recurrence in numpy loops."""
    rng = np.random.default_rng(0)
    S, D, Hm, P, G, N, K = 5, 6, 2, 2, 1, 3, 3
    inner, conv = Hm * P, Hm * P + 2 * G * N
    lp = {"ln": rng.normal(1, 0.1, D), "w_in": rng.normal(0, 0.5, (D, inner + conv + Hm)),
          "conv_w": rng.normal(0, 0.5, (conv, K)), "conv_b": rng.normal(0, 0.1, conv),
          "dt_bias": rng.normal(0, 0.3, Hm), "a_log": rng.normal(0, 0.3, Hm),
          "d": rng.normal(1, 0.1, Hm), "norm": rng.normal(1, 0.1, inner),
          "w_out": rng.normal(0, 0.5, (inner, D))}
    lp = {k: v.astype(np.float32) for k, v in lp.items()}
    x = rng.normal(size=(S, D)).astype(np.float32)
    got = REF._mamba(jnp.asarray(x), {k: jnp.asarray(v) for k, v in lp.items()},
                     {"ssm_groups": G, "eps": 1e-5}, None)
    zxd = _rms(x, lp["ln"]) @ lp["w_in"]
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + conv], zxd[:, inner + conv:]
    act = np.zeros_like(xbc)
    for t in range(S):
        acc = lp["conv_b"].copy()
        for j in range(K):
            if t - (K - 1) + j >= 0:
                acc += lp["conv_w"][:, j] * xbc[t - (K - 1) + j]
        act[t] = acc / (1 + np.exp(-acc))
    state = np.zeros((Hm, P, N), np.float32)
    want = np.zeros((S, D), np.float32)
    for t in range(S):
        X = act[t, :inner].reshape(Hm, P)
        B, C = act[t, inner:inner + N], act[t, inner + N:]
        y = np.zeros((Hm, P), np.float32)
        for i in range(Hm):
            step = np.log1p(np.exp(dt[t, i] + lp["dt_bias"][i]))
            state[i] = np.exp(step * -np.exp(lp["a_log"][i])) * state[i] \
                + step * np.outer(X[i], B)
            y[i] = state[i] @ C + lp["d"][i] * X[i]
        y = y.reshape(-1) * (z[t] / (1 + np.exp(-z[t])))
        want[t] = (_rms(y, lp["norm"])) @ lp["w_out"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_expert_block_is_the_mixture_written_out():
    """Six experts of which 1..4 are held, top two, selection bias, scale
    five: chosen and weighted per token in numpy loops."""
    rng = np.random.default_rng(1)
    S, D, E, Z, F, Fs = 4, 6, 6, 3, 5, 4
    lp = {"ln": rng.normal(1, 0.1, D), "router": rng.normal(0, 1, (D, E)),
          "select_bias": rng.normal(0, 0.5, E), "w_down": rng.normal(0, 0.5, (D, Z)),
          "w_up": rng.normal(0, 0.5, (Z, D)), "w1": rng.normal(0, 0.5, (E, Z, F)),
          "w2": rng.normal(0, 0.5, (E, F, Z)), "v1": rng.normal(0, 0.5, (D, Fs)),
          "v2": rng.normal(0, 0.5, (Fs, D))}
    lp = {k: v.astype(np.float32) for k, v in lp.items()}
    x = rng.normal(size=(S, D)).astype(np.float32)
    held = dict(lp, w1=lp["w1"][1:5], w2=lp["w2"][1:5])
    lm = {"top_k": 2, "route_scale": 5.0, "expert_offset": 1, "eps": 1e-5}
    got = REF._experts(jnp.asarray(x), {k: jnp.asarray(v) for k, v in held.items()},
                       lm, None)
    h = _rms(x, lp["ln"])
    want = np.zeros((S, D), np.float32)
    for t in range(S):
        s = 1 / (1 + np.exp(-(h[t] @ lp["router"])))
        chosen = np.argsort(-(s + lp["select_bias"]))[:2]
        u = h[t] @ lp["w_down"]
        mixed = np.zeros(Z, np.float32)
        for e in chosen:
            if 1 <= e < 5:          # the others live elsewhere
                mixed += 5.0 * s[e] / s[chosen].sum() \
                    * (np.maximum(u @ lp["w1"][e], 0) ** 2 @ lp["w2"][e])
        want[t] = mixed @ lp["w_up"] \
            + np.maximum(h[t] @ lp["v1"], 0) ** 2 @ lp["v2"]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_attention_block_shares_kv_heads_by_sixteen_or_by_two():
    rng = np.random.default_rng(2)
    S, D, H, KV, Dh = 4, 6, 4, 2, 3
    lp = {"ln": np.ones(D), "wq": rng.normal(0, 0.5, (D, H, Dh)),
          "wk": rng.normal(0, 0.5, (D, KV, Dh)), "wv": rng.normal(0, 0.5, (D, KV, Dh)),
          "wo": rng.normal(0, 0.5, (H, Dh, D))}
    lp = {k: v.astype(np.float32) for k, v in lp.items()}
    x = rng.normal(size=(S, D)).astype(np.float32)
    got = REF._attention(jnp.asarray(x), {k: jnp.asarray(v) for k, v in lp.items()},
                         {"eps": 1e-5})
    h = _rms(x, lp["ln"])
    want = np.zeros((S, D), np.float32)
    for t in range(S):
        for i in range(H):
            q = h[t] @ lp["wq"][:, i]
            keys = h[:t + 1] @ lp["wk"][:, i // 2]
            vals = h[:t + 1] @ lp["wv"][:, i // 2]
            p = np.exp(keys @ q / np.sqrt(Dh))
            want[t] += (p / p.sum()) @ vals @ lp["wo"][i]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _rehearsal_model():
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    sz = CFG["rehearse"]["sizes"]
    model = HybridLM(HybridLMConfig(dtype=jnp.float32, **sz["lm"]))
    return model, model.init(jax.random.PRNGKey(0)), sz["reference"]


def test_reference_agrees_with_the_program_at_the_rehearsal_sizes():
    model, params, lm = _rehearsal_model()
    toks = np.random.default_rng(3).integers(0, 256, (21,)).astype(np.int32)
    np.testing.assert_allclose(
        model.apply(params, jnp.asarray(toks[None]))[0],
        REF.logits(params, toks, lm=lm), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("how", REF.DEGRADATIONS)
def test_each_degradation_moves_its_block(how):
    """What the tolerance is read against is not a no-op: on weights of
    order one, each degraded block leaves the full one by far more than
    float32 rounding, and the reading it feeds is well-formed."""
    rng = np.random.default_rng(5)
    f = lambda *s: jnp.asarray(rng.normal(0, 0.5, s), jnp.float32)  # noqa
    x = f(12, 8)
    if how == "bf16_state":
        lp = {"ln": 1 + f(8), "w_in": f(8, 4 + 10 + 2), "conv_w": f(10, 3),
              "conv_b": f(10), "dt_bias": f(2), "a_log": f(2) - 2.0,
              "d": 1 + f(2), "norm": 1 + f(4), "w_out": f(4, 8)}
        lm = {"ssm_groups": 1, "eps": 1e-5}
        full, moved = (REF._mamba(x, lp, lm, d) for d in (None, how))
    else:
        lp = {"ln": 1 + f(8), "router": f(8, 6) * 4, "select_bias": f(6),
              "w_down": f(8, 3), "w_up": f(3, 8), "w1": f(6, 3, 5),
              "w2": f(6, 5, 3), "v1": f(8, 4), "v2": f(4, 8)}
        lm = {"top_k": 2, "route_scale": 5.0, "expert_offset": 0,
              "eps": 1e-5}
        full, moved = (REF._experts(x, lp, lm, d) for d in (None, how))
    assert float(jnp.abs(full - moved).max()) > 1e-3 * float(
        jnp.abs(full).max())
    model, params, lm = _rehearsal_model()
    toks = np.random.default_rng(4).integers(0, 256, (40,)).astype(np.int32)
    tokens, routed, logprobs = REF.simulate(params, toks[:8], toks[8:], 64,
                                            40, lm=lm, degrade=how)
    assert tokens.shape == logprobs.shape == (32,)
    assert routed.shape == (3, 39, 3) and float(logprobs.max()) <= 0.0
    gaps, _, missed, scored = REF.served_token_gaps(
        params, toks[:8], toks[8:], 64, 40, lm=lm, routed=routed,
        scored=tokens)
    assert gaps.shape == (32,) and float(gaps.min()) >= 0.0
    # told the same choices and degraded the same way, the forward is the
    # control's own: the matched comparison reads (about) zero there
    _, _, _, own = REF.served_token_gaps(
        params, toks[:8], toks[8:], 64, 40, lm=lm, routed=routed,
        scored=tokens, degrade=how)
    if how != "no_bias":        # (told, the bias no longer chooses)
        np.testing.assert_allclose(own, logprobs, atol=1e-5)


def test_told_routing_takes_the_choice_and_counts_what_it_would_not_choose():
    """Told its own free choices the reference computes what it computed
    and misses none; told a choice with one expert swapped for the one its
    scores rank last it uses that one — the output moves — and counts
    exactly the swapped triples; a row of -1s routes freely."""
    model, params, lm = _rehearsal_model()
    toks = np.random.default_rng(6).integers(0, 256, (24,)).astype(np.int32)
    x, used, _ = REF._forward(params, toks, lm, None, None)
    own = np.stack([np.asarray(u) for u in used])        # [E blocks, S, k]
    again, _, missed = REF._forward(params, toks, lm, None, own)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(x))
    assert int(missed) == 0
    swapped = own.copy()
    for e in range(own.shape[0]):
        for t in range(0, 24, 3):
            absent = sorted(
                set(range(CFG["rehearse"]["sizes"]["lm"]["num_experts"]))
                - set(own[e, t].tolist()))
            swapped[e, t, 0] = absent[-1]
    swapped[:, 5] = -1                                   # free
    moved, _, missed = REF._forward(params, toks, lm, None, swapped[:, :20])
    assert int(missed) == own.shape[0] * 7               # t = 0, 3, .., 18
    assert float(jnp.abs(moved - x)[:20].max()) > 1e-5
    gaps, _, missed, logprobs = REF.served_token_gaps(
        params, toks[:8], toks[8:], 32, 16, lm=lm, routed=own[:, :23])
    assert gaps.shape == logprobs.shape == (16,) and int(missed) == 0
    want = jax.nn.log_softmax(REF.logits(params, toks[:23], lm=lm))
    np.testing.assert_allclose(
        logprobs, want[np.arange(7, 23), toks[8:]], atol=1e-5)
    # a program that skips the bias chose otherwise: its choices are missed
    tokens, chose, _ = REF.simulate(params, toks[:8], toks[8:], 32, 16,
                                    lm=lm, degrade="no_bias")
    _, _, missed, _ = REF.served_token_gaps(
        params, toks[:8], toks[8:], 32, 16, lm=lm, routed=chose,
        scored=tokens)
    assert int(missed) > 0.1 * chose.size


def test_grouped_products_are_read_by_instruction_name(monkeypatch):
    """``decode_trace.grouped_product_ms`` counts the ``ragged-dot``
    kernels of whole decode executions in the window and nothing else:
    not another unnamed custom call, not a prefill's products, not an
    execution the window cuts."""
    from benchmarks.harness import decode_trace, program_trace
    call = ' = f32[8,8]{1,0} custom-call(...), custom_call_target=' \
        '"tpu_custom_call"'
    ops = [("%ragged-dot-none.1" + call, 110.0, 140.0),     # decode 1
           ("%ragged-dot-none" + call, 150.0, 160.0),
           ("%some-other-kernel.2" + call, 170.0, 190.0),
           ("%fusion.3 = f32[8]{0} fusion(...)", 100.0, 200.0),
           ("%ragged-dot-none.1" + call, 310.0, 330.0),     # a prefill
           ("%ragged-dot-none.1" + call, 410.0, 450.0),     # decode 2
           ("%ragged-dot-none.1" + call, 910.0, 950.0)]     # cut by the end
    modules = [("jit_d(1)", 100.0, 200.0), ("jit_p(2)", 300.0, 340.0),
               ("jit_d(1)", 400.0, 500.0), ("jit_d(1)", 900.0, 1100.0),
               ("jit_d(1)", 1200.0, 1300.0)]
    out = {"window": [50.0, 1000.0], "device": {"programs": {
        "serving/lm/decode-w16": {"module": "jit_d(1)", "executions": 2,
                                  "busy_s": 2e-7},
        "serving/lm/prefill-s128": {"module": "jit_p(2)", "executions": 1,
                                    "busy_s": 4e-8}}}}
    monkeypatch.setattr(program_trace, "_loaded", lambda trace: out)
    monkeypatch.setattr(program_trace, "newest_xplane", lambda: "x")
    monkeypatch.setattr(program_trace, "read_events", lambda path: {
        "ops": ops, "modules": modules, "spans": []})
    # (30 + 10 + 40) ns over two executions, in ms
    assert decode_trace.grouped_product_ms({}) == pytest.approx(40e-6)


# --------------------------------------------------- bytes, counted by hand
def test_ops_bytes_at_the_published_sizes():
    """ISSUE 27's arithmetic: an expert 5.505 M parameters = 11.0 MB; an
    ``E`` layer 759 M here; an ``M`` layer 109.6 M; the ``*`` layer 35.7 M;
    4.38 B in the blocks and 268 M in embedding and head; state 20.3 MiB a
    slot; K/V 1 KiB a token."""
    assert OPS.expert_bytes(LM) == 2 * 1024 * 2688 * 2 == 11_010_048
    per = OPS.block_weight_bytes(LM)
    e_params = per["E"] // 2 + 128 * 5_505_024
    assert abs(e_params - 759e6) < 1e6, e_params
    assert abs(per["M"] / 2 - 109.6e6) < 0.3e6, per["M"] / 2
    assert abs(per["*"] / 2 - 35.7e6) < 0.1e6, per["*"] / 2
    total = OPS.parameter_count(LM)
    assert abs(total - (4.38e9 + 268e6)) < 0.02e9, total
    state = OPS.ssm_state_bytes_per_row(LM) + OPS.conv_tail_bytes_per_row(LM)
    assert state == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert abs(state / 2 ** 20 - 20.3) < 0.05
    assert OPS.kv_bytes_per_token(LM) == 1024
    # a decode iteration at 128 rows holding 1,100 tokens each, 127.5
    # experts hit a layer: experts 7.0 GB, state 5.4 GB, the rest 2.0 GB
    scopes = OPS.scope_bytes(LM, 128, 128 * 1100, 5 * 127.5)
    assert abs(scopes["mx.moe_experts"] - 7.02e9) < 0.01e9
    assert abs(scopes["mx.ssm_update"] - 5.37e9) < 0.01e9
    assert abs(scopes["weights"] - 1.98e9) < 0.05e9, scopes["weights"]
    assert scopes["mx.paged_attention"] == 128 * 1100 * 1024
    assert OPS.decode_iteration_bytes(LM, 128, 128 * 1100, 5 * 127.5) \
        == sum(scopes.values())


# ------------------------------------------------------ the file, the cell
def test_configuration_file_keeps_the_catalogs_numbers():
    """Every number of the published config under its own key, but the
    four in ``reduced``; every width as published; the router 512 wide."""
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size", "num_nextn_predict_layers"]
    assert CFG["published"] == {"num_hidden_layers": 88,
                                "n_routed_experts": 512,
                                "vocab_size": 131072,
                                "num_nextn_predict_layers": 1}
    pattern = CFG["hybrid_override_pattern"]
    assert len(pattern) == 88 and pattern[27:38] == LM["pattern"]
    assert (LM["d_model"], LM["num_heads"], LM["num_kv_heads"],
            LM["head_dim"]) == (CFG["hidden_size"], CFG["num_attention_heads"],
                                CFG["num_key_value_heads"], CFG["head_dim"])
    assert (LM["ssm_heads"], LM["ssm_head_dim"], LM["ssm_groups"],
            LM["ssm_state"], LM["conv_kernel"], LM["chunk"]) == (
        CFG["mamba_num_heads"], CFG["mamba_head_dim"], CFG["n_groups"],
        CFG["ssm_state_size"], CFG["conv_kernel"], CFG["chunk_size"])
    assert (LM["num_experts"], LM["top_k"], LM["moe_latent"],
            LM["expert_ff"], LM["shared_ff"], LM["route_scale"]) == (
        512, CFG["num_experts_per_tok"], CFG["moe_latent_size"],
        CFG["moe_intermediate_size"],
        CFG["moe_shared_expert_intermediate_size"],
        CFG["routed_scaling_factor"])
    assert LM["experts_held"] == CFG["n_routed_experts"] == 128
    assert LM["vocab_size"] == CFG["vocab_size"] == 32768
    assert all(isinstance(v, str) and v for v in CFG["assumed"].values())
    for key in ("deployment", "sizes_analysis", "tolerance", "rehearse"):
        assert CFG[key], key


def test_the_cell_rehearses_traced():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "nemotron3super-serve-reason", "--seed", "2147483659",
         "--seconds", "2", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsed"] == "nemotron3super-serve-reason"
    assert line["correct"] is True
    for name in ("decode_step_ms.reason", "decode_fill.reason",
                 "paged_kernel_share.reason", "compiles_in_window.reason",
                 "moe_load_max_over_mean.reason", "kv_window_fill.longgen"):
        assert name in line["metric_names"], line["metric_names"]
