"""The ``joyai_flash_pp8`` configuration: its file against the catalog row
it was taken from, its reference (``reference/joyai_flash_pp8.py``: the
published interleaved rotary pairing on the stored column order, the
absorbed form it does NOT use, every control), its byte counts against
hand counts, and the cell's files making a rehearsal."""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REF = manifest.load_module("reference", "joyai_flash_pp8")
OPS = manifest.load_module("ops_bytes", "joyai_flash_pp8")
CFG = manifest.load_json("configs", "joyai_flash_pp8.json")
LM = {"top_k": 2, "route_scale": 2.5, "rope_theta": 1e4, "eps": 1e-6}
#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: ``JoyAI-LLM-Flash``): every number and flag of it, as published
CATALOG = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 7168, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 8,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280}


def _params(seed=0, V=64, D=32, H=4, Rq=24, Rkv=16, dn=8, dr=4, dv=8, E=8,
            F=48):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 96))
    n = lambda *s: jax.random.normal(next(keys), s, jnp.float32) * 0.2  # noqa

    def latent():
        return {"ln": 1.0 + n(D), "w_dq": n(D, Rq), "q_norm": 1.0 + n(Rq),
                "w_uq": n(Rq, H, dn + dr), "w_dkv": n(D, Rkv + dr),
                "kv_norm": 1.0 + n(Rkv), "w_uk": n(Rkv, H, dn),
                "w_uv": n(Rkv, H, dv), "wo": n(H, dv, D)}

    blocks = {"00": latent(),
              "01": {"ln": 1.0 + n(D), "w_gate": n(D, F), "w_up": n(D, F),
                     "w_down": n(F, D)},
              "02": latent(),
              "03": {"ln": 1.0 + n(D), "router": n(D, E) * 5,
                     "select_bias": n(E) * 0.1, "w_gate": n(E, D, F),
                     "w_up": n(E, D, F), "w_down": n(E, F, D),
                     "v_gate": n(D, F), "v_up": n(D, F), "v_down": n(F, D)}}
    return {"embed": n(V, D) * 5, "head": n(V, D), "final_norm": 1.0 + n(D),
            "layers": blocks}


# ------------------------------------------------------ the configuration
@pytest.mark.parametrize("key", sorted(CATALOG))
def test_the_file_holds_the_catalog_rows_key(key):
    """Every key of the catalog row is in the file under the same name,
    unchanged but for the two ``reduced`` names, whose published values
    stand under ``published``."""
    if key in CFG["reduced"]:
        assert CFG["published"][key] == CATALOG[key]
        assert CFG[key] != CATALOG[key]
    else:
        assert CFG[key] == CATALOG[key]


def test_the_cut_is_in_depth_only_and_the_sizes_follow_the_file():
    assert CFG["reduced"] == ["num_hidden_layers",
                              "num_nextn_predict_layers"]
    assert CFG["num_hidden_layers"] == 5
    assert CFG["num_nextn_predict_layers"] == 0
    assert CFG["source"].startswith(
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
        "config.json")
    lm = CFG["sizes"]["lm"]
    dense = CFG["first_k_dense_replace"]
    assert lm["pattern"] == "LF" * dense + "LG" * (
        CFG["num_hidden_layers"] - dense)
    assert lm["depth"] == 2 * CFG["published"]["num_hidden_layers"]
    for ours, theirs in (
            ("d_model", "hidden_size"), ("num_heads", "num_attention_heads"),
            ("q_rank", "q_lora_rank"), ("kv_rank", "kv_lora_rank"),
            ("nope_dim", "qk_nope_head_dim"),
            ("rope_dim", "qk_rope_head_dim"), ("v_dim", "v_head_dim"),
            ("num_experts", "n_routed_experts"),
            ("top_k", "num_experts_per_tok"),
            ("expert_ff", "moe_intermediate_size"),
            ("mlp_ff", "intermediate_size"), ("vocab_size", "vocab_size"),
            ("route_scale", "routed_scaling_factor"),
            ("rope_theta", "rope_theta"), ("eps", "rms_norm_eps")):
        assert lm[ours] == CFG[theirs], ours
    assert lm["shared_ff"] == CFG["n_shared_experts"] \
        * CFG["moe_intermediate_size"]
    assert "experts_held" not in lm          # all 256 are held
    manifest_cfg = next(c for c in json.load(open(os.path.join(
        ROOT, "BENCHMARK.json")))["configs"] if c["name"] == CFG["name"])
    assert manifest_cfg["reduced"] == CFG["reduced"]


def test_the_traffic_gives_every_seed_the_same_work():
    """Clients = block = the decode slots, a fixed output length: the
    first wave is one whole stratified block whatever the seed."""
    tp = manifest.load_json("traffic", "offline_docreason_s64.json")
    slots = CFG["sizes"]["decode_batch"]
    assert tp["arrivals"] == {"kind": "closed", "clients": slots}
    assert tp["block"] == slots == CFG["knobs"]["serving.decode_slots"]
    assert tp["new_tokens"] == {"dist": "fixed", "value": 1536}
    page = CFG["sizes"]["page_tokens"]
    assert tp["max_context"] == tp["prompt_buckets"][-1] + 1536
    assert CFG["knobs"]["serving.kv_pages"] \
        == slots * math.ceil(tp["max_context"] / page)
    gen = manifest.load_module("generators", "lm_requests")
    plans = [gen.generate(seed, tp, 1000) for seed in (1, 2 ** 31 + 11)]
    work = [sorted((len(r["prompt"]), r["max_new"])
                   for r in plan["requests"][:slots]) for plan in plans]
    lens = [sorted(len(r["prompt"]) for r in plan["requests"])
            for plan in plans]
    quotas = [sorted(r["max_new"] for r in plan["requests"][:slots])
              for plan in plans]
    assert lens[0] == lens[1] and quotas[0] == quotas[1]
    assert work[0] != work[1]                 # the seed only pairs them
    assert {r["max_new"] for r in plans[0]["requests"][slots:]} == {1536}


# --------------------------------------------------------- the byte counts
def test_ops_bytes_are_the_hand_counts():
    lm = CFG["sizes"]["lm"]
    per = OPS.block_params(lm)
    # latent attention a layer: 3.15 + 9.44 + 1.18 + 8.39 + 4.19 M + norms
    assert per["L"] == 2048 * 1536 + 1536 + 1536 * 32 * 192 \
        + 2048 * 576 + 512 + 512 * 32 * 256 + 32 * 128 * 2048 + 2048
    assert round(per["L"] / 1e6, 2) == 26.35
    assert per["F"] == 3 * 2048 * 7168 + 2048
    assert per["G"] == 2048 * 256 + 3 * 2048 * 768 + 2048
    assert OPS.expert_bytes(lm) == 9437184
    assert OPS.latent_bytes_per_token(lm) == 5760
    assert OPS.parameter_count(lm) == 5558141952
    assert round(OPS.parameter_count(lm) * 2 / 2 ** 30, 2) == 10.35
    need = OPS.scope_bytes(lm, 64 * 6900, 4 * 221)
    assert need["mx.moe_experts"] == 884 * 9437184
    assert need["mx.latent_attention"] == 441600 * 5760
    assert need["weights"] == OPS.weight_bytes(lm) == 2 * (
        5 * per["L"] + per["F"] + 4 * per["G"] + 129280 * 2048 + 2048) \
        + 4 * 256 * 4
    assert OPS.decode_iteration_bytes(lm, 441600, 884) == sum(need.values())
    assert OPS.latent_attention_flops(lm, 1000) \
        == 2 * 5 * 32 * 1000 * (576 + 512)
    assert OPS.prefill_attention_flops(lm, 8192) \
        == 2 * 5 * 32 * (8192 * 8193 // 2) * 320


# ----------------------------------------------------------- the reference
def test_the_published_pairing_on_stored_columns_is_rotate_half():
    """Interleaved pairs on the published order = rotate-half on the
    stored (de-interleaved) order: the scores are the same numbers."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(7, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(7, 8)), jnp.float32)
    pos = jnp.arange(7, dtype=jnp.float32)

    def half(x):
        freq = 1e4 ** (-jnp.arange(4, dtype=jnp.float32) / 4)
        ang = pos[:, None] * freq
        a, b = x[:, :4], x[:, 4:]
        return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                                b * jnp.cos(ang) + a * jnp.sin(ang)], -1)

    pub = [REF._rope_interleaved(REF._published_order(x), pos, 1e4)
           for x in (q, k)]
    with jax.default_matmul_precision("highest"):
        want = half(q) @ half(k).T
        got = pub[0] @ pub[1].T
    assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.asarray(REF._published_order(jnp.arange(8.0))).tolist() \
        == [0, 4, 1, 5, 2, 6, 3, 7]


def test_the_expanded_form_is_the_absorbed_one():
    """The reference computes the expanded form; the absorbed form the
    program decodes with — ``q_nope W_uk^T`` against the latent, the
    attended latent through ``W_uv`` — is the same function."""
    params = _params()
    lp = {k: v for k, v in params["layers"]["00"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (9, 32), jnp.float32)
    lm = dict(REF._lm(LM))
    with jax.default_matmul_precision("highest"):
        want = REF._latent_attention(x, lp, lm, None)
        n = REF._rms(x, lp["ln"], lm["eps"])
        cq = REF._rms(n @ lp["w_dq"], lp["q_norm"], lm["eps"])
        q = jnp.einsum("sr,rhe->she", cq, lp["w_uq"])
        ckr = n @ lp["w_dkv"]
        c = REF._rms(ckr[:, :16], lp["kv_norm"], lm["eps"])
        pos = jnp.arange(9, dtype=jnp.float32)
        q_rope = REF._rope_interleaved(REF._published_order(q[..., 8:]), pos,
                                       lm["rope_theta"])
        k_rope = REF._rope_interleaved(REF._published_order(ckr[:, 16:]),
                                       pos, lm["rope_theta"])
        q_abs = jnp.einsum("she,rhe->shr", q[..., :8], lp["w_uk"])
        s = (jnp.einsum("shr,tr->hst", q_abs, c)
             + jnp.einsum("she,te->hst", q_rope, k_rope)) / math.sqrt(12)
        s = jnp.where(jnp.tril(jnp.ones((9, 9), bool)), s, -jnp.inf)
        ctx = jnp.einsum("hst,tr->shr", jax.nn.softmax(s, axis=-1), c)
        got = jnp.einsum("she,hed->sd",
                         jnp.einsum("shr,rhe->she", ctx, lp["w_uv"]),
                         lp["wo"])
    assert np.abs(np.asarray(got - want)).max() <= 2e-5 * max(
        1.0, np.abs(np.asarray(want)).max())


@pytest.mark.parametrize("control", REF.DEGRADATIONS)
def test_every_control_moves_the_output(control):
    params = _params()
    toks = np.random.default_rng(0).integers(0, 64, (24,)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(REF.logits(params, toks, lm=LM))
        low = np.asarray(REF.logits(params, toks, lm=LM, degrade=control))
    apart = np.abs(full - low).max() / np.abs(full).max()
    assert apart > 1e-3, apart
    assert np.isfinite(low).all()


def test_served_gaps_and_simulate_speak_the_drivers_protocol():
    params = _params()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 64, (9,)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        tokens, routed, logprobs = REF.simulate(params, prompt, [3] * 6, 32,
                                                8, lm=LM)
        gaps, absmax, missed, lp = REF.served_token_gaps(
            params, prompt, [3] * 6, 32, 8, lm=LM, routed=routed,
            scored=tokens)
        bad = np.asarray(routed).copy()
        bad[0, :, 0] = (bad[0, :, 1] + 1 + np.arange(14) % 3) % 8
        missed_bad = REF.served_token_gaps(
            params, prompt, [3] * 6, 32, 8, lm=LM, routed=bad,
            scored=tokens)[2]
    assert routed.shape == (1, 14, 2) and missed == 0
    assert int(missed_bad) > 0          # a choice is not taken on trust
    assert np.asarray(gaps).shape == (6,) and float(absmax) > 0
    assert np.abs(np.asarray(gaps)).max() == 0.0   # its own best tokens
    assert np.allclose(np.asarray(lp), np.asarray(logprobs), atol=1e-5)


# ------------------------------------------------------------------ the cell
def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "joyaiflash-serve-docreason", "--seed", "3000000019",
         "--seconds", "2", "--trace", "1", "--rehearse", "--override",
         'traffic.reference_degrade=["no_route_norm"]'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode in (0, 1), proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsed"] == "joyaiflash-serve-docreason"
    checks = next(x for x in lines if x.get("what") == "checks")["checks"]
    # the served program passes every check of its own, the matched ones
    # among them; the control has its entries
    assert all(v for k, v in checks.items() if ":" not in k), checks
    for how in CFG["tolerance"]["matched"]:
        assert "nearer_full_than_" + how in checks
    assert "routing_within_tolerance" in checks
    assert "no_route_norm:served_tokens_within_tolerance" in checks
    # what needs no device trace is read in a rehearsal too
    for metric in ("moe_experts_hit_share.docreason",
                   "paged_kernel_share.docreason",
                   "moe_load_max_over_mean.docreason",
                   "kv_window_fill.longgen", "decode_fill.docreason"):
        assert metric in last["metric_names"], last["metric_names"]
