"""The ``brumby_14b_pp5`` reference (``reference/brumby_14b_pp5.py``): its
quadratic and recurrent forms are one function, every control it can
simulate moves the output, and the cell's files make a rehearsal."""
import json
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
REF = manifest.load_module("reference", "brumby_14b_pp5")
LM = {"rope_theta": 1e4, "eps": 1e-6}


def _params(seed=0, layers=2, V=64, D=32, H=4, KV=2, Dh=8, F=48):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    n = lambda *s: jax.random.normal(next(keys), s, jnp.float32) * 0.2  # noqa
    blocks = {}
    for i in range(layers):
        blocks["%02d" % (2 * i)] = {
            "ln": 1.0 + n(D), "wq": n(D, H, Dh), "wk": n(D, KV, Dh),
            "wv": n(D, KV, Dh), "wg": n(D, KV), "bg": 3.0 + n(KV),
            "qn": 1.0 + n(Dh), "kn": 1.0 + n(Dh), "wo": n(H, Dh, D)}
        blocks["%02d" % (2 * i + 1)] = {
            "ln": 1.0 + n(D), "w_gate": n(D, F), "w_up": n(D, F),
            "w_down": n(F, D)}
    return {"embed": n(V, D) * 5, "head": n(V, D), "final_norm": 1.0 + n(D),
            "layers": blocks}


@pytest.mark.parametrize("length", [1, 7, 300])
def test_recurrent_form_is_the_quadratic_one(length):
    """(300 rows: more than one block of query rows.)"""
    rng = np.random.default_rng(length)
    q = jnp.asarray(np.abs(rng.normal(size=(length, 2, 3, 4))), jnp.float32)
    k = jnp.asarray(np.abs(rng.normal(size=(length, 2, 4))), jnp.float32)
    v = jnp.asarray(rng.normal(size=(length, 2, 4)), jnp.float32)
    logg = jnp.log(jnp.asarray(rng.uniform(0.5, 0.999, size=(length, 2)),
                               jnp.float32))
    with jax.default_matmul_precision("highest"):
        quad = REF.retention_quadratic(q, k, v, logg)
        rec = REF.retention_recurrent(q, k, v, logg)
    assert np.abs(np.asarray(quad) - np.asarray(rec)).max() <= 2e-5 * max(
        1.0, np.abs(np.asarray(quad)).max())
    # position 0 hears itself alone: its value, whatever q and k
    assert np.allclose(quad[0], np.broadcast_to(v[0][:, None], quad[0].shape),
                       atol=1e-6)


@pytest.mark.parametrize("control", REF.DEGRADATIONS)
def test_every_control_moves_the_output(control):
    params = _params()
    toks = np.random.default_rng(0).integers(0, 64, (24,)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        full = np.asarray(REF.logits(params, toks, lm=LM))
        low = np.asarray(REF.logits(params, toks, lm=LM, degrade=control))
    apart = np.abs(full - low).max() / np.abs(full).max()
    # bf16 state is a rounding; the others change the function
    assert apart > (1e-5 if control == "bf16_state" else 1e-2), apart
    assert np.isfinite(low).all()


def test_served_gaps_and_simulate_speak_the_drivers_protocol():
    params = _params()
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 64, (9,)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        tokens, routed, logprobs = REF.simulate(params, prompt, [3] * 6, 32,
                                                8, lm=LM)
        gaps, absmax, missed, lp = REF.served_token_gaps(
            params, prompt, list(np.asarray(tokens)), 32, 8, lm=LM,
            routed=routed)
    assert routed.shape == (0, 14, 0) and missed == 0
    assert np.asarray(gaps).shape == (6,) and float(absmax) > 0
    assert np.abs(np.asarray(gaps)[:1]).max() == 0.0   # its own best token
    assert np.allclose(np.asarray(lp)[:1], np.asarray(logprobs)[:1],
                       atol=1e-5)


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "brumby14b-serve-reason", "--seed", "3000000019",
         "--seconds", "2", "--trace", "0", "--rehearse", "--override",
         'traffic.reference_degrade=["no_rope"]'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode in (0, 1), proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = [json.loads(x) for x in proc.stdout.strip().splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert last["rehearsed"] == "brumby14b-serve-reason"
    checks = next(x for x in lines if x.get("what") == "checks")["checks"]
    # the served program passes every check of its own; the matched one
    # is made though no routing is told; the control has its entries
    assert all(v for k, v in checks.items() if ":" not in k), checks
    assert "nearer_full_than_bf16_state" in checks
    assert "no_rope:served_tokens_within_tolerance" in checks
