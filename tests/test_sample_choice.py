"""The next-token choice pays for the tier of work the batch's sampling
controls ask for (``models.transformer.sample_tier``): every row of every
tier bit-equal to the one-path choice it replaced (kept here, verbatim,
as the oracle), the sort of the vocabulary inside a branch of a
conditional and nowhere else, and the engine's ``serving.sample_tier.*``
counters and ``sample_tier`` span argument reading what the device
branches on."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import deploy, generation, telemetry
from mxnet_tpu.models import HybridLM, HybridLMConfig
from mxnet_tpu.models.transformer import (SAMPLE_TIERS, TransformerLM,
                                          TransformerLMConfig, sample_tier)

PAGE = 4


def _choose_before_the_tiers(logits, positions, sample):
    """``TransformerLM._choose`` as it stood at f3b56e7 (PR 32), verbatim
    but for ``self``: one path for every batch."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if sample is None:
        return greedy
    temp = sample["temperature"].astype(jnp.float32)        # [B]
    top_k = sample["top_k"].astype(jnp.int32)               # [B]
    top_p = sample["top_p"].astype(jnp.float32)             # [B]
    keys = sample["key"].astype(jnp.uint32)                 # [B, 2]
    V = logits.shape[-1]
    safe_t = jnp.where(temp > 0, temp, 1.0)
    scaled = logits / safe_t[:, None]
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    # top-k: the kth-largest scaled logit is the row threshold
    k_idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    keep = jnp.where((top_k > 0)[:, None], scaled >= kth, True)
    # top-p (nucleus): keep the smallest sorted prefix whose
    # probability mass reaches p — token i survives while the mass
    # BEFORE it is < p, so the first token always survives
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    in_nucleus = (csum - probs) < top_p[:, None]
    thr = jnp.min(jnp.where(in_nucleus, sorted_desc, jnp.inf),
                  axis=-1, keepdims=True)
    keep &= jnp.where((top_p < 1.0)[:, None], scaled >= thr, True)
    masked = jnp.where(keep, scaled, -jnp.inf)
    gum = jax.vmap(lambda kr, pos: jax.random.gumbel(
        jax.random.fold_in(kr, pos), (V,), jnp.float32))(
            keys, positions.astype(jnp.uint32))
    choice = jnp.argmax(masked + gum, axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, choice, greedy)


#: per-row (temperature, top_k, top_p) of a batch of six, and its tier
BATCHES = {
    "all_greedy": ([(0.0, 0, 1.0)] * 6, "argmax"),
    # greedy rows carrying truncation controls still ask for nothing
    "greedy_with_controls": ([(0.0, 5, 1.0), (0.0, 0, 0.3), (0.0, 2, 0.5),
                              (0.0, 0, 1.0), (0.0, 1, 0.9), (0.0, 7, 1.0)],
                             "argmax"),
    "temperature_only": ([(0.7, 0, 1.0), (1.0, 0, 1.0), (1.9, 0, 1.0),
                          (0.05, 0, 1.0), (3.0, 0, 1.0), (1.3, 0, 1.0)],
                         "gumbel"),
    "greedy_and_temperature": ([(0.0, 4, 0.2), (1.1, 0, 1.0), (0.0, 0, 1.0),
                                (0.6, 0, 1.0), (0.0, 0, 0.7), (2.0, 0, 1.0)],
                               "gumbel"),
    "top_k": ([(0.8, 3, 1.0), (1.0, 1, 1.0), (1.5, 10, 1.0),
               (0.4, 2, 1.0), (2.5, 40, 1.0), (1.0, 5, 1.0)], "sorted"),
    "top_p": ([(0.8, 0, 0.9), (1.0, 0, 0.5), (1.5, 0, 0.05),
               (0.4, 0, 0.99), (2.5, 0, 0.7), (1.0, 0, 0.3)], "sorted"),
    "top_k_and_top_p": ([(0.8, 8, 0.9), (1.0, 3, 0.5), (1.5, 20, 0.6),
                         (0.4, 2, 0.99), (2.5, 30, 0.7), (1.0, 4, 0.2)],
                        "sorted"),
    "mixed": ([(0.0, 0, 1.0), (0.9, 0, 1.0), (1.2, 6, 1.0),
               (0.0, 3, 0.4), (1.7, 0, 0.8), (0.5, 9, 0.6)], "sorted"),
    "one_truncating_row": ([(1.0, 0, 1.0), (0.0, 0, 1.0), (0.7, 0, 1.0),
                            (1.4, 0, 0.95), (0.0, 0, 1.0), (2.2, 0, 1.0)],
                           "sorted"),
}


def _sample(rows, seed=0):
    temp, top_k, top_p = zip(*rows)
    keys = np.random.default_rng(seed).integers(
        0, 2 ** 32, size=(len(rows), 2), dtype=np.uint32)
    return {"temperature": np.asarray(temp, np.float32),
            "top_k": np.asarray(top_k, np.int32),
            "top_p": np.asarray(top_p, np.float32), "key": keys}


@pytest.mark.parametrize("vocab", [61, 1000])
@pytest.mark.parametrize("batch", list(BATCHES))
def test_every_tier_chooses_what_the_one_path_chose(batch, vocab):
    """Fixed keys and positions, logits with ties in them: the ids of
    every row are the oracle's, and the batch's tier (host predicate on
    the ``numpy`` operands, device predicate on the ``jnp`` ones) is the
    one its controls ask for."""
    rows, tier = BATCHES[batch]
    rng = np.random.default_rng(vocab)
    logits = rng.normal(0.0, 3.0, size=(len(rows), vocab)).astype(np.float32)
    logits[:, 7] = logits[:, 3]           # a tie inside every row
    logits[1] = np.round(logits[1])       # and a row of many
    positions = np.asarray([1, 5, 5, 17, 2, 900], np.int32)
    sample = _sample(rows, seed=vocab)
    controls = [sample[k] for k in ("temperature", "top_k", "top_p")]
    assert SAMPLE_TIERS[int(sample_tier(*controls))] == tier
    assert SAMPLE_TIERS[int(jax.jit(sample_tier)(*controls))] == tier
    model = TransformerLM(TransformerLMConfig(
        vocab_size=vocab, num_layers=1, d_model=8, num_heads=1, d_ff=8,
        max_len=8, dtype=jnp.float32))
    got = jax.jit(model._choose)(logits, positions, sample)
    want = jax.jit(_choose_before_the_tiers)(logits, positions, sample)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    greedy_rows = sample["temperature"] == 0
    np.testing.assert_array_equal(np.asarray(got)[greedy_rows],
                                  logits.argmax(-1)[greedy_rows])


def _toy(kind):
    if kind == "transformer":
        model = TransformerLM(TransformerLMConfig(
            vocab_size=61, num_layers=2, d_model=16, num_heads=2, d_ff=32,
            max_len=16, dtype=jnp.float32))
        return model, model.init(jax.random.PRNGKey(1)), \
            model.init_kv_pages(8, PAGE)
    model = HybridLM(HybridLMConfig(
        vocab_size=96, pattern="MEM*E", d_model=32, num_heads=4,
        num_kv_heads=2, head_dim=8, ssm_heads=4, ssm_head_dim=8,
        ssm_groups=2, ssm_state=16, conv_kernel=4, chunk=4, num_experts=16,
        top_k=3, moe_latent=16, expert_ff=24, shared_ff=40, route_scale=2.5,
        experts_held=8, expert_offset=4, max_len=64, dtype=jnp.float32))
    return model, model.init(jax.random.PRNGKey(0)), \
        model.init_kv_pages(8, PAGE, slots=6)


@pytest.mark.parametrize("kind", ["transformer", "hybrid"])
def test_decode_step_samples_what_the_one_path_sampled(kind, monkeypatch):
    """Through a toy model's whole ``decode_step``, a batch of each tier:
    the tokens are those of the same step with the one-path choice put
    back in the model's place."""
    model, params, kv = _toy(kind)
    tokens = jnp.asarray([3, 9, 27, 1, 44, 60], jnp.int32)
    positions = jnp.asarray([0, 2, 1, 3, 0, 5], jnp.int32)
    table = jnp.arange(6, dtype=jnp.int32).reshape(6, 1)

    def step(sample):
        return np.asarray(jax.jit(
            lambda s: model.decode_step(params, kv, tokens, positions, table,
                                        PAGE, sample=s)[1])(sample))

    samples = [_sample(BATCHES[b][0], seed=3)
               for b in ("all_greedy", "temperature_only", "mixed")]
    got = [step(s) for s in samples]
    monkeypatch.setattr(
        type(model), "_choose",
        lambda self, *args: _choose_before_the_tiers(*args))
    for ids, s in zip(got, samples):
        np.testing.assert_array_equal(ids, step(s))
    assert not np.array_equal(got[0], got[1])   # the draws do move tokens


# ------------------------------------------------------- lowered programs
def _decode_text(sampling):
    model, params, kv = _toy("transformer")
    sample = _sample(BATCHES["mixed"][0]) if sampling else None
    return jax.jit(lambda s: model.decode_step(
        params, kv, jnp.zeros((6,), jnp.int32), jnp.ones((6,), jnp.int32),
        jnp.zeros((6, 2), jnp.int32), PAGE, sample=s)).lower(
            sample).as_text(debug_info=True)


def test_the_sort_is_lowered_inside_a_branch_of_a_conditional():
    """A sampling decode program's text: one ``stablehlo.case`` of three
    branches, under ``mx.sample``; the vocabulary's sort — and the
    cumulative sum over it — stand in its last branch and nowhere else,
    the Gumbel draws (``threefry``) in the last two, and the sort carries
    the scope's name."""
    text = _decode_text(sampling=True)
    assert text.count("stablehlo.case") == 1
    head, rest = text.split('"stablehlo.case"', 1)
    # the case op's regions end where its result types are given
    body, tail = re.split(r"\n\s*\}\) : \(tensor<i32>\) -> ", rest, 1)
    branches = body.split("}, {")
    assert len(branches) == len(SAMPLE_TIERS)
    outside = head + tail
    assert "stablehlo.sort" not in outside
    assert ["stablehlo.sort" in b for b in branches] == [False, False, True]
    assert ["threefry" in b or "rng_bit_generator" in b
            for b in branches] == [False, True, True]
    assert "stablehlo.sort" not in _decode_text(sampling=False)
    assert re.search(r'mx\.sample/[^"]*sort', text)


#: sha256 of a plain (``sampling=False``, format v4) artifact's programs at
#: f3b56e7 (PR 32), source locations aside: ``_choose`` returns the arg-max
#: before it reads a control, so what such an artifact holds did not move
PLAIN_PROGRAMS_BEFORE_THE_TIERS = {
    "prefill-s8":
        "1486ffd3ddbff7043a77e74f8fd1f45db9486b390a20946f0cb2de8fc89c2c24",
    "decode-w1":
        "71ab08fbf88047d57a7218696602ffb54acbfe94c6415f36e91dcffafa91ed59",
    "decode-w4":
        "62e90490996659ffb7e326465e7b12981881375cac5674d05b213d34dc017970",
}


@pytest.fixture(scope="module")
def plain_artifact(tmp_path_factory):
    model, params, _ = _toy("transformer")
    prefix = str(tmp_path_factory.mktemp("plain") / "lm")
    deploy.export_generation(model, params, prefix, page_size=PAGE,
                             max_context=16, prompt_buckets=(8,),
                             include_params=False)
    return prefix


@pytest.mark.parametrize("program", list(PLAIN_PROGRAMS_BEFORE_THE_TIERS))
def test_plain_exports_are_the_parents(plain_artifact, program):
    """The programs ``export_generation`` writes without ``sampling`` hold
    no conditional and are, line for line, the parent's."""
    import hashlib
    from jax import export as jexport
    with open("%s-%s.stablehlo" % (plain_artifact, program), "rb") as f:
        text = jexport.deserialize(f.read()).mlir_module()
    assert "stablehlo.case" not in text and "stablehlo.sort" not in text
    text = "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#loc"))
    text = re.sub(r"\(#loc\d*\)", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PLAIN_PROGRAMS_BEFORE_THE_TIERS[program]


# ------------------------------------------------- the engine's counters
def _tiers():
    return {t: telemetry.counter("serving.sample_tier." + t).value
            for t in SAMPLE_TIERS}


def test_engine_counts_the_tier_of_every_dispatch(tmp_path):
    """A server fed greedy, then temperature-only, then top-p requests (one
    at a time, so each dispatch holds one kind of row): every prefill and
    every decode iteration counts one ``serving.sample_tier.*`` — the one
    the predicate gives for the operands it was handed — and says so on
    its span."""
    from _util import profiled_spans
    model, params, _ = _toy("transformer")
    prefix = str(tmp_path / "lm")
    deploy.export_generation(model, params, prefix, page_size=PAGE,
                             max_context=16, prompt_buckets=(8,),
                             sampling=True)
    pred = deploy.load_generator(prefix)
    prompt = np.asarray([5, 17, 40], np.int32)
    asked = (("argmax", {}), ("argmax", {"top_k": 4, "top_p": 0.5}),
             ("gumbel", {"temperature": 0.8, "seed": 3}),
             ("sorted", {"temperature": 0.8, "top_p": 0.6, "seed": 3}),
             ("sorted", {"temperature": 1.2, "top_k": 5, "seed": 4}))
    telemetry.reset()
    seen = []

    def run():
        eng = generation.GenerationEngine("m", pred, num_pages=16,
                                          decode_slots=2).start()
        try:
            for _, controls in asked:
                before = _tiers()
                eng.submit(prompt, 5, **controls).result(timeout=60)
                seen.append({t: n - before[t] for t, n in _tiers().items()})
        finally:
            eng.stop()

    spans = profiled_spans(run, tmp_path, ("engine.prefill", "engine.decode"))
    # one prefill and four decode iterations a request of five tokens
    for (tier, _), counted in zip(asked, seen):
        assert counted == dict(dict.fromkeys(SAMPLE_TIERS, 0), **{tier: 5}), \
            (tier, counted)
    snap = telemetry.snapshot()["timers"]
    assert sum(_tiers().values()) == (snap["serving.prefill_ms"]["count"]
                                      + snap["serving.decode_step_ms"]["count"])
    for name, each in (("engine.prefill", 1), ("engine.decode", 4)):
        said = [s[3]["sample_tier"] for s in spans if s[0] == name]
        assert said == [t for t, _ in asked for _ in range(each)], said
