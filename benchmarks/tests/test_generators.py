"""(b) The generators are pure functions of seed and parameters."""
import numpy as np

from benchmarks.harness import manifest

LM = manifest.load_module("generators", "lm_requests")
STEPS = manifest.load_module("generators", "steady_steps")


def _traffic(name):
    return manifest.load_json("traffic", name + ".json")


def _same(a, b):
    return all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new"] == y["max_new"] and x["due_s"] == y["due_s"]
               for x, y in zip(a["requests"], b["requests"]))


def test_lm_requests_same_seed_same_requests_other_seed_other():
    for name in ("offline_longgen", "chat_short"):
        tp = _traffic(name)
        a, b = LM.generate(3000000019, tp, 50272), \
            LM.generate(3000000019, tp, 50272)
        c = LM.generate(5, tp, 50272)
        assert len(a["requests"]) == tp["requests"]
        assert _same(a, b) and not _same(a, c)


def test_every_seed_gets_the_same_sizes_in_another_order():
    tp = _traffic("chat_short")
    a, c = LM.generate(1, tp, 50272), LM.generate(2, tp, 50272)
    for key in ("max_new",):
        assert sorted(r[key] for r in a["requests"]) == \
            sorted(r[key] for r in c["requests"])
    assert sorted(r["prompt"].size for r in a["requests"]) == \
        sorted(r["prompt"].size for r in c["requests"])
    assert [r["max_new"] for r in a["requests"]] != \
        [r["max_new"] for r in c["requests"]]
    # and every block of `block` requests holds the same sizes
    blk = tp["block"]
    first = sorted(r["max_new"] for r in a["requests"][:blk])
    assert first == sorted(r["max_new"] for r in a["requests"][blk:2 * blk])


def test_length_clips_and_medians_hold():
    for name in ("offline_longgen", "chat_short"):
        tp = _traffic(name)
        reqs = LM.generate(11, tp, 50272)["requests"]
        skip = tp["arrivals"].get("clients", 0) if "first_wave" in tp else 0
        plen = np.array([r["prompt"].size for r in reqs])
        new = np.array([r["max_new"] for r in reqs[skip:]])
        p, n = tp["prompt_tokens"], tp["new_tokens"]
        assert plen.min() >= p["min"] and plen.max() <= p["max"]
        assert new.min() >= n["min"] and new.max() <= n["max"]
        assert abs(np.median(plen) - p["median"]) <= 0.05 * p["median"]
        assert (plen + np.array([r["max_new"] for r in reqs])).max() \
            <= tp["max_context"]
        assert plen.max() <= max(tp["prompt_buckets"])
        assert all(0 <= r["prompt"].min() and r["prompt"].max() < 50272
                   for r in reqs)


def test_first_wave_is_shortened_and_closed_loop_has_no_due_times():
    tp = _traffic("offline_longgen")
    out = LM.generate(4, tp, 50272)
    k = tp["arrivals"]["clients"]
    assert out["clients"] == k and out["rate"] is None
    assert all(r["due_s"] is None for r in out["requests"])
    wave = np.array([r["max_new"] for r in out["requests"][:k]])
    rest = np.array([r["max_new"] for r in out["requests"][k:2 * k]])
    assert wave.mean() < 0.7 * rest.mean() and wave.min() >= 1


def test_poisson_mean_rate_holds_and_arrivals_increase():
    tp = _traffic("chat_short")
    out = LM.generate(9, tp, 50272)
    due = np.array([r["due_s"] for r in out["requests"]])
    gaps = np.diff(np.concatenate([[0.0], due]))
    rate = tp["arrivals"]["rate"]
    assert out["rate"] == rate and (gaps > 0).all()
    assert abs(gaps.mean() * rate - 1.0) < 0.01
    # exponential gaps: coefficient of variation near 1
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1
    bursty = dict(tp, arrivals={"kind": "open", "rate": rate, "cv": 3.0})
    g = np.diff([r["due_s"] for r in LM.generate(9, bursty, 50272)[
        "requests"]])
    assert abs(g.mean() * rate - 1.0) < 0.05 and g.std() / g.mean() > 2.0


def test_shared_prefix_share():
    tp = dict(_traffic("chat_short"), requests=128,
              shared_prefix={"pool": 2, "tokens": 16, "share": 0.5},
              prompt_tokens={"dist": "fixed", "value": 64})
    reqs = LM.generate(3, tp, 1000)["requests"]
    heads = {r["prompt"][:16].tobytes() for r in reqs}
    counts = sorted(sum(r["prompt"][:16].tobytes() == h for r in reqs)
                    for h in heads)
    assert len(heads) == 64 + 2 and counts[-2] + counts[-1] == 64


def test_steady_steps_is_a_pure_function():
    tp = _traffic("steady_steps")
    sizes = {"image": 8, "classes": 10}
    a, b = STEPS.generate(7, tp, sizes, 2), STEPS.generate(7, tp, sizes, 2)
    c = STEPS.generate(8, tp, sizes, 2)
    assert a["batch"] == 2 * tp["per_chip_batch"] == a["data"].shape[0]
    assert np.array_equal(a["data"], b["data"]) and \
        np.array_equal(a["label"], b["label"])
    assert not np.array_equal(a["data"], c["data"])
    assert a["data"].dtype == np.float32 and a["label"].max() < 10
