"""Generation requests from a seed and a traffic file: one general generator.

Every seed gets the SAME set of sizes and arrivals in another order, so that
the seed does not change the work: lengths and inter-arrival gaps are the
quantiles of their distribution at the mid-points of ``block`` equal slices
(a stratified sample with no randomness in the values), and the seed only
permutes each block and draws the token ids.

Traffic file parameters:

  arrivals        {"kind": "closed", "clients": n}   n requests outstanding;
                                                     the next is sent when
                                                     one resolves
                  {"kind": "open", "rate": r, "cv": c}   arrivals on a
                                                     schedule: gaps with mean
                                                     1/r, exponential
                                                     (Poisson, cv 1) or
                                                     gamma with that cv
  prompt_tokens   a distribution (below), in tokens
  new_tokens      a distribution, in tokens
  first_wave      {"scale": [lo, hi]}  closed loop: the first ``clients``
                  requests' new tokens scaled by a stratified U(lo, hi), so
                  completions do not march in step
  shared_prefix   {"pool": k, "tokens": t, "share": s}  a share s of the
                  requests starts with one of k prefixes of t tokens
  block           requests per stratified block
  requests        how many to make (the dispatcher cycles if it runs out)

A distribution is ``{"dist": "lognormal", "median": m, "sigma": s, "min":
lo, "max": hi}`` or ``{"dist": "uniform", "min": lo, "max": hi}`` or
``{"dist": "fixed", "value": v}``; values are clipped to [min, max].

Returns ``{"requests": [{"prompt": int32[n], "max_new": k, "due_s": t |
None}], "clients": n | None, "rate": r | None}``: a pure function of seed,
parameters and vocabulary size."""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

from benchmarks.harness.stats import fold_seed


def quantiles(spec, count):
    """``count`` values: the distribution's quantiles at slice mid-points,
    clipped, as whole numbers >= 1."""
    u = (np.arange(count) + 0.5) / count
    if spec["dist"] == "fixed":
        vals = np.full(count, float(spec["value"]))
    elif spec["dist"] == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError("unknown distribution %r" % spec["dist"])
    lo = spec.get("min", 1)
    hi = spec.get("max", float("inf"))
    return np.maximum(np.clip(np.rint(vals), lo, hi), 1).astype(np.int64)


def gaps(rate, cv, count):
    """``count`` inter-arrival gaps with mean 1/rate: exponential quantiles
    for cv 1 (Poisson arrivals); for another cv, quantiles of a gamma sample
    drawn from a FIXED seed (the same for every run seed)."""
    u = (np.arange(count) + 0.5) / count
    if abs(cv - 1.0) < 1e-9:
        g = -np.log1p(-u)
    else:
        shape = 1.0 / (cv * cv)
        draw = np.random.default_rng(12345).gamma(shape, 1.0 / shape,
                                                  count * 256)
        g = np.quantile(draw, u)
    return g / g.mean() / rate


def generate(seed, params, vocab):
    rng = np.random.default_rng(fold_seed(seed, 1))
    block = int(params["block"])
    total = int(params["requests"])
    n_blocks = math.ceil(total / block)
    arrivals = params["arrivals"]

    def blocks(values):
        return np.concatenate([rng.permutation(values)
                               for _ in range(n_blocks)])[:total]

    plen = blocks(quantiles(params["prompt_tokens"], block))
    new = blocks(quantiles(params["new_tokens"], block))
    clients = rate = None
    due = [None] * total
    if arrivals["kind"] == "closed":
        clients = int(arrivals["clients"])
        wave = params.get("first_wave")
        if wave:
            lo, hi = wave["scale"]
            k = min(clients, total)
            scale = rng.permutation(lo + (np.arange(k) + 0.5) / k * (hi - lo))
            new[:k] = np.maximum(np.rint(new[:k] * scale), 1).astype(np.int64)
    elif arrivals["kind"] == "open":
        rate = float(arrivals["rate"])
        due = list(np.cumsum(blocks(gaps(rate, float(arrivals.get("cv", 1.0)),
                                         block))))
    else:
        raise ValueError("unknown arrivals kind %r" % arrivals["kind"])

    prefix = params.get("shared_prefix")
    pool = shared = None
    if prefix:
        pool = rng.integers(0, vocab, (int(prefix["pool"]),
                                       int(prefix["tokens"])))
        per_block = int(round(float(prefix["share"]) * block))
        shared = blocks(np.arange(block) < per_block)
    requests = []
    for i in range(total):
        prompt = rng.integers(0, vocab, (int(plen[i]),)).astype(np.int32)
        if shared is not None and shared[i]:
            head = pool[int(rng.integers(0, len(pool)))][:len(prompt)]
            prompt[:len(head)] = head
        requests.append({"prompt": prompt, "max_new": int(new[i]),
                         "due_s": None if due[i] is None else float(due[i])})
    return {"requests": requests, "clients": clients, "rate": rate}
