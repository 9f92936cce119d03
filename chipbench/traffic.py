"""The one traffic generator: a cell's ``traffic`` parameters and a seed in,
requests out.

Every seed gets the same multiset of sizes and (open loop) the same
multiset of gaps between arrivals, in another order: the sizes are the
stratified quantiles of the distributions the cell's file names, and the
seed only permutes them and draws the prompts' token ids. So two seeds
offer the same amount of work, and a run's numbers differ by the order
alone. Uses numpy and the standard library; never imports jax.
"""

import math
from statistics import NormalDist

import numpy as np


def quantiles(spec, n):
    """``n`` whole numbers at the stratified quantiles of ``spec``."""
    qs = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(q)) for q in qs])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + qs * (spec["max"] + 1 - spec["min"])
        vals = np.floor(vals)
    elif spec["dist"] == "fixed":
        vals = np.full(n, spec["value"])
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    lo = spec.get("min", -math.inf)
    hi = spec.get("max", math.inf)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def arrival_gaps(spec, n, span_s):
    """``n`` gaps between arrivals whose sum is ``span_s``: the stratified
    quantiles of the process the cell names, scaled to the span."""
    qs = (np.arange(n) + 0.5) / n
    if spec.get("arrivals", "poisson") == "poisson":
        gaps = -np.log1p(-qs)
    elif spec["arrivals"] == "even":
        gaps = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {spec['arrivals']!r}")
    return gaps * (span_s / gaps.sum())


def generate(spec, seed, seconds, vocab):
    """The load generator's whole input for one run.

    Open loop: ``round(rate * (ramp + seconds))`` requests, each with the
    time it is due (seconds after the start of the ramp). Closed loop:
    ``population`` requests, dealt to ``clients`` queues that cycle."""
    rng = np.random.default_rng(int(seed))
    span = float(spec["ramp_s"]) + float(seconds)
    if spec["loop"] == "open":
        n = max(1, int(round(spec["rate_per_s"] * span)))
    elif spec["loop"] == "closed":
        n = int(spec["population"])
    else:
        raise ValueError(f"unknown loop {spec['loop']!r}")
    prompt_lens = rng.permutation(quantiles(spec["prompt_tokens"], n))
    output_lens = rng.permutation(quantiles(spec["output_tokens"], n))
    requests = []
    for i in range(n):
        body = {
            "prompt": rng.integers(0, vocab, size=int(prompt_lens[i])).tolist(),
            "max_new_tokens": int(output_lens[i]),
            "temperature": float(spec.get("temperature", 0.0)),
            "stream": True,
        }
        requests.append({"id": i, "body": body})
    plan = {
        "loop": spec["loop"], "ramp_s": float(spec["ramp_s"]),
        "seconds": float(seconds), "drain_s": float(spec.get("drain_s", 60)),
        "requests": requests,
    }
    if spec["loop"] == "open":
        gaps = rng.permutation(arrival_gaps(spec, n, span))
        due = np.cumsum(gaps) - gaps[0] * 0.5
        for r, t in zip(requests, due):
            r["due_s"] = float(t)
    else:
        plan["clients"] = int(spec["clients"])
    return plan
