"""The one generator of serving traffic. A traffic mix is a file of its
parameters; everything here is a function of (parameters, vocabulary,
seed, seconds) and of nothing else.

    arrivals    {"process": "backlog", "count": n}             all due at 0
                {"process": "poisson", "rate_rps": r}          an open loop
    prompt_len, output_len
                {"dist": "lognormal", "median", "sigma", "min", "max"}
                {"dist": "uniform", "min", "max"}
    sampling    keyword arguments of serving.Request (do_sample, top_k, ...)

The amount of work is fixed and only its order is drawn: an open loop
has exactly round(rate * seconds) arrivals (a Poisson process conditioned
on its count, which is uniform order statistics), and lengths are the
evenly spaced quantiles of their distribution, shuffled block by block.
Two seeds then offer the same load in another order, and a run-to-run
difference is the system's and not the dice's.
"""
import math
from statistics import NormalDist

import numpy as np


BLOCK = 32


def _quantiles(spec, n, rng):
    """n values at evenly spaced quantiles of `spec`, in a drawn order that
    keeps every run of BLOCK consecutive values spread over the whole
    distribution: value i lies in the stratum its block's shuffle gives it,
    at the offset its block drew. The first hundred requests of a backlog,
    or a few seconds of an open loop, then carry the same mix as the whole."""
    full, tail = divmod(n, BLOCK)
    offset = (rng.permutation(full) + 0.5) / max(full, 1)
    u = np.concatenate(
        [(rng.permutation(BLOCK) + offset[j]) / BLOCK for j in range(full)]
        + [(rng.permutation(tail) + 0.5) / max(tail, 1)])
    dist = spec["dist"]
    if dist == "uniform":
        vals = spec["min"] + u * (spec["max"] + 1 - spec["min"]) - 0.5
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def _due(arrivals, seconds, rng):
    process = arrivals["process"]
    if process == "backlog":
        return np.zeros(int(arrivals["count"]))
    if process == "poisson":
        n = max(1, round(arrivals["rate_rps"] * seconds))
        return np.sort(rng.uniform(0.0, seconds, n))
    raise ValueError(f"unknown arrival process {process!r}")


def generate(params, vocab_size, seed, seconds):
    """The requests of one run, in order of their due times: dicts with
    `due` (seconds from the window's start), `prompt` (token ids),
    `max_new_tokens`, `sampling` and `seed` (of the request's own RNG
    stream in the engine)."""
    rng = np.random.default_rng([int(seed), 0x7261])
    due = _due(params["arrivals"], seconds, rng)
    plens = _quantiles(params["prompt_len"], len(due), rng)
    olens = _quantiles(params["output_len"], len(due), rng)
    return [{"due": float(due[i]),
             "prompt": rng.integers(0, vocab_size, plens[i]),
             "max_new_tokens": int(olens[i]),
             "sampling": dict(params.get("sampling") or {}),
             "seed": i}
            for i in range(len(due))]
