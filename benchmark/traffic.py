"""The one general traffic generator. A traffic mix is a data file of
parameters (``workloads/<cell>.json``, key ``traffic``); a new mix needs no
code. Every seed gets the same multiset of request sizes, in another order,
so the seed moves the order of the work and not its amount."""

import math
from typing import List, Tuple

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole-number sizes at evenly spaced quantiles of the
    distribution ``spec`` names: the sample every seed shares."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(spec.get("lo", 0)), float(spec.get("hi", 0))
    if spec["dist"] == "uniform":
        x = lo + (hi - lo) * u
    elif spec["dist"] == "log_uniform":
        x = np.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * u)
    elif spec["dist"] == "choice":      # each value an equal share of the pool
        x = np.sort(np.resize(np.asarray(spec["values"], float), n))
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.rint(x).astype(np.int64)


def request_sizes(traffic: dict, seed: int) -> List[Tuple[int, int]]:
    """``traffic['pool']`` pairs of (prompt tokens, output tokens). The two
    quantile grids are paired by a fixed permutation, so the pool is the same
    for every seed; ``seed`` only shuffles the order requests are sent in."""
    n = int(traffic["pool"])
    prompts = _quantiles(traffic["prompt_len"], n)
    outputs = _quantiles(traffic["output_len"], n)
    outputs = outputs[np.random.default_rng(0).permutation(n)]
    order = np.random.default_rng(seed).permutation(n)
    return [(int(prompts[i]), int(outputs[i])) for i in order]


def prompt_tokens(seed: int, index: int, length: int,
                  vocab_size: int) -> np.ndarray:
    """Token ids of request ``index`` under ``seed``."""
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, vocab_size, size=length, dtype=np.int64)


def token_batches(seed: int, rows: int, seq: int, vocab_size: int):
    """Endless host iterator of fresh ``[rows, seq]`` int32 token batches."""
    rng = np.random.default_rng(seed)
    while True:
        yield rng.integers(0, vocab_size, size=(rows, seq), dtype=np.int32)
