"""Sessions that are all resident at once: long-lived requests, each
prefilled in set-up, whose decode the measured window times.

Every seed gets the same set of prompt lengths (fixed quantiles of a
lognormal, clipped and rounded), so the work and the compiled shapes do
not change with the seed; the seed picks which session gets which length
and draws every prompt token.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def prompt_lengths(spec: dict, n: int) -> list[int]:
    """The ``n`` quantiles (i + 1/2) / n of the mix's lognormal, clipped to
    [min, max] and rounded to ``round_to`` tokens."""
    out = []
    for i in range(n):
        z = NormalDist().inv_cdf((i + 0.5) / n)
        x = spec["lognormal_median"] * math.exp(spec["lognormal_sigma"] * z)
        x = min(max(x, spec["min"]), spec["max"])
        out.append(int(round(x / spec["round_to"])) * spec["round_to"])
    return out


def generate(mix: dict, vocab: int, seed: int) -> list[dict]:
    """Requests as ``{"tokens": int32 array, "max_new": int}``."""
    dep = mix["deployment"]
    n = mix["sessions"]
    if n > dep["slots"]:
        raise ValueError(f"{n} resident sessions need {n} slots, not {dep['slots']}")
    rng = np.random.default_rng(seed)
    lengths = rng.permutation(prompt_lengths(mix["prompt_tokens"], n))
    if mix["max_new"] != "capacity_minus_prompt":
        raise ValueError(f"unknown max_new rule {mix['max_new']!r}")
    reqs = []
    for length in lengths:
        length = int(length)
        if not 0 < length < dep["capacity"]:
            raise ValueError(f"prompt of {length} tokens in capacity {dep['capacity']}")
        toks = rng.integers(0, vocab, size=length, dtype=np.int32)
        reqs.append({"tokens": toks, "max_new": dep["capacity"] - length})
    return reqs
