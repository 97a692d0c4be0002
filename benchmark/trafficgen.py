"""The one generator of traffic: it reads a mix file's parameters and makes,
from --seed, what a driver feeds the system. A new mix is a new data file.

Every seed gets the SAME schedule of sizes: the ``distinct`` stratified
quantiles of each length law, paired and ordered by shuffles that are fixed in
the mix (``schedule_seed``). A seed changes the token ids (and the weights),
never the work nor when it comes. An order of the seed's own was tried first
(PR 24): a 25 s window holds about one and a half cycles of 32 sizes, which
ones depends on the order, and runs of different seeds then spread by 2% in
tokens/s and 6% in a TTFT percentile where two runs of one seed differ by 0.3%.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def law_quantiles(law: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a length law, clipped and rounded."""
    q = (np.arange(n) + 0.5) / n
    if law["law"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        vals = law["median"] * np.exp(law["sigma"] * z)
    elif law["law"] == "uniform":
        vals = law["min"] + q * (law["max"] - law["min"])
    elif law["law"] == "fixed":
        vals = np.full(n, law["value"], float)
    else:
        raise ValueError(f"unknown length law {law['law']!r}")
    lo, hi = law.get("min", 1), law.get("max", math.inf)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def law_max(law: dict) -> int:
    """The longest length a law can give."""
    return int(law["value"] if law["law"] == "fixed" else law["max"])


def request_sizes(mix: dict) -> list[tuple[int, int]]:
    """(prompt_len, output_len) pairs in the mix's own fixed order. Callers
    cycle through it."""
    n = int(mix["distinct"])
    prompts = law_quantiles(mix["prompt_len"], n)
    outputs = law_quantiles(mix["output_len"], n)
    rng = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    pairing, order = rng.permutation(n), rng.permutation(n)
    return [(int(prompts[i]), int(outputs[pairing[i]])) for i in order]


def head_start(mix: dict, n: int) -> np.ndarray:
    """Share of its output length that each of a closed loop's first ``n``
    requests gets, as if caught mid-answer, so that the callers do not all
    finish together. Fixed in the mix, like the order."""
    return np.random.default_rng([int(mix.get("schedule_seed", 0)), 7]).uniform(0.05, 1.0, size=n)


class Prompts:
    """Token ids for request number ``i``: uniform over the vocabulary, with a
    share of requests opening on one of ``pool`` shared prefixes."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.vocab, self.seed = vocab, int(seed)
        sp = mix.get("shared_prefix") or {}
        self.share = float(sp.get("share", 0.0))
        rng = np.random.default_rng([self.seed, 2])
        self.pool = [
            rng.integers(0, vocab, size=int(sp.get("len", 0)), dtype=np.int64).astype(np.int32)
            for _ in range(int(sp.get("pool", 1)) if self.share > 0 else 0)
        ]

    def make(self, i: int, length: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 3, i])
        ids = rng.integers(0, self.vocab, size=length, dtype=np.int64).astype(np.int32)
        if self.pool and rng.random() < self.share:
            pre = self.pool[int(rng.integers(len(self.pool)))][:length]
            ids[: len(pre)] = pre
        return ids


def arrivals(mix: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Open-loop send times in [0, horizon): Poisson at ``rate_rps``, or
    bursts of ``burst`` requests whose starts are Poisson at rate / burst."""
    rate, burst = float(mix["rate_rps"]), int(mix.get("burst", 1))
    rng = np.random.default_rng([int(seed), 4])
    n = int(rate * horizon_s * 2 / burst) + 16
    starts = np.cumsum(rng.exponential(burst / rate, size=n))
    times = np.repeat(starts, burst)
    return times[times < horizon_s]


def train_tokens(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """[steps, batch, seq + 1] int32 ids, log-uniform over the vocabulary
    (Zipf exponent 1), every row a draw of its own."""
    shape = (int(mix["token_file_steps"]), int(mix["global_batch"]), int(mix["seq_len"]) + 1)
    u = np.random.default_rng([int(seed), 5]).random(shape)
    return np.minimum(np.floor(vocab**u).astype(np.int32) - 1, vocab - 1)
