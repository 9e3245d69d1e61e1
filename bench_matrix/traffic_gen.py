"""The one traffic generator: every mix is a file of parameters read here.

Same seed, same inputs. Lengths are drawn by stratified inverse-CDF sampling:
the request stream is made of cycles of `strata` requests, and a cycle holds
exactly one length from the middle of each of `strata` equal-probability
slices of the distribution, in an order shuffled from the seed. So every
seed offers the same multiset of lengths per cycle (a fixed amount of work)
and differs in order and in token ids. Prompt and output lengths are
shuffled independently.

The open-loop arrival sampler (seeded inverse-CDF over a rate profile) is
copied in idea from `benchmarks/load_harness.make_trace`, which runs on a
virtual clock at toy sizes and is listed in PERF.md for deletion.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile(dist: dict, u: float) -> int:
    """The length at probability `u` of a length distribution, clipped."""
    kind = dist["dist"]
    if kind == "fixed":
        x = dist["value"]
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = math.exp(
            math.log(dist["median"]) + dist["sigma"] * NormalDist().inv_cdf(u)
        )
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", x)
    return int(min(max(round(x), lo), hi))


def length_cycle(dist: dict, strata: int) -> np.ndarray:
    """One length from the middle of each of `strata` probability slices."""
    return np.array(
        [quantile(dist, (i + 0.5) / strata) for i in range(strata)], np.int64
    )


class RequestStream:
    """An endless, seeded stream of (prompt token ids, max new tokens)."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.rng = np.random.default_rng([seed, 0x5E47E])
        self.vocab = vocab
        self.prompts = length_cycle(traffic["prompt_tokens"], traffic["strata"])
        self.outputs = length_cycle(traffic["output_tokens"], traffic["strata"])
        self.prefix_len = int(traffic.get("shared_prefix_tokens", 0))
        groups = int(traffic.get("prefix_groups", 1))
        self.prefixes = self.rng.integers(
            0, vocab, (groups, self.prefix_len), dtype=np.int32
        )
        self.count = 0
        self._cycle = []

    def next(self):
        if not self._cycle:
            p = self.rng.permutation(self.prompts)
            o = self.rng.permutation(self.outputs)
            self._cycle = list(zip(p.tolist(), o.tolist()))[::-1]
        n_prompt, n_out = self._cycle.pop()
        body = self.rng.integers(
            0, self.vocab, (max(n_prompt - self.prefix_len, 1),), dtype=np.int32
        )
        if self.prefix_len:
            head = self.prefixes[self.rng.integers(len(self.prefixes))]
            body = np.concatenate([head, body])
        self.count += 1
        return body, int(n_out)


def open_arrivals(arrival: dict, seed: int, duration_s: float) -> np.ndarray:
    """Seeded open-loop arrival times in [0, duration_s): a Poisson number
    of requests placed by inverse CDF over the rate profile, which is
    `rate_per_s` with a burst of `factor` times that for `length_s` seconds
    every `every_s` seconds."""
    rng = np.random.default_rng([seed, 0xA771])
    grid = np.linspace(0.0, duration_s, 8192)
    dens = np.ones_like(grid)
    burst = arrival.get("burst")
    if burst:
        dens[(grid % burst["every_s"]) < burst["length_s"]] = burst["factor"]
    dens /= dens.mean()
    cum = np.concatenate([[0.0], np.cumsum((dens[1:] + dens[:-1]) / 2)])
    cum /= cum[-1]
    n = rng.poisson(arrival["rate_per_s"] * duration_s)
    return np.sort(np.interp(rng.uniform(size=n), cum, grid))


def train_batches(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """(n_batches, global_batch, seq) int32 token ids, uniform over the
    vocabulary; inputs and targets are the same array (next-token loss)."""
    rng = np.random.default_rng([seed, 0x7A11])
    shape = (traffic["n_batches"], traffic["global_batch"], traffic["seq"])
    return rng.integers(0, vocab, shape, dtype=np.int32)


def check_sequence(vocab: int, seed: int, length: int) -> np.ndarray:
    """The one seeded sequence the correctness check runs."""
    rng = np.random.default_rng([seed, 0xC0DE])
    return rng.integers(0, vocab, (length,), dtype=np.int32)
