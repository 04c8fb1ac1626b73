"""The benchmark's traffic generator: everything a run offers, from the seed.

Every seed gets the same *set* of sizes and delays in another order, so
seeds change which bytes and coefficients flow, not how much work there
is: delays are stratified draws (the distribution's quantiles at
(i + ½)/n) shuffled by the seed, and the number of duplicates is fixed.

* :func:`open_loop` — decode-server traffic: generation starts form a
  Poisson process at ``rate_gen_per_s``; each generation's ``k + extra``
  coded packets follow with unit-mean lognormal gaps (σ = ``gap_sigma``)
  scaled to ``gap_mean_ms``; ``duplicate_rate`` of the packets re-send
  the previous packet of their generation.
* :func:`u32` — the 32-bit seeds a coded-round cell draws per attempt
  (its PRNG key and its erasure pattern).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, *stream); any size of seed."""
    return np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed), spawn_key=tuple(int(s) for s in stream)))


def u32(seed: int, *stream: int) -> int:
    return int(rng_for(seed, *stream).integers(0, 1 << 32, dtype=np.uint64))


def stratified(kind: str, n: int, rng: np.random.Generator, *,
               mean: float, sigma: float = 1.0) -> np.ndarray:
    """`n` delays with the given mean: quantiles at (i + ½)/n, shuffled."""
    q = (np.arange(n) + 0.5) / n
    if kind == "exponential":
        x = -np.log1p(-q)
    elif kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(p)) for p in q])
        x = np.exp(-0.5 * sigma * sigma + sigma * z)
    else:
        raise ValueError(f"unknown delay distribution {kind!r}")
    return rng.permutation(mean * x / x.mean())


@dataclass
class OpenLoop:
    """Packets in due order, and the generations they belong to."""

    k: int
    n: int                      # packets per generation
    starts: np.ndarray          # (G,) seconds from the window's start
    due: np.ndarray             # (G, n) seconds
    seeds: np.ndarray           # (G, n) uint32 coefficient seeds
    seeded: np.ndarray          # (G,) bool: ships the seed, not the row
    order: np.ndarray           # (G·n,) flat packet indices in due order

    @property
    def gens(self) -> int:
        return int(self.starts.shape[0])


def open_loop(traffic: dict, seed: int, seconds: float,
              rate: float | None = None, stream: int = 0) -> OpenLoop:
    """Decode-server traffic for a window of `seconds`."""
    rate = float(rate if rate is not None else traffic["rate_gen_per_s"])
    k, extra = int(traffic["k"]), int(traffic["extra_packets"])
    n = k + extra
    G = max(1, int(math.floor(rate * seconds)))
    rng = rng_for(seed, 17, stream)
    # G gaps of mean 1/rate sum to G/rate <= seconds on every seed
    starts = np.cumsum(stratified("exponential", G, rng, mean=1.0 / rate))
    gaps = stratified("lognormal", G * n, rng,
                      mean=traffic["gap_mean_ms"] / 1e3,
                      sigma=traffic["gap_sigma"]).reshape(G, n)
    due = starts[:, None] + np.cumsum(gaps, axis=1)
    seeds = rng.integers(0, 1 << 32, size=(G, n), dtype=np.uint64
                         ).astype(np.uint32)
    # duplicates spread evenly over generations (⌊·⌋ or ⌈·⌉ each), so
    # every generation keeps at least k distinct packets and decodes
    n_dup = int(round(traffic["duplicate_rate"] * G * n))
    per = np.full(G, n_dup // G)
    per[rng.choice(G, size=n_dup % G, replace=False)] += 1
    for g in range(G):
        for j in sorted(rng.choice(np.arange(1, n), size=per[g],
                                   replace=False).tolist()):
            seeds[g, j] = seeds[g, j - 1]
    seeded = (np.arange(G) % 2 == 0) if traffic["wire"] == "alternate" \
        else np.full(G, traffic["wire"] == "seeded")
    order = np.argsort(due.reshape(-1), kind="stable")
    return OpenLoop(k, n, starts, due, seeds, seeded, order)
