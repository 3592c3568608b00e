"""Open-loop request schedules, drawn from a traffic mix and a seed.

A serving mix (traffic/<name>.json) gives its arrivals and lengths as
parameters:

    rate_per_s     mean requests per second
    arrival_cv     coefficient of variation of the Gamma inter-arrival
                   times (1: Poisson; 2: bursts)
    prompt_len     lognormal {"median", "sigma", "min", "max"}
    prompt_ladder  optional: each prompt length goes up to the next of
                   these lengths (or down to the largest), for a program
                   that compiles one prefill per prompt length
    output_len     lognormal {"median", "sigma", "min", "max"}
    temperature    sampling temperature of most requests
    greedy_share   share of requests decoded greedily (``GREEDY_T``)

The seed draws the order: every seed gets the same set of n gaps, n
prompt lengths and n output lengths, each set the quantiles of its
distribution at (i + 0.5) / n, i < n, and the seed shuffles each set
and draws the prompts' tokens, which requests are greedy, and the
sampling keys. So the seeds of a cell do the same work in other orders.
The gaps are made to have exactly the mix's mean and CV at this n (the
plain quantiles of a Gamma with CV 2 read 1.79 at n = 24, their top
stratum cut off).
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

# the temperature of a greedy request: logits / 1e-4 make the sampler's
# pick the largest logit but where two lie within about 1e-3 of each other
GREEDY_T = 1e-4


@dataclasses.dataclass
class Schedule:
    arrive_s: np.ndarray     # (n,) seconds from the window's start
    prompt_len: np.ndarray   # (n,) int
    output_len: np.ndarray   # (n,) int, tokens to generate
    temperature: np.ndarray  # (n,) float32
    keys: np.ndarray         # (n, 2) uint32, raw PRNG keys
    prompts: list            # n int32 arrays

    def __len__(self):
        return len(self.arrive_s)

    @property
    def greedy(self) -> np.ndarray:
        return self.temperature <= GREEDY_T


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """n stratified lognormal lengths, rounded and clipped to the spec."""
    z = np.array([NormalDist().inv_cdf(u) for u in quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _cv(x):
    return float(np.std(x) / np.mean(x))


def gamma_gaps(rate: float, cv: float, n: int) -> np.ndarray:
    """n stratified Gamma gaps: the quantiles of a Gamma with this CV,
    raised to the power that gives the set exactly this CV (1 at large
    n) and scaled to the mean 1/rate."""
    from scipy.stats import gamma
    shape = 1.0 / cv ** 2
    g = gamma.ppf(quantiles(n), shape) / shape
    if n > 1:
        lo, hi = 0.25, 4.0
        for _ in range(100):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if _cv(g ** mid) < cv else (lo, mid)
        g = g ** (0.5 * (lo + hi))
    return g * (n / rate) / g.sum()


def on_ladder(lengths: np.ndarray, ladder) -> np.ndarray:
    """Each length up to the next rung, or down to the largest."""
    rungs = np.sort(np.asarray(ladder))
    i = np.minimum(np.searchsorted(rungs, lengths), len(rungs) - 1)
    return rungs[i]


def prompt_lengths(mix: dict) -> list:
    """Every prompt length the mix can produce."""
    spec = mix["prompt_len"]
    if mix.get("prompt_ladder"):
        lo, hi = on_ladder(np.array([spec["min"], spec["max"]]),
                           mix["prompt_ladder"])
        return [int(r) for r in sorted(mix["prompt_ladder"]) if lo <= r <= hi]
    return list(range(int(spec["min"]), int(spec["max"]) + 1))


def requests(mix: dict, seconds: float) -> int:
    """Requests that arrive in ``seconds`` at the mix's rate."""
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def greedy_requests(mix: dict, n: int) -> int:
    return int(round(mix.get("greedy_share", 0.0) * n))


def draw(mix: dict, seed: int, seconds: float, vocab: int) -> Schedule:
    """The requests that arrive in ``seconds`` at the mix's rate."""
    n = requests(mix, seconds)
    rng = np.random.default_rng([int(seed) % 2**64, n])
    gaps = rng.permutation(gamma_gaps(mix["rate_per_s"],
                                      mix["arrival_cv"], n))
    arrive = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    plen = lognormal_lengths(mix["prompt_len"], n)
    if mix.get("prompt_ladder"):
        plen = on_ladder(plen, mix["prompt_ladder"])
    plen = rng.permutation(plen)
    olen = rng.permutation(lognormal_lengths(mix["output_len"], n))
    greedy = np.arange(n) < greedy_requests(mix, n)
    temp = np.where(rng.permutation(greedy), GREEDY_T,
                    mix.get("temperature", 1.0)).astype(np.float32)
    keys = rng.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
    prompts = [rng.integers(0, vocab, size=int(p), dtype=np.int32)
               for p in plen]
    return Schedule(arrive, plen, olen, temp, keys, prompts)
