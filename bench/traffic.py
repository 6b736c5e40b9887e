"""The one traffic generator. A mix is a data file (``bench/traffic/<mix>
.json``) of parameters; this module turns it and a seed into requests.

Every seed gets the same set of sizes and the same set of gaps between
arrivals, in another order: the sizes are quantiles of the stated
distribution (so the set is fixed by the mix and the request count), and
the seed shuffles them and draws the token ids. Runs with different seeds
then do the same amount of work.

Length distributions (``prompt`` and ``output``):

- ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b,
  "round_to": r}``: quantiles of a lognormal, clipped to [a, b], rounded
  up to a multiple of r (then clipped to b again);
- ``{"dist": "uniform", "min": a, "max": b, "round_to": r}``: the values
  a, a + r, ..., b in equal shares.

Modes: ``open_loop`` (Poisson arrivals at ``rate_per_s`` over the window,
gaps the quantiles of an exponential) and ``saturated`` (a queue kept
``queue_depth`` deep from a list of ``set_size`` requests, repeated in a
new order each time it runs out).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Planned:
    uid: int
    due_s: float            # scheduled arrival after the window opens
    prompt: np.ndarray      # (L,) int32
    max_new: int


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    r = int(spec.get("round_to", 1))
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        x = np.clip(x, lo, hi)
        x = np.minimum(np.ceil(x / r) * r, hi)
    elif spec["dist"] == "uniform":
        values = np.arange(lo, hi + 1, r)
        x = values[np.minimum((u * len(values)).astype(int),
                              len(values) - 1)]
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return x.astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def _requests(mix: dict, n: int, seed: int, vocab: int, uid0: int = 0,
              stream: int = 0):
    rng = _rng(seed, stream)
    prompts = rng.permutation(quantile_lengths(mix["prompt"], n))
    outs = rng.permutation(quantile_lengths(mix["output"], n))
    return [(uid0 + i, rng.integers(0, vocab, size=int(p)).astype(np.int32),
             int(o)) for i, (p, o) in enumerate(zip(prompts, outs))]


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def open_loop(mix: dict, seed: int, seconds: float, vocab: int
              ) -> List[Planned]:
    """Arrivals over (0, seconds]: ``rate_per_s * seconds`` requests, the
    gaps exponential quantiles scaled so the last arrives at the close."""
    n = n_requests(mix, seconds)
    rng = _rng(seed, 1)
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    return [Planned(uid, float(t), p, o) for (uid, p, o), t in
            zip(_requests(mix, n, seed, vocab), due)]


def saturated(mix: dict, seed: int, vocab: int) -> Iterator[Planned]:
    """An endless list, ``set_size`` requests at a time; all due at 0."""
    n, cycle = int(mix["set_size"]), 0
    while True:
        for uid, p, o in _requests(mix, n, seed, vocab, uid0=cycle * n,
                                   stream=2 + cycle):
            yield Planned(uid, 0.0, p, o)
        cycle += 1


def exercised_lengths(mix: dict, seconds: float) -> List[int]:
    """The prompt lengths a run of this mix sends: what set-up warms."""
    if mix["mode"] == "open_loop":
        n = n_requests(mix, seconds)
    else:
        n = int(mix["set_size"])
    return sorted(set(quantile_lengths(mix["prompt"], n).tolist()))
