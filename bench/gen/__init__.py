"""Traffic generators, one module per kind.

A traffic file (``bench/traffic/<mix>.json``) names its generator in
``"generator"``; :func:`load` imports ``bench/gen/<generator>.py`` and
calls its ``generate(params, seed, vocab, seconds)``.  Every generator
returns a :class:`Traffic`: the requests in the order they are due, each
with its due time in seconds from the start of the load, and the bounds
of the measured window on that same clock.

Lengths and gaps are drawn *stratified*: a segment of ``n`` items takes
the ``n`` quantiles ``(i + 0.5) / n`` of its distribution, and the seed
only permutes them.  So every seed offers the same amount of work in each
segment (ramp, window, tail), in another order, and the spread between
runs with different seeds is the system's, not the generator's.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    """One request of a traffic mix."""

    due: float                  # seconds after the load starts
    prompt: np.ndarray          # int32 token ids
    max_new: int
    in_window: bool = False     # due inside the measured window
    tag: str = ""               # generator's note, e.g. "session 3 turn 2"


@dataclasses.dataclass
class Traffic:
    """A whole run's requests and the measured window on their clock.

    ``offline`` traffic has no schedule: ``reqs`` is the backlog in the
    order it is fed, and ``backlog`` is how many requests the feeder keeps
    waiting per engine lane."""

    reqs: List[Req]
    window: tuple               # (open, close) seconds after load start
    tail: float                 # seconds of load offered after the close
    offline: bool = False
    backlog: float = 0.0


def quantiles(n: int) -> np.ndarray:
    """The ``n`` stratified probabilities ``(i + 0.5) / n``."""
    return (np.arange(n) + 0.5) / max(n, 1)


def lognormal(n: int, spec: dict, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal, ints, permuted.

    ``spec``: ``median``, ``sigma`` (of the log), ``min``, ``max``."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf(u) for u in quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(x)


def uniform_ints(n: int, lo: int, hi: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified draws from the integers ``lo..hi``, permuted."""
    x = np.floor(lo + quantiles(n) * (hi - lo + 1)).astype(np.int64)
    return rng.permutation(np.clip(x, lo, hi))


def poisson_times(n: int, length: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` arrival times in ``[0, length)`` with stratified exponential
    gaps (permuted), scaled so the ``n`` arrivals fill exactly
    ``length``: a Poisson process conditioned on its count."""
    if n <= 0:
        return np.zeros((0,))
    gaps = rng.permutation(-np.log1p(-quantiles(n)))
    # each arrival sits mid-way through its own gap, and the n gaps
    # together span the segment
    return (np.cumsum(gaps) - gaps / 2) * (length / gaps.sum())


def exp_gaps(n: int, mean: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified exponential gaps of mean ``mean``, permuted."""
    return rng.permutation(-mean * np.log1p(-quantiles(n)))


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    """``n`` uniform random token ids."""
    return rng.integers(0, vocab, int(n), dtype=np.int32)


def segments(ramp: float, window: float, tail: float):
    """(start, length, in_window) of the three load segments."""
    return ((0.0, ramp, False), (ramp, window, True),
            (ramp + window, tail, False))


def count(rate: float, length: float) -> int:
    """Arrivals a segment holds at ``rate`` (rounded, at least 0)."""
    return max(int(math.floor(rate * length + 0.5)), 0)


def load(params: dict, seed: int, vocab: int, seconds: float,
         gen_dir: Optional[str] = None) -> Traffic:
    """Build a traffic mix from its parameters with the generator it
    names."""
    name = params["generator"]
    if not name.replace("_", "").isalnum():
        raise ValueError(f"bad generator name {name!r}")
    mod = importlib.import_module(f"gen.{name}")
    return mod.generate(params, seed, vocab, seconds)
