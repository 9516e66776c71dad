"""Independent requests arriving as a Poisson process (chat).

``rate`` requests per second; prompt and output lengths are clipped
lognormals (``prompt``, ``output``); no two prompts share a prefix.
"""
from __future__ import annotations

import numpy as np

from gen import Req, Traffic, count, lognormal, poisson_times, segments, tokens


def generate(params: dict, seed: int, vocab: int, seconds: float) -> Traffic:
    """Poisson arrivals over ramp + window + tail."""
    ramp, tail = float(params["ramp_s"]), float(params["tail_s"])
    rng = np.random.default_rng([seed, 1])
    trng = np.random.default_rng([seed, 2])
    reqs = []
    for start, length, in_window in segments(ramp, seconds, tail):
        n = count(params["rate"], length)
        times = start + poisson_times(n, length, rng)
        plens = lognormal(n, params["prompt"], rng)
        olens = lognormal(n, params["output"], rng)
        for t, pl, ol in zip(times, plens, olens):
            reqs.append(Req(due=float(t), prompt=tokens(trng, pl, vocab),
                            max_new=int(ol), in_window=in_window))
    reqs.sort(key=lambda r: r.due)
    return Traffic(reqs=reqs, window=(ramp, ramp + seconds), tail=tail)
