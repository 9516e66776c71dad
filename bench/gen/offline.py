"""An offline backlog: batch jobs over a code base.

Prompt and output lengths are those of another generator's mix
(``lengths_from``: that mix's whole parameter set, e.g. the session
mix's), taken in the order it would send them, but
every prompt is fresh random tokens, so no two share a prefix.  The
feeder keeps ``backlog`` x lanes requests waiting; ``ramp_s`` seconds of
feeding come before the window opens.
"""
from __future__ import annotations

import numpy as np

from gen import Req, Traffic, load, tokens


def generate(params: dict, seed: int, vocab: int, seconds: float) -> Traffic:
    """The backlog, in feeding order; due times are all 0."""
    src = load(params["lengths_from"], seed, vocab, seconds)
    trng = np.random.default_rng([seed, 3])
    reqs = [Req(due=0.0, prompt=tokens(trng, len(r.prompt), vocab),
                max_new=r.max_new, tag=r.tag) for r in src.reqs]
    ramp = float(params["ramp_s"])
    return Traffic(reqs=reqs, window=(ramp, ramp + seconds), tail=0.0,
                   offline=True, backlog=float(params["backlog"]))
