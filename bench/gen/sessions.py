"""Multi-turn sessions that share growing prefixes (code completion).

Sessions start as a Poisson process at ``session_rate`` per second.  Each
holds ``turns`` = [lo, hi] requests, scheduled ``turn_gap_s`` (mean of an
exponential) apart whether or not the previous one has finished.  The
first prompt is ``first_prompt`` (clipped lognormal) random tokens; each
later prompt is the previous one plus ``growth`` = [lo, hi] new tokens,
never longer than ``prompt_cap``.  Outputs follow ``output``.

A session belongs to the segment (ramp, window, tail) its first request
falls in; its later requests are due wherever their times fall, and only
those due inside the window are measured.

The schedule (session starts, turns, lengths, gaps) is drawn from a fixed
stream, the same for every seed; the seed draws the tokens (and, in the
harness, the weights).  Permuting long openers from seed to seed moved
the tails by more than any bound could hold: TPOT p50 read 94-128 ms over
six seeds, and each seed within 1-15% of itself on a second run.
"""
from __future__ import annotations

import numpy as np

from gen import (Req, Traffic, count, exp_gaps, lognormal, poisson_times,
                 segments, tokens, uniform_ints)


def session_lengths(params: dict, n: int, rng: np.random.Generator):
    """Per-session prompt lengths, output lengths and turn gaps, as lists
    of lists, stratified over the ``n`` sessions and their requests."""
    turns = uniform_ints(n, *params["turns"], rng)
    first = lognormal(n, params["first_prompt"], rng)
    n_req = max(int(turns.sum()), 1)
    growth = uniform_ints(n_req, *params["growth"], rng)
    outs = lognormal(n_req, params["output"], rng)
    gaps = exp_gaps(n_req, params["turn_gap_s"], rng)
    plens, olens, tgaps, k = [], [], [], 0
    for s in range(n):
        p, o, g = [], [], []
        for turn in range(int(turns[s])):
            p.append(int(first[s]) if turn == 0 else
                     min(p[-1] + int(growth[k]), params["prompt_cap"]))
            o.append(int(outs[k]))
            g.append(float(gaps[k]))
            k += 1
        plens.append(p)
        olens.append(o)
        tgaps.append(g)
    return plens, olens, tgaps


def generate(params: dict, seed: int, vocab: int, seconds: float) -> Traffic:
    """Sessions over ramp + window + tail; see the module docstring."""
    ramp, tail = float(params["ramp_s"]), float(params["tail_s"])
    rng = np.random.default_rng([0, 1])
    trng = np.random.default_rng([seed, 2])
    reqs = []
    sid = 0
    for start, length, _ in segments(ramp, seconds, tail):
        n = count(params["session_rate"], length)
        starts = start + poisson_times(n, length, rng)
        plens, olens, tgaps = session_lengths(params, n, rng)
        for s in range(n):
            t = float(starts[s])
            prompt = tokens(trng, plens[s][0], vocab)
            for turn, (pl, ol) in enumerate(zip(plens[s], olens[s])):
                if turn:
                    t += tgaps[s][turn]
                    grow = pl - len(prompt)
                    prompt = np.concatenate(
                        [prompt, tokens(trng, grow, vocab)])
                reqs.append(Req(due=t, prompt=prompt, max_new=ol,
                                tag=f"session {sid} turn {turn}"))
            sid += 1
    close = ramp + seconds
    for r in reqs:
        r.in_window = ramp <= r.due < close
    reqs.sort(key=lambda r: r.due)
    return Traffic(reqs=reqs, window=(ramp, close), tail=tail)
