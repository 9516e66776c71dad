#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.5,1,2,4

One process, one engine: each rate offers the cell's traffic mix at that
rate (the mix's ``session_rate`` or ``rate``) for a ramp, ``--seconds`` of
window and a drain, and prints one JSON line per rate: the window's TTFT
and TPOT quantiles, the share of its requests that finished in the
drain, and the median TTFT of the window's last fifth over its first
fifth (above 2 the queue grew through the window).  A cell's rate is set
once, at about 0.8 x the highest rate that holds, and written into its
traffic file.  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gen  # noqa: E402
import run  # noqa: E402
from measure import percentile, tpots, ttfts  # noqa: E402


def clear(eng) -> None:
    """Cancel whatever the engine still holds (the last rate's tail)."""
    for r in list(eng.scheduler.running) + list(eng.scheduler.waiting):
        eng.cancel(r.request_id)
    eng.take_finished()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--ramp", type=float, default=None,
                    help="seconds of load before each window (default: "
                         "the mix's ramp_s)")
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    b = run.Bench(cell, args.seed)
    key = "session_rate" if "session_rate" in cell["traffic"] else "rate"
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = dict(cell["traffic"], **{key: rate})
        if args.ramp is not None:
            mix["ramp_s"] = args.ramp
        traffic = gen.load(mix, args.seed, b.cfg["vocab_size"], args.seconds)
        rec = run.drive(b, traffic, args.seconds, False)
        w = sorted(rec["window"], key=lambda t: t.due)
        tt = ttfts(w, rec["run"]["end"])
        tp = tpots(w)
        fifth = max(len(w) // 5, 1)
        early = percentile(ttfts(w[:fifth], rec["run"]["end"]), 50)
        late = percentile(ttfts(w[-fifth:], rec["run"]["end"]), 50)
        c = rec["counters"]
        print(json.dumps({
            "rate": rate, "requests": len(w),
            "finished_share": sum(t.done for t in w) / max(len(w), 1),
            "ttft_p50_ms": 1e3 * percentile(tt, 50),
            "ttft_p95_ms": 1e3 * percentile(tt, 95),
            "tpot_p50_ms": 1e3 * percentile(tp, 50) if tp else None,
            "tpot_p95_ms": 1e3 * percentile(tp, 95) if tp else None,
            "late_over_early_ttft": late / early,
            "tokens_per_s": c["tokens_decoded"] / c["t"],
            "steps": len(rec["steps"]),
            "prefix_tokens_reused": c["prefix_tokens_reused"],
            "tokens_prefilled": c["tokens_prefilled"],
            "preemptions": c["preemptions"]}), flush=True)
        clear(b.eng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
