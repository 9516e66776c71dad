#!/usr/bin/env python3
"""Read the check's two sides for a cell: the program and the control.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed, one set-up and one window at the cell's own load, as a
benchmark run makes them; then the sampled requests run through the
float32 reference, and through the controls: the same reference with
every weight matrix and every activation entering one rounded to int8,
or to float8 e4m3, the precision steps below the program's bf16.  At each served position
the control's first choice is read against the float32 reference's best
logit.  One JSON line per seed: for the program's served tokens and for
each control, the widest gap, the mean gap (what a benchmark run
compares) and the share of positions off the reference's first choice.  The
limit in the configuration lies between the two.  The benchmark's runs
never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import gen  # noqa: E402
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        b = run.Bench(cell, seed)
        traffic = gen.load(cell["traffic"], seed, b.cfg["vocab_size"],
                           args.seconds)
        rec = run.drive(b, traffic, args.seconds, False)
        chk = run.check(b, rec, controls=("int8", "fp8"))
        print(json.dumps({"seed": seed, "program": chk["served"],
                          "int8": chk["int8"], "fp8": chk["fp8"]}),
              flush=True)
        del b, rec, chk
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
