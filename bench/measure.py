"""Arithmetic the harness and the metric readers share.

Percentiles are nearest-rank over every request due in the window: a
request that never got a first token counts at the time it waited, and
none is dropped for being long, shed or failed.
"""
from __future__ import annotations

import math
from typing import List

import counts


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    if not values:
        raise ValueError("no samples for a percentile")
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


def ttfts(window, t_end: float) -> List[float]:
    """Due time to first token of every request due in the window; one
    with no token by ``t_end`` counts at ``t_end - due``."""
    return [(t.first if t.n else t_end) - t.due for t in window]


def tpots(window) -> List[float]:
    """``(last - first token) / (tokens - 1)`` of every request with two
    tokens or more."""
    return [(t.last - t.first) / (t.n - 1) for t in window if t.n >= 2]


def step_work(rec: dict, steps) -> dict:
    """FLOPs and attention bytes ``steps`` needed, summed."""
    cfg = rec["cfg"]
    tot = {}
    for _, _, segs in steps:
        if segs is None:
            continue
        c = counts.step_counts(cfg, segs, window=cfg["sliding_window"],
                               kv_bytes=rec["kv_bytes"],
                               act_bytes=rec["act_bytes"])
        for k, v in c.items():
            tot[k] = tot.get(k, 0) + v
    return tot


def traced_mfu(rec: dict):
    """The traced steps' model FLOPs over the traced window at the bf16
    peak of every chip the cell runs on, in percent (None without a trace
    or peaks)."""
    tr, pk = rec.get("trace"), rec.get("peaks")
    if not tr or not pk or any(s[2] is None for s in rec["trace_steps"]) \
            or not rec["trace_steps"]:
        return None
    w = step_work(rec, rec["trace_steps"])
    flops = w["matmul_flops"] + w["head_flops"] + w["attn_flops"]
    return 100.0 * flops / (tr["window_s"] * rec["chips"] * pk["bf16_flops"])


def traced_attn_roofline(rec: dict):
    """Least time the chip needs for the traced steps' attention, over
    the device time of the attention kernel's events, in percent."""
    tr, pk = rec.get("trace"), rec.get("peaks")
    if not tr or not pk or not tr["kernel_s"] or not rec["trace_steps"] \
            or any(s[2] is None for s in rec["trace_steps"]):
        return None
    need = 0.0
    for step in rec["trace_steps"]:
        w = step_work(rec, [step])
        need += counts.roofline_seconds(w["attn_flops"], w["attn_bytes"],
                                        pk["bf16_flops"],
                                        pk["hbm_bytes_per_s"])[0]
    return 100.0 * need / tr["kernel_s"]


def traced_idle(rec: dict):
    """Share of the traced window with no operation on the device, in
    percent."""
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
