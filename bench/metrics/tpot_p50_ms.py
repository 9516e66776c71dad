"""Median of (last - first token) / (tokens - 1) over the requests due in
the window that emitted two tokens or more, in ms."""
from measure import percentile, tpots


def read(rec):
    t = tpots(rec["window"])
    return 1e3 * percentile(t, 50) if t else None
