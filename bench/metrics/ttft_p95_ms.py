"""95th percentile of due time to first token, over every request due in
the window (one with no token by the end of the drain counts at its
wait), in ms."""
from measure import percentile, ttfts


def read(rec):
    t = ttfts(rec["window"], rec["run"]["end"])
    return 1e3 * percentile(t, 95) if t else None
