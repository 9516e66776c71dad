"""Scheduler + KV manager: 95th percentile of the scheduler's queue wait,
from the engine's ``t_submit`` stamp to its ``t_admit`` stamp (the
request's first move into a lane), over finished requests due in the
window, in ms."""
from measure import percentile


def read(rec):
    waits = [r.t_admit - r.t_submit for r in
             (t.ticket.result for t in rec["window"] if t.done)
             if r is not None and getattr(r, "t_admit", 0.0) and r.t_submit]
    return 1e3 * percentile(waits, 95) if waits else None
