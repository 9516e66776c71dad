"""Front end: 95th percentile of the step thread's mailbox wait, from the
moment the generator handed a request to ``AsyncEngine.submit`` to the
engine's ``t_submit`` stamp, over finished requests due in the window,
in ms.  (How late the generator itself ran is logged apart.)"""
from measure import percentile


def read(rec):
    lags = [t.ticket.result.t_submit - t.sent for t in rec["window"]
            if t.done and t.ticket.result.t_submit]
    return 1e3 * percentile(lags, 95) if lags else None
