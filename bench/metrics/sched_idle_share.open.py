"""Scheduler + KV manager: share of the traced window in which no
operation ran on the chip while the innermost program span open was
``serve.sched*`` (scheduling, admission, KV slot reservation) or
``serve.kv*`` (swap-ins and copy-on-write copies), in percent."""
from progtrace import idle_share, is_sched


def read(rec):
    return idle_share(rec, is_sched)
