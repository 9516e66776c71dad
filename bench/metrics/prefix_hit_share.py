"""Scheduler + KV manager: prompt tokens served from the prefix cache
over prompt tokens admitted (served from cache or computed) in the
window, in percent."""


def read(rec):
    c = rec["counters"]
    total = c["prefix_tokens_reused"] + c["tokens_prefilled"]
    return 100.0 * c["prefix_tokens_reused"] / total if total else None
