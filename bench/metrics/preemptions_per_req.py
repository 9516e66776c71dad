"""KV manager: preemptions in the window per request completed in it."""


def read(rec):
    done = len(rec["window"])
    return rec["counters"]["preemptions"] / done if done else None
