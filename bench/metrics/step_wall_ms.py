"""Engine host path: mean host-clock time of one ``engine.step`` call in
the window (it ends by pulling the step's tokens to the host), in ms."""


def read(rec):
    steps = rec["steps"]
    if not steps:
        return None
    return 1e3 * sum(b - a for a, b, _ in steps) / len(steps)
