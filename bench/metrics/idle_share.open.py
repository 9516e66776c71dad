"""Device: share of the traced window in which no operation ran on the
chip, in percent."""
from measure import traced_idle


def read(rec):
    return traced_idle(rec)
