"""Engine host path: share of the traced window in which no operation ran
on the chip while the innermost program span open was
``serve.engine.*`` (batch build and commits, dispatch, sampling,
emission) or ``serve.step`` itself, in percent."""
from progtrace import idle_share, is_host


def read(rec):
    return idle_share(rec, is_host)
