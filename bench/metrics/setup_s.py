"""Seconds from the start of the harness to the window's opening:
imports, weights, engine, warm-up compiles and the traffic's ramp."""


def read(rec):
    return rec["setup_s"]
