"""Model step: mean device-busy time of one step cycle (from one
``serve.engine.dispatch`` span's start to the next one's) over the
traced cycles whose dispatch carried no prefill token, in ms."""
from progtrace import decode_step_ms


def read(rec):
    return decode_step_ms(rec)
