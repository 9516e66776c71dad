"""Model step: FLOPs the traced steps needed (matmuls of every processed
token, the LM head of the rows read, attention over each token's
window-clipped context; ``bench/counts.py``) over the traced window at
the chip's bf16 peak, in percent."""
from measure import traced_mfu


def read(rec):
    return traced_mfu(rec)
