"""Kernel: the least time the chip needs for the traced steps' attention
(each query against its window-clipped context; each lane's attended K/V
read once per layer, plus queries and outputs; ``bench/counts.py``),
over the device time of the paged-attention kernel's events, in
percent."""
from measure import traced_attn_roofline


def read(rec):
    return traced_attn_roofline(rec)
