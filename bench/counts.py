"""Operations and bytes the served model needs, from its shapes.

These count the work the algorithm requires, not what an implementation
happens to do, so every implementation of a step is judged on the same
work:

* matmuls: 2 FLOPs per multiply-add for every non-embedding weight of
  every layer, once per processed (real, not padding) token;
* LM head: ``2 * d_model * vocab`` only for rows whose logits are read —
  the rows that emit a token and the rows that verify a draft;
* attention: each query token against its causal context clipped to the
  sliding window, ``4 * n_heads * head_dim`` FLOPs per (query, key) pair
  (QK^T and PV), per layer;
* attention bytes: each lane's attended K/V span read once per layer per
  step, plus its queries and outputs.

A step is described by its *segments*: ``(first_position, n_tokens,
n_read_rows)`` for every scheduled request.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

Segment = Tuple[int, int, int]          # (first position, tokens, rows read)


def head_dim(cfg: dict) -> int:
    """Per-head width (``head_dim``, else d_model / n_heads)."""
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix multiplications (no norms, biases or
    embeddings)."""
    d, H, Hkv, D = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        head_dim(cfg)
    attn = d * H * D * 2 + d * Hkv * D * 2            # q, o and k, v
    mats = 3 if cfg["mlp_type"] in ("swiglu", "geglu") else 2
    return attn + mats * d * cfg["d_ff"]


def context_sum(first: int, n: int, window: int) -> int:
    """Sum over positions ``first .. first+n-1`` of the keys each query
    attends to: ``min(p + 1, window)`` (``window`` 0 = unbounded)."""
    lo, hi = first + 1, first + n                  # p + 1 over the segment
    if not window or hi <= window:
        return (lo + hi) * n // 2
    if lo >= window:
        return window * n
    below = (lo + window) * (window - lo + 1) // 2   # lo .. window
    return below + window * (hi - window)


def kv_span(first: int, n: int, window: int) -> int:
    """Distinct key positions a segment's queries read together."""
    end = first + n                                 # one past the last key
    start = max(0, first - window + 1) if window else 0
    return end - start


def step_counts(cfg: dict, segments: Iterable[Segment], *, window: int,
                kv_bytes: int, act_bytes: int) -> Dict[str, float]:
    """FLOPs and bytes one serving step needs, summed over its segments
    and layers.  ``kv_bytes``/``act_bytes`` are the itemsizes of the KV
    pool and of the attention's queries and outputs."""
    L, H, Hkv = cfg["n_layers"], cfg["n_heads"], cfg["n_kv_heads"]
    D = head_dim(cfg)
    tokens = rows = ctx = span = 0
    for first, n, read in segments:
        tokens += n
        rows += read
        ctx += context_sum(first, n, window)
        span += kv_span(first, n, window)
    attn_flops = 4 * H * D * ctx * L
    attn_bytes = (span * Hkv * D * 2 * kv_bytes
                  + tokens * H * D * 2 * act_bytes) * L
    return {
        "tokens": tokens,
        "rows_read": rows,
        "matmul_flops": 2 * layer_matmul_params(cfg) * L * tokens,
        "head_flops": 2 * cfg["d_model"] * cfg["vocab_size"] * rows,
        "attn_flops": attn_flops,
        "attn_bytes": attn_bytes,
    }


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> Tuple[float, str]:
    """Least time the chip could take for the work, and which bound sets
    it (``"compute"`` or ``"memory"``)."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
