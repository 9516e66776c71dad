"""Operations and bytes the served model needs, from its shapes.

These count the work the algorithm requires, not what an implementation
happens to do, so every implementation of a step is judged on the same
work:

* matmuls: 2 FLOPs per multiply-add for every non-embedding weight that
  a token uses in each layer, once per processed (real, not padding)
  token: attention, then a dense MLP, or in a mixture-of-experts layer
  the router, the ``experts_per_token`` routed experts and the shared
  experts (:func:`layer_matmul_params`);
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

from typing import Dict, Iterable, List, Tuple

Segment = Tuple[int, int, int]          # (first position, tokens, rows read)


def head_dim(cfg: dict) -> int:
    """Per-head width (``head_dim``, else d_model / n_heads)."""
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def layer_matmul_params(cfg: dict) -> List[int]:
    """For each layer of a configuration file, the weights of its matrix
    multiplications that one token uses (no norms, biases or embeddings):

    * attention: q and o over every query head, k and v over the kv heads;
    * a dense MLP of ``d_ff`` (gated: three matrices, else two); in a
      model with ``moe``, its leading ``first_dense_layers`` are dense of
      ``dense_d_ff`` (``d_expert`` where that is 0);
    * every other layer of a model with ``moe``: the router (``d_model x
      n_experts``), ``experts_per_token`` routed experts of width
      ``d_expert`` (always gated, three matrices) and ``n_shared_experts``
      shared experts, one MLP of ``n_shared_experts x d_expert``.

    These are the whole model's weights, however many chips share each
    layer (a cell's peak is that of all its chips).  Expert parallelism,
    a chip holding only some of the experts, has no count of its own:
    the program has no such layer yet.  Defaults are the program's
    (``mlp_type`` swiglu; no shared experts, no leading dense layers).
    """
    d, H, Hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    D = head_dim(cfg)
    attn = d * H * D * 2 + d * Hkv * D * 2            # q, o and k, v
    gated = cfg.get("mlp_type", "swiglu") in ("swiglu", "geglu")

    def mlp(width: int) -> int:
        return (3 if gated else 2) * d * width

    moe = cfg.get("moe")
    if moe is None:
        return [attn + mlp(cfg["d_ff"])] * cfg["n_layers"]
    n_dense = moe.get("first_dense_layers", 0)
    d_expert = moe["d_expert"]
    dense = attn + mlp(moe.get("dense_d_ff", 0) or d_expert)
    sparse = (attn + d * moe["n_experts"]
              + moe["experts_per_token"] * 3 * d * d_expert
              + mlp(moe.get("n_shared_experts", 0) * d_expert))
    return [dense] * n_dense + [sparse] * (cfg["n_layers"] - n_dense)


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
        "matmul_flops": 2 * sum(layer_matmul_params(cfg)) * tokens,
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
