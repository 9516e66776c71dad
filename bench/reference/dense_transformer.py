"""Plain reference of a dense decoder-only transformer.

Pre-norm blocks (LayerNorm with bias or RMSNorm), grouped-query attention
with rotary positions (half-split rotation) and a causal sliding window,
a non-gated tanh-GELU or a SwiGLU MLP, optional biases, an untied or tied
LM head.  It follows the published descriptions of StarCoder2
(arXiv:2402.19173) and Mistral 7B (arXiv:2310.06825) with one departure,
which the configuration file states: the norm epsilon is the value the
served program uses (``norm_eps``).

Everything is float32 with ``Precision.HIGHEST`` matmuls: no kernel, no
paging, no batching.  It runs layer by layer (one jitted layer over the
stacked weights, indexed in place, cast to float32 inside) and attends
in blocks of query rows, so that a whole sequence fits beside the
weights.

``mode="int8"`` and ``mode="fp8"`` are the controls: every weight matrix
and every activation that enters one is rounded first, to int8 (per
output channel and per token, symmetric) or to float8 e4m3 (scaled per
output channel and per token so the largest value maps to 448): the
precision steps below the bf16 the program computes in.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # query rows per attention block
PAD = 2048              # sequences are padded to a multiple of this


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
def _shapes(cfg: dict) -> Dict:
    """Leaf shapes of the weights, in the layout the program serves."""
    L, d, H, Hkv = cfg["n_layers"], cfg["d_model"], cfg["n_heads"], \
        cfg["n_kv_heads"]
    D = cfg.get("head_dim") or d // H
    ff, V = cfg["d_ff"], cfg["vocab_size"]
    bias = cfg["use_bias"]

    def norm(prefix=()):
        p = {"scale": prefix + (d,)}
        if cfg["norm_type"] == "layernorm" and bias:
            p["bias"] = prefix + (d,)
        return p

    attn = {"wq": (L, d, H, D), "wk": (L, d, Hkv, D), "wv": (L, d, Hkv, D),
            "wo": (L, H, D, d)}
    if bias:
        attn.update(bq=(L, H, D), bk=(L, Hkv, D), bv=(L, Hkv, D), bo=(L, d))
    if cfg["mlp_type"] == "swiglu":
        mlp = {"w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d)}
    else:
        mlp = {"w_up": (L, d, ff), "w_down": (L, ff, d)}
    if bias:
        mlp.update(b_up=(L, ff), b_down=(L, d))
    p = {"embed": {"embedding": (V, d)}, "final_norm": norm(),
         "blocks": {"attn_norm": norm((L,)), "attn": attn,
                    "mlp_norm": norm((L,)), "mlp": mlp}}
    if not cfg["tie_embeddings"]:
        p["head"] = {"w": (d, V)}
    return p


def _std(path: str, shape, cfg: dict) -> float:
    """Standard deviation of one leaf: fan-in scaling for matrices, the
    output projections shrunk by sqrt(2 * layers), small biases, norm
    scales near 1 (their mean is added in :func:`make_params`)."""
    name = path.split("/")[-1]
    L = cfg["n_layers"]
    if name == "embedding":
        return 0.02
    if name in ("wq", "wk", "wv", "w_gate", "w_up"):
        return shape[1] ** -0.5
    if name == "wo":
        return (shape[1] * shape[2] * 2 * L) ** -0.5
    if name == "w_down":
        return (shape[1] * 2 * L) ** -0.5
    if name == "w":
        return shape[0] ** -0.5
    return 0.02                         # biases, norm scales and biases


def make_params(cfg: dict, key, dtype) -> Dict:
    """Random weights from ``key``, every leaf in ``dtype``: one jitted
    call on the default device, each leaf drawn in its own type."""
    shapes = _shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    paths = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in flat]

    def build(key):
        leaves = []
        for i, (path, (_, shape)) in enumerate(zip(paths, flat)):
            x = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            x = x * jnp.asarray(_std(path, shape, cfg), dtype)
            if path.endswith("scale"):
                x = x + jnp.asarray(1.0, dtype)
            leaves.append(x)
        return jax.tree_util.tree_unflatten(tree, leaves)

    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _fake_int8(x, axis):
    """Symmetric int8 rounding along ``axis`` (the reduced axis keeps one
    scale per remaining index), returned in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _fake_fp8(x, axis):
    """Rounding to float8 e4m3 with one scale per index left after
    ``axis`` (the largest magnitude maps to 448), in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    """``x (S, K) @ w (K, N)`` in float32, or in a control's precision
    (one scale per token of ``x``, per output channel of ``w``)."""
    if mode in ("int8", "fp8"):
        q = _fake_int8 if mode == "int8" else _fake_fp8
        x, w = q(x, axis=1), q(w, axis=0)
    return jnp.dot(x, w, precision=HI)


def _norm(x, p, cfg):
    eps = cfg["norm_eps"]
    if cfg["norm_type"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"]
    return y + p["bias"] if "bias" in p else y


def _rope(x, pos, theta):
    """Half-split rotary embedding of ``x (S, H, D)`` at ``pos (S,)``."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window):
    """Causal (sliding-window) GQA attention of one sequence, in blocks
    of query rows.  q (S, H, D), k/v (S, Hkv, D)."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qb = qb.reshape(Q_BLOCK, Hkv, G, D)
        s = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / D ** 0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
        return o.reshape(Q_BLOCK, H, D)

    out = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return out.reshape(S, H, D)


def _cfg_key(cfg: dict):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.partial(jax.jit, static_argnames=("ck", "mode"))
def _layer(x, blocks, i, ck, mode):
    cfg = dict(ck)
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), blocks)
    S, d = x.shape
    H, Hkv = cfg["n_heads"], cfg["n_kv_heads"]
    D = cfg.get("head_dim") or d // H
    pos = jnp.arange(S)
    a = p["attn"]
    h = _norm(x, p["attn_norm"], cfg)

    def proj(w, b, n):
        y = _mm(h, w.reshape(d, n * D), mode).reshape(S, n, D)
        return y + b if b is not None else y

    q = proj(a["wq"], a.get("bq"), H)
    k = proj(a["wk"], a.get("bk"), Hkv)
    v = proj(a["wv"], a.get("bv"), Hkv)
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    o = _attention(q, k, v, cfg["sliding_window"])
    o = _mm(o.reshape(S, H * D), a["wo"].reshape(H * D, d), mode)
    x = x + (o + a["bo"] if "bo" in a else o)
    h = _norm(x, p["mlp_norm"], cfg)
    m = p["mlp"]
    if cfg["mlp_type"] == "swiglu":
        u = jax.nn.silu(_mm(h, m["w_gate"], mode)) * _mm(h, m["w_up"], mode)
    else:
        u = _mm(h, m["w_up"], mode)
        u = jax.nn.gelu(u + m["b_up"] if "b_up" in m else u,
                        approximate=True)
    y = _mm(u, m["w_down"], mode)
    return x + (y + m["b_down"] if "b_down" in m else y)


@functools.partial(jax.jit, static_argnames=("ck", "mode"))
def _head(x, rows, final_norm, w, ck, mode):
    cfg = dict(ck)
    h = _norm(x[rows], jax.tree.map(lambda a: a.astype(jnp.float32),
                                    final_norm), cfg)
    return _mm(h, w.astype(jnp.float32), mode)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits(params: Dict, cfg: dict, tokens: np.ndarray, rows: np.ndarray,
           mode: str = "float32") -> np.ndarray:
    """Reference logits ``(len(rows), vocab)`` at ``rows`` of the one
    sequence ``tokens`` (row ``t`` predicts token ``t + 1``)."""
    n = len(tokens)
    padded = -(-n // PAD) * PAD
    seq = np.zeros((padded,), np.int32)
    seq[:n] = tokens
    ck = _cfg_key(cfg)
    x = _embed(params["embed"]["embedding"], seq)
    for i in range(cfg["n_layers"]):
        x = _layer(x, params["blocks"], np.int32(i), ck=ck, mode=mode)
    w = params["embed"]["embedding"].T if cfg["tie_embeddings"] \
        else params["head"]["w"]
    # rows padded to a multiple of 128, so few shapes compile
    r = np.zeros((-(-len(rows) // 128) * 128,), np.int32)
    r[:len(rows)] = rows
    out = _head(x, r, params["final_norm"], w, ck=ck, mode=mode)
    return np.asarray(out)[:len(rows)]
