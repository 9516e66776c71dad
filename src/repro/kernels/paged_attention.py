"""Pallas TPU paged-attention kernels — rectangular (per-lane chunk),
ragged (flat token stream) and segment-tiled (flat stream in q tiles).

A chunk of C query tokens per lane (C = 1 is plain decode) attends over its
KV sequence scattered across fixed-size physical blocks of a shared pool;
the ragged variant (:func:`paged_attention_ragged`) drops the per-lane
rectangle entirely and serves one flat 1-D stream of mixed prefill/decode
tokens with per-token lane metadata, and the tiled variant
(:func:`paged_attention_ragged_tiled`, the serving default) reads each
lane's blocks once per q tile of that stream.  No materialized
(B, S, ...) gather ever exists in HBM: the block tables are
*scalar-prefetch* operands (``pltpu.PrefetchScalarGridSpec``) that name
the physical block each DMA fetches straight from the pool.

Pool layout (head-major): K/V pools are ``(num_blocks, Hkv, bs, D)``, so
one (block, kv-head) tile is a contiguous ``(bs, D)`` slab whose last two
dims satisfy the TPU tiling rule (bs a multiple of 8, D of 128) for any
Hkv, and one block of every head is a contiguous ``(Hkv, bs, D)`` slab.
int8 pools carry ``(num_blocks, Hkv, bs)`` float32 scale pools; a
scale fetch covers every head of the block (``(Hkv, bs)``) and the kernel
picks its head's row.  Per-slot scales are applied to the score row (K)
and the probability row (V) — lane-major broadcasts — instead of
dequantizing the (bs, D) tiles.

Schedule of the rectangular and per-token grids:
  * grid = (lane or token, kv_head, logical_block); the trailing axis runs
    sequentially on a TPU core, carrying the online-softmax state (m, l,
    acc) for one lane/head across that lane's blocks in VMEM scratch; the
    block tables sit in the K/V BlockSpec index maps;
  * blocks at or past the lane's context length are skipped with
    ``pl.when`` (their DMA still targets a legal pool slot — idle table
    entries point at the reserved null block 0);
  * GQA + chunking: all C chunk tokens of all G = H/Hkv query heads of a
    kv head ride in one (C*G, D) tile; row r of the tile is chunk token
    ``r // G``, so its absolute position is ``q_starts[b] + r // G`` and
    the causal mask *inside* the chunk falls out of one iota compare;
  * padded chunk rows (past a lane's real q_len) compute finite garbage
    the caller ignores — their kv reads stay inside the lane's legal
    blocks, so they can never fault.

Schedule of the tiled grid:
  * grid = (q_tile,); the tile's (Hkv, tile*G, D) query slab rides in by
    BlockSpec, and the pools stay in HBM;
  * the tile's live blocks come from ``tile_meta``: from the block of its
    first row's window start (0 without a window) to the block of its
    deepest row's position; an inert capacity tile has none;
  * a ``fori_loop`` walks them with ``KV_BUFFERS`` block copies in
    flight — each copy one (Hkv, bs, D) slab per pool (and the (Hkv, bs)
    scale rows of an int8 pool) — and applies each block to every kv
    head's online-softmax state in turn, in the same order and with the
    same masks as the per-head grids: only the blocks no row can see are
    left out, so the outputs agree to float rounding.

Validated in interpret mode against ``ref.paged_attention_*reference``
(tests/test_kernels_paged_attention.py) and compiled for a described TPU
v5e in tests/test_chip_compile.py; the pure-JAX reference is also the
production CPU path (kernels/ops.py dispatches on backend), and the
serving path on >1-device meshes — a Pallas call is opaque to GSPMD, so
mesh-sharded engines pin ``use_kernel=False`` until these kernels grow a
shard_map wrapper (each shard would run the identical grid over its
kv-head slice of the pool; see ``docs/ARCHITECTURE.md`` §7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import (TILE_HI, TILE_LANE, TILE_LO, TILE_POS0,
                               TILE_WINDOW)

NEG_INF = -1e30
# KV blocks in flight in the tiled kernel's block loop (four timed no
# better than two on a v5e)
KV_BUFFERS = 2


def _split_refs(rest, quantized: bool):
    """(scale refs or None, o_ref, m_scr, l_scr, acc_scr) from a kernel's
    trailing refs."""
    if quantized:
        ks_ref, vs_ref, *rest = rest
        return (ks_ref, vs_ref), *rest
    return None, *rest


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _attend_block(q, k_ref, v_ref, scale_refs, head, mask_fn, m_scr, l_scr,
                  acc_scr):
    """One online-softmax step of the (R, D) query slab ``q`` (already
    scaled) against one (bs, D) KV block; ``mask_fn(shape)`` gives the
    (R, bs) visibility mask.  int8 blocks apply their per-slot scales to
    the score row (K) and the probability row (V): the first ``bs`` lanes
    of row ``head`` of the (Hkv, >= bs) scale refs."""
    k = k_ref[...].astype(jnp.float32)                   # (bs, D)
    v = v_ref[...]                                       # (bs, D)
    bs = k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (R, bs)
    if scale_refs is not None:
        ks_ref, vs_ref = scale_refs
        s = s * ks_ref[pl.ds(head, 1), :][:, :bs]        # (1, bs) K scales
        v = v.astype(jnp.float32)
    s = jnp.where(mask_fn(s.shape), s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    if scale_refs is not None:
        p = p * vs_ref[pl.ds(head, 1), :][:, :bs]        # (1, bs) V scales
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (R, D)
    m_scr[...] = m_new


def _kv_specs(block_index, bs: int, D: int, Hkv: int, quantized: bool):
    """BlockSpecs of the K/V pools (and scale pools) for a grid whose
    ``block_index(*grid_ids, *prefetch)`` names the physical block of
    each (…, kv_head, logical_block) step."""
    kv = pl.BlockSpec((None, None, bs, D),
                      lambda *a: (block_index(*a), a[1], 0, 0))
    specs = [kv, kv]
    if quantized:
        sc = pl.BlockSpec((None, Hkv, bs),
                          lambda *a: (block_index(*a), 0, 0))
        specs += [sc, sc]
    return specs


def _paged_attn_kernel(tables_ref, ctx_ref, start_ref, q_ref, k_ref, v_ref,
                       *rest, block_size: int, window: int, scale: float,
                       group: int, quantized: bool):
    scale_refs, o_ref, m_scr, l_scr, acc_scr = _split_refs(rest, quantized)
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)          # logical block index within lane b

    @pl.when(j == 0)
    def _():
        _init_state(m_scr, l_scr, acc_scr)

    ctx = ctx_ref[b]              # valid tokens in lane b after this chunk
    start = start_ref[b]          # absolute position of chunk row 0

    def mask_fn(shape):
        kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        mask = kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        return mask

    @pl.when(j * block_size < ctx)
    def _():
        q = q_ref[...].astype(jnp.float32) * scale       # (C*G, D)
        _attend_block(q, k_ref, v_ref, scale_refs, h, mask_fn, m_scr, l_scr,
                      acc_scr)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


def _paged_attention_rows(q_rows: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, block_tables: jax.Array,
                          ctx_lens: jax.Array, q_starts: jax.Array, *,
                          group: int, window: int, interpret: bool,
                          k_scale=None, v_scale=None) -> jax.Array:
    """Shared launcher: q_rows (B, Hkv, R, D) with R = C * group rows."""
    B, Hkv, R, D = q_rows.shape
    num_blocks, Hkv_p, bs, _ = k_pool.shape
    assert Hkv_p == Hkv, (Hkv_p, Hkv)
    max_blocks = block_tables.shape[1]
    quantized = k_scale is not None

    kernel = functools.partial(_paged_attn_kernel, block_size=bs,
                               window=window, scale=1.0 / (D ** 0.5),
                               group=group, quantized=quantized)
    q_spec = pl.BlockSpec((None, None, R, D),
                          lambda b, h, j, tables, ctx, starts: (b, h, 0, 0))
    in_specs = [q_spec] + _kv_specs(
        lambda b, h, j, tables, ctx, starts: tables[b, j], bs, D, Hkv,
        quantized)
    operands = [q_rows, k_pool, v_pool]
    if quantized:
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, max_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),   # m
            pltpu.VMEM((R, 1), jnp.float32),   # l
            pltpu.VMEM((R, D), jnp.float32),   # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, D), q_rows.dtype),
        interpret=interpret,
        name="paged_attention_rect",
    )(block_tables.astype(jnp.int32), ctx_lens.astype(jnp.int32),
      q_starts.astype(jnp.int32), *operands)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array, ctx_lens: jax.Array, *,
                    window: int = 0, interpret: bool = False,
                    k_scale: jax.Array = None,
                    v_scale: jax.Array = None) -> jax.Array:
    """Decode (q_len = 1): q (B, Hkv, G, D) at position ``ctx_lens - 1``;
    pools: (num_blocks, Hkv, bs, D); block_tables: (B, max_blocks) int32
    physical ids (null block = 0 for unallocated logical blocks);
    ctx_lens: (B,) int32.  With int8 pools, ``k_scale``/``v_scale``
    ((num_blocks, Hkv, bs) float32) ride the same table-indexed DMAs and
    scale each block's score and probability rows in VMEM.  Returns
    (B, Hkv, G, D)."""
    B, Hkv, G, D = q.shape
    return _paged_attention_rows(q, k_pool, v_pool, block_tables, ctx_lens,
                                 ctx_lens - 1, group=G, window=window,
                                 interpret=interpret, k_scale=k_scale,
                                 v_scale=v_scale)


def _ragged_attn_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                        block_size: int, window: int, scale: float,
                        quantized: bool):
    scale_refs, o_ref, m_scr, l_scr, acc_scr = _split_refs(rest, quantized)
    t = pl.program_id(0)          # flat token index
    h = pl.program_id(1)
    j = pl.program_id(2)          # logical block index within the token's lane

    @pl.when(j == 0)
    def _():
        _init_state(m_scr, l_scr, acc_scr)

    tpos = pos_ref[t]             # token t's absolute position in its lane

    def mask_fn(shape):
        kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        mask = kpos <= tpos
        if window:
            mask &= (tpos - kpos) < window
        return mask

    @pl.when(j * block_size <= tpos)
    def _():
        q = q_ref[...].astype(jnp.float32) * scale       # (G, D)
        _attend_block(q, k_ref, v_ref, scale_refs, h, mask_fn, m_scr, l_scr,
                      acc_scr)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_ragged(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, token_tables: jax.Array,
                           token_pos: jax.Array, *, window: int = 0,
                           interpret: bool = False,
                           k_scale: jax.Array = None,
                           v_scale: jax.Array = None) -> jax.Array:
    """Flat-token-stream paged attention: q (T, Hkv, G, D) — one mixed
    batch of T tokens from many lanes with NO per-lane rectangle.  Token t
    attends causally over its own lane's blocks (``token_tables[t]``, the
    lane's block-table row scalar-prefetched per token) up to its absolute
    position ``token_pos[t]``.  The grid is (token, kv_head, block): the
    kernel does work proportional to the real scheduled tokens, and each
    token's block sweep stops at its *own* position (``j*bs <= pos``) —
    strictly less kv traffic than the rectangular kernel, which sweeps
    every row to the lane's full context.  Padding tokens (null tables,
    position 0) stay inside the reserved null block and yield garbage the
    caller ignores.  Returns (T, Hkv, G, D)."""
    T, Hkv, G, D = q.shape
    num_blocks, Hkv_p, bs, _ = k_pool.shape
    assert Hkv_p == Hkv, (Hkv_p, Hkv)
    max_blocks = token_tables.shape[1]
    quantized = k_scale is not None

    kernel = functools.partial(_ragged_attn_kernel, block_size=bs,
                               window=window, scale=1.0 / (D ** 0.5),
                               quantized=quantized)
    q_spec = pl.BlockSpec((None, None, G, D),
                          lambda t, h, j, tables, pos: (t, h, 0, 0))
    in_specs = [q_spec] + _kv_specs(
        lambda t, h, j, tables, pos: tables[t, j], bs, D, Hkv, quantized)
    operands = [q, k_pool, v_pool]
    if quantized:
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, Hkv, max_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),   # m
            pltpu.VMEM((G, 1), jnp.float32),   # l
            pltpu.VMEM((G, D), jnp.float32),   # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, Hkv, G, D), q.dtype),
        interpret=interpret,
        name="paged_attention_ragged",
    )(token_tables.astype(jnp.int32), token_pos.astype(jnp.int32),
      *operands)


def _live_blocks(lo, hi, pos0, block_size: int, window: int):
    """First and last logical block holding a key that some row of the
    tile ``[lo, hi)`` (row ``lo`` at position ``pos0``) may see: blocks
    past the deepest row's causal bound, and with a window those wholly
    below the first row's window, hold none.  An inert tile (lo == hi)
    gets an empty range."""
    maxpos = pos0 + hi - 1 - lo            # deepest causal bound in the tile
    j_hi = jnp.where(lo < hi, maxpos // block_size, -1)
    j_lo = (jnp.maximum(pos0 - window + 1, 0) // block_size if window
            else jnp.int32(0))
    return j_lo, j_hi


def _tiled_ragged_attn_kernel(meta_ref, tables_ref, q_ref, k_hbm, v_hbm,
                              *rest, block_size: int, tile: int,
                              window: int, scale: float, group: int,
                              quantized: bool):
    scale_hbm, o_ref, m_scr, l_scr, acc_scr, *bufs = _split_refs(
        rest, quantized)
    *vmem_bufs, sem = bufs                 # (k, v[, k_scale, v_scale]), sem
    hbm = (k_hbm, v_hbm) + (scale_hbm or ())
    t = pl.program_id(0)          # tile = one (q-window, segment) pair
    Hkv = q_ref.shape[0]

    lo = meta_ref[TILE_LO, t]     # the tile's flat-row span [lo, hi)
    hi = meta_ref[TILE_HI, t]
    pos0 = meta_ref[TILE_POS0, t]          # sequence position of row lo
    lane = meta_ref[TILE_LANE, t]
    row0 = meta_ref[TILE_WINDOW, t] * tile  # flat row of the window's row 0
    j_lo, j_hi = _live_blocks(lo, hi, pos0, block_size, window)
    n = j_hi - j_lo + 1                    # live blocks; 0 for inert tiles

    def copies(i):
        """The copies of the tile's i-th live block into buffer
        ``i % KV_BUFFERS``: one (Hkv, bs, D) slab per pool, and the
        block's (Hkv, bs) scale rows of an int8 pool."""
        blk = tables_ref[lane, j_lo + i]
        slot = jax.lax.rem(i, KV_BUFFERS)
        return [pltpu.make_async_copy(src.at[blk], dst.at[slot],
                                      sem.at[slot])
                for src, dst in zip(hbm, vmem_bufs)]

    def start(i):
        @pl.when(i < n)
        def _():
            for c in copies(i):
                c.start()

    _init_state(m_scr, l_scr, acc_scr)
    for i in range(KV_BUFFERS - 1):        # fill all buffers but one
        start(i)

    def body(i, carry):
        start(i + KV_BUFFERS - 1)          # into the buffer freed at i - 1
        for c in copies(i):
            c.wait()
        j = j_lo + i
        slot = jax.lax.rem(i, KV_BUFFERS)
        kbuf, vbuf = vmem_bufs[0].at[slot], vmem_bufs[1].at[slot]
        scale_refs = (None if scale_hbm is None else
                      tuple(b.at[slot] for b in vmem_bufs[2:]))
        shape = (q_ref.shape[1], block_size)
        tok = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        qpos = pos0 + tok - lo
        kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # in-tile causal mask + window-rows outside this tile's segment
        mask = (tok >= lo) & (tok < hi) & (kpos <= qpos)
        if window:
            mask &= (qpos - kpos) < window
        for h in range(Hkv):
            q = q_ref[h].astype(jnp.float32) * scale     # (tile*G, D)
            _attend_block(q, kbuf.at[h], vbuf.at[h], scale_refs, h,
                          lambda _: mask, m_scr.at[h], l_scr.at[h],
                          acc_scr.at[h])
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    _finalize(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("tile", "window", "interpret"))
def paged_attention_ragged_tiled(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, block_tables: jax.Array,
                                 tile_meta: jax.Array, row_tile: jax.Array,
                                 *, tile: int, window: int = 0,
                                 interpret: bool = False,
                                 k_scale: jax.Array = None,
                                 v_scale: jax.Array = None) -> jax.Array:
    """Segment-tiled flat-stream paged attention: q (T, Hkv, G, D), the
    same mixed 1-D token batch as :func:`paged_attention_ragged`, but tiled
    so each lane's KV blocks are DMA'd once per *q-tile* instead of once
    per token, and only the blocks the tile can see.

    The stream is covered by fixed ``tile``-row q windows; ``tile_meta``
    (5, n_tiles) int32 (rows = ``ref.TILE_*``; built by
    ``serving.batch.build_tile_map``) names, per tile, the window it loads,
    its flat-row span ``[lo, hi)`` inside one segment, the sequence
    position of row ``lo``, and the owning lane whose ``block_tables`` row
    the block loop walks.  The grid is (tile,): one (Hkv, tile*G, D) query
    slab rides in per tile, and a ``fori_loop`` walks the tile's live
    logical blocks — from the block of the first row's window start (0
    without a window) to the block of the deepest row's position — copying
    each as one (Hkv, bs, D) slab per pool with ``KV_BUFFERS`` copies in
    flight, and updating every kv head's online-softmax state from it.
    Straddled windows are split into one tile per segment; each tile
    masks the window rows outside its own span, and the per-row outputs
    are gathered back through ``row_tile`` (T,).  Inert capacity-padding
    tiles (lo == hi) run no iteration; stream-padding rows yield finite
    garbage the caller ignores.  Returns (T, Hkv, G, D).
    """
    T, Hkv, G, D = q.shape
    num_blocks, Hkv_p, bs, _ = k_pool.shape
    assert Hkv_p == Hkv, (Hkv_p, Hkv)
    n_tiles = tile_meta.shape[1]
    R = tile * G

    n_windows = -(-T // tile)
    pad = n_windows * tile - T
    qw = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    qw = qw.reshape(n_windows, tile, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    qw = qw.reshape(n_windows, Hkv, R, D)

    quantized = k_scale is not None
    kernel = functools.partial(_tiled_ragged_attn_kernel, block_size=bs,
                               tile=tile, window=window,
                               scale=1.0 / (D ** 0.5), group=G,
                               quantized=quantized)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    operands = [qw, k_pool, v_pool]
    bufs = [pltpu.VMEM((KV_BUFFERS, Hkv, bs, D), k_pool.dtype)] * 2
    if quantized:
        # a DMA out of HBM must move whole 128-lane rows: pad each (bs,)
        # scale row to them (the HBM tiling pads it so already)
        lanes = -(-bs // 128) * 128
        operands += [jnp.pad(s, ((0, 0), (0, 0), (0, lanes - bs)))
                     for s in (k_scale, v_scale)]
        bufs += [pltpu.VMEM((KV_BUFFERS, Hkv, lanes), jnp.float32)] * 2
    slab = pl.BlockSpec((None, Hkv, R, D),
                        lambda t, meta, tables: (meta[TILE_WINDOW, t], 0, 0,
                                                 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[slab] + [hbm] * (len(operands) - 1),
        out_specs=pl.BlockSpec((None, Hkv, R, D),
                               lambda t, meta, tables: (t, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, R, 1), jnp.float32),   # m
            pltpu.VMEM((Hkv, R, 1), jnp.float32),   # l
            pltpu.VMEM((Hkv, R, D), jnp.float32),   # acc
            *bufs,
            pltpu.SemaphoreType.DMA((KV_BUFFERS,)),
        ],
    )
    out_tiles = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, Hkv, R, D), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention_tiled",
    )(tile_meta.astype(jnp.int32), block_tables.astype(jnp.int32),
      *operands)

    # gather every real row's (Hkv, G, D) slab back from its owning tile
    t_idx = row_tile[:T].astype(jnp.int32)
    off = jnp.clip(jnp.arange(T) - tile_meta[TILE_WINDOW, t_idx] * tile,
                   0, tile - 1)
    rows = out_tiles.reshape(n_tiles, Hkv, tile, G, D)
    return rows[t_idx, :, off]                        # (T, Hkv, G, D)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_chunk(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, block_tables: jax.Array,
                          q_starts: jax.Array, ctx_lens: jax.Array, *,
                          window: int = 0, interpret: bool = False,
                          k_scale: jax.Array = None,
                          v_scale: jax.Array = None) -> jax.Array:
    """Chunked prefill/decode: q (B, Hkv, C, G, D) — C query tokens per
    lane, token c at absolute position ``q_starts[b] + c``, causally masked
    inside the chunk.  ``ctx_lens`` (B,) is each lane's total valid kv
    length after the chunk (bounds the block sweep; padded chunk rows past
    it yield garbage the caller ignores).  Returns (B, Hkv, C, G, D)."""
    B, Hkv, C, G, D = q.shape
    q_rows = q.reshape(B, Hkv, C * G, D)
    out = _paged_attention_rows(q_rows, k_pool, v_pool, block_tables,
                                ctx_lens, q_starts, group=G, window=window,
                                interpret=interpret, k_scale=k_scale,
                                v_scale=v_scale)
    return out.reshape(B, Hkv, C, G, D)
