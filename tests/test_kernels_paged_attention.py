"""Paged-attention Pallas kernel (interpret mode) vs the pure-jnp oracle."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention import (paged_attention,
                                           paged_attention_chunk,
                                           paged_attention_ragged,
                                           paged_attention_ragged_tiled)
from repro.kernels.ref import (paged_attention_chunk_reference,
                               paged_attention_ragged_reference,
                               paged_attention_ragged_tiled_reference,
                               paged_attention_reference,
                               pool_gather_stats)
from repro.kernels import ops
from repro.serving.batch import (TILE_HI, TILE_LO, TILE_POS0,
                                 build_tile_map)


def _setup(key, B, Hkv, G, D, num_blocks, bs, max_blocks, ctx, dtype):
    ks = jax.random.split(key, 3)
    H = Hkv * G
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    k_pool = jax.random.normal(ks[1], (num_blocks, Hkv, bs, D), dtype)
    v_pool = jax.random.normal(ks[2], (num_blocks, Hkv, bs, D), dtype)
    tables = np.zeros((B, max_blocks), np.int32)
    free = list(range(1, num_blocks))
    for b in range(B):
        for j in range(-(-int(ctx[b]) // bs)):
            tables[b, j] = free.pop(0)
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(ctx)


@pytest.mark.parametrize("G", [1, 4])
@pytest.mark.parametrize("window", [0, 5])
def test_kernel_matches_reference(key, G, window):
    B, Hkv, D, bs, max_blocks = 3, 2, 64, 8, 4
    num_blocks = B * max_blocks + 1
    ctx = np.array([1, 9, 26], np.int32)     # partial / mid / near-full
    q, kp, vp, tables, ctxj = _setup(key, B, Hkv, G, D, num_blocks, bs,
                                     max_blocks, ctx, jnp.float32)
    ref = paged_attention_reference(q, kp, vp, tables, ctxj, window=window)
    qg = q.reshape(B, Hkv, G, D)
    out = paged_attention(qg, kp, vp, tables, ctxj, window=window,
                          interpret=True).reshape(B, H := Hkv * G, D)
    assert out.shape == (B, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_ignores_null_block_contents(key):
    """Garbage in the reserved null block must not leak into any lane."""
    B, Hkv, G, D, bs, max_blocks = 2, 1, 2, 32, 4, 3
    num_blocks = 8
    ctx = np.array([4, 6], np.int32)
    q, kp, vp, tables, ctxj = _setup(key, B, Hkv, G, D, num_blocks, bs,
                                     max_blocks, ctx, jnp.float32)
    out1 = paged_attention(q.reshape(B, Hkv, G, D), kp, vp, tables, ctxj,
                           interpret=True)
    kp2 = kp.at[0].set(1e4)
    vp2 = vp.at[0].set(-1e4)
    out2 = paged_attention(q.reshape(B, Hkv, G, D), kp2, vp2, tables, ctxj,
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_ops_wrapper_dispatches_to_reference_on_cpu(key):
    """On the CPU backend the wrapper must use the XLA reference path and
    accept the model-native (B, 1, H, D) query layout."""
    B, Hkv, G, D, bs, max_blocks = 2, 2, 2, 16, 4, 2
    ctx = np.array([3, 7], np.int32)
    q, kp, vp, tables, ctxj = _setup(key, B, Hkv, G, D, 8, bs,
                                     max_blocks, ctx, jnp.float32)
    out = ops.paged_attention(q[:, None], kp, vp, tables, ctxj)
    assert out.shape == (B, 1, Hkv * G, D)
    ref = paged_attention_reference(q, kp, vp, tables, ctxj)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                               atol=1e-6)


def test_reference_masks_positions_beyond_ctx(key):
    """Rewriting KV entries at/after ctx_len must not change the output."""
    B, Hkv, G, D, bs, max_blocks = 1, 1, 1, 16, 4, 2
    ctx = np.array([5], np.int32)
    q, kp, vp, tables, ctxj = _setup(key, B, Hkv, G, D, 8, bs,
                                     max_blocks, ctx, jnp.float32)
    out1 = paged_attention_reference(q, kp, vp, tables, ctxj)
    blk = int(np.asarray(tables)[0, 1])      # holds positions 4..7
    kp2 = kp.at[blk, :, 2:].set(99.0)           # positions 6,7 >= ctx
    vp2 = vp.at[blk, :, 2:].set(-99.0)
    out2 = paged_attention_reference(q, kp2, vp2, tables, ctxj)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# variable q_len (chunked prefill) generalization
# ---------------------------------------------------------------------------
def _chunk_setup(key, B, Hkv, G, D, bs, max_blocks, C, starts, lens, dtype):
    ks = jax.random.split(key, 3)
    H = Hkv * G
    num_blocks = B * max_blocks + 1
    q = jax.random.normal(ks[0], (B, C, H, D), dtype)
    k_pool = jax.random.normal(ks[1], (num_blocks, Hkv, bs, D), dtype)
    v_pool = jax.random.normal(ks[2], (num_blocks, Hkv, bs, D), dtype)
    tables = np.zeros((B, max_blocks), np.int32)
    free = list(range(1, num_blocks))
    ends = starts + lens
    for b in range(B):
        for j in range(-(-int(ends[b]) // bs)):
            tables[b, j] = free.pop(0)
    return q, k_pool, v_pool, jnp.asarray(tables)


@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("window", [0, 5])
def test_chunk_kernel_matches_chunk_reference(key, G, window):
    """Variable q_len per lane with causal masking inside the chunk: the
    Pallas kernel (interpret mode) must match the pure-jnp chunk oracle on
    every real (non-padded) query row."""
    B, Hkv, D, bs, max_blocks, C = 3, 2, 32, 4, 6, 5
    starts = np.array([0, 3, 9], np.int32)   # fresh / mid-block / deep lane
    lens = np.array([5, 4, 2], np.int32)     # full chunk / padded / padded
    q, kp, vp, tables = _chunk_setup(key, B, Hkv, G, D, bs, max_blocks, C,
                                     starts, lens, jnp.float32)
    ref = paged_attention_chunk_reference(q, kp, vp, tables,
                                          jnp.asarray(starts), window=window)
    H = Hkv * G
    q5 = jnp.transpose(q.reshape(B, C, Hkv, G, D), (0, 2, 1, 3, 4))
    out = paged_attention_chunk(q5, kp, vp, tables, jnp.asarray(starts),
                                jnp.asarray(starts + lens), window=window,
                                interpret=True)
    out = jnp.transpose(out, (0, 2, 1, 3, 4)).reshape(B, C, H, D)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(out[b, :lens[b]]),
                                   np.asarray(ref[b, :lens[b]]),
                                   atol=2e-5, rtol=2e-5)


def test_chunk_kernel_single_token_equals_decode_kernel(key):
    """C = 1 chunks must reproduce the decode kernel exactly (same online
    softmax sweep, q_starts = ctx - 1)."""
    B, Hkv, G, D, bs, max_blocks = 3, 2, 2, 32, 8, 4
    num_blocks = B * max_blocks + 1
    ctx = np.array([1, 9, 26], np.int32)
    q, kp, vp, tables, ctxj = _setup(key, B, Hkv, G, D, num_blocks, bs,
                                     max_blocks, ctx, jnp.float32)
    qg = q.reshape(B, Hkv, G, D)
    dec = paged_attention(qg, kp, vp, tables, ctxj, interpret=True)
    chk = paged_attention_chunk(qg[:, :, None], kp, vp, tables, ctxj - 1,
                                ctxj, interpret=True)[:, :, 0]
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(chk))


def test_chunk_reference_is_causal_inside_chunk(key):
    """Query c must not see kv positions written for later chunk tokens:
    corrupting position start+c+1 changes row c+1 but never row c."""
    B, Hkv, G, D, bs, max_blocks, C = 1, 1, 1, 16, 4, 3, 4
    starts = np.array([2], np.int32)
    lens = np.array([4], np.int32)
    q, kp, vp, tables = _chunk_setup(key, B, Hkv, G, D, bs, max_blocks, C,
                                     starts, lens, jnp.float32)
    out1 = paged_attention_chunk_reference(q, kp, vp, tables,
                                           jnp.asarray(starts))
    p = int(starts[0]) + 2                   # the chunk's 3rd position
    blk = int(np.asarray(tables)[0, p // bs])
    kp2 = kp.at[blk, :, p % bs].set(37.0)
    vp2 = vp.at[blk, :, p % bs].set(-37.0)
    out2 = paged_attention_chunk_reference(q, kp2, vp2, tables,
                                           jnp.asarray(starts))
    np.testing.assert_array_equal(np.asarray(out1[0, :2]),
                                  np.asarray(out2[0, :2]))
    assert not np.allclose(np.asarray(out1[0, 2]), np.asarray(out2[0, 2]))


def test_ops_chunk_wrapper_dispatches_to_reference_on_cpu(key):
    B, Hkv, G, D, bs, max_blocks, C = 2, 2, 2, 16, 4, 4, 3
    starts = np.array([0, 5], np.int32)
    lens = np.array([3, 2], np.int32)
    q, kp, vp, tables = _chunk_setup(key, B, Hkv, G, D, bs, max_blocks, C,
                                     starts, lens, jnp.float32)
    out = ops.paged_attention_chunk(q, kp, vp, tables, jnp.asarray(starts),
                                    jnp.asarray(lens))
    ref = paged_attention_chunk_reference(q, kp, vp, tables,
                                          jnp.asarray(starts))
    assert out.shape == (B, C, Hkv * G, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# ragged flat-token-stream generalization
# ---------------------------------------------------------------------------
# Each lane contributes a contiguous segment of (start, n) query tokens to
# one flat stream; {all-decode, one-big-prefill+decodes, multi-prefill}
# exercises the mixes the unified serving step actually schedules, with
# segment starts straddling block boundaries (start % bs != 0, segments
# crossing into the next block).
SEGMENT_MIXES = {
    "all_decode": [(4, 1), (9, 1), (0, 1), (14, 1)],
    "one_prefill_plus_decodes": [(3, 1), (0, 9), (7, 1)],
    "multi_prefill": [(2, 6), (0, 5), (5, 7)],
}


def _ragged_setup(key, segments, Hkv, G, D, bs, max_blocks, dtype):
    """Build pools + disjoint per-lane tables + the flat token metadata."""
    ks = jax.random.split(key, 3)
    H = Hkv * G
    T = sum(n for _, n in segments)
    n_lanes = len(segments)
    num_blocks = n_lanes * max_blocks + 1
    q = jax.random.normal(ks[0], (T, H, D), dtype)
    k_pool = jax.random.normal(ks[1], (num_blocks, Hkv, bs, D), dtype)
    v_pool = jax.random.normal(ks[2], (num_blocks, Hkv, bs, D), dtype)
    tables = np.zeros((n_lanes, max_blocks), np.int32)
    free = list(range(1, num_blocks))
    token_tables = np.zeros((T, max_blocks), np.int32)
    token_pos = np.zeros((T,), np.int32)
    off = 0
    for lane, (start, n) in enumerate(segments):
        for j in range(-(-(start + n) // bs)):
            tables[lane, j] = free.pop(0)
        token_tables[off:off + n] = tables[lane]
        token_pos[off:off + n] = start + np.arange(n)
        off += n
    return q, k_pool, v_pool, tables, token_tables, token_pos


@pytest.mark.parametrize("mix", sorted(SEGMENT_MIXES))
@pytest.mark.parametrize("G", [1, 2, 4])
def test_ragged_reference_matches_per_lane_chunk_reference(key, mix, G):
    """The flat-stream oracle must agree with the naive per-lane chunk
    oracle on every segment: flattening is a layout change, not a math
    change."""
    segments = SEGMENT_MIXES[mix]
    Hkv, D, bs, max_blocks = 2, 16, 4, 4
    q, kp, vp, tables, ttab, tpos = _ragged_setup(
        key, segments, Hkv, G, D, bs, max_blocks, jnp.float32)
    flat = paged_attention_ragged_reference(q, kp, vp, jnp.asarray(ttab),
                                            jnp.asarray(tpos))
    off = 0
    for lane, (start, n) in enumerate(segments):
        per_lane = paged_attention_chunk_reference(
            q[None, off:off + n], kp, vp,
            jnp.asarray(tables[lane:lane + 1]),
            jnp.asarray([start], jnp.int32))
        np.testing.assert_allclose(np.asarray(flat[off:off + n]),
                                   np.asarray(per_lane[0]),
                                   atol=2e-5, rtol=2e-5)
        off += n


@pytest.mark.parametrize("mix", sorted(SEGMENT_MIXES))
@pytest.mark.parametrize("G", [1, 2])
@pytest.mark.parametrize("window", [0, 5])
def test_ragged_kernel_matches_ragged_reference(key, mix, G, window):
    """Pallas flat-stream kernel (interpret mode) vs the pure-jnp oracle
    across q_len mixes, GQA ratios, and block-straddling positions."""
    segments = SEGMENT_MIXES[mix]
    Hkv, D, bs, max_blocks = 2, 32, 4, 4
    q, kp, vp, tables, ttab, tpos = _ragged_setup(
        key, segments, Hkv, G, D, bs, max_blocks, jnp.float32)
    T, H, D = q.shape
    ref = paged_attention_ragged_reference(q, kp, vp, jnp.asarray(ttab),
                                           jnp.asarray(tpos), window=window)
    qg = q.reshape(T, Hkv, G, D)
    out = paged_attention_ragged(qg, kp, vp, jnp.asarray(ttab),
                                 jnp.asarray(tpos), window=window,
                                 interpret=True).reshape(T, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ragged_single_token_rows_equal_decode_kernel(key):
    """A flat stream of pure decodes must reproduce the rectangular decode
    kernel row for row (same online-softmax sweep per token)."""
    segments = SEGMENT_MIXES["all_decode"]
    Hkv, G, D, bs, max_blocks = 2, 2, 32, 4, 4
    q, kp, vp, tables, ttab, tpos = _ragged_setup(
        key, segments, Hkv, G, D, bs, max_blocks, jnp.float32)
    T, H, _ = q.shape
    qg = q.reshape(T, Hkv, G, D)
    flat = paged_attention_ragged(qg, kp, vp, jnp.asarray(ttab),
                                  jnp.asarray(tpos), interpret=True)
    # the same tokens as a (B = T)-lane decode batch at ctx = pos + 1
    dec = paged_attention(qg, kp, vp, jnp.asarray(ttab),
                          jnp.asarray(tpos) + 1, interpret=True)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(dec))


def test_ragged_padding_rows_are_inert(key):
    """Null-table / position-0 padding rows (the bucket tail) must not
    fault and must not change any real row's output."""
    segments = SEGMENT_MIXES["multi_prefill"]
    Hkv, G, D, bs, max_blocks = 2, 2, 16, 4, 4
    q, kp, vp, tables, ttab, tpos = _ragged_setup(
        key, segments, Hkv, G, D, bs, max_blocks, jnp.float32)
    T = q.shape[0]
    pad = 6
    qp = jnp.concatenate([q, jnp.zeros((pad,) + q.shape[1:], q.dtype)])
    ttab_p = np.concatenate([ttab, np.zeros((pad, max_blocks), np.int32)])
    tpos_p = np.concatenate([tpos, np.zeros((pad,), np.int32)])
    ref = paged_attention_ragged_reference(q, kp, vp, jnp.asarray(ttab),
                                           jnp.asarray(tpos))
    out = paged_attention_ragged_reference(qp, kp, vp, jnp.asarray(ttab_p),
                                           jnp.asarray(tpos_p))
    np.testing.assert_array_equal(np.asarray(out[:T]), np.asarray(ref))
    assert np.all(np.isfinite(np.asarray(out)))      # garbage, but finite


def test_ragged_kernel_ignores_null_block_contents(key):
    """Scribbling the reserved null block must not leak into any lane."""
    segments = SEGMENT_MIXES["one_prefill_plus_decodes"]
    Hkv, G, D, bs, max_blocks = 1, 2, 32, 4, 4
    q, kp, vp, tables, ttab, tpos = _ragged_setup(
        key, segments, Hkv, G, D, bs, max_blocks, jnp.float32)
    T = q.shape[0]
    qg = q.reshape(T, Hkv, G, D)
    out1 = paged_attention_ragged(qg, kp, vp, jnp.asarray(ttab),
                                  jnp.asarray(tpos), interpret=True)
    out2 = paged_attention_ragged(qg, kp.at[0].set(1e4),
                                  vp.at[0].set(-1e4), jnp.asarray(ttab),
                                  jnp.asarray(tpos), interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_ops_ragged_wrapper_dispatches_to_reference_on_cpu(key):
    """On the CPU backend the wrapper must use the XLA reference path and
    accept the model-native (T, H, D) flat query layout."""
    segments = SEGMENT_MIXES["multi_prefill"]
    Hkv, G, D, bs, max_blocks = 2, 2, 16, 4, 4
    q, kp, vp, tables, ttab, tpos = _ragged_setup(
        key, segments, Hkv, G, D, bs, max_blocks, jnp.float32)
    out = ops.paged_attention_ragged(q, kp, vp, jnp.asarray(ttab),
                                     jnp.asarray(tpos))
    ref = paged_attention_ragged_reference(q, kp, vp, jnp.asarray(ttab),
                                           jnp.asarray(tpos))
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# segment-tiled generalization: a (q_tile) grid walking each tile's live
# KV blocks
# ---------------------------------------------------------------------------
# (tile, segments, T_pad): each segment is (start_pos, n_tokens) for one
# lane, packed back to back into a flat stream padded to T_pad.  The mixes
# pin the geometry the tiled grid must survive: q windows straddling
# segment boundaries (segment offsets not multiples of tile), segments
# both smaller and larger than a tile, and start positions straddling KV
# block edges (start % bs != 0 with bs = 4 below).
TILED_MIXES = {
    "straddling_boundaries": (4, [(3, 1), (0, 9), (7, 5), (14, 1)], 16),
    "segments_smaller_than_tile": (16, [(0, 3), (1, 2), (4, 6)], 16),
    "segments_larger_than_tile": (4, [(0, 17), (5, 9)], 32),
    "all_decode": (8, [(2, 1), (5, 1), (9, 1), (0, 1)], 8),
    # contexts ending exactly on a block edge (8, 12), one token past it
    # (9, 13), and at max_blocks * bs (32)
    "block_edges": (4, [(7, 1), (8, 1), (31, 1), (4, 8), (9, 4), (20, 12)],
                    32),
    # 2 real tiles in a capacity of 4 windows + 2 lanes
    "mostly_inert": (16, [(5, 1), (26, 2)], 64),
    # decodes between prefill chunks, windows straddling every boundary
    "mixed_straddle": (8, [(10, 1), (0, 11), (22, 1), (4, 6), (17, 1),
                           (1, 3)], 24),
}


def _tiled_setup(key, segments, Hkv, G, D, bs, max_blocks, tile, T_pad,
                 dtype):
    """Pools + per-lane tables + flat-token metadata + the TileMap."""
    q, k_pool, v_pool, tables, token_tables, token_pos = _ragged_setup(
        key, segments, Hkv, G, D, bs, max_blocks, dtype)
    T = q.shape[0]
    if T_pad > T:            # bucket tail: lane-0/pos-0 padding rows
        ks = jax.random.split(key, 2)
        q = jnp.concatenate(
            [q, jax.random.normal(ks[1], (T_pad - T,) + q.shape[1:], dtype)])
        token_tables = np.concatenate(
            [token_tables, np.zeros((T_pad - T, max_blocks), np.int32)])
        token_pos = np.concatenate(
            [token_pos, np.zeros((T_pad - T,), np.int32)])
    offs, lens, lanes, pos0 = [], [], [], []
    off = 0
    for lane, (start, n) in enumerate(segments):
        offs.append(off); lens.append(n); lanes.append(lane)
        pos0.append(start)
        off += n
    tm = build_tile_map(offs, lens, lanes, pos0, T_pad, len(segments), tile)
    return q, k_pool, v_pool, tables, token_tables, token_pos, tm, T


@pytest.mark.parametrize("mix", sorted(TILED_MIXES))
@pytest.mark.parametrize("G", [1, 4, 8])
def test_tiled_reference_matches_per_token_reference(key, mix, G):
    """The segment-tiled oracle must agree with the per-token flat oracle
    on every real row: tiling is a scheduling change, not a math change.
    Agreement is to float32 rounding, not bit for bit: the two oracles
    contract different batch shapes, and XLA's CPU dot may sum D in a
    different order per shape (observed: 2.4e-7 at G = 4 and 8)."""
    tile, segments, T_pad = TILED_MIXES[mix]
    Hkv, D, bs, max_blocks = 2, 16, 4, 8
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, T_pad, jnp.float32)
    per_tok = paged_attention_ragged_reference(
        q, kp, vp, jnp.asarray(ttab), jnp.asarray(tpos))
    tiled = paged_attention_ragged_tiled_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile)
    np.testing.assert_allclose(np.asarray(tiled[:T]),
                               np.asarray(per_tok[:T]), rtol=1e-6, atol=1e-6)
    assert np.all(np.isfinite(np.asarray(tiled)))    # padding rows: finite


@pytest.mark.parametrize("mix", sorted(TILED_MIXES))
@pytest.mark.parametrize("G", [1, 4, 8])
@pytest.mark.parametrize("window", [0, 5])
def test_tiled_kernel_matches_tiled_reference(key, mix, G, window):
    """Pallas segment-tiled kernel (interpret mode) vs the tiled oracle vs
    the per-token oracle, across boundary-straddling tiles, GQA group
    sizes, block-edge positions, and sliding windows."""
    tile, segments, T_pad = TILED_MIXES[mix]
    Hkv, D, bs, max_blocks = 2, 32, 4, 8
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, T_pad, jnp.float32)
    ref_t = paged_attention_ragged_tiled_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile, window=window)
    per_tok = paged_attention_ragged_reference(
        q, kp, vp, jnp.asarray(ttab), jnp.asarray(tpos), window=window)
    H = Hkv * G
    qg = q.reshape(T_pad, Hkv, G, D)
    out = paged_attention_ragged_tiled(
        qg, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile, window=window,
        interpret=True).reshape(T_pad, H, D)
    np.testing.assert_allclose(np.asarray(out[:T]), np.asarray(ref_t[:T]),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out[:T]), np.asarray(per_tok[:T]),
                               atol=2e-5, rtol=2e-5)


def test_tiled_kernel_padding_tiles_are_inert(key):
    """Capacity-padding tiles (lo == hi) and stream-padding rows must not
    change any real row, in kernel or reference — scribbling the null
    block and growing the tile capacity is invisible."""
    tile, segments, T_pad = TILED_MIXES["straddling_boundaries"]
    Hkv, G, D, bs, max_blocks = 2, 2, 16, 4, 8
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, T_pad, jnp.float32)
    qg = q.reshape(T_pad, Hkv, G, D)
    out1 = paged_attention_ragged_tiled(
        qg, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile, interpret=True)
    # double the inert capacity + poison the null block
    meta2 = np.concatenate([tm.meta, np.zeros_like(tm.meta)], axis=1)
    out2 = paged_attention_ragged_tiled(
        qg, kp.at[0].set(1e4), vp.at[0].set(-1e4), jnp.asarray(tables),
        jnp.asarray(meta2), jnp.asarray(tm.row_tile), tile=tile,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(out1[:T]), np.asarray(out2[:T]))
    assert np.all(np.isfinite(np.asarray(out2)))
    r1 = paged_attention_ragged_tiled_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile)
    r2 = paged_attention_ragged_tiled_reference(
        q, kp.at[0].set(1e4), vp.at[0].set(-1e4), jnp.asarray(tables),
        jnp.asarray(meta2), jnp.asarray(tm.row_tile), tile=tile)
    np.testing.assert_array_equal(np.asarray(r1[:T]), np.asarray(r2[:T]))


def test_tiled_single_tile_equals_decode_kernel(key):
    """Pure-decode tiles must reproduce the rectangular decode kernel row
    for row (same online-softmax sweep per token)."""
    tile, segments, T_pad = TILED_MIXES["all_decode"]
    Hkv, G, D, bs, max_blocks = 2, 2, 32, 4, 8
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, T_pad, jnp.float32)
    qg = q.reshape(T_pad, Hkv, G, D)
    out = paged_attention_ragged_tiled(
        qg, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile, interpret=True)
    dec = paged_attention(qg[:T], kp, vp, jnp.asarray(ttab[:T]),
                          jnp.asarray(tpos[:T]) + 1, interpret=True)
    np.testing.assert_allclose(np.asarray(out[:T]), np.asarray(dec),
                               atol=2e-5, rtol=2e-5)


def test_ops_tiled_wrapper_dispatches_to_reference_on_cpu(key):
    """On the CPU backend the wrapper must use the tiled XLA reference and
    accept the model-native (T, H, D) flat query layout."""
    tile, segments, T_pad = TILED_MIXES["segments_larger_than_tile"]
    Hkv, G, D, bs, max_blocks = 2, 2, 16, 4, 8
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, T_pad, jnp.float32)
    out = ops.paged_attention_ragged_tiled(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile)
    ref = paged_attention_ragged_tiled_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# instrumented-reference regression: KV gather traffic scales with
# tiles/lanes, not tokens — the fix for the ~30% all-prefill CPU gap
# ---------------------------------------------------------------------------
def test_tiled_reference_gathers_each_block_once_per_lane(key):
    """A 256-token single-segment prefill must read each pool block once
    (one span gather per lane), where the per-token reference reads every
    block once per token — 256x the traffic."""
    T = 256
    tile, bs, max_blocks = 16, 8, 32
    Hkv, G, D = 1, 2, 16
    segments = [(0, T)]
    q, kp, vp, tables, ttab, tpos, tm, _ = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, T, jnp.float32)
    pool_gather_stats["blocks"] = 0
    paged_attention_ragged_tiled_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile)
    tiled_reads = pool_gather_stats["blocks"]
    pool_gather_stats["blocks"] = 0
    paged_attention_ragged_reference(q, kp, vp, jnp.asarray(ttab),
                                     jnp.asarray(tpos))
    per_token_reads = pool_gather_stats["blocks"]
    # one lane: k and v pools each gathered once -> each block read once
    assert tiled_reads == 2 * max_blocks
    assert per_token_reads == 2 * T * max_blocks
    assert per_token_reads == T * tiled_reads


def test_tiled_reference_gather_traffic_independent_of_tokens(key):
    """Doubling the scheduled token count must not change the tiled
    reference's pool traffic (it scales with lanes), while the per-token
    reference's doubles."""
    tile, bs, max_blocks = 8, 4, 16
    Hkv, G, D = 2, 2, 16
    counts = {}
    for name, segments in (("short", [(0, 16), (0, 16)]),
                           ("long", [(0, 32), (0, 32)])):
        T = sum(n for _, n in segments)
        q, kp, vp, tables, ttab, tpos, tm, _ = _tiled_setup(
            key, segments, Hkv, G, D, bs, max_blocks, tile, T, jnp.float32)
        pool_gather_stats["blocks"] = 0
        paged_attention_ragged_tiled_reference(
            q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
            jnp.asarray(tm.row_tile), tile=tile)
        tiled_reads = pool_gather_stats["blocks"]
        pool_gather_stats["blocks"] = 0
        paged_attention_ragged_reference(q, kp, vp, jnp.asarray(ttab),
                                         jnp.asarray(tpos))
        counts[name] = (tiled_reads, pool_gather_stats["blocks"])
    assert counts["long"][0] == counts["short"][0]       # lanes unchanged
    assert counts["long"][1] == 2 * counts["short"][1]   # tokens doubled


def test_tile_map_partitions_real_rows(key):
    """Host-side contract: tiles are disjoint, within-window, within-
    segment slabs whose union is exactly the real token rows."""
    for mix in sorted(TILED_MIXES):
        tile, segments, T_pad = TILED_MIXES[mix]
        offs, lens, lanes, pos0 = [], [], [], []
        off = 0
        for lane, (start, n) in enumerate(segments):
            offs.append(off); lens.append(n); lanes.append(lane)
            pos0.append(start)
            off += n
        tm = build_tile_map(offs, lens, lanes, pos0, T_pad, len(segments),
                            tile)
        total = off
        assert tm.cu_seqlens[0] == 0 and tm.cu_seqlens[-1] == total
        assert np.all(np.diff(tm.cu_seqlens) >= 1)
        covered = np.zeros(total, bool)
        for t in range(tm.n_tiles):
            lo, hi = tm.meta[TILE_LO, t], tm.meta[TILE_HI, t]
            assert lo < hi
            assert lo // tile == (hi - 1) // tile        # one window
            s = np.searchsorted(tm.cu_seqlens, lo, side="right") - 1
            assert hi <= tm.cu_seqlens[s + 1]            # one segment
            assert not covered[lo:hi].any()
            covered[lo:hi] = True
            assert np.all(tm.row_tile[lo:hi] == t)
        assert covered.all()
        for t in range(tm.n_tiles, tm.meta.shape[1]):    # inert capacity
            assert tm.meta[TILE_LO, t] == tm.meta[TILE_HI, t]


def _tile_map(segments, T_pad, tile):
    """The TileMap of back-to-back ``(start, n)`` segments, lane = index."""
    offs = np.cumsum([0] + [n for _, n in segments[:-1]]).tolist()
    return build_tile_map(offs, [n for _, n in segments],
                          list(range(len(segments))),
                          [start for start, _ in segments], T_pad,
                          len(segments), tile)


def _visible_blocks(tm, bs, window):
    """Brute force: (tile, KV block) pairs in which some key is visible to
    some row of the tile (causal, and inside the window when one is set)."""
    n = 0
    for t in range(tm.n_tiles):
        lo, hi, pos0 = (int(tm.meta[r, t]) for r in (TILE_LO, TILE_HI,
                                                     TILE_POS0))
        blocks = set()
        for p in range(pos0, pos0 + hi - lo):
            first = max(0, p - window + 1) if window else 0
            blocks.update(k // bs for k in range(first, p + 1))
        n += len(blocks)
    return n


@pytest.mark.parametrize("window", [0, 3, 5, 9])
@pytest.mark.parametrize("mix", sorted(TILED_MIXES))
def test_tile_map_kv_blocks_counts_visible_blocks(mix, window):
    """``TileMap.kv_blocks`` — the tiled kernel's live (tile, block)
    visits — is exactly the brute-force count of pairs holding a key some
    row of the tile may see, for every mix, block size and window."""
    tile, segments, T_pad = TILED_MIXES[mix]
    tm = _tile_map(segments, T_pad, tile)
    for bs in (1, 4, 8):
        assert tm.kv_blocks(bs, window) == _visible_blocks(tm, bs, window)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_tiled_kernel_skips_blocks_below_the_window(key, quantized):
    """A window shorter than the context leaves each tile's first blocks
    dead (the loop starts at j_lo > 0): the kernel still matches the
    reference, and never reads those blocks — poisoning them with NaN (the
    K/V slabs, or an int8 pool's scale rows) changes nothing."""
    tile, bs, max_blocks, window = 8, 4, 8, 5
    segments = [(29, 1), (22, 3), (30, 2), (3, 1)]
    Hkv, G, D = 2, 4, 32
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, 8, jnp.float32)
    assert tm.kv_blocks(bs, window) < tm.kv_blocks(bs)
    sc = {}
    if quantized:
        kp, vp, sc = _quantized_pools(jax.random.fold_in(key, 1), kp.shape)
    ref = paged_attention_ragged_tiled_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile, window=window, **sc)[:T]
    dead = [tables[lane, j] for lane, (start, _) in enumerate(segments)
            for j in range(max(start - window + 1, 0) // bs)]
    assert dead
    if quantized:
        sc = {n: a.at[jnp.asarray(dead)].set(jnp.nan) for n, a in sc.items()}
    else:
        kp = kp.at[jnp.asarray(dead)].set(jnp.nan)
        vp = vp.at[jnp.asarray(dead)].set(jnp.nan)
    out = paged_attention_ragged_tiled(
        q.reshape(8, Hkv, G, D), kp, vp, jnp.asarray(tables),
        jnp.asarray(tm.meta), jnp.asarray(tm.row_tile), tile=tile,
        window=window, interpret=True, **sc).reshape(8, Hkv * G, D)[:T]
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_tiled_kernel_matches_the_old_grids_maths_at_serving_widths(key):
    """The reference computes what the old (tile, kv_head, block) grid
    did — every block of the lane, each head on its own; the kernel walks
    only the live blocks with all heads per block.  Same inputs, same
    rows within the tolerance of the other tiled tests, at a served head
    width (D = 128, block 16): decode lanes of many depths beside two
    prefill chunks that straddle windows, inert capacity tiles beside."""
    tile, bs, max_blocks = 16, 16, 8
    Hkv, G, D = 2, 4, 128
    rng = np.random.default_rng(14)
    segments = [(int(c), 1) for c in rng.integers(0, max_blocks * bs, 9)]
    segments += [(0, 37), (61, 20)]
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, 128, jnp.float32)
    assert tm.n_tiles < tm.meta.shape[1]
    ref = paged_attention_ragged_tiled_reference(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(tm.meta),
        jnp.asarray(tm.row_tile), tile=tile)
    out = paged_attention_ragged_tiled(
        q.reshape(128, Hkv, G, D), kp, vp, jnp.asarray(tables),
        jnp.asarray(tm.meta), jnp.asarray(tm.row_tile), tile=tile,
        interpret=True).reshape(128, Hkv * G, D)
    np.testing.assert_allclose(np.asarray(out[:T]), np.asarray(ref[:T]),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# int8 pools: per-(block, kv-head, slot) scales ride the same table-indexed
# DMAs; the kernels scale score/probability rows, the oracles dequantize
# the gathered spans — equal up to float32 rounding
# ---------------------------------------------------------------------------
def _quantized_pools(key, shape):
    """Random int8 K/V pools (num_blocks, Hkv, bs, D) + positive float32
    scale pools (num_blocks, Hkv, bs)."""
    ks = jax.random.split(key, 4)
    k = jax.random.randint(ks[0], shape, -127, 128).astype(jnp.int8)
    v = jax.random.randint(ks[1], shape, -127, 128).astype(jnp.int8)
    k_scale = jax.random.uniform(ks[2], shape[:3], jnp.float32, 1e-3, 2e-2)
    v_scale = jax.random.uniform(ks[3], shape[:3], jnp.float32, 1e-3, 2e-2)
    return k, v, {"k_scale": k_scale, "v_scale": v_scale}


@pytest.mark.parametrize("grid", ["rect", "per_token", "tiled"])
def test_int8_kernels_match_int8_references(key, grid):
    """Every grid's int8 path (interpret mode) against its oracle, at a
    GQA ratio with Hkv > 1 and a block-straddling segment mix."""
    tile, segments, T_pad = TILED_MIXES["straddling_boundaries"]
    Hkv, G, D, bs, max_blocks = 2, 4, 32, 4, 8
    q, kp, vp, tables, ttab, tpos, tm, T = _tiled_setup(
        key, segments, Hkv, G, D, bs, max_blocks, tile, T_pad, jnp.float32)
    kq, vq, sc = _quantized_pools(jax.random.fold_in(key, 1), kp.shape)
    H = Hkv * G
    if grid == "rect":
        # every flat row as its own one-token lane at ctx = pos + 1
        ref = paged_attention_reference(q[:T], kq, vq, jnp.asarray(ttab[:T]),
                                        jnp.asarray(tpos[:T]) + 1, **sc)
        out = paged_attention(q[:T].reshape(T, Hkv, G, D), kq, vq,
                              jnp.asarray(ttab[:T]),
                              jnp.asarray(tpos[:T]) + 1, interpret=True,
                              **sc).reshape(T, H, D)
    elif grid == "per_token":
        ref = paged_attention_ragged_reference(
            q, kq, vq, jnp.asarray(ttab), jnp.asarray(tpos), **sc)[:T]
        out = paged_attention_ragged(
            q.reshape(T_pad, Hkv, G, D), kq, vq, jnp.asarray(ttab),
            jnp.asarray(tpos), interpret=True, **sc).reshape(T_pad, H, D)[:T]
    else:
        ref = paged_attention_ragged_tiled_reference(
            q, kq, vq, jnp.asarray(tables), jnp.asarray(tm.meta),
            jnp.asarray(tm.row_tile), tile=tile, **sc)[:T]
        out = paged_attention_ragged_tiled(
            q.reshape(T_pad, Hkv, G, D), kq, vq, jnp.asarray(tables),
            jnp.asarray(tm.meta), jnp.asarray(tm.row_tile), tile=tile,
            interpret=True, **sc).reshape(T_pad, H, D)[:T]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
