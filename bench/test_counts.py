"""counts.py against FLOPs and bytes worked out by hand for one step."""
import counts

CFG = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
       "head_dim": 2, "d_ff": 16, "vocab_size": 10, "mlp_type": "gelu"}


def test_layer_matmul_params_by_hand():
    # q 8x4x2, o 4x2x8, k and v 8x2x2 each, up 8x16 and down 16x8; per
    # layer
    assert counts.layer_matmul_params(CFG) == \
        [64 + 64 + 32 + 32 + 128 + 128] * 2
    gated = dict(CFG, mlp_type="swiglu")
    assert counts.layer_matmul_params(gated) == [448 + 128] * 2


def test_moe_layer_matmul_params_by_hand():
    # attention 192 a layer; 3 layers, the first dense (gelu, 8x12 and
    # 12x8), then router 8x6, 2 of 6 routed experts of width 4 (gate, up,
    # down: 3x8x4 each) and 2 shared of width 4 (one gelu MLP of 8: 8x8
    # and 8x8)
    moe = dict(CFG, n_layers=3, moe={"n_experts": 6, "experts_per_token": 2,
                                     "d_expert": 4, "n_shared_experts": 2,
                                     "first_dense_layers": 1,
                                     "dense_d_ff": 12})
    dense = 192 + 2 * 8 * 12
    sparse = 192 + 48 + 2 * 3 * 8 * 4 + 2 * 8 * 8
    assert counts.layer_matmul_params(moe) == [dense, sparse, sparse]
    # dense_d_ff 0: the leading dense layers take d_expert
    moe["moe"] = dict(moe["moe"], dense_d_ff=0)
    assert counts.layer_matmul_params(moe)[0] == 192 + 2 * 8 * 4


def test_context_sum_and_span_by_hand():
    # positions 0..3, no window: 1+2+3+4
    assert counts.context_sum(0, 4, 0) == 10
    # positions 5..7 under a window of 4: each sees 4 keys
    assert counts.context_sum(5, 3, 4) == 12
    # positions 2..5 under a window of 4: 3, 4, 4, 4
    assert counts.context_sum(2, 4, 4) == 15
    # a decode token at position 100 with a window of 4 reads keys 97..100
    assert counts.kv_span(100, 1, 4) == 4
    # a chunk at 10..13 with a window of 4 reads keys 7..13
    assert counts.kv_span(10, 4, 4) == 7
    assert counts.kv_span(0, 3, 0) == 3


def test_step_counts_by_hand():
    # one prefill chunk at positions 0..2 that emits (1 row read) and
    # one decode at position 6 with 2 drafts (3 tokens, 3 rows read),
    # window 4
    segs = [(0, 3, 1), (6, 3, 3)]
    c = counts.step_counts(CFG, segs, window=4, kv_bytes=2, act_bytes=2)
    assert c["tokens"] == 6 and c["rows_read"] == 4
    assert c["matmul_flops"] == 2 * 448 * 2 * 6
    assert c["head_flops"] == 2 * 8 * 10 * 4
    # contexts: 1+2+3 and 4+4+4; 4 * H * D per key, per layer
    assert c["attn_flops"] == 4 * 4 * 2 * (6 + 12) * 2
    # spans: 3 keys and keys 3..8 (6); K and V, Hkv 2, D 2, 2 bytes;
    # plus q and out: 6 tokens x H 4 x D 2 x 2 x 2 bytes; per layer
    assert c["attn_bytes"] == ((3 + 6) * 2 * 2 * 2 * 2 + 6 * 4 * 2 * 2 * 2) * 2


def test_roofline_picks_the_larger_bound():
    t, bound = counts.roofline_seconds(1e12, 1e9, 1e14, 1e12)
    assert bound == "compute" and t == 1e-2
    t, bound = counts.roofline_seconds(1e9, 1e10, 1e14, 1e12)
    assert bound == "memory" and t == 1e-2
