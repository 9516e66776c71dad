"""A configuration file becomes the program's ArchConfig key by key, and
its counts follow the layers the program builds."""
import json
import os

import jax
import pytest

import counts
import run

HERE = os.path.dirname(os.path.abspath(__file__))
DENSE = ["configs/mistral-7b-16L.json", "configs/starcoder2-7b-8L.json"]
MATMULS = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "router"}
ROUTED = {"experts_w_gate", "experts_w_up", "experts_w_down"}


def load(rel):
    return json.load(open(os.path.join(HERE, rel)))


def old_arch_config(cfg):
    """The harness's former construction: a fixed list of dense keys."""
    run.program_on_path()
    from repro.configs.base import ArchConfig
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
            "vocab_size", "head_dim", "rope_theta", "sliding_window",
            "mlp_type", "norm_type", "use_bias", "tie_embeddings")
    return ArchConfig(name=cfg["name"], family=cfg["family"],
                      source=cfg["source"], **{k: cfg[k] for k in keys})


def old_layer_matmul_params(cfg):
    d, H, Hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    D = cfg.get("head_dim") or d // H
    mats = 3 if cfg["mlp_type"] in ("swiglu", "geglu") else 2
    return d * H * D * 2 + d * Hkv * D * 2 + mats * d * cfg["d_ff"]


@pytest.mark.parametrize("rel", DENSE)
def test_dense_configs_build_the_same_arch_config(rel):
    cfg = load(rel)
    assert run.arch_config(cfg) == old_arch_config(cfg)


@pytest.mark.parametrize("rel", DENSE)
def test_dense_step_counts_are_unchanged(rel):
    cfg = load(rel)
    segs = [(0, 300, 1), (517, 1, 1), (4000, 64, 1), (90, 7, 3)]
    c = counts.step_counts(cfg, segs, window=cfg["sliding_window"],
                           kv_bytes=2, act_bytes=2)
    tokens = 300 + 1 + 64 + 7
    assert c["matmul_flops"] == \
        2 * old_layer_matmul_params(cfg) * cfg["n_layers"] * tokens
    assert counts.layer_matmul_params(cfg) == \
        [old_layer_matmul_params(cfg)] * cfg["n_layers"]


@pytest.mark.parametrize("bad", [{"n_layer": 2}, {"moe": {"n_expert": 4}}])
def test_an_unknown_key_is_refused_by_name(bad):
    cfg = load("testdata/tiny-moe.json")
    if "moe" in bad:
        cfg["moe"] = dict(cfg["moe"], **bad["moe"])
    else:
        cfg.update(bad)
    with pytest.raises(run.BenchError, match="n_layer|n_expert"):
        run.arch_config(cfg)


def test_a_missing_field_is_refused():
    cfg = load("testdata/tiny-dense.json")
    del cfg["d_model"]
    with pytest.raises(run.BenchError, match="d_model"):
        run.arch_config(cfg)


def tiny_moe():
    cfg = load("testdata/tiny-moe.json")
    arch = run.arch_config(cfg)
    from repro.configs.base import MoEConfig
    from repro.models import build_model
    assert isinstance(arch.moe, MoEConfig) and arch.is_moe and arch.qk_norm
    assert arch.moe.n_experts == 8 and arch.moe.dense_d_ff == 96
    return cfg, arch, build_model(arch)


def test_a_moe_file_builds_the_programs_moe_model():
    cfg, arch, api = tiny_moe()
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    assert "head_blocks" in shapes and len(shapes["head_blocks"]) == 1
    assert shapes["blocks"]["moe"]["experts_w_gate"].shape == (2, 8, 64, 32)
    params = api.init(jax.random.PRNGKey(0))
    tokens = jax.numpy.arange(12, dtype=jax.numpy.int32)[None] % 512
    logits, aux = api.forward(params, tokens)
    assert logits.shape == (1, 12, 512) and bool(jax.numpy.isfinite(
        logits).all())


def test_moe_counts_follow_the_programs_leaves():
    """Every matmul weight the program's initializer makes, routed experts
    weighted by the share of them one token uses."""
    cfg, arch, api = tiny_moe()
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    share = arch.moe.experts_per_token / arch.moe.n_experts
    total = 0.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ROUTED:
            total += leaf.size * share
        elif name in MATMULS:
            total += leaf.size
    per_layer = counts.layer_matmul_params(cfg)
    assert len(per_layer) == 3 and per_layer[0] != per_layer[1]
    assert sum(per_layer) == total
    c = counts.step_counts(cfg, [(0, 5, 1)], window=0, kv_bytes=2,
                           act_bytes=2)
    assert c["matmul_flops"] == 2 * total * 5


def test_a_cell_on_several_chips_needs_the_configurations_mesh():
    assert run.serving_mesh(["chip"], {}) is None
    for cfg in ({}, {"mesh": {"model": 2}}, {"mesh": {"data": 4}}):
        with pytest.raises(run.BenchError, match="mesh"):
            run.serving_mesh(["a", "b", "c", "d"], cfg)
