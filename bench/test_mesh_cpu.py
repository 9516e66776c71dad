"""A cell on four chips, run whole on four virtual CPU devices: the
weights are made sharded, the program's engine serves on the (1, 4)
mesh, the check passes on the sharded weights and fails with the
exchange between chips left out or with the fp8 control, and the result
reports the cell's four devices.  It runs in a child process, since JAX
fixes its device count when it starts."""
import json
import os
import subprocess
import sys

import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))

CHILD = r"""
import json, os, sys
sys.path.insert(0, {here!r})
import measure, run

cfg = json.load(open(os.path.join({here!r}, "testdata", "tiny-dense.json")))
cfg.update(n_kv_heads=4, mesh={{"model": 4}})
traffic = json.load(open(os.path.join({here!r}, "testdata",
                                      "tiny-sessions.json")))
e2e = [{{"name": "ttft_p95_ms", "unit": "ms"}},
       {{"name": "setup_s", "unit": "s"}}]
cell = {{"name": "tiny-tp4", "chips": 4, "cfg": cfg, "traffic": traffic,
        "end_to_end": e2e, "per_layer": []}}
seen, recs = {{}}, []


def hook(eng):
    wq = eng.params["blocks"]["attn"]["wq"]
    seen.update(tp=eng.tp, mesh=int(eng.mesh.devices.size),
                wq_spec=str(wq.sharding.spec),
                wq_shard=list(wq.addressable_shards[0].data.shape),
                kernel=eng.use_kernel)


drive = run.drive
run.drive = lambda *a, **k: recs.append(drive(*a, **k)) or recs[-1]
r = run.run_cell(cell, {seed}, 3.0, False, require_tpu=False,
                 use_cache=False, engine_hook=hook)
rec = recs[0]
rec.update(trace={{"window_s": 2.0}}, peaks={{"bf16_flops": 1e12}},
           trace_steps=[s for s in rec["steps"] if s[2] is not None])
w = measure.step_work(rec, rec["trace_steps"])
flops = w["matmul_flops"] + w["head_flops"] + w["attn_flops"]
import jax, numpy as np, gen
from reference import dense_transformer as ref


def no_exchange(eng):
    # the all-reduce after each row-parallel matmul left out, as the first
    # chip sees it: only its own heads' and its own d_ff slice's share
    blocks = eng.params["blocks"]

    def own(a, n):
        return jax.jit(lambda x: x.at[:, n:].set(0),
                       out_shardings=a.sharding)(a)

    attn = dict(blocks["attn"], wo=own(blocks["attn"]["wo"],
                                       cfg["n_heads"] // 4))
    mlp = dict(blocks["mlp"], w_down=own(blocks["mlp"]["w_down"],
                                         cfg["d_ff"] // 4))
    eng.params = dict(eng.params, blocks=dict(blocks, attn=attn, mlp=mlp))


broken = run.run_cell(cell, {seed}, 3.0, False, require_tpu=False,
                      use_cache=False, engine_hook=no_exchange)
b = run.Bench(cell, {seed}, require_tpu=False, use_cache=False)
tr = gen.load(cell["traffic"], {seed}, cfg["vocab_size"], 3.0)
chk = run.check(b, run.drive(b, tr, 3.0, False), controls=("fp8",))
key = run.seed_key(jax, {seed})
mesh = run.serving_mesh(jax.devices()[:4], cfg)
whole = run.make_weights(jax, ref, cfg, key, jax.numpy.float32, None)
sharded = run.make_weights(jax, ref, cfg, key, jax.numpy.float32, mesh)
same = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
           zip(jax.tree.leaves(whole), jax.tree.leaves(sharded)))
print(json.dumps({{"result": r, "seen": seen, "chips": rec["chips"],
                  "same_weights": same, "no_exchange": broken["compared"],
                  "fp8_mean": chk["fp8"]["mean"],
                  "program_passes": run.passes(chk["compared"]),
                  "mfu": measure.traced_mfu(rec),
                  "mfu_one_chip": 100.0 * flops / (2.0 * 1e12)}}))
"""


@pytest.fixture(scope="module")
def tp4():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c",
                        CHILD.format(here=HERE, seed=2**31 + 23)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_cell_serves_on_the_mesh_and_passes(tp4):
    r = tp4["result"]
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == 4 and tp4["chips"] == 4
    assert set(r["metrics"]) == {"ttft_p95_ms", "setup_s"}


def test_weights_are_sharded_as_the_program_serves_them(tp4):
    s = tp4["seen"]
    assert s["tp"] == 4 and s["mesh"] == 4 and not s["kernel"]
    # wq (layers, d, H, D): the 4 query heads split over "model"
    assert "model" in s["wq_spec"] and s["wq_shard"][2] == 1


def test_exchange_between_chips_left_out_fails(tp4):
    assert not run.passes(tp4["no_exchange"]), tp4["no_exchange"]


def test_fp8_control_on_the_mesh_fails_the_limit(tp4):
    limit = json.load(open(os.path.join(HERE, "testdata",
                                        "tiny-dense.json")))
    assert tp4["fp8_mean"] > limit["correct"]["mean_logit_gap"]
    assert tp4["program_passes"]


def test_sharded_weights_equal_the_whole_ones_bit_for_bit(tp4):
    assert tp4["same_weights"]


def test_mfu_divides_by_the_peaks_of_four_chips(tp4):
    assert tp4["mfu"] == pytest.approx(tp4["mfu_one_chip"] / 4, rel=1e-12)
