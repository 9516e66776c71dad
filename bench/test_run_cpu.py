"""The whole run on the CPU at a tiny size: the check passes on the
served path, fails on a broken one and on the control, and ``main``
refuses to report without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import gen
import run

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 11
SECONDS = 3.0


def tiny_cell():
    cfg = json.load(open(os.path.join(HERE, "testdata", "tiny-dense.json")))
    traffic = json.load(open(os.path.join(HERE, "testdata",
                                          "tiny-sessions.json")))
    e2e = [{"name": n, "unit": u} for n, u in (("ttft_p95_ms", "ms"),
                                                ("tpot_p50_ms", "ms"),
                                                ("setup_s", "s"))]
    return {"name": "tiny", "chips": 1, "cfg": cfg, "traffic": traffic,
            "end_to_end": e2e, "per_layer": []}


def cpu_run(hook=None, seed=SEED):
    return run.run_cell(tiny_cell(), seed, SECONDS, False,
                        require_tpu=False, use_cache=False,
                        engine_hook=hook)


def test_served_path_passes_the_check():
    r = cpu_run()
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ttft_p95_ms", "tpot_p50_ms", "setup_s"}
    assert list(r)[-1] == "compared"
    assert r["compared"]["sampled_tokens"]["value"] > 0


def test_step_that_returns_its_state_unchanged_fails():
    import jax

    def hook(eng):
        step = eng._step
        eng._step = jax.jit(lambda p, c, t: (step(p, c, t)[0], c))

    r = cpu_run(hook)
    assert not r["correct"], r["compared"]


def test_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.serving import engine as engine_mod
    emit = engine_mod._emit_token
    calls = {"n": 0}

    def altered(eng, req, tok):
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            tok = (tok + 1) % 512
        return emit(eng, req, tok)

    monkeypatch.setattr(engine_mod, "_emit_token", altered)
    r = cpu_run()
    assert not r["correct"], r["compared"]


def test_fp8_control_fails_the_limit():
    cell = tiny_cell()
    b = run.Bench(cell, SEED, require_tpu=False, use_cache=False)
    traffic = gen.load(cell["traffic"], SEED, cell["cfg"]["vocab_size"],
                       SECONDS)
    rec = run.drive(b, traffic, SECONDS, False)
    chk = run.check(b, rec, controls=("fp8",))
    assert chk["fp8"]["mean"] > cell["cfg"]["correct"]["mean_logit_gap"]
    assert run.passes(chk["compared"])


def test_main_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", "m7-chat-open", "--seed", "1",
                     "--seconds", "1"]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_a_checkout_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "m7-chat-open", "--seed", "1",
                        "--seconds", "1"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "serving program is not in this checkout" in p.stderr


def test_offline_backlog_on_cpu():
    cell = tiny_cell()
    cell["traffic"] = json.load(open(os.path.join(HERE, "testdata",
                                                  "tiny-offline.json")))
    cell["end_to_end"] = [{"name": "out_tok_per_s", "unit": "tokens/s"},
                          {"name": "setup_s", "unit": "s"}]
    cell["per_layer"] = [{"name": "preemptions_per_req", "unit": "1/req"}]
    r = run.run_cell(cell, SEED, SECONDS, False, require_tpu=False,
                     use_cache=False)
    assert r["correct"], r["compared"]
    assert r["metrics"]["out_tok_per_s"]["value"] > 0
    assert r["attempted"] > 0 and r["failed"] == 0
