"""The serving path's own spans and its admission stamp.

A tiny ``PagedDecodeEngine`` is driven through ``AsyncEngine`` under a
CPU profiler trace; the ``.xplane.pb`` is read back with
``jax.profiler.ProfileData``:

  * one ``serve.step`` per engine step, ``step_num`` consecutive;
  * every other ``serve.*`` span nests inside a ``serve.step``;
  * one ``serve.engine.dispatch`` per step, its args those of the step's
    :class:`~repro.serving.scheduler.StepDecision` and, on the tiled
    grid, the live (tile, KV block) visits of its
    :class:`~repro.serving.batch.TileMap`;
  * ``t_submit <= t_admit <= t_first_token``, and a preempted request
    keeps the ``t_admit`` of its first admission;
  * greedy outputs are token-identical with the profiler on and off.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving import AsyncEngine, PagedDecodeEngine
from repro.serving import trace
from repro.serving.batch import RaggedBatch, padded_pow2

KW = dict(n_slots=3, cache_len=64, block_size=4, chunk_tokens=8,
          cache_dtype=jnp.float32, compute_dtype=jnp.float32)
MAX_NEW = 8


@pytest.fixture(scope="module")
def model():
    cfg = get_config("gemma-7b").smoke_variant()
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


def _prompts(cfg, n, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         int(rng.integers(3, 12))).astype(np.int32)
            for _ in range(n)]


def _serve(eng, prompts, trace_dir=None):
    """Serve ``prompts`` through ``AsyncEngine`` (traced into
    ``trace_dir`` when given); returns (finished requests in submission
    order, the StepDecision of every engine step in order)."""
    decisions = []
    step = eng.step

    def recording_step():
        d = step()
        decisions.append(d)
        return d

    eng.step = recording_step
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        with AsyncEngine(eng) as fe:
            tickets = [fe.submit(p, MAX_NEW) for p in prompts]
            done = [fe.result(t, timeout=300) for t in tickets]
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return done, decisions


def _serve_spans(trace_dir):
    """``serve.*`` events of the one trace under ``trace_dir``, as
    (name, start_ns, end_ns, stats, host line)."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1, found
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats), (plane.name, i))
                       for e in line.events if e.name.startswith("serve."))
    return out


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """The traced run: (engine, finished requests, each step's decision,
    the ``serve.*`` spans, each step's TileMap)."""
    cfg, api, params = model
    eng = PagedDecodeEngine(api, params, **KW)
    trace_dir = str(tmp_path_factory.mktemp("serve-trace"))
    tile_maps = []
    tiles = RaggedBatch.tiles

    def recording_tiles(batch, *a, **kw):
        tile_maps.append(tiles(batch, *a, **kw))
        return tile_maps[-1]

    RaggedBatch.tiles = recording_tiles
    try:
        done, decisions = _serve(eng, _prompts(cfg, 5), trace_dir)
    finally:
        RaggedBatch.tiles = tiles
    return eng, done, decisions, _serve_spans(trace_dir), tile_maps


def test_one_step_span_per_engine_step(traced):
    eng, _, decisions, spans, _ = traced
    steps = sorted((s for s in spans if s[0] == trace.STEP),
                   key=lambda s: s[1])
    assert len(steps) == eng.steps == len(decisions) > 0
    assert [s[3]["step_num"] for s in steps] == list(range(eng.steps))
    assert len({s[4] for s in steps}) == 1          # the step thread


def test_every_span_nests_inside_its_step(traced):
    _, _, _, spans, _ = traced
    steps = [s for s in spans if s[0] == trace.STEP]
    names = {s[0] for s in spans}
    assert names == {trace.STEP, trace.SCHED, trace.SCHED_ADMIT,
                     trace.SCHED_RESERVE, trace.KV_COPY, trace.PREPARE,
                     trace.DISPATCH, trace.SAMPLE, trace.EMIT}
    for name, a, b, _, line in spans:
        if name == trace.STEP:
            continue
        assert sum(1 for s in steps if s[4] == line and s[1] <= a
                   and b <= s[2]) == 1, (name, a, b)


def test_one_dispatch_per_step_with_the_decision_args(traced):
    eng, _, decisions, spans, tile_maps = traced
    steps = sorted((s for s in spans if s[0] == trace.STEP),
                   key=lambda s: s[1])
    disp = [s for s in spans if s[0] == trace.DISPATCH]
    budget = KW["n_slots"] * KW["chunk_tokens"]
    assert any(d.n_prefill_tokens for d in decisions)
    assert any(not d.n_prefill_tokens for d in decisions)
    assert len(tile_maps) == len(decisions)
    for st, d, tm in zip(steps, decisions, tile_maps):
        inside = [s for s in disp if st[1] <= s[1] and s[2] <= st[2]]
        assert len(inside) == 1
        args = inside[0][3]
        real = sum(d.num_scheduled.values())
        kv_blocks = tm.kv_blocks(KW["block_size"], eng.window)
        assert 0 < kv_blocks <= tm.meta.shape[1] * eng.max_blocks
        assert args == {"tokens": padded_pow2(max(real, 1), budget),
                        "real_tokens": real,
                        "prefill_tokens": d.n_prefill_tokens,
                        "decode_lanes": d.n_decode,
                        "kv_blocks": kv_blocks}
    assert eng.stats()["kv_block_visits"] == sum(
        tm.kv_blocks(KW["block_size"], eng.window) for tm in tile_maps)


def test_admission_stamp_lies_between_submit_and_first_token(traced):
    _, done, _, _, _ = traced
    for r in done:
        assert 0 < r.t_submit <= r.t_admit <= r.t_first_token, r.request_id


def test_a_preempted_request_keeps_its_first_admission(model):
    cfg, api, params = model
    eng = PagedDecodeEngine(api, params, num_blocks=9, **KW)
    for p in _prompts(cfg, 6, seed=1):
        eng.submit(p, MAX_NEW)
    first = {}
    while eng.has_work():
        eng.step()
        for r in eng.scheduler.running:
            first.setdefault(r.request_id, r.t_admit)
    done = eng.take_finished()
    preempted = [r for r in done if r.n_preemptions > 0]
    assert preempted, "the pool must be tight enough to preempt"
    for r in done:
        assert r.t_admit == first[r.request_id] > 0
        assert r.t_submit <= r.t_admit <= r.t_first_token


def test_outputs_identical_with_the_profiler_on_and_off(model, traced):
    cfg, api, params = model
    _, on, _, _, _ = traced
    off, _ = _serve(PagedDecodeEngine(api, params, **KW), _prompts(cfg, 5))
    assert [r.generated for r in on] == [r.generated for r in off]
    assert all(len(r.generated) == MAX_NEW for r in on)
