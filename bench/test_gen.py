"""Traffic is a function of the seed, and every seed offers the same
work in the window."""
import json
import os

import numpy as np
import pytest

import gen

TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))


def mix(name):
    return json.load(open(os.path.join(TRAFFIC, name + ".json")))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = gen.load(mix(name), 2**31 + 99, 1000, 10.0)
    b = gen.load(mix(name), 2**31 + 99, 1000, 10.0)
    assert len(a.reqs) == len(b.reqs) > 0
    for x, y in zip(a.reqs, b.reqs):
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


def test_poisson_window_work_is_the_same_for_every_seed():
    def window_sizes(seed):
        t = gen.load(mix("chat-open"), seed, 1000, 10.0)
        w = [r for r in t.reqs if r.in_window]
        assert all(t.window[0] <= r.due < t.window[1] for r in w)
        return (sorted(len(r.prompt) for r in w),
                sorted(r.max_new for r in w))

    assert window_sizes(1)[0] and window_sizes(1) == window_sizes(2)


def test_sessions_schedule_is_the_same_for_every_seed():
    p = mix("complete-sessions")
    a, b = gen.load(p, 1, 1000, 10.0), gen.load(p, 2, 1000, 10.0)
    assert [(r.due, len(r.prompt), r.max_new) for r in a.reqs] == \
        [(r.due, len(r.prompt), r.max_new) for r in b.reqs]
    assert not np.array_equal(a.reqs[0].prompt, b.reqs[0].prompt)


def test_sessions_grow_their_prefix_and_respect_the_cap():
    p = mix("complete-sessions")
    t = gen.load(p, 5, 1000, 10.0)
    by_session = {}
    for r in t.reqs:
        by_session.setdefault(r.tag.split(" turn ")[0], []).append(r)
    for reqs in by_session.values():
        for a, b in zip(reqs, reqs[1:]):
            assert len(b.prompt) >= len(a.prompt)
            assert np.array_equal(b.prompt[:len(a.prompt)], a.prompt)
    assert max(len(r.prompt) for r in t.reqs) <= p["prompt_cap"]


def test_offline_prompts_are_unique_with_session_lengths():
    t = gen.load(mix("batch-unshared"), 5, 1000, 10.0)
    s = gen.load(mix("batch-unshared")["lengths_from"], 5, 1000, 10.0)
    assert t.offline and [len(r.prompt) for r in t.reqs] == \
        [len(r.prompt) for r in s.reqs]
    heads = {tuple(r.prompt[:16]) for r in t.reqs}
    assert len(heads) == len(t.reqs)


def test_every_request_fits_its_configuration():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    confs = {c["name"]: c["file"] for c in spec["configs"]}
    for w in spec["workloads"]:
        cfg = json.load(open(os.path.join(root, confs[w["config"]])))
        t = gen.load(mix(w["traffic"]), 3, cfg["vocab_size"], 30.0)
        longest = max(len(r.prompt) + r.max_new for r in t.reqs)
        assert longest + cfg["engine"]["draft_k"] <= cfg["engine"]["cache_len"]
