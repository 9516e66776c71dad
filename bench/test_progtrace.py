"""The reduction of the program's own spans: the idle split by innermost
span, the step cycles, and the readers built on them, on hand-made
intervals, on random ones against a brute-force split, and on one real
CPU trace."""
import glob
import importlib.util
import os
import random
import threading
import types

import pytest

import devtrace
import progtrace as pt
import run

HERE = os.path.dirname(os.path.abspath(__file__))

# device ops (s): busy 1.0-1.5, 2.0-2.25 and 2.625-2.75 in the window
# 0.5-3.0 (dyadic, so sums are exact)
OPS = [(1.0, 1.25), (1.25, 1.5), (2.0, 2.25), (2.125, 2.1875),
       (2.625, 2.75)]
W = (0.5, 3.0)
# two steps of the program; the first a prefill, the second decode only
SPANS = [
    ("serve.step", 0.75, 1.75, {"step_num": 0}),
    ("serve.sched", 0.75, 0.875, {}),
    ("serve.sched.admit", 0.75, 0.8125, {}),
    ("serve.engine.prepare", 0.875, 1.0, {}),
    ("serve.engine.dispatch", 1.0, 1.0625,
     {"tokens": 64, "real_tokens": 40, "prefill_tokens": 38,
      "decode_lanes": 2}),
    ("serve.engine.sample", 1.0625, 1.5625, {}),
    ("serve.engine.emit", 1.5625, 1.75, {}),
    ("serve.step", 1.75, 2.5, {"step_num": 1}),
    ("serve.sched", 1.75, 1.9375, {}),
    ("serve.kv.copy", 1.9375, 1.96875, {}),
    ("serve.engine.dispatch", 2.0, 2.0625,
     {"tokens": 2, "real_tokens": 2, "prefill_tokens": 0,
      "decode_lanes": 2}),
    ("serve.engine.emit", 2.25, 2.5, {}),
    ("serve.step", 2.5, 2.875, {"step_num": 2}),
    ("serve.engine.dispatch", 2.5625, 2.625,
     {"tokens": 2, "real_tokens": 2, "prefill_tokens": 0,
      "decode_lanes": 2}),
]


def idle_seconds(ops, t0, t1):
    return sum(b - a for a, b in devtrace.gaps([("", a, b) for a, b in ops],
                                               t0, t1))


def test_idle_split_by_innermost_span():
    by = pt.idle_split(OPS, SPANS, *W)
    # gaps 0.5-1.0, 1.5-2.0, 2.25-2.625 and 2.75-3.0
    assert by == {None: 0.25 + 0.125,
                  "serve.sched.admit": 0.0625,
                  "serve.sched": 0.0625 + 0.1875,
                  "serve.kv.copy": 0.03125,
                  "serve.engine.prepare": 0.125,
                  "serve.engine.sample": 0.0625,
                  "serve.engine.emit": 0.1875 + 0.25,
                  "serve.engine.dispatch": 0.0625,
                  "serve.step": 0.03125 + 0.0625 + 0.125}
    assert sum(by.values()) == idle_seconds(OPS, *W) == 1.625
    # the harness's reduction of the same device ops leaves the same
    # idle time
    r = devtrace.reduce({"/device:TPU:0": [("op", a, b) for a, b in OPS]},
                        [("bench.window", *W)], "none")
    assert sum(by.values()) == r["window_s"] - r["busy_s"]


def brute_split(ops, spans, t0, t1):
    """Cut the window at every bound; name each piece by the span that
    :func:`progtrace.innermost` defines, found by a search at its
    midpoint."""
    cuts = sorted({t0, t1} | {x for a, b in ops for x in (a, b)
                              if t0 < x < t1}
                  | {x for _, a, b, _ in spans for x in (a, b)
                     if t0 < x < t1})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        m = (a + b) / 2
        if any(x <= m < y for x, y in ops):
            continue
        open_ = [s for s in spans if s[1] <= m < s[2]]
        name = min(open_, key=lambda s: (s[2] - s[1], -s[1]))[0] \
            if open_ else None
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def random_nest(rng, a, b, depth, out):
    """Properly nested spans inside ``[a, b]``, as one host thread
    writes them: up to three disjoint children a level."""
    if not depth:
        return
    cuts = sorted(rng.uniform(a, b) for _ in range(2 * rng.randint(1, 3)))
    for s, e in zip(cuts[::2], cuts[1::2]):
        out.append((f"serve.{depth}.{len(out)}", s, e, {}))
        random_nest(rng, s, e, depth - 1, out)


@pytest.mark.parametrize("seed", range(20))
def test_sweep_agrees_with_brute_force(seed):
    rng = random.Random(seed)
    ops = []
    for _ in range(rng.randint(0, 30)):
        a = rng.uniform(-1, 11)
        ops.append((a, a + rng.expovariate(2.0)))
    spans = []
    random_nest(rng, -0.5, 10.5, 3, spans)
    if seed % 2:      # overlapping, unnested spans too: shortest wins
        for _ in range(10):
            a = rng.uniform(-1, 11)
            spans.append((f"serve.x{_}", a, a + rng.uniform(0, 3), {}))
    got = pt.idle_split(ops, spans, 0.0, 10.0)
    want = brute_split(ops, spans, 0.0, 10.0)
    assert len(want) > 3
    for k in got.keys() | want.keys():
        assert got.get(k, 0.0) == pytest.approx(want.get(k, 0.0), abs=1e-9)
    assert sum(got.values()) == pytest.approx(idle_seconds(ops, 0.0, 10.0),
                                              abs=1e-9)


def test_innermost_tiles_the_window():
    pieces = pt.innermost(SPANS, *W)
    assert pieces[0][0] == W[0] and pieces[-1][1] == W[1]
    assert all(p[1] == q[0] for p, q in zip(pieces, pieces[1:]))
    assert pt.innermost([], *W) == [(W[0], W[1], None)]


def test_step_cycles_and_the_decode_only_filter():
    cyc = pt.step_cycles(OPS, SPANS, *W)
    # cycles 1.0-2.0 (busy 1.0-1.5) and 2.0-2.5625 (busy 2.0-2.25); the
    # last dispatch opens no cycle that closes inside the window
    assert [(a, b) for a, b, _, _ in cyc] == [(1.0, 2.0), (2.0, 2.5625)]
    assert [s for _, _, s, _ in cyc] == [0.5, 0.25]
    assert [args["prefill_tokens"] for *_, args in cyc] == [38, 0]


def fake_rec(monkeypatch, devices, spans, window=W):
    monkeypatch.setattr(pt, "load",
                        lambda rec: (window, devices, spans))
    return {"cell": "tiny", "trace": {"window": window,
                                      "window_s": window[1] - window[0]}}


def test_the_readers_on_hand_made_intervals(monkeypatch):
    rec = fake_rec(monkeypatch, {"/device:TPU:0": OPS}, SPANS)
    sched = pt.idle_share(rec, pt.is_sched)
    host = pt.idle_share(rec, pt.is_host)
    assert sched == 100 * (0.0625 + 0.25 + 0.03125) / 2.5
    assert host == 100 * (0.125 + 0.0625 + 0.4375 + 0.0625 + 0.21875) / 2.5
    outside = 100 * 0.375 / 2.5
    assert sched + host + outside == 100 * idle_seconds(OPS, *W) / 2.5
    assert pt.decode_step_ms(rec) == 250.0          # the decode-only cycle
    # two devices: the split and the cycles averaged over them
    rec = fake_rec(monkeypatch, {"/device:TPU:0": OPS,
                                 "/device:TPU:1": [(0.5, 3.0)]}, SPANS)
    assert pt.idle_share(rec, pt.is_sched) == sched / 2
    assert pt.decode_step_ms(rec) == (250.0 + 562.5) / 2


def test_readers_without_program_spans_read_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(pt, "OUT_DIR", str(tmp_path))
    rec = {"cell": "tiny", "trace": {"window": W, "window_s": 2.5}}
    assert pt.load(rec) is None                 # no trace file at all
    assert pt.load({"cell": "tiny", "trace": None}) is None
    for name in ("sched_idle_share.open", "host_idle_share.open",
                 "decode_step_ms.open"):
        assert reader(name)(rec) is None
    assert pt.step_thread({("/host:CPU", 0): SPANS[:1],
                           ("/host:CPU", 1): SPANS[7:8]}) is None
    assert pt.step_thread({("/host:CPU", 0): SPANS[1:2]}) is None


def test_trace_dir_is_where_run_traces():
    assert pt.trace_dir("m7-chat-open") == os.path.join(
        run.OUT_DIR, "trace-m7-chat-open")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_queue_wait_reads_the_admission_stamp():
    def track(t_submit, t_admit=None):
        r = types.SimpleNamespace(t_submit=t_submit)
        if t_admit is not None:
            r.t_admit = t_admit
        return types.SimpleNamespace(
            done=True, ticket=types.SimpleNamespace(result=r))

    read = reader("queue_wait_p95_ms")
    window = [track(1.0, 1.0 + i / 1000) for i in range(20)]
    assert read({"window": window}) == pytest.approx(18.0)
    # a program that stamps no admission: nothing to read
    assert read({"window": [track(1.0) for _ in range(5)]}) is None
    # shed before admission: left out
    assert read({"window": [track(1.0, 0.0), track(1.0, 1.002)]}) \
        == pytest.approx(2.0)


def test_span_args_from_a_real_cpu_trace(tmp_path):
    import jax
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    f = jax.jit(lambda x: x * 2 + 1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for n in range(3):
            with StepTraceAnnotation("serve.step", step_num=n):
                with TraceAnnotation("serve.engine.dispatch", tokens=64,
                                     real_tokens=40, prefill_tokens=n,
                                     decode_lanes=2):
                    y = f(jax.numpy.ones(8))
                with TraceAnnotation("serve.engine.sample"):
                    y.block_until_ready()
        with TraceAnnotation("bench.other"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = devtrace.find_xplane(str(tmp_path))
    devices, lines = pt.read(path)
    assert pt.read(path) is pt.read(path)             # parsed once
    spans = pt.step_thread(lines)
    assert [s[3]["step_num"] for s in spans if s[0] == "serve.step"] \
        == [0, 1, 2]
    disp = [s[3] for s in spans if s[0] == "serve.engine.dispatch"]
    assert disp == [{"tokens": 64, "real_tokens": 40, "prefill_tokens": n,
                     "decode_lanes": 2} for n in range(3)]
    assert all(s[0].startswith("serve.") for s in spans)
    assert all(s[1] <= s[2] for s in spans)

    # a second thread that steps: the reader cannot tell the steps apart
    jax.profiler.start_trace(str(tmp_path / "two"))
    try:
        def step(n):
            with StepTraceAnnotation("serve.step", step_num=n):
                pass
        step(0)
        t = threading.Thread(target=step, args=(1,))
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    _, lines = pt.read(glob.glob(str(tmp_path / "two" / "plugins" /
                                     "profile" / "*" / "*.xplane.pb"))[0])
    assert pt.step_thread(lines) is None
