#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a model configuration (``bench/configs/<config>.json``) and
a traffic mix (``bench/traffic/<mix>.json``); both are found by name, as
are the metric readers (``bench/metrics/<metric>.py``).  The program's
``ArchConfig`` takes every key of the configuration file that is not the
harness's own (:func:`arch_config`).  One run is one process with one
engine on the chips its cell asks for:

1. set-up: weights made on the device from ``--seed`` by the
   configuration's reference module (on several chips, made already
   sharded over the program's ``(1, chips)`` serving mesh), the program's
   ``DecodeEngine`` built on them (with that mesh), every token bucket of
   its jitted step compiled, and the traffic's ramp offered; ``setup_s``
   runs from the start of this script to the window's opening;
2. the window: ``--seconds`` of the mix through ``AsyncEngine``, the
   program's streaming front end, with the profiler on when ``--trace 1``;
3. the drain: load keeps arriving until every request due in the window
   has finished (open loop), or the feeder stops (offline);
4. the check: the program's state is freed, and a sample of finished
   requests drawn from the seed is run through the float32 reference;
   ``correct`` holds when every served token's logit lies within the
   configuration's limit of the reference's best.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the check compared with
its limit.  The same numbers end standard error.  Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 1.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import typing  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import gen  # noqa: E402
from measure import percentile  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# the TPU runtime's own logs stay inside the checkout too
os.environ.setdefault("TPU_LOG_DIR", os.path.join(OUT_DIR, "tpu_logs"))
KERNEL = "paged_attention"          # device events of the attention kernel
TRACE_MAX_S = 10.0                  # the traced part of a --trace 1 window


class BenchError(RuntimeError):
    """A run that cannot report: no chip, a missing file, a bad cell."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell, found by name
# ---------------------------------------------------------------------------
def read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise BenchError(f"cannot read {path}: {e}") from e


def load_cell(name: str, root: str = ROOT) -> dict:
    """The workload ``name`` with its configuration, traffic and metric
    entries, every file resolved by name."""
    spec = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    conf = confs[cell["config"]]
    cfg = read_json(os.path.join(root, conf["file"]))
    traffic = read_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])

    return {"name": name, "chips": cell["chips"], "cfg": cfg,
            "traffic": traffic,
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    """``read(record)`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no reader for metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_module(name: str):
    if not name.replace("_", "").isalnum():
        raise BenchError(f"bad reference name {name!r}")
    return importlib.import_module(f"reference.{name}")


def peaks_for(kind: str) -> dict:
    table = read_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise BenchError(f"device {kind!r} is not in bench/peaks.json")
    return table[kind]


def program_on_path() -> None:
    """Make the serving program (``src/`` of this checkout) importable."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def seed_key(jax, seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    lo, hi = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


# ---------------------------------------------------------------------------
# Compile clock
# ---------------------------------------------------------------------------
class CompileClock:
    """Backend compiles and persistent-cache hits, with seconds."""

    def __init__(self, jax):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self):
        return self.compiles, self.cache_hits


# ---------------------------------------------------------------------------
# Harness spans and per-step counts around the calls into the engine
# ---------------------------------------------------------------------------
class Recorder:
    """Wraps ``engine.step``, ``scheduler.schedule``, the jitted model
    step and the row gather of one engine in harness spans.  Records the
    host time of every step and, for each, the segments the scheduler
    sent (first position, tokens, rows whose logits are read), from which
    :mod:`counts` gives its FLOPs and bytes."""

    def __init__(self, jax, eng, tracing: bool):
        self.eng = eng
        self.steps: List[tuple] = []       # (t0, t1, segments or None)
        self.snapshots: Dict[str, dict] = {}
        self._want: List[tuple] = []       # (label, time asked)
        self._segs = None
        ann = jax.profiler.TraceAnnotation if tracing else None

        def span(name, fn):
            if ann is None:
                return fn

            def wrapped(*a, **k):
                with ann("bench." + name):
                    return fn(*a, **k)
            return wrapped

        step, sched = eng.step, eng.scheduler.schedule

        def schedule():
            d = sched()
            try:
                segs = []
                for r in d.scheduled:
                    n = d.num_scheduled[r.request_id]
                    drafts = len(d.drafts.get(r.request_id, ()))
                    emits = r.cursor + n - drafts == len(r.feed)
                    segs.append((r.cursor, n, 1 + drafts if emits else 0))
            except AttributeError:
                segs = None        # a decision this reader cannot count
            self._segs = segs
            return d

        def timed_step():
            while self._want:
                self.snapshots[self._want.pop()[0]] = self.stats()
            t0 = time.perf_counter()
            d = step()
            self.steps.append((t0, time.perf_counter(), self._segs))
            return d

        eng.scheduler.schedule = span("schedule", schedule)
        for attr, name in (("_step", "dispatch"), ("_gather_greedy",
                                                    "gather")):
            if hasattr(eng, attr):
                setattr(eng, attr, span(name, getattr(eng, attr)))
        eng.step = span("step", timed_step)

    def stats(self) -> dict:
        s = self.eng.stats()
        s["t"] = time.perf_counter()
        return s

    def snapshot(self, label: str) -> None:
        """Take ``eng.stats()`` on the step thread before its next step
        (the counters cannot move before then)."""
        self._want.append((label, time.perf_counter()))

    def settle(self) -> None:
        """Once the step thread has stopped: the snapshots still wanted
        are what the engine held when they were asked for."""
        while self._want:
            label, t = self._want.pop()
            self.snapshots[label] = dict(self.stats(), t=t)


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b
            if isinstance(b.get(k), (int, float)) and k in a}


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------
# keys of a configuration file that the harness reads itself; every other
# key is a field of the program's ArchConfig
HARNESS_KEYS = frozenset((
    "paper", "reference", "published", "reduced", "assumed", "deployment",
    "weight_dtype", "compute", "engine", "correct", "norm_eps", "mesh"))


def _build_dataclass(cls, values: dict, where: str):
    """``cls(**values)``, a field whose type is a dataclass built from its
    dict the same way; a key that is no field is a :class:`BenchError`."""
    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(values) - names)
    if unknown:
        raise BenchError(f"{where}: {unknown} name no field of the "
                         f"program's {cls.__name__}")
    kw = {}
    for k, v in values.items():
        sub = [t for t in typing.get_args(hints[k]) + (hints[k],)
               if dataclasses.is_dataclass(t)]
        if sub and isinstance(v, dict):
            v = _build_dataclass(sub[0], v, f"{where}.{k}")
        kw[k] = v
    try:
        return cls(**kw)
    except TypeError as e:
        raise BenchError(f"{where}: {e}") from e


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for a configuration file: every key
    that is not the harness's own, sub-configurations (``moe``, ``ssm``,
    ``xlstm``, ``frontend``) built from their dicts."""
    program_on_path()
    from repro.configs.base import ArchConfig
    model = {k: v for k, v in cfg.items() if k not in HARNESS_KEYS}
    return _build_dataclass(ArchConfig, model, cfg.get("name", "config"))


def serving_mesh(devices, cfg: dict):
    """None for a cell on one chip; on several, the program's serving mesh
    over them: ``(1, chips)`` on ``("data", "model")``, every layer
    tensor-parallel.  The configuration's ``mesh`` names that degree."""
    if len(devices) == 1:
        return None
    if cfg.get("mesh") != {"model": len(devices)}:
        raise BenchError(f"a cell on {len(devices)} chips needs the "
                         f"configuration's mesh to be {{'model': "
                         f"{len(devices)}}}, not {cfg.get('mesh')}")
    from jax.sharding import Mesh
    return Mesh(np.array(devices).reshape(1, len(devices)),
                ("data", "model"))


def make_weights(jax, ref, cfg: dict, key, dtype, mesh):
    """The reference module's weights from ``key``.  On a mesh they are
    made already sharded as the program serves them
    (``serving_param_specs``), so no device ever holds them whole."""
    if mesh is None:
        return ref.make_params(cfg, key, dtype)
    from repro.launch import sharding as shlib
    from repro.launch.mesh import mesh_axis_sizes

    def make(k):
        return ref.make_params(cfg, k, dtype)

    specs = shlib.serving_param_specs(jax.eval_shape(make, key),
                                      mesh_axis_sizes(mesh))
    return jax.jit(make, out_shardings=shlib.to_named(specs, mesh))(key)


def check_layout(jax, api, params) -> None:
    """The weights the reference module made have exactly the leaves the
    program's own initializer would make."""
    want = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    got_s = jax.tree.map(lambda a: tuple(a.shape), params)
    want_s = jax.tree.map(lambda a: tuple(a.shape), want)
    if jax.tree.structure(got_s) != jax.tree.structure(want_s) or \
            jax.tree.leaves(got_s) != jax.tree.leaves(want_s):
        raise BenchError("the reference's weight layout differs from the "
                         "program's parameters")


def pow2_upto(n: int) -> List[int]:
    out, t = [], 1
    while t <= n:
        out.append(t)
        t *= 2
    return out


def warm_buckets(eng, cfg: dict, vocab: int) -> None:
    """One engine step of every pow2 token bucket the budget allows,
    through the engine itself (prefill of fresh random prompts), then a
    drain: every shape of the jitted step and of the row gather behind
    it that this cell can send.  Then the engine's block copies
    (copy-on-write, a power-of-two count of pairs) and its read of one
    block for the host tier, on the null block, where the engine has
    them: what a prefix hit and an eviction run inside the window."""
    import jax
    e = cfg["engine"]
    rng = np.random.default_rng(12345)
    for T in pow2_upto(e["token_budget"]):
        k = max(1, T // e["chunk_tokens"])
        for _ in range(k):
            eng.submit(rng.integers(0, vocab, min(T, e["chunk_tokens"]),
                                    dtype=np.int32), 2)
        eng.step()
        eng.run_until_drained()
    cow, put = getattr(eng, "_cow", None), getattr(eng, "_put", None)
    if cow is not None and put is not None:
        for n in (1, 2, 4, 8):
            zero = put(np.zeros((n,), np.int32))
            eng.cache = cow(eng.cache, zero, zero)
    read = getattr(eng, "_read_block_payload", None)
    if read is not None:
        read(0)
    jax.block_until_ready(eng.cache)


class CompileLog(logging.Handler):
    """Names of the programs JAX compiles once it is switched on (after
    the warm-up: whatever it catches compiled inside the run)."""

    def __init__(self, jax):
        super().__init__(logging.WARNING)
        self.names: Dict[str, int] = {}
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)

    def emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of "):
            name = msg[len("Finished XLA compilation of "):].split(" in ")[0]
            self.names[name] = self.names.get(name, 0) + 1


# ---------------------------------------------------------------------------
# Load
# ---------------------------------------------------------------------------
class Track:
    """One request's stream, as its client sees it."""

    __slots__ = ("req", "due", "first", "last", "n", "ticket", "sent")

    def __init__(self, req, due: float):
        self.req, self.due = req, due
        self.first = self.last = 0.0
        self.n = 0
        self.ticket = None
        self.sent = 0.0

    def sink(self, tok, finished) -> None:
        if tok is None:
            return
        now = time.perf_counter()
        if self.n == 0:
            self.first = now
        self.last = now
        self.n += 1

    @property
    def done(self) -> bool:
        return self.ticket is not None and self.ticket.done.is_set()


def sleep_until(t: float) -> None:
    while True:
        dt = t - time.perf_counter()
        if dt <= 0:
            return
        time.sleep(min(dt, 0.05))


def drive_open(fe, traffic, rec: Recorder, trace_cb) -> dict:
    """Offer the schedule; measure the requests due in the window."""
    t0 = time.perf_counter()
    w_open, w_close = (t0 + traffic.window[0], t0 + traffic.window[1])
    deadline = w_close + traffic.tail
    tracks = [Track(r, t0 + r.due) for r in traffic.reqs]
    window = [t for t in tracks if t.req.in_window]
    opened = closed = False
    pending = list(window)
    for t in tracks:
        if not opened and t.due >= w_open:
            sleep_until(w_open)
            rec.snapshot("open")
            trace_cb("start")
            opened = True
        if not closed and t.due >= w_close:
            sleep_until(w_close)
            rec.snapshot("close")
            closed = True
        sleep_until(t.due)
        if closed:
            pending = [p for p in pending if not p.done]
            if not pending or time.perf_counter() >= deadline:
                break
        t.sent = time.perf_counter()
        t.ticket = fe.submit(t.req.prompt, t.req.max_new, sink=t.sink)
        trace_cb("poll")
    if not opened:                  # a mix with nothing due after the edges
        sleep_until(w_open)
        rec.snapshot("open")
        trace_cb("start")
    if not closed:
        sleep_until(w_close)
        rec.snapshot("close")
    while any(not p.done for p in window) and \
            time.perf_counter() < deadline:
        time.sleep(0.05)
        trace_cb("poll")
    t_end = time.perf_counter()
    return {"t0": t0, "open": w_open, "close": w_close, "end": t_end,
            "tracks": tracks, "window_tracks": window}


def drive_offline(fe, traffic, rec: Recorder, trace_cb, lanes: int) -> dict:
    """Keep ``backlog`` x lanes requests waiting beyond the running ones;
    count the tokens emitted in the window."""
    t0 = time.perf_counter()
    w_open, w_close = t0 + traffic.window[0], t0 + traffic.window[1]
    target = int(math.ceil((1 + traffic.backlog) * lanes))
    feed = iter(traffic.reqs)
    tracks: List[Track] = []
    live: List[Track] = []
    opened = False
    while True:
        now = time.perf_counter()
        if not opened and now >= w_open:
            rec.snapshot("open")
            trace_cb("start")
            opened = True
        if now >= w_close:
            rec.snapshot("close")
            break
        live = [t for t in live if not t.done]
        while len(live) < target:
            r = next(feed, None)
            if r is None:
                raise BenchError("the offline backlog ran dry; the mix "
                                 "needs more requests")
            t = Track(r, now)
            t.sent = now
            t.ticket = fe.submit(r.prompt, r.max_new, sink=t.sink)
            tracks.append(t)
            live.append(t)
        trace_cb("poll")
        time.sleep(0.01)
    # let the close snapshot land, then stop
    for _ in range(200):
        if "close" in rec.snapshots:
            break
        time.sleep(0.01)
    return {"t0": t0, "open": w_open, "close": w_close,
            "end": time.perf_counter(), "tracks": tracks,
            "window_tracks": [t for t in tracks
                              if t.done and w_open <= t.last < w_close]}


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
def sample_requests(window: List[Track], seed: int, target_tokens: int):
    """Finished requests to check: the longest (prompt + output), the one
    with the longest prompt, then others drawn from the seed until
    ``target_tokens`` served tokens are in the sample."""
    done = [t for t in window if t.done and t.ticket.result is not None
            and not t.ticket.result.cancelled and not t.ticket.result.shed]
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    order = sorted(done, key=lambda t: -(len(t.req.prompt) + t.req.max_new))
    pick = [order[0]]
    longest_prompt = max(done, key=lambda t: len(t.req.prompt))
    if longest_prompt is not pick[0]:
        pick.append(longest_prompt)
    rest = [t for t in done if t not in pick]
    for i in rng.permutation(len(rest)):
        if sum(len(t.ticket.result.generated) for t in pick) >= target_tokens:
            break
        pick.append(rest[i])
    return pick


def served_gaps(ref, params, cfg: dict, tracks, controls=()):
    """For each sampled request: how far below the reference's best logit
    each served token lies, and for each mode in ``controls`` the gap of
    the token that reference, in that precision, puts first."""
    out = []
    for t in tracks:
        prompt = np.asarray(t.req.prompt, np.int32)
        gen_ = np.asarray(t.ticket.result.generated, np.int32)
        seq = np.concatenate([prompt, gen_[:-1]])
        rows = np.arange(len(prompt) - 1, len(seq))
        lg = ref.logits(params, cfg, seq, rows, "float32")
        best = lg.max(axis=-1)
        served = best - lg[np.arange(len(rows)), gen_]
        item = {"served": served}
        for mode in controls:
            pick = ref.logits(params, cfg, seq, rows, mode).argmax(axis=-1)
            item[mode] = best - lg[np.arange(len(rows)), pick]
        out.append(item)
    return out


# ---------------------------------------------------------------------------
# One run: set-up, drive, check
# ---------------------------------------------------------------------------
class Bench:
    """What set-up builds: the program's engine on the seed's weights,
    every shape the cell can send compiled."""

    def __init__(self, cell: dict, seed: int, *, require_tpu: bool = True,
                 use_cache: bool = True, engine_hook=None):
        program_on_path()
        try:
            import jax
            from repro.models import build_model
            from repro.serving import DecodeEngine
        except ImportError as e:
            raise BenchError(f"the serving program is not in this "
                             f"checkout ({e})") from e
        self.jax, self.cell, self.seed = jax, cell, seed
        devices = jax.devices()
        if require_tpu and devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX's first device is "
                             f"{devices[0].platform}")
        if len(devices) < cell["chips"]:
            raise BenchError(f"the cell needs {cell['chips']} chips; "
                             f"JAX sees {len(devices)}")
        self.devices = devices[:cell["chips"]]
        self.dev = self.devices[0]
        self.peaks = peaks_for(self.dev.device_kind) if require_tpu else None
        if use_cache:
            # the directory JAX_COMPILATION_CACHE_DIR names, where it is
            # set; otherwise a fixed one inside the checkout (the path is
            # part of the cache's key).  Every program is kept, the
            # engine's many small gathers too, so a run after the first
            # loads them instead of compiling.
            os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
            from repro.launch.compile_cache import enable_compile_cache
            enable_compile_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
        self.clock = CompileClock(jax)
        self.cfg = cfg = cell["cfg"]
        e = cfg["engine"]
        self.ref = reference_module(cfg["reference"])
        self.api = build_model(arch_config(cfg))
        dtype = {"float32": jax.numpy.float32,
                 "bfloat16": jax.numpy.bfloat16}[cfg["weight_dtype"]]
        self.mesh = serving_mesh(self.devices, cfg)
        t = time.perf_counter()
        self.params = make_weights(jax, self.ref, cfg, seed_key(jax, seed),
                                   dtype, self.mesh)
        jax.block_until_ready(self.params)
        check_layout(jax, self.api, self.params)
        log(f"weights: {sum(x.size for x in jax.tree.leaves(self.params))}"
            f" {cfg['weight_dtype']} in {time.perf_counter() - t:.3f} s"
            f" on {len(self.devices)} device(s)")
        self.eng = DecodeEngine(
            self.api, self.params, n_slots=e["n_slots"],
            cache_len=e["cache_len"], block_size=e["block_size"],
            num_blocks=e["num_blocks"], chunk_tokens=e["chunk_tokens"],
            token_budget=e["token_budget"], draft_k=e["draft_k"],
            window=cfg["sliding_window"], mesh=self.mesh)
        if engine_hook is not None:
            engine_hook(self.eng)
        t = time.perf_counter()
        warm_buckets(self.eng, cfg, cfg["vocab_size"])
        self.warm = self.clock.snap()
        self.compile_log = CompileLog(jax)
        log(f"warm-up: {len(pow2_upto(e['token_budget']))} step buckets in "
            f"{time.perf_counter() - t:.3f} s; compiles {self.warm[0]} "
            f"({self.clock.seconds:.3f} s), persistent-cache hits "
            f"{self.warm[1]}")

    def free(self) -> None:
        """Drop the engine (its KV pool and compiled steps' buffers)."""
        self.eng = None
        gc.collect()


def drive(b: Bench, traffic, seconds: float, tracing: bool) -> dict:
    """Offer ``traffic`` to the engine through ``AsyncEngine``; returns
    the run record the metric readers take."""
    from repro.serving import AsyncEngine
    jax = b.jax
    e = b.cfg["engine"]
    trace_dir = os.path.join(OUT_DIR, "trace-" + b.cell["name"])
    ts = {"on": False}

    def trace_cb(what):
        if not tracing:
            return
        if what == "start":
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            ts["ann"] = jax.profiler.TraceAnnotation("bench.window")
            ts["ann"].__enter__()
            ts.update(on=True, t0=time.perf_counter())
        elif ts["on"] and (what == "stop" or time.perf_counter() - ts["t0"]
                           >= min(TRACE_MAX_S, seconds)):
            ts["ann"].__exit__(None, None, None)
            ts["t1"] = time.perf_counter()
            jax.profiler.stop_trace()
            ts["on"] = False

    rec = Recorder(jax, b.eng, tracing)
    with AsyncEngine(b.eng) as fe:
        if traffic.offline:
            run = drive_offline(fe, traffic, rec, trace_cb, e["n_slots"])
        else:
            run = drive_open(fe, traffic, rec, trace_cb)
    rec.settle()
    trace_cb("stop")
    counters = delta(rec.snapshots["open"], rec.snapshots["close"])
    steps = [s for s in rec.steps if run["open"] <= s[0] < run["close"]]
    c = b.clock.snap()
    log(f"window: {len(steps)} steps; compiles after warm-up "
        f"{c[0] - b.warm[0]}, persistent-cache hits after warm-up "
        f"{c[1] - b.warm[1]}; programs compiled after warm-up: "
        f"{dict(sorted(b.compile_log.names.items(), key=lambda kv: -kv[1])[:12])}")
    if not traffic.offline:
        late = [t.sent - t.due for t in run["tracks"] if t.sent]
        log(f"generator lateness: p50 {percentile(late, 50) * 1e3:.3f} ms,"
            f" p99 {percentile(late, 99) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms over {len(late)} requests")
    rec_ = {"cfg": b.cfg, "cell": b.cell["name"], "seconds": seconds,
            "run": run, "window": run["window_tracks"],
            "counters": counters, "steps": steps, "peaks": b.peaks,
            "chips": len(b.devices),
            "trace": None, "trace_steps": [],
            "kv_bytes": 2, "act_bytes": 2, "offline": traffic.offline}
    if tracing:
        from devtrace import find_xplane, read_xplane, reduce
        devs, spans = read_xplane(find_xplane(trace_dir))
        rec_["trace"] = tr = reduce(devs, spans, KERNEL)
        rec_["trace_steps"] = [s for s in steps
                               if ts["t0"] <= s[0] < ts["t1"]]
        log(f"trace: window {tr['window_s']:.3f} s, busy "
            f"{tr['busy_s']:.3f} s, {KERNEL} {tr['kernel_s']:.3f} s in "
            f"{tr['kernel_events']} events over "
            f"{len(rec_['trace_steps'])} steps; idle by host span "
            f"{ {k: round(v, 4) for k, v in tr['idle_by_span'].items()} }")
    return rec_


def outcome(rec: dict) -> tuple:
    """(attempted, failed): open loop, the requests due in the window, and
    those of them shed, cancelled or still without a first token at the
    end of the drain; offline, the requests sent before the close, and
    those shed or cancelled."""
    def bad(t):
        r = t.ticket.result if t.done else None
        return r is not None and (r.cancelled or r.shed)

    run = rec["run"]
    if rec["offline"]:
        sent = [t for t in run["tracks"] if t.sent < run["close"]]
        return len(sent), len([t for t in sent if bad(t)])
    w = rec["window"]
    return len(w), len([t for t in w if t.n == 0 or bad(t)])


def gap_stats(arrays) -> dict:
    """Widest gap, mean gap and share of positions off the reference's
    first choice, over the gaps of every sampled token."""
    g = np.concatenate(arrays) if arrays else np.zeros((0,))
    if not len(g):
        return {"max": math.inf, "mean": math.inf, "off": math.inf, "n": 0}
    return {"max": float(g.max()), "mean": float(g.mean()),
            "off": float((g > 0).mean()), "n": int(len(g))}


def check(b: Bench, rec: dict, controls=()) -> dict:
    """Run the sampled requests through the reference, after the engine
    is freed.  Returns the compared numbers, the served tokens' gap
    statistics, and those of each control mode in ``controls``."""
    cfg = b.cfg
    sample = sample_requests(rec["window"], b.seed,
                             cfg["correct"]["sample_tokens"])
    b.free()
    t = time.perf_counter()
    gaps = served_gaps(b.ref, b.params, cfg, sample, controls)
    st = gap_stats([g["served"] for g in gaps])
    short = sum(1 for tr in sample
                if len(tr.ticket.result.generated) != tr.req.max_new)
    log(f"check: {len(sample)} requests, {st['n']} served tokens against "
        f"the float32 reference in {time.perf_counter() - t:.3f} s; widest "
        f"gap {st['max']}, mean gap {st['mean']}, share off the "
        f"reference's first choice {st['off']}")
    out = {"served": st, "compared": {
        "mean_logit_gap": {"value": st["mean"],
                           "limit": cfg["correct"]["mean_logit_gap"]},
        "sampled_tokens": {"value": st["n"], "limit": 1},
        "short_outputs": {"value": short, "limit": 0}}}
    for mode in controls:
        out[mode] = gap_stats([g[mode] for g in gaps])
    return out


def passes(compared: dict) -> bool:
    """``mean_logit_gap`` and ``short_outputs`` at or under their limits,
    ``sampled_tokens`` at or over its floor."""
    c = compared
    return (c["mean_logit_gap"]["value"] <= c["mean_logit_gap"]["limit"]
            and c["short_outputs"]["value"] <= c["short_outputs"]["limit"]
            and c["sampled_tokens"]["value"] >= c["sampled_tokens"]["limit"])


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, require_tpu: bool = True,
             use_cache: bool = True, engine_hook=None) -> dict:
    """Set up, drive, check; returns the result object (not printed)."""
    b = Bench(cell, seed, require_tpu=require_tpu, use_cache=use_cache,
              engine_hook=engine_hook)
    traffic = gen.load(cell["traffic"], seed, b.cfg["vocab_size"], seconds)
    rec = drive(b, traffic, seconds, trace)
    rec["setup_s"] = rec["run"]["open"] - t_start
    attempted, failed = outcome(rec)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in b.devices)
    device = {"platform": b.dev.platform, "kind": b.dev.device_kind,
              "count": len(b.devices), "memory_peak_bytes": peak}
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    metrics = {}
    for m in wanted:
        v = metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        tr = rec["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    chk = check(b, rec)
    result["correct"] = passes(chk["compared"])
    result["compared"] = chk["compared"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        log("bench: FAIL: --seed must be >= 0 and --seconds > 0")
        return 2
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except BenchError as e:
        log(f"bench: FAIL: {e}")
        return 1
    for name, c in result["compared"].items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
