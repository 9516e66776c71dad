"""The serving program's own spans in a profiler trace, and the idle time
and step cycles they explain.

The program writes host spans named ``serve.*`` on the profiler's clock
(``src/repro/serving/trace.py``), beside the device's operations.  This
module reads both, with each span's args, from the ``.xplane.pb`` that a
``--trace 1`` run leaves in ``.bench_out/trace-<cell>`` (parsed once per
file), and reduces them over the traced window:

* idle split: each stretch of the window with no device operation
  (``devtrace.gaps``) is cut across the innermost program span open over
  each part of it (the shortest; none: the step thread was between
  steps), in one sweep over sorted intervals;
* step cycles: from one ``serve.engine.dispatch`` span's start to the
  next one's, each with the device's busy time inside it.

Where the trace holds no ``serve.step`` (a program without spans), or
holds it on more than one host thread (slices stepped from a pool), the
readers get nothing: they return None.
"""
from __future__ import annotations

import functools
import heapq
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import devtrace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, ".bench_out")     # where run.py traces
PREFIX = "serve."
STEP = "serve.step"
DISPATCH = "serve.engine.dispatch"

Span = Tuple[str, float, float, dict]     # (name, start s, end s, args)
Interval = Tuple[float, float]


def trace_dir(cell: str) -> str:
    """The directory ``run.py`` traces cell ``cell`` into."""
    return os.path.join(OUT_DIR, "trace-" + cell)


def is_sched(name: Optional[str]) -> bool:
    """Scheduler + KV manager: ``serve.sched*`` and ``serve.kv*``."""
    return name is not None and name.startswith(("serve.sched",
                                                 "serve.kv"))


def is_host(name: Optional[str]) -> bool:
    """Engine host path: ``serve.engine.*`` and ``serve.step``'s self
    time."""
    return name is not None and (name == STEP
                                 or name.startswith("serve.engine."))


# ---------------------------------------------------------------------------
# Reading
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=2)
def _parse(path: str, mtime_ns: int, size: int):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    lines: Dict[tuple, List[Span]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans = [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9,
                          dict(e.stats))
                         for e in line.events if e.name.startswith(PREFIX)]
                if spans:
                    lines[(plane.name, i)] = spans
    return devices, lines


def read(path: str) -> Tuple[Dict[str, List[Interval]],
                             Dict[tuple, List[Span]]]:
    """(device-op intervals per device plane, program spans per host
    thread) of one trace file, times in seconds on the trace's clock."""
    st = os.stat(path)
    return _parse(path, st.st_mtime_ns, st.st_size)


def step_thread(lines: Dict[tuple, List[Span]]) -> Optional[List[Span]]:
    """The spans of the one host thread that ran ``serve.step``; None
    where no thread or several did."""
    stepping = [spans for spans in lines.values()
                if any(s[0] == STEP for s in spans)]
    return stepping[0] if len(stepping) == 1 else None


def load(rec: dict):
    """(window, device-op intervals per device, program spans) of a
    traced run's record; None where it holds nothing to read."""
    tr = rec.get("trace")
    if not tr:
        return None
    try:
        path = devtrace.find_xplane(trace_dir(rec["cell"]))
    except RuntimeError:
        return None
    devices, lines = read(path)
    spans = step_thread(lines)
    if not devices or spans is None:
        return None
    return tr["window"], devices, spans


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------
def innermost(spans: Sequence[Span], t0: float, t1: float
              ) -> List[Tuple[float, float, Optional[str]]]:
    """Pieces ``(a, b, name)`` that tile ``[t0, t1]`` in order, each named
    by the innermost span open over it: the shortest, the later-starting
    on a tie; None where no span is open.  One sweep over the spans'
    sorted bounds, with the open spans in a heap keyed by length."""
    starts = sorted((max(a, t0), min(b, t1), b - a, a, name)
                    for name, a, b, _ in spans if min(b, t1) > max(a, t0))
    cuts = sorted({t0, t1}.union(*((s[0], s[1]) for s in starts)))
    heap: list = []
    out = []
    k = 0
    for x, nxt in zip(cuts, cuts[1:]):
        while k < len(starts) and starts[k][0] <= x:
            a, b, length, a0, name = starts[k]
            heapq.heappush(heap, (length, -a0, k, b, name))
            k += 1
        while heap and heap[0][3] <= x:
            heapq.heappop(heap)
        out.append((x, nxt, heap[0][4] if heap else None))
    return out


def idle_split(ops: Sequence[Interval], spans: Sequence[Span], t0: float,
               t1: float) -> Dict[Optional[str], float]:
    """Idle seconds of ``[t0, t1]`` (no device operation) by the innermost
    program span open over each part of them (None: none open); the
    values sum to the window's idle time."""
    gaps = devtrace.gaps([("", a, b) for a, b in ops], t0, t1)
    pieces = innermost(spans, t0, t1)
    out: Dict[Optional[str], float] = {}
    i = 0
    for ga, gb in gaps:
        while pieces[i][1] <= ga:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < gb:
            a, b, name = pieces[j]
            cut = min(b, gb) - max(a, ga)
            if cut > 0:
                out[name] = out.get(name, 0.0) + cut
            j += 1
    return out


def step_cycles(ops: Sequence[Interval], spans: Sequence[Span], t0: float,
                t1: float) -> List[Tuple[float, float, float, dict]]:
    """``(start, end, device-busy seconds, dispatch args)`` of each cycle
    from one ``serve.engine.dispatch`` span's start to the next one's,
    both inside ``[t0, t1]``."""
    disp = sorted(((a, args) for name, a, _, args in spans
                   if name == DISPATCH and t0 <= a <= t1),
                  key=lambda d: d[0])
    busy = devtrace.union([(max(a, t0), min(b, t1)) for a, b in ops
                           if min(b, t1) > max(a, t0)])
    out = []
    j = 0
    for (a, args), (b, _) in zip(disp, disp[1:]):
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k, s = j, 0.0
        while k < len(busy) and busy[k][0] < b:
            s += min(busy[k][1], b) - max(busy[k][0], a)
            k += 1
        out.append((a, b, s, args))
    return out


# ---------------------------------------------------------------------------
# What the metric readers take
# ---------------------------------------------------------------------------
def split(rec: dict) -> Optional[Dict[Optional[str], float]]:
    """Idle seconds of the traced window by innermost program span,
    averaged over the devices; None where the trace has nothing to
    read."""
    got = load(rec)
    if got is None:
        return None
    (t0, t1), devices, spans = got
    out: Dict[Optional[str], float] = {}
    for ops in devices.values():
        for k, v in idle_split(ops, spans, t0, t1).items():
            out[k] = out.get(k, 0.0) + v / len(devices)
    return out


def idle_share(rec: dict, layer: Callable[[Optional[str]], bool]):
    """Share of the traced window with no device operation while the
    innermost program span open belongs to ``layer``, in percent."""
    by = split(rec)
    if by is None:
        return None
    return 100.0 * sum(v for k, v in by.items() if layer(k)) \
        / rec["trace"]["window_s"]


def decode_step_ms(rec: dict):
    """Mean device-busy ms of the step cycles whose dispatch carried no
    prefill token, averaged over the devices."""
    got = load(rec)
    if got is None:
        return None
    (t0, t1), devices, spans = got
    per_dev = []
    for ops in devices.values():
        busy = [s for _, _, s, args in step_cycles(ops, spans, t0, t1)
                if args.get("prefill_tokens") == 0]
        if busy:
            per_dev.append(sum(busy) / len(busy))
    return 1e3 * sum(per_dev) / len(per_dev) if per_dev else None
