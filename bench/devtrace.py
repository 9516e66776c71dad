"""Reduce a profiler trace to device busy time, kernel time and idle gaps.

The harness wraps the traced part of the window in the host span
``bench.window`` and its calls into the engine in host spans of its own
(``bench.step``, ``bench.schedule``, ``bench.dispatch``, ...); the
profiler puts them on the same clock as the device's operations.  This
module reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain tuples, and reduces those:

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to the
  window and averaged over the devices;
* kernel time: the summed durations of the events whose name holds a
  given substring;
* idle gaps: the stretches of the window with no device operation, each
  named by the innermost harness span open at its midpoint (``idle``
  where none is: the host was waiting for requests).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]        # (name, start s, end s)

# control-flow ops span the ops they run: left out of the top-ops list
_CONTROL = re.compile(r"^(while|conditional|call)(\.\d+)?$")

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def op_name(event_name: str) -> str:
    """The HLO op's own name from a device event's name, which on a TPU
    is the whole instruction (``%name = type op(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(device ops per device plane, harness host spans) from one trace
    file, times in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((op_name(e.name), e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9)
                               for e in line.events)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` a trace directory holds."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


def window_of(spans: Sequence[Event]) -> Tuple[float, float]:
    """Bounds of the harness's ``bench.window`` span."""
    w = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(w) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
    return w[0][1], w[0][2]


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to ``[t0, t1]``; those outside it dropped."""
    out = []
    for name, a, b in events:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out.append((name, a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged, sorted union of intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(ops: Sequence[Event], t0: float, t1: float) -> float:
    """Seconds of ``[t0, t1]`` in which some operation ran."""
    return sum(b - a for a, b in union([(a, b) for _, a, b
                                        in clip(ops, t0, t1)]))


def kernel_seconds(ops: Sequence[Event], t0: float, t1: float,
                   substring: str) -> Tuple[float, int]:
    """(summed duration, count) of the events named with ``substring``."""
    hits = [(a, b) for name, a, b in clip(ops, t0, t1) if substring in name]
    return sum(b - a for a, b in hits), len(hits)


def top_ops(ops: Sequence[Event], t0: float, t1: float,
            n: int = 10) -> List[List]:
    """The ``n`` operation names that took most device time."""
    tot: Dict[str, float] = {}
    for name, a, b in clip(ops, t0, t1):
        if not _CONTROL.match(name):
            tot[name] = tot.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def gaps(ops: Sequence[Event], t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """The stretches of ``[t0, t1]`` with no device operation."""
    out, t = [], t0
    for a, b in union([(a, b) for _, a, b in clip(ops, t0, t1)]):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t1 > t:
        out.append((t, t1))
    return out


def span_at(spans: Sequence[Event], t: float) -> str:
    """Innermost harness span open at ``t`` (the shortest containing it),
    without the ``bench.`` prefix; ``idle`` where none is."""
    best = None
    for name, a, b in spans:
        if name != WINDOW_SPAN and a <= t < b and \
                (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0][len(SPAN_PREFIX):] if best else "idle"


def idle_gaps(ops: Sequence[Event], spans: Sequence[Event], t0: float,
              t1: float, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps, as ``[host span at the gap, seconds]``."""
    g = sorted(gaps(ops, t0, t1), key=lambda ab: ab[0] - ab[1])[:n]
    return [[span_at(spans, (a + b) / 2), b - a] for a, b in g]


def idle_by_span(ops: Sequence[Event], spans: Sequence[Event], t0: float,
                 t1: float) -> Dict[str, float]:
    """Idle seconds summed by the host span open at each gap."""
    out: Dict[str, float] = {}
    for a, b in gaps(ops, t0, t1):
        k = span_at(spans, (a + b) / 2)
        out[k] = out.get(k, 0.0) + (b - a)
    return out


def reduce(devices: Dict[str, List[Event]], spans: Sequence[Event],
           kernel: str) -> Dict:
    """Everything the harness reads from one trace, averaged over the
    devices that ran something."""
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    t0, t1 = window_of(spans)
    n = len(devices)
    busy = sum(busy_seconds(ops, t0, t1) for ops in devices.values()) / n
    ksec = sum(kernel_seconds(ops, t0, t1, kernel)[0]
               for ops in devices.values()) / n
    kcount = sum(kernel_seconds(ops, t0, t1, kernel)[1]
                 for ops in devices.values())
    first = next(iter(devices.values()))
    return {
        "window": (t0, t1),
        "window_s": t1 - t0,
        "busy_s": busy,
        "kernel_s": ksec,
        "kernel_events": kcount,
        "device_ops": top_ops(first, t0, t1),
        "idle_gaps": idle_gaps(first, spans, t0, t1),
        "idle_by_span": idle_by_span(first, spans, t0, t1),
    }
