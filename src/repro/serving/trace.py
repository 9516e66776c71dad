"""Host spans of the serving path, on the profiler's own clock.

The spans are always on: while no trace is active a span costs about a
microsecond, so the profiler is the only switch.  Capture them with
``jax.profiler.trace(dir)`` (or ``start_trace``/``stop_trace``) around
serving; they land in the trace's ``.xplane.pb`` beside the device's
operations, each span's keyword arguments as event stats.  Nothing is
kept in memory here: the profiler buffers the spans and writes them out
when the trace stops.

The prefix of each name is its layer:

=========================  ============================================
Span                       Covers
=========================  ============================================
*engine host path*
``serve.step``             one ``PagedDecodeEngine.step`` (``step_num``);
                           its self time is the rest of the step
``serve.engine.prepare``   batch, tables and metadata built and committed
``serve.engine.dispatch``  the jitted model step's call; args ``tokens``
                           (the bucket), ``real_tokens``,
                           ``prefill_tokens``, ``decode_lanes``, and
                           on the tiled grid ``kv_blocks`` (the
                           kernel's (tile, KV block) visits a layer)
``serve.engine.sample``    row gather, argmax and the pull to the host
``serve.engine.emit``      accept, emit, rewind, shed and the SLO update
*scheduler + KV manager*
``serve.sched``            one ``Scheduler.schedule``
``serve.sched.admit``      admission of waiting requests into lanes
``serve.sched.reserve``    a KV slot reserved for every scheduled token
``serve.kv.copy``          host->device swap-ins and copy-on-write copies
=========================  ============================================
"""
from __future__ import annotations

from jax.profiler import StepTraceAnnotation, TraceAnnotation

STEP = "serve.step"
SCHED = "serve.sched"
SCHED_ADMIT = "serve.sched.admit"
SCHED_RESERVE = "serve.sched.reserve"
KV_COPY = "serve.kv.copy"
PREPARE = "serve.engine.prepare"
DISPATCH = "serve.engine.dispatch"
SAMPLE = "serve.engine.sample"
EMIT = "serve.engine.emit"


def span(name: str, **args) -> TraceAnnotation:
    """A host span ``name``; ``args`` become the trace event's stats."""
    return TraceAnnotation(name, **args)


def step_span(step_num: int, **args) -> StepTraceAnnotation:
    """The ``serve.step`` span of engine step ``step_num`` (the profiler
    groups a trace into steps by it)."""
    return StepTraceAnnotation(STEP, step_num=step_num, **args)
