"""One stage timer, three sinks.

A stage is a named interval of the serving path (``coproc.read``,
``coproc.stage.explode_find``, ``storage.append``). Timing one takes one
clock read at each end, and the same two reads feed

(a) always: the stage's histogram on ``/metrics`` (what the benchmark's
    per-layer metrics read as window deltas);
(b) unless the stage says ``annotate=False``, and free while no profile
    runs: a ``jax.profiler`` ``TraceAnnotation`` named ``rp:<stage>``
    around the interval, so that in a profiled run the stage lies on the
    xplane's ``/host:`` plane, on the clock of the device operations, and
    an idle gap of the device can be laid at it. JAX is never imported for
    this: the annotation binds once ``jax`` is in ``sys.modules`` (a broker
    with coproc off has none), and costs one ``is_enabled()`` call (~20 ns)
    while no profile runs. A stage that is mostly a wait for somebody else
    (a parked long poll) opts out: its annotation would cover whatever the
    host did meanwhile and take the device's idle gaps for itself;
(c) only when ``tracer.enabled``: the span ring (``observability/trace.py``),
    with ``parent`` = the span that was ambient when the stage began.

Two forms. ``with stage(name, hist):`` wraps a block and makes the stage
the ambient span for what runs inside (its own two reads are ``.t0`` and
``.t1``, for a caller whose neighbouring intervals begin or end on them).
``t0 = begin(name)`` ... ``close(name, hist, t0)`` is for code that holds
its ``t0`` across branches, threads or awaits (the engine's ``t_*`` stages,
the pacemaker's phases); the annotation has to be entered at the start, so
``begin`` takes the name too. ``close`` also takes a plain
``time.perf_counter()`` value, back-dated where the caller holds a duration
and not a start (the produce handler's ``queue``): such a stage has sinks
(a) and (c) only.

Stages that await on the event loop interleave with other coroutines'
stages on the loop thread's line of the profile; they need not nest.
"""

from __future__ import annotations

import sys
import time

from redpanda_tpu.observability.probes import record_us
from redpanda_tpu.observability.trace import _NOOP, _current_trace, tracer

# "the ambient trace" for close(): None is a real value there (the caller
# had no trace, record no span)
AMBIENT = object()

_annotation = None  # jax.profiler.TraceAnnotation, once jax has been imported


def _bind_annotation():
    global _annotation
    # never import jax here; and a jax that another thread is still
    # importing has no ``profiler`` attribute yet: try again next time
    ann = getattr(getattr(sys.modules.get("jax"), "profiler", None),
                  "TraceAnnotation", None)
    if ann is not None:
        _annotation = ann
    return ann


class _Annotated(float):
    """A ``t0`` that also holds the entered ``rp:`` annotation of its stage
    (only while a profile runs; otherwise ``begin`` returns a plain float)."""

    __slots__ = ("annotation",)


def begin(name: str, *, annotate: bool = True, **args) -> float:
    """Start stage ``name``: its ``t0`` (``time.perf_counter()``).
    ``annotate=False`` keeps the stage off the profile (sink (b)); ``args``
    ride on the profile's annotation as its arguments (a bucket's rows)."""
    ann = annotate and (_annotation or _bind_annotation())
    if not ann or not ann.is_enabled():
        return time.perf_counter()
    a = ann("rp:" + name, **args)
    a.__enter__()
    t0 = _Annotated(time.perf_counter())
    t0.annotation = a
    return t0


def close(name: str, hist, t0: float, *, trace_id=AMBIENT, span=_NOOP, **ring) -> float:
    """End the stage begun at ``t0``; returns its duration in seconds.

    ``hist``: the histogram that takes the duration in microseconds (None:
    the caller keeps its own books from the return value, as the engine
    does under its stats lock). Ring: ``span``, if the caller entered one
    at ``t0`` (``enter_at``), is committed on this clock read; otherwise a
    span ``name`` is recorded under ``trace_id`` (default: the ambient
    trace; None: no span). ``ring``: ``Tracer.record``'s ``parent`` and
    ``span_id``, for a stage whose parent is not the ambient span (it runs
    on another thread, or begins before its parent does)."""
    t1 = time.perf_counter()
    dt = t1 - t0
    if type(t0) is _Annotated:
        t0.annotation.__exit__(None, None, None)
    if hist is not None:
        # with the SLO layer's exemplar capture: a breach links to this trace
        record_us(hist, int(dt * 1e6))
    if span is not _NOOP:
        span.exit_at(t1)
    elif tracer.enabled and trace_id is not None:
        tid = _current_trace.get() if trace_id is AMBIENT else trace_id
        if tid is not None:
            tracer.record(name, dt * 1e6, tid, start_perf=float(t0), **ring)
    return dt


class stage:
    """``with stage(name, hist) as sp:`` — the block is the stage. ``sp`` is
    the tracer's span (``sp.trace_id``, ``sp.set(k, v)``), the shared no-op
    when tracing is off. ``annotate`` is ``begin``'s; the other keywords
    are ``Tracer.span``'s (``root``, ``trace_id``, ``no_slow``, ``node``)."""

    # ``t0`` / ``t1``: the stage's own two clock reads, for a caller whose
    # neighbouring intervals have to begin or end on them (the pacemaker's
    # engine phase is the sum of its legs by sharing these)
    __slots__ = ("_name", "_hist", "_span", "t0", "t1", "_annotate")

    def __init__(self, name: str, hist=None, *, annotate: bool = True, **span_kw) -> None:
        self._name = name
        self._hist = hist
        self._annotate = annotate
        self._span = tracer.span(name, **span_kw) if tracer.enabled else _NOOP

    def __enter__(self):
        self.t0 = t0 = begin(self._name, annotate=self._annotate)
        self._span.enter_at(t0)
        return self._span

    def __exit__(self, *exc) -> bool:
        # close(), with the span this stage entered: with none to commit the
        # ring takes nothing (a mid-path stage outside any trace must not
        # mint an orphan)
        self.t1 = t1 = time.perf_counter()
        t0 = self.t0
        if type(t0) is _Annotated:
            t0.annotation.__exit__(None, None, None)
        if self._hist is not None:
            record_us(self._hist, int((t1 - t0) * 1e6))
        self._span.exit_at(t1)
        return False
