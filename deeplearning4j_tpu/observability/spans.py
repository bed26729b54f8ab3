"""Span API: one ``with span("epoch/3/fwd")`` feeds BOTH trace viewers.

``ProfilerListener`` already captures XPlane windows, but user-defined
phases only show up there if the code annotates them — and ad-hoc
``jax.profiler.TraceAnnotation`` calls leave no persistent record once the
trace window closes. A span does triple duty: the annotation makes the
phase visible in xprof/perfetto timelines, the registry histogram keeps an
always-on latency distribution a ``/metrics`` scraper can watch between
(or without) profiler windows, and enter/exit events go into the flight
recorder's ring so a crash bundle carries the recent span timeline (which
phase the run died inside, not just that it died).

Span names are hierarchical-by-convention (``"epoch/3/stage"``); the
registry series is labeled with the name verbatim, so high-cardinality
names (per-step indices) belong in the annotation half only — pass
``metric_name`` to collapse them for the histogram.

Since the request-tracing plane landed (observability/tracing.py), a
``span()`` additionally opens a REAL trace span under the thread's ambient
trace context: training phases called inside a traced request show up in
its ``/serve/traces/<id>`` tree, and outside any trace the span costs one
no-op context manager. No call site outside observability/ changed.
"""
from __future__ import annotations

import contextlib
import time
import weakref
from typing import Optional

from .flight_recorder import global_recorder
from .metrics import global_registry
from .names import SPAN_SECONDS
from .tracing import NOOP_SPAN, current_span, trace_span


#: registry -> {histogram label: series}, so a span resolves its series once
#: per name and not on every call (a registry's ``clear()`` detaches the
#: handles, as it does every handle a call site keeps)
_series_of = weakref.WeakKeyDictionary()


def _series(reg, label: str):
    by_label = _series_of.get(reg)
    if by_label is None:
        by_label = _series_of.setdefault(reg, {})
    series = by_label.get(label)
    if series is None:
        series = by_label[label] = reg.histogram(
            SPAN_SECONDS, "wall seconds of user/framework span() phases"
        ).labels(name=label)
    return series


@contextlib.contextmanager
def span(name: str, metric_name: Optional[str] = None, registry=None,
         recorder=None):
    """Annotate a phase in XPlane traces AND record its wall time in the
    registry histogram ``dl4j_span_seconds{name=...}`` AND leave
    ``span_enter``/``span_exit`` events in the flight-recorder ring (the
    exit event carries the interval as ``t0_ns``/``t1_ns``) AND open a trace span under the ambient trace context (tracing.py).

    ``metric_name`` overrides the histogram label (use it to collapse
    per-index names like ``epoch/3`` into a bounded series like ``epoch``).
    """
    reg = registry if registry is not None else global_registry()
    # explicit None check: an EMPTY recorder is falsy (__len__ == 0)
    rec = recorder if recorder is not None else global_recorder()
    series = _series(reg, metric_name or name)
    try:
        import jax.profiler as _prof
        ann = _prof.TraceAnnotation(name)
    except Exception:  # pragma: no cover - profiler API absent
        ann = contextlib.nullcontext()
    rec.record("span_enter", name=name)
    # a real trace span only under an ambient trace — a bare training
    # phase must not mint root traces into the ring
    tspan = trace_span(metric_name or name) if current_span() is not None \
        else NOOP_SPAN
    t0, t0_ns = time.perf_counter(), time.time_ns()
    with ann, tspan:
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            series.observe(dt)
            # the interval on the clock of the fit path's record_span()
            # events; record() itself, for the lint rule that pairs
            # span_enter with span_exit by the event's name
            rec.record("span_exit", name=name, dur_s=dt, t0_ns=t0_ns,
                       t1_ns=time.time_ns())
