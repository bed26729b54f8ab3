"""What ``setup_s`` is made of, read from the program's own spans.

The program times its start (``deeplearning4j_tpu/observability/startup.py``,
``nn/compile_cache.py``): ``startup.before_import`` (the OS's start of the
process to the first line of the package), ``startup.import``,
``startup.init``, one ``fit.call`` per ``fit_iterator`` / ``ParallelWrapper.fit``
call, and inside a call's first ``fit.dispatch`` the resolution of its
program, ``compile.resolve`` (the ring's ``compile`` record, with ``fn``,
``hit`` and the executable's memory figures) over ``compile.store_read`` and
``compile.deserialize`` (a hit) or ``compile.lower``, ``compile.backend`` and
``compile.store_write`` (a miss). Every span is one record of the flight
recorder's ring, ``t0_ns``/``t1_ns`` on ``time.time_ns()``'s clock, as
``span_reduce.py`` describes.

Set-up is everything that ended before the window began. ``run.py`` gives
the window's start on ``time.perf_counter()``'s clock; the two clocks are
laid on each other where a reader runs (they drift by microseconds over a
run).

Every reader returns ``None``, and says why on standard error, where the
ring has dropped records (it holds 4,096; set-up's are the oldest, so they go
first) or the program has no such span (an older program).
"""
from __future__ import annotations

import sys
import time

_said: set = set()


def log(msg: str) -> None:
    """One line to standard error, each message once a run."""
    if msg not in _said:
        _said.add(msg)
        print(f"[startup] {msg}", file=sys.stderr, flush=True)


def seconds(span: dict) -> float:
    return (span["t1_ns"] - span["t0_ns"]) / 1e9


def ring():
    """Every finished span of the program's ring, oldest record first, or
    None where the ring has dropped any."""
    from deeplearning4j_tpu.observability.flight_recorder import (
        global_recorder)

    rec = global_recorder()
    if rec.dropped:
        log(f"the ring dropped {rec.dropped} records: set-up's spans are the "
            f"oldest, so nothing is read from it")
        return None
    return [e for e in rec.snapshot() if "t0_ns" in e and "name" in e]


def window_start_ns(ctx: dict) -> int:
    """The window's start on the spans' clock."""
    offset = time.time_ns() - round(time.perf_counter() * 1e9)
    return round(ctx["window"]["t_start"] * 1e9) + offset


def setup_spans(ctx: dict, *names: str):
    """The spans called one of ``names`` that ended before the window, in
    the order of their starts; None where the ring holds none (said once)."""
    spans = ring()
    if spans is None:
        return None
    before = window_start_ns(ctx)
    mine = sorted((s for s in spans
                   if s["name"] in names and s["t1_ns"] <= before),
                  key=lambda s: s["t0_ns"])
    if not mine:
        log(f"no span {' / '.join(names)} ended before the window: a "
            f"program without it")
        return None
    return mine


def span_seconds(ctx: dict, name: str, first_only: bool = False):
    """Seconds under the set-up spans called ``name``, summed (or the first
    alone); None where there is none."""
    mine = setup_spans(ctx, name)
    if mine is None:
        return None
    return seconds(mine[0]) if first_only else sum(map(seconds, mine))


def within(span: dict, outer: dict) -> bool:
    return outer["t0_ns"] <= span["t0_ns"] and span["t1_ns"] <= outer["t1_ns"]


def first_call(ctx: dict):
    """``(the process's first fit.call, the set-up spans inside it)``."""
    calls = setup_spans(ctx, "fit.call")
    if calls is None:
        return None
    return calls[0], [s for s in ring() if within(s, calls[0])]


def first_stage_s(ctx: dict):
    """The first ``fit.call``'s start to the end of its first group's
    ``input.h2d``: the producer's start, the slots' allocation and first
    touch, the cast and the put."""
    found = first_call(ctx)
    if found is None:
        return None
    call, inside = found
    puts = [s["t1_ns"] for s in inside if s["name"] == "input.h2d"]
    if not puts:
        log("the first fit.call staged no group")
        return None
    return (min(puts) - call["t0_ns"]) / 1e9


def first_steps_s(ctx: dict):
    """The end of the first ``fit.dispatch``'s ``compile.resolve`` (the
    dispatch's start where it resolved nothing) to the end of that group's
    ``fit.listeners``: the call's own launch and the device's first K steps,
    ended by the listeners' reads of the scores."""
    found = first_call(ctx)
    if found is None:
        return None
    _, inside = found
    dispatches = sorted((s for s in inside if s["name"] == "fit.dispatch"),
                        key=lambda s: s["t0_ns"])
    if not dispatches:
        log("the first fit.call dispatched nothing")
        return None
    first = dispatches[0]
    done = [s["t1_ns"] for s in inside if s["name"] == "fit.listeners"
            and s.get("group") == first.get("group")]
    if not done:
        return None
    began = max([s["t1_ns"] for s in inside
                 if s["name"] == "compile.resolve" and within(s, first)]
                + [first["t0_ns"]])
    return (max(done) - began) / 1e9


def deserialize_s(ctx: dict):
    """Seconds under the ``compile.deserialize`` children of set-up's
    resolutions; 0 on a run that compiled everything, None where the program
    writes no ``compile.resolve``."""
    if setup_spans(ctx, "compile.resolve") is None:
        return None
    before = window_start_ns(ctx)
    return sum(seconds(s) for s in ring()
               if s["name"] == "compile.deserialize" and s["t1_ns"] <= before)


def union_seconds(spans: list) -> float:
    """Seconds covered by at least one of ``spans``: overlapping spans (an
    ``init`` inside a ``fit.call``) count once."""
    total, end = 0, None
    for t0, t1 in sorted((s["t0_ns"], s["t1_ns"]) for s in spans):
        if end is None or t0 > end:
            total, end = total + t1 - t0, t1
        elif t1 > end:
            total, end = total + t1 - end, t1
    return total / 1e9


def program_s(ctx: dict):
    """What the program owns of ``setup_s``: the union of ``startup.import``,
    ``startup.init`` and the ``fit.call``s that ended before the window. What
    is left of ``setup_s`` after it and ``startup.before_import`` goes to
    standard error as the harness between the program's phases."""
    mine = setup_spans(ctx, "startup.import", "startup.init", "fit.call")
    if mine is None:
        return None
    owned = union_seconds(mine)
    before = span_seconds(ctx, "startup.before_import", first_only=True)
    if before is not None and "setup_s" in ctx:
        log(f"setup_s {ctx['setup_s']:.3f} = before the package's import "
            f"{before:.3f} + the program {owned:.3f} + the harness between "
            f"the program's phases {ctx['setup_s'] - before - owned:.3f}")
    return owned


def step_program_temp_gb(ctx: dict):
    """``temp_bytes`` of the ``compile.resolve`` span of the program the
    window's dispatches ran (their ``fit.dispatch`` spans' ``path``): the
    compiler's own figure for the step's temporaries. None where the runtime
    gave no figure for the executable."""
    spans = ring()
    if spans is None:
        return None
    paths = [s.get("path") for s in spans if s["name"] == "fit.dispatch"]
    mine = [s for s in spans if s["name"] == "compile.resolve"
            and paths and s.get("fn") == paths[-1]]
    if not mine:
        log("no compile.resolve span of the window's step program")
        return None
    temp = mine[-1].get("temp_bytes")
    if temp is None:
        log(f"the runtime gave no memory figures for {paths[-1]}")
        return None
    return temp / 1e9
