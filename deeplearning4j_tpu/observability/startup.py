"""The program's own account of its start: process start to the first
finished step, read from the flight recorder's ring.

A trainer pays the time to the first step at every start and every resume.
Its parts are spans in the ring (``flight_recorder.record_span``), one record
each, written at its end and never on the per-dispatch path:

* ``startup.before_import``: the OS's start of the process (``/proc/self/stat``
  against ``CLOCK_BOOTTIME``; left out where the OS gives none) to the first
  line of ``deeplearning4j_tpu/__init__.py``: the interpreter, the caller's
  other imports (``import jax``), the device runtime's start, whatever the
  caller did before it needed the package. No change to the program moves it.
* ``startup.import``: the first line to the last of the package's
  ``__init__`` (:func:`record_import` writes both).
* ``startup.init``: ``MultiLayerNetwork.init`` / ``ComputationGraph.init``.
* ``fit.call``: one ``fit_iterator`` / ``ParallelWrapper.fit`` call, entry to
  return. Inside the first are the first group's ``input.*`` spans, the
  first ``fit.dispatch``, which holds ``compile.resolve`` and its children
  (``nn/compile_cache.py``), and the ``fit.listeners`` that end when the
  first scores have been read.

:func:`time_to_first_step` lays them out as rows; the fit loop logs them
once, at ``INFO``, when the process's first ``fit.call`` returns.
"""
from __future__ import annotations

import logging
import os
import sys
import time
from typing import List, Optional

from .flight_recorder import FlightRecorder, global_recorder

log = logging.getLogger(__name__)


def process_start_ns() -> Optional[int]:
    """When the OS started this process, on ``time.time_ns()``'s clock, to a
    clock tick (10 ms); None where the OS does not say (no ``/proc``, no
    ``CLOCK_BOOTTIME``). Field 22 of ``/proc/self/stat`` counts ticks from
    boot to the process's start, suspended time included, as
    ``CLOCK_BOOTTIME`` does."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read()
        # field 2, the command's name, may hold spaces and parentheses
        ticks = int(stat[stat.rindex(b")") + 2:].split()[19])
        since_boot_ns = ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        now_boot_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME)
        return time.time_ns() - (now_boot_ns - since_boot_ns)
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def record_import(t0_ns: int,
                  recorder: Optional[FlightRecorder] = None) -> None:
    """The last line of the package's ``__init__``: ``startup.before_import``
    (where the OS says when the process started, and not after ``t0_ns``)
    and ``startup.import`` from ``t0_ns``, the ``__init__``'s first line."""
    rec = global_recorder() if recorder is None else recorder
    t1_ns = time.time_ns()
    start_ns = process_start_ns()
    root = start_ns is not None and 0 < start_ns <= t0_ns
    if root:
        rec.record_span("startup.before_import", start_ns, t0_ns,
                        argv0=sys.argv[0] if sys.argv else "")
    rec.record_span("startup.import", t0_ns, t1_ns,
                    cause="startup.before_import" if root else None)


def _seconds(span: dict) -> float:
    return (span["t1_ns"] - span["t0_ns"]) / 1e9


def time_to_first_step(events: Optional[list] = None) -> Optional[List[dict]]:
    """Rows of the time from the process's start to the return of its first
    ``fit.call``, from ``events`` (the global ring's ``snapshot()`` by
    default); None where no ``fit.call`` has returned yet or the ring has
    lost the start. Each row is ``{"row", "s"}``; the first is ``total``, a
    ``program`` row adds ``fn``, ``hit`` and ``parts`` (its ``compile.*``
    children's seconds by name), and ``other`` is what lies under none of
    the rows (the caller's own work between the program's phases)."""
    if events is None:
        events = global_recorder().snapshot()
    spans = [e for e in events if "t0_ns" in e and "name" in e]
    first = lambda name: next((s for s in spans if s["name"] == name), None)
    call, imported = first("fit.call"), first("startup.import")
    if call is None or imported is None:
        return None
    inside = [s for s in spans
              if call["t0_ns"] <= s["t0_ns"] and s["t1_ns"] <= call["t1_ns"]]
    before = first("startup.before_import")
    t_zero = (before or imported)["t0_ns"]
    rows = [{"row": "total", "s": (call["t1_ns"] - t_zero) / 1e9}]
    if before is not None:
        rows.append({"row": "before_import", "s": _seconds(before)})
    rows.append({"row": "import", "s": _seconds(imported)})
    inits = [s for s in spans if s["name"] == "startup.init"
             and s["t1_ns"] <= call["t0_ns"]]
    rows.append({"row": "init", "s": sum(map(_seconds, inits))})
    staged = next((s for s in inside if s["name"] == "input.h2d"), None)
    if staged is not None:
        rows.append({"row": "first_group_staged",
                     "s": (staged["t1_ns"] - call["t0_ns"]) / 1e9})
    within = lambda s, outer: (outer["t0_ns"] <= s["t0_ns"]
                               and s["t1_ns"] <= outer["t1_ns"])
    resolutions = [s for s in inside if s["name"] == "compile.resolve"]
    for s in resolutions:
        rows.append({"row": "program", "fn": s.get("fn"), "hit": s.get("hit"),
                     "s": _seconds(s),
                     "parts": {c["name"].split(".", 1)[1]: _seconds(c)
                               for c in inside
                               if c.get("cause") == "compile.resolve"
                               and within(c, s)}})
    dispatch = next((s for s in inside if s["name"] == "fit.dispatch"), None)
    done = dispatch and next(
        (s for s in inside if s["name"] == "fit.listeners"
         and s.get("group") == dispatch.get("group")), None)
    if done:
        # from the end of the dispatch's own resolution: the call's launch
        # and the device's first K steps, ended by the listeners' score reads
        began_ns = max([s["t1_ns"] for s in resolutions
                        if within(s, dispatch)] + [dispatch["t0_ns"]])
        rows.append({"row": "first_steps", "k": dispatch.get("k"),
                     "s": (done["t1_ns"] - began_ns) / 1e9})
    rows.append({"row": "other",
                 "s": rows[0]["s"] - sum(r["s"] for r in rows[1:])})
    return rows


def format_rows(rows: List[dict]) -> str:
    """``time_to_first_step``'s rows on one line."""
    out = []
    for r in rows[1:]:
        if r["row"] == "program":
            parts = ", ".join(f"{k.replace('_', ' ')} {v:.1f}"
                              for k, v in r["parts"].items())
            out.append(f"program {r['fn']} "
                       f"{'loaded' if r['hit'] else 'compiled'} in "
                       f"{r['s']:.1f}" + (f" ({parts})" if parts else ""))
        elif r["row"] == "first_steps":
            out.append(f"first {r['k']} steps {r['s']:.1f}")
        else:
            out.append(f"{r['row'].replace('_', ' ')} {r['s']:.1f}")
    return f"time to first step {rows[0]['s']:.1f} s: " + ", ".join(out)


_logged = False


def log_time_to_first_step() -> None:
    """The operator's line, once a process: called where a ``fit.call`` has
    just been written; every call after the first returns at once."""
    global _logged
    if _logged:
        return
    _logged = True
    if log.isEnabledFor(logging.INFO):
        rows = time_to_first_step()
        if rows:
            log.info(format_rows(rows))
