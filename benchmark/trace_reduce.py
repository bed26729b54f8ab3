"""From a profiler trace (.xplane.pb) to the numbers the metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. What a
v5e trace holds (looked at by hand, PR 24): one plane ``/device:TPU:<n>`` per
chip with the lines ``XLA Modules`` (one event per executed program), ``XLA
Ops`` (one per HLO operation; a ``while`` spans the operations of its body,
so times are summed only over operations that nest nothing) and ``Async XLA
Ops``; and ``/host:CPU`` with one line per thread, where the Python tracer's
events start with ``$``. An operation's name is its HLO text.

The slice is cut ``limit_s`` after the first device operation (stopping the
profiler takes seconds during which the device is still recorded), and then
to whole periods of the step program, from one execution's start to the
next one's: each period holds one dispatch's work and the idle time that
follows it, so the idle share does not depend on how many periods fit. The
first execution in the trace is left out: tracing may have begun inside it.
"""
from __future__ import annotations

import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# a convolution or dot, alone or as the root of an output fusion
MATMUL = re.compile(r"\s(convolution|dot)\(|kind=kOutput|kind=kConv")
CONTAINER = re.compile(r"\s(while|conditional|call)\(")


def union(intervals):
    """Merged, sorted copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def gaps(merged, lo, hi):
    """Intervals of ``[lo, hi]`` that ``merged`` leaves uncovered."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=kOutput, ...`` ->
    ``fusion.12 kOutput bf16[128,56,56,256]``."""
    m = re.match(r"%?(\S+) = (?:\()?(\w+\[[\d,]*\])?", hlo)
    if not m:
        return hlo[:80]
    kind = re.search(r"kind=(\w+)", hlo)
    op = re.search(r"\}?\s([a-z][\w-]*)\(", hlo)
    parts = [m.group(1), kind.group(1) if kind else (op.group(1) if op else ""),
             m.group(2) or ""]
    return " ".join(p for p in parts if p)[:100]


def _clip(s, e, lo, hi):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce(path, limit_s=None, top=10):
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), limit_s, top)


def reduce_profile(profile, limit_s=None, top=10):
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {l.name: l for l in plane.lines}
            if OPS_LINE in lines and MODULES_LINE in lines:
                devices.append(lines)
    if not devices:
        raise ValueError("the trace has no device plane with operations")

    start = min(ev.start_ns for lines in devices
                for ev in lines[OPS_LINE].events)
    cut = None if limit_s is None else start + limit_s * 1e9

    # the step program is the module that took most device time; the window
    # reduced is from its first start to its last start among the executions
    # that ended before the cut: a whole number of periods, each one dispatch
    # and the gap after it (a single execution stands alone, with no gap)
    modules = {}
    for ev in devices[0][MODULES_LINE].events:
        end = ev.start_ns + ev.duration_ns
        if cut is None or end <= cut:
            modules.setdefault(ev.name, []).append((ev.start_ns, end))
    if not modules:
        raise ValueError("no program ran to its end inside the traced slice")
    step_module = max(modules, key=lambda k: sum(e - s for s, e in modules[k]))
    # the profiler can start in the middle of an execution, whose first
    # operations are then missing: the first one seen is never counted
    runs = sorted(modules[step_module])[1:] or sorted(modules[step_module])
    starts = [s for s, _ in runs]
    lo, hi = (starts[0], starts[-1]) if len(runs) > 1 else runs[0]
    step_gaps_ms = [(b - a) / 1e6 for a, b in zip(starts, starts[1:])]

    busy_ns, matmul_ns, by_op = 0.0, 0.0, {}
    first_merged = None
    for lines in devices:
        iv = []
        for ev in lines[OPS_LINE].events:
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if c is None:
                continue
            iv.append(c)
            if CONTAINER.search(ev.name):
                continue
            d = c[1] - c[0]
            by_op[ev.name] = by_op.get(ev.name, 0.0) + d
            if MATMUL.search(ev.name):
                matmul_ns += d
        merged = union(iv)
        busy_ns += sum(e - s for s, e in merged)
        if first_merged is None:
            first_merged = merged
    n = len(devices)

    spans = list(host_spans(profile))
    idle = sorted(((e - s, s, e) for s, e in gaps(first_merged, lo, hi)),
                  reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "devices": n,
        "matmul_s": matmul_ns / 1e9 / n,
        "step_module": step_module,
        "dispatches": max(len(runs) - 1, 1),
        "dispatch_gaps_ms": step_gaps_ms,
        "dispatch_gap_ms_p50": (statistics.median(step_gaps_ms)
                                if step_gaps_ms else None),
        "dispatch_gap_ms_max": max(step_gaps_ms) if step_gaps_ms else None,
        "breakdown": {
            "device_ops": [[short_name(k), v / 1e9 / n] for k, v in
                           sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[blame(spans, s, e), d / 1e9]
                          for d, s, e in idle[:top]]},
    }


def host_spans(profile, min_ns=1_000_000):
    """(thread, name, start, end) of host events of a millisecond or more."""
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns >= min_ns:
                    yield (line.name, ev.name, ev.start_ns,
                           ev.start_ns + ev.duration_ns)


# spans of the benchmark's own trace thread say nothing about the program
_OWN = ("stop_trace", "start_trace", "$time sleep")


def blame(spans, s, e) -> str:
    """What the host was doing in the idle gap ``[s, e]``: the innermost
    (shortest) host span that covers at least half of it."""
    best, best_len = "no host span", None
    for thread, name, hs, he in spans:
        if any(o in name for o in _OWN):
            continue
        cover = min(e, he) - max(s, hs)
        if cover >= 0.5 * (e - s) and (best_len is None or he - hs < best_len):
            best, best_len = f"{thread or 'thread'}: {name.lstrip('$')}", he - hs
    return best[:120]
