"""The program's own spans and scopes, laid over the profiler trace.

``trace_reduce.py`` reads what the device did; this file reads what the
program says it was doing, from the two things a reader may take from the
program besides its registry:

* **the flight recorder's ring** (``global_recorder().snapshot()``): one
  record per finished span, ``name``, ``t0_ns``, ``t1_ns`` on
  ``time.time_ns()``'s clock, ``thread``, ``group`` (the staged K-step group,
  shared from pull to dispatch) and ``cause``. The fit path writes
  ``input.pull``, ``input.stack``, ``input.cast``, ``input.h2d`` (producer
  thread), ``fit.wait``, ``fit.dispatch`` (the ``step`` event) and
  ``fit.listeners`` (fit loop).
* **the step program's optimised module**
  (``global_tracker().executable("<Class>.multistep").as_text()``): every
  instruction's ``op_name`` holds the ``jax.named_scope`` it was traced under
  (``layer/<name>``, ``loss``, ``update``; backward is JAX's own
  ``transpose(jvp(...))`` around them).

The profile is taken with ``host_tracer_level = 0``, at which a
``TraceAnnotation`` records nothing, so the spans do not come through the
profile. They need not: the profile's ``Task Environment`` plane carries
``profile_start_time`` (ns since the epoch, ``CLOCK_REALTIME``) and every
event's ``start_ns`` counts from it, so ``profile_start_time + start_ns`` is
on the spans' clock.

The slice is ``trace_reduce``'s: whole periods of the step program, the first
execution left out. It is found again from what that reduction reported (the
step module's name and the number of periods) and checked against its
``window_s``; the two cannot drift apart unnoticed.

**Where a fusion's time goes.** The trace's event is the fusion's own HLO
line; its members' ``op_name``s are in the module text. A fusion that holds a
convolution or dot goes to that instruction's layer and phase (XLA fuses a
weight-gradient convolution with the updater's subtraction: the root sits in
``update``, the work is backward). One without goes to its root's: members
of an earlier phase are its inputs, computed again in place (the forward
comparison inside a ReLU's backward, the gradient's last conversion inside
the updater's subtraction). Where a root tuple's outputs lie in different
phases the fusion is booked as ``mixed``. An operation under no scope of the
program's, or of another program, is ``unscoped``.

Where the program has no spans or no scopes (an older program), every reader
returns ``None`` and nothing is raised. Two tables go to standard error:
device time by layer and phase, and the device's idle seconds by the span
that owned them. Idle time between a dispatch call's return and its
execution's start is ``fit.launch``'s (the device has its work and has not
begun it: on a v5e the staged group's copy is still arriving), whatever the
host is doing by then.
"""
from __future__ import annotations

import bisect
import os
import re
import statistics
import sys

from trace_reduce import (CONTAINER, DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                          _clip, gaps, union)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

PRODUCER = ("input.pull", "input.stack", "input.cast", "input.h2d")
FIT_LOOP = ("fit.dispatch", "fit.listeners")
OWNERS = PRODUCER + FIT_LOOP + ("fit.launch",)
PHASES = ("forward", "backward", "update", "mixed", "unscoped")

_memo: dict = {}


def log(msg: str) -> None:
    print(f"[spans] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the program
def program_spans() -> list:
    """Every finished span in the program's ring, oldest first."""
    from deeplearning4j_tpu.observability.flight_recorder import (
        global_recorder)

    return [e for e in global_recorder().snapshot()
            if "t0_ns" in e and "name" in e]


def program_module_text(cell: dict):
    """The optimised module of the cell's K-step program, or None."""
    from deeplearning4j_tpu.observability.compile_tracker import (
        global_tracker)

    cls = cell["config"]["network"].rsplit(".", 1)[1]
    exe = global_tracker().executable(f"{cls}.multistep")
    return exe.as_text() if exe is not None else None


def window_groups(spans: list, dispatches: int) -> set:
    """The groups of the last ``dispatches`` K-step dispatches in the ring:
    the window's (set-up's first dispatch comes before them)."""
    steps = [s for s in spans if s["name"] == "fit.dispatch"
             and s.get("group") is not None]
    return {s["group"] for s in steps[-dispatches:]} if dispatches else set()


def stage_ms_per_batch(ctx: dict, name: str):
    """Milliseconds a batch of the window spent in the producer span
    ``name``, from the ring alone: the span's time over the window's groups /
    the window's steps. The four producer spans add up to
    ``input.stage_ms_per_batch``, which reads the same boundaries as one
    counter."""
    steps, spans = ctx["window"]["steps"], program_spans()
    groups = window_groups(spans, ctx["window"]["dispatches"])
    total = sum(s["t1_ns"] - s["t0_ns"] for s in spans
                if s["name"] == name and s.get("group") in groups)
    return total / 1e6 / steps if total and steps else None


# ------------------------------------------------------- the module's text
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_LAYER = re.compile(r"layer/([^/()]+)")
_LOSS = re.compile(r"(?:^|[/(])loss(?:[/)]|$)")
_EVENT_NAME = re.compile(r"^%?([\w.\-]+) = ")
_OPERAND = re.compile(r"%([\w.\-]+)")


def phase_of(op_name):
    """``(phase, layer)`` of one operation from its ``op_name``, or None
    where it was traced under no scope of the program's."""
    if not op_name:
        return None
    layer = _LAYER.search(op_name)
    layer = layer.group(1) if layer else ("loss" if _LOSS.search(op_name)
                                          else None)
    if "transpose(" in op_name and layer:
        return "backward", layer
    if "update" in op_name.split("/"):
        return "update", "update"
    return ("forward", layer) if layer else None


def _skip_shape(rest: str) -> str:
    """What follows an instruction's result shape (a tuple shape nests)."""
    if not rest.startswith("("):
        return rest[rest.find(" "):]
    depth = 0
    for i, c in enumerate(rest):
        depth += (c == "(") - (c == ")")
        if depth == 0:
            return rest[i + 1:]
    return ""


def parse_module(text: str) -> dict:
    """``{computation: [(name, opcode, op_name, calls, outputs), ...]}``;
    ``outputs`` is None but for a computation's root: the root's own name,
    or the names a root ``tuple`` gathers."""
    comps, cur = {}, None
    for line in text.splitlines():
        if cur is None or not line.startswith(" "):
            m = _COMPUTATION.match(line)
            cur = comps.setdefault(m.group(1), []) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, name, rest = m.groups()
        body = _skip_shape(rest)
        opcode = _OPCODE.match(body)
        opcode = opcode.group(1) if opcode else ""
        op_name, calls = _OP_NAME.search(rest), _CALLS.search(rest)
        outputs = None
        if root:
            outputs = (_OPERAND.findall(body[:body.find(")")])
                       if opcode == "tuple" else [name])
        cur.append((name, opcode, op_name.group(1) if op_name else None,
                    calls.group(1) if calls else None, outputs))
    return comps


def _fusion_phase(members: list, own):
    """``(phase, layer)`` of a fusion from its members (this file's
    docstring); ``own`` is what the fusion's own ``op_name`` says."""
    phases = {m[0]: phase_of(m[2]) for m in members}
    product = next((phases[m[0]] for m in members
                    if m[1] in ("convolution", "dot") and phases[m[0]]), None)
    if product:
        return product
    outputs = next((m[4] for m in members if m[4]), [])
    out = [phases[o] for o in outputs if phases.get(o)] or ([own] if own
                                                            else [])
    if out:
        same = all(p[0] == out[0][0] for p in out)
        return out[0] if same else ("mixed", out[0][1])
    # no output says where it belongs (the compiler's own instructions):
    # the latest phase among the members, whose inputs the others compute
    seen = [p for p in phases.values() if p]
    return max(seen, key=lambda p: PHASES.index(p[0])) if seen else None


def classify_module(text: str) -> dict:
    """``{instruction: (phase, layer)}`` for every instruction of the module.
    Empty where the module holds no scope of the program's at all."""
    comps = parse_module(text)
    out, scoped = {}, False
    for instructions in comps.values():
        for name, opcode, op_name, calls, _ in instructions:
            own = phase_of(op_name)
            if opcode == "fusion" and calls in comps:
                own = _fusion_phase(comps[calls], own)
            scoped = scoped or own is not None
            out[name] = own or ("unscoped", "")
    return out if scoped else {}


# ------------------------------------------------------------- the profile
def find_xplane(cell_name: str):
    """The traced run's profile, where ``run.py`` is documented to put it."""
    for root, _, files in os.walk(os.path.join(ROOT, ".bench_out", "trace",
                                               cell_name)):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    return None


def profile_start_ns(profile):
    for plane in profile.planes:
        if plane.name == "Task Environment":
            for key, value in plane.stats:
                if key == "profile_start_time":
                    return int(value)
    return None


def slice_of(runs: list, trace: dict):
    """``trace_reduce``'s ``[lo, hi]`` from the step module's executions
    (sorted, in the profile's own ns) and what that reduction reported."""
    n = trace["dispatches"]
    candidates = []
    if len(runs) > n + 1:
        candidates.append((runs[1][0], runs[1 + n][0]))
    if n == 1:
        candidates += [(r[0], r[1]) for r in runs[:2]]
    for lo, hi in candidates:
        if abs((hi - lo) / 1e9 - trace["window_s"]) < 1e-9:
            return lo, hi
    raise ValueError(
        f"span_reduce cannot find trace_reduce's slice again: {n} periods of "
        f"{trace['step_module']} over {trace['window_s']} s do not match the "
        f"{len(runs)} executions in the profile")


def launches(executions: list, dispatches: list) -> list:
    """Per execution of the slice, ``(since, start)`` in ns: from the later
    of its dispatch span's start and the previous execution's end to its
    start on the device. With them comes ``(returned, start)``: the part of
    that after the dispatch call had returned, when the host was already
    elsewhere (``fit.launch`` in the idle table).

    ``executions``: ``[(start, end), ...]`` in the spans' clock, each with
    its predecessor before it (the first is context only); ``dispatches``:
    the ``fit.dispatch`` spans' ``(t0, t1)``, sorted. The n-th dispatch pairs
    with the n-th execution; the pairing is anchored at the execution the
    device waited longest for, which no queue held back: its dispatch is the
    one begun nearest to its start, on either side, so that clocks that do
    not agree show as a negative time and not as a pairing one off."""
    if len(executions) < 2 or not dispatches:
        return []
    anchor = max(range(1, len(executions)),
                 key=lambda i: executions[i][0] - executions[i - 1][1])
    j = min(range(len(dispatches)),
            key=lambda d: abs(dispatches[d][0] - executions[anchor][0]))
    out = []
    for i in range(1, len(executions)):
        d = j + i - anchor
        if 0 <= d < len(dispatches):
            start, before = executions[i][0], executions[i - 1][1]
            out.append(((max(dispatches[d][0], before), start),
                        (max(dispatches[d][1], before), start)))
    return out


def owner_pieces(spans: list) -> list:
    """Disjoint ``(start, end, owner)`` pieces of the fit loop's time, sorted:
    ``fit.dispatch`` and ``fit.listeners`` own themselves; a ``fit.wait`` is
    owned by the producer spans of the group it waited for, and is ``fit.wait
    (bare)`` where none of them ran."""
    by_group, pieces = {}, []
    for s in spans:
        if s["name"] in PRODUCER:
            by_group.setdefault(s.get("group"), []).append(s)
    for s in spans:
        if s["name"] in FIT_LOOP:
            pieces.append((s["t0_ns"], s["t1_ns"], s["name"]))
        elif s["name"] == "fit.wait":
            mine = [c + (p["name"],) for p in by_group.get(s.get("group"), ())
                    for c in [_clip(p["t0_ns"], p["t1_ns"], s["t0_ns"],
                                    s["t1_ns"])] if c]
            pieces += mine
            pieces += [g + ("fit.wait (bare)",) for g in gaps(
                union(m[:2] for m in mine), s["t0_ns"], s["t1_ns"])]
    return sorted(pieces)


def idle_owners(idle: list, spans: list) -> dict:
    """Idle nanoseconds of the device by the span that owned them
    (``owner_pieces``); what lies under no span at all is ``no span``."""
    pieces, owners, first = owner_pieces(spans), {}, 0
    for lo, hi in idle:
        while first < len(pieces) and pieces[first][1] <= lo:
            first += 1
        left, i = hi - lo, first
        while i < len(pieces) and pieces[i][0] < hi:
            c = _clip(pieces[i][0], pieces[i][1], lo, hi)
            if c:
                name, took = pieces[i][2], c[1] - c[0]
                owners[name] = owners.get(name, 0) + took
                left -= took
            i += 1
        if left:
            owners["no span"] = owners.get("no span", 0) + left
    return owners


def reduce_profile(profile, spans: list, module_text, trace: dict,
                   ksteps: int) -> dict:
    """Everything the span readers take, from one profile, the program's
    spans and its step module's text; ``trace`` is ``trace_reduce``'s
    reduction of the same profile."""
    t_zero = profile_start_ns(profile)
    if t_zero is None:
        raise ValueError("the profile has no Task Environment plane with "
                         "profile_start_time")
    devices = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {l.name: l for l in plane.lines}
            if OPS_LINE in lines and MODULES_LINE in lines:
                devices.append(lines)
    runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                  for ev in devices[0][MODULES_LINE].events
                  if ev.name == trace["step_module"])
    lo, hi = slice_of(runs, trace)
    steps = trace["dispatches"] * ksteps
    inside = [r for r in runs if lo <= r[0] < hi]
    starts = [r[0] for r in inside]
    # an event's start_ns is a float: ns since the epoch do not fit one, so
    # everything below stays on the profile's own clock
    out = {"lo_ns": t_zero + round(lo), "hi_ns": t_zero + round(hi),
           "steps": steps}

    # device time by layer and phase: operations that nest nothing, as
    # trace_reduce sums them, each booked where the module's text says
    where = classify_module(module_text) if module_text else {}
    by_layer, merged_first = {}, None
    for lines in devices:
        busy = []
        for ev in lines[OPS_LINE].events:
            c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if c is None:
                continue
            busy.append(c)
            if CONTAINER.search(ev.name):
                continue
            m = _EVENT_NAME.match(ev.name)
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            mine = i >= 0 and ev.start_ns < inside[i][1]
            key = where.get(m.group(1) if m else "") if mine else None
            key = key or ("unscoped", "")
            by_layer[key] = by_layer.get(key, 0.0) + c[1] - c[0]
        if merged_first is None:
            merged_first = union(busy)
    n = len(devices)
    out["by_layer_ms"] = {k: v / 1e6 / n / steps for k, v in by_layer.items()}
    out["phase_ms"] = None
    if where:
        out["phase_ms"] = {p: sum(v for k, v in out["by_layer_ms"].items()
                                  if k[0] == p) for p in PHASES}

    # the program's spans, in the profile's own ns
    local = [dict(s, t0_ns=s["t0_ns"] - t_zero, t1_ns=s["t1_ns"] - t_zero)
             for s in spans]
    out["spans_in_slice"] = sum(1 for s in local
                                if s["t1_ns"] > lo and s["t0_ns"] < hi)
    dispatches = sorted((s["t0_ns"], s["t1_ns"]) for s in local
                        if s["name"] == "fit.dispatch")
    # the launches that end a period of the slice: of the executions that
    # start in (lo, hi], each with its predecessor
    paired = launches([r for r in runs if lo <= r[0] <= hi], dispatches)
    out["launch_ms"] = [(start - since) / 1e6 for (since, start), _ in paired]

    # idle time: first what lies between a dispatch call's return and its
    # execution's start (the device has its work and has not begun it: the
    # host is by then staging the next group, which is not the cause), then
    # the rest by the span that owned it
    idle = gaps(merged_first, lo, hi)
    out["idle_s"] = sum(e - s for s, e in idle) / 1e9
    owners = {}
    if out["spans_in_slice"]:
        launching = [p for _, p in paired if p[1] > p[0]]
        rest = gaps(union([tuple(b) for b in merged_first] + launching),
                    lo, hi)
        owners = idle_owners(rest, local)
        owners["fit.launch"] = (sum(e - s for s, e in idle)
                                - sum(e - s for s, e in rest))
    out["idle_by_owner_s"] = {k: v / 1e9 for k, v in owners.items()}
    out["clock_check"] = clock_check(profile, local, lo, hi)
    return out


def clock_check(profile, local_spans: list, lo, hi):
    """The shared clock, shown: for every ``input.cast`` span of the slice,
    the Python tracer's frame of the call that did the cast
    (``_stage_host``), and how far their starts lie apart (ns). (The ends
    differ by what the span holds besides: freeing the float32 stack.)"""
    frames = sorted(ev.start_ns for plane in profile.planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if ev.name.endswith(" _stage_host"))
    apart = []
    for s in local_spans:
        if s["name"] == "input.cast" and lo <= s["t0_ns"] < hi and frames:
            i = bisect.bisect_left(frames, s["t0_ns"])
            apart.append(min(abs(f - s["t0_ns"])
                             for f in frames[max(i - 1, 0):i + 1]))
    return {"spans": len(apart), "max_apart_ns": max(apart)} if apart else None


def report(cell_name: str, r: dict) -> None:
    if r["phase_ms"]:
        log(f"{cell_name}: device ms a step by layer and phase "
            f"({r['steps']} steps in the slice)")
        layers = {}
        for (phase, layer), ms in r["by_layer_ms"].items():
            layers.setdefault(layer or "-", {})[phase] = ms
        rows = sorted(layers.items(), key=lambda kv: -sum(kv[1].values()))
        log(f"  {'layer':<28}" + "".join(f"{p:>10}" for p in PHASES))
        for layer, ms in rows[:40]:
            log(f"  {layer:<28}" + "".join(f"{ms.get(p, 0.0):10.3f}"
                                           for p in PHASES))
        if len(rows) > 40:
            rest = {p: sum(ms.get(p, 0.0) for _, ms in rows[40:])
                    for p in PHASES}
            log(f"  {f'({len(rows) - 40} more layers)':<28}"
                + "".join(f"{rest[p]:10.3f}" for p in PHASES))
        total = sum(r["phase_ms"].values())
        log(f"  {'all':<28}" + "".join(f"{r['phase_ms'][p]:10.3f}"
                                       for p in PHASES)
            + f"   sum {total:.3f} ms; mixed "
            f"{100 * r['phase_ms']['mixed'] / total:.2f}%, unscoped "
            f"{100 * r['phase_ms']['unscoped'] / total:.2f}%")
    if r["idle_by_owner_s"]:
        log(f"{cell_name}: device idle {r['idle_s']:.3f} s of the slice, by "
            f"the span that owned it")
        for name, s in sorted(r["idle_by_owner_s"].items(),
                              key=lambda kv: -kv[1]):
            log(f"  {name:<18}{s:9.3f} s {100 * s / r['idle_s']:6.1f}%")
    if r["launch_ms"]:
        log(f"{cell_name}: launch (dispatch or previous end -> device start) "
            f"ms: min {min(r['launch_ms']):.3f} median "
            f"{statistics.median(r['launch_ms']):.3f} max "
            f"{max(r['launch_ms']):.3f} over {len(r['launch_ms'])} groups")
    if r["clock_check"]:
        log(f"{cell_name}: input.cast spans against the Python tracer's "
            f"_stage_host frames: starts at most "
            f"{r['clock_check']['max_apart_ns'] / 1e3:.1f} us apart over "
            f"{r['clock_check']['spans']} groups")


def reduce(ctx: dict):
    """The reduction of this run's profile, made once for all readers; None
    where the run left no profile."""
    path = find_xplane(ctx["cell"]["name"])
    if path is None:
        return None
    if path not in _memo:
        from jax.profiler import ProfileData

        r = reduce_profile(
            ProfileData.from_file(path), program_spans(),
            program_module_text(ctx["cell"]), ctx["trace"],
            int(ctx["cell"]["traffic"]["dispatch_ksteps"]))
        report(ctx["cell"]["name"], r)
        _memo[path] = r
    return _memo[path]


# -------------------------------------------------------------- the readers
def phase_ms(ctx: dict, phase: str):
    r = reduce(ctx)
    return r["phase_ms"][phase] if r and r["phase_ms"] else None


def idle_attributed_pct(ctx: dict):
    r = reduce(ctx)
    if not r or not r["idle_by_owner_s"] or not r["idle_s"]:
        return None
    owned = sum(s for name, s in r["idle_by_owner_s"].items()
                if name in OWNERS)
    return 100.0 * owned / r["idle_s"]
