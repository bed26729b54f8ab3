"""Device time by ``jax.named_scope`` inside a layer, and what the expert
layers counted: the readings of the metrics a language-model cell adds.

``span_reduce.py`` books an operation's time to its layer and phase; here it
is booked to the scope path the program traced it under (``attn``,
``attn/core``, ``moe/router``, ``moe/dispatch``, ``moe/experts``,
``moe/shared`` inside ``layer/<index>_<Class>``), forward and backward alike:
an ``op_name`` keeps the path inside JAX's ``transpose(jvp(...))`` and inside
a rematerialised block. A fusion goes where its convolution or dot was traced,
else where its root was (``span_reduce``'s rule); a Pallas kernel's event is
its custom call, whose own ``op_name`` says where it belongs.

The slice is ``trace_reduce``'s (whole periods of the step program), found
again as ``span_reduce`` finds it. Where the program has no such scope, no
step module or no counter (an older program), every reader returns ``None``
and nothing is raised.
"""
from __future__ import annotations

import bisect
import re

import flops
import span_reduce
from costs import Share
from trace_reduce import CONTAINER, _clip

_memo: dict = {}
_COUNTER = re.compile(r"^(\w+)\{layer=(\w+)\}$")


def scope_names(text: str) -> dict:
    """``{instruction: op_name}`` for every instruction of the module; a
    fusion takes its product's ``op_name``, else its root's, else its own."""
    comps = span_reduce.parse_module(text)
    out = {}
    for instructions in comps.values():
        for name, opcode, op_name, calls, _ in instructions:
            if opcode == "fusion" and calls in comps:
                members = comps[calls]
                inner = next((m[2] for m in members
                              if m[1] in ("convolution", "dot") and m[2]),
                             None) or next((m[2] for m in members
                                            if m[4] and m[2]), None)
                op_name = inner or op_name
            out[name] = op_name or ""
    return out


def _events(ctx: dict):
    """``[(op_name, ms a step)]`` of the slice's device operations inside
    executions of the step module, averaged over the chips; None where the
    run left no profile or the program no module text."""
    path = span_reduce.find_xplane(ctx["cell"]["name"])
    text = span_reduce.program_module_text(ctx["cell"]) if path else None
    if not text:
        return None
    if path not in _memo:
        from jax.profiler import ProfileData

        trace = ctx["trace"]
        names = scope_names(text)
        devices = []
        for plane in ProfileData.from_file(path).planes:
            if span_reduce.DEVICE_PLANE.match(plane.name):
                lines = {l.name: l for l in plane.lines}
                if (span_reduce.OPS_LINE in lines
                        and span_reduce.MODULES_LINE in lines):
                    devices.append(lines)
        runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in devices[0][span_reduce.MODULES_LINE].events
                      if ev.name == trace["step_module"])
        lo, hi = span_reduce.slice_of(runs, trace)
        inside = [r for r in runs if lo <= r[0] < hi]
        starts = [r[0] for r in inside]
        steps = trace["dispatches"] * int(
            ctx["cell"]["traffic"]["dispatch_ksteps"])
        by_name = {}
        for lines in devices:
            for ev in lines[span_reduce.OPS_LINE].events:
                c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
                if c is None or CONTAINER.search(ev.name):
                    continue
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                if i < 0 or ev.start_ns >= inside[i][1]:
                    continue
                m = span_reduce._EVENT_NAME.match(ev.name)
                key = names.get(m.group(1) if m else "", "")
                by_name[key] = by_name.get(key, 0.0) + c[1] - c[0]
        scale = 1e6 * len(devices) * steps
        _memo[path] = [(k, v / scale) for k, v in by_name.items()]
        report(ctx["cell"]["name"], _memo[path], steps)
    return _memo[path]


_SCOPE = re.compile(r"/(attn/core|attn|moe/\w+|ffn)(?:/|$)")


def report(cell_name: str, events: list, steps: int) -> None:
    """Device ms a step by scope inside the layers, to standard error."""
    table = {}
    for name, ms in events:
        m = _SCOPE.search(name)
        scope = m.group(1) if m else (
            "loss" if span_reduce._LOSS.search(name) else
            "update" if "update" in name.split("/") else
            "layer (no inner scope)" if "layer/" in name else "unscoped")
        back = "backward" if "transpose(" in name else "forward"
        table.setdefault(scope, {}).setdefault(back, 0.0)
        table[scope][back] += ms
    span_reduce.log(f"{cell_name}: device ms a step by scope ({steps} steps "
                    f"in the slice; recomputed forward counts as backward)")
    for scope, t in sorted(table.items(), key=lambda kv: -sum(kv[1].values())):
        span_reduce.log(f"  {scope:<24}{t.get('forward', 0.0):10.3f}"
                        f"{t.get('backward', 0.0):10.3f}")
    span_reduce.log(f"  {'all':<24}"
                    f"{sum(ms for _, ms in events):10.3f}")


def scope_ms(ctx: dict, pattern: str):
    """Device milliseconds of one train step under the scopes ``pattern``
    (a regular expression searched in the ``op_name``) matches, forward and
    backward; None where nothing was traced under such a scope."""
    events = _events(ctx)
    if not events:
        return None
    rx = re.compile(pattern)
    hit = [ms for name, ms in events if rx.search(name)]
    return sum(hit) if hit else None


ATTENTION = r"/attn(/|$)"
ATTENTION_CORE = r"/attn/core(/|$)"
MOE = r"/moe/(router|dispatch|experts|shared)(/|$)"
MOE_EXPERTS = r"/moe/experts(/|$)"
DENSE = re.compile(r"/(attn|ffn|moe/shared|moe/router)(/|$)")


def dense_share(ctx: dict):
    """A language model's dense products against the chip's peak, by scope
    and not by who runs the operation (``metrics/kernel.dense_roofline.py``).

    Numerator: the operations a step requires (forward and two gradient
    products, ``flops.py``'s rule; nothing recomputed) of every product that
    no kernel metric of its own accounts for. The configuration's reference
    says which: an entry of its ``layers()`` with a ``scope`` belongs to the
    kernel traced under that scope; one without is a dense product.
    Denominator: the device time a step of every event under ``attn``,
    ``ffn``, ``moe/shared``, ``moe/router`` and ``loss``, less the time under
    a scope a tagged entry names. None where the reference tags nothing
    (every product is XLA's: ``kernel.matmul_roofline`` reads such a cell) or
    nothing was traced under those scopes."""
    need = flops.train_flops_by_scope(ctx["cell"]["config"])
    dense = need.pop(None, 0)
    events = _events(ctx)
    if not need or not dense or not events:
        return None
    tagged = re.compile("/(" + "|".join(map(re.escape, need)) + ")(/|$)")
    ms = sum(t for name, t in events
             if (DENSE.search(name) or span_reduce._LOSS.search(name))
             and not tagged.search(name))
    if not ms:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["device"]["count"]
    batch = int(ctx["cell"]["traffic"]["batch"])
    return Share(least_s=dense * batch / peak, device_s=ms / 1e3)


def by_layer(ctx: dict, counter: str) -> dict:
    """``{layer: value}`` of the program's per-layer series ``counter`` over
    the window."""
    out = {}
    for key, value in ctx["counters"].items():
        m = _COUNTER.match(key)
        if m and m.group(1) == counter:
            out[m.group(2)] = value
    return out
