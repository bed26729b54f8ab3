"""Compile/retrace tracker for the framework's jit seams.

JAX recompiles silently: a `DtypePolicy` flip, a stray Python-float hparam,
or an unpadded final batch each mint a new executable, and the only symptom
is a step that takes seconds instead of milliseconds. The reference never had
this failure mode (ND4J ops are eager), so its listener pipeline has no slot
for it. This tracker closes the gap: every policy-keyed cache miss in
``LazyScore._jit`` (multilayer + graph networks), every parallel-wrapper /
training-master / pipeline-trainer program build, goes through ``wrap()``,
which records the compile — cache key, wall time, triggering abstract
shapes, active dtype-policy key — and raises a rate-limited warning when the
same function recompiles often enough to look like a retrace storm.

Two timing sources are recorded when available:

* **wall**: ``perf_counter`` around the first call for a new abstract
  signature — dispatch + trace + lower + compile as the user experiences it.
* **backend**: ``jax.monitoring`` duration events whose key mentions
  compile/lowering, attributed to whichever tracked call is active on this
  thread. This isolates genuine XLA compile time from tracing overhead.

Steps are counted by the fit loops calling ``note_step()``; the storm window
is measured in those steps so the warning threshold reads as "N compiles of
one function within M training steps" regardless of dispatch fusion.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from .metrics import global_registry
from .names import (JIT_BACKEND_COMPILE_SECONDS, JIT_COMPILE_SECONDS,
                    JIT_COMPILE_TOTAL, RECOMPILE_STORM_WARNINGS_TOTAL,
                    STEP_MFU)

log = logging.getLogger(__name__)

#: storm defaults: >= STORM_THRESHOLD compiles of one function within
#: STORM_WINDOW_STEPS training steps -> one warning (then suppressed for a
#: window so a pathological loop logs once per window, not once per step)
STORM_THRESHOLD = 3
STORM_WINDOW_STEPS = 200

_MAX_EVENTS = 1000

#: published bf16 peak FLOP/s of one chip, by ``jax.Device.device_kind`` —
#: THE peaks table (bench.py reads it too). A kind that is not here is an
#: error, never a default: add it with its source.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # Google Cloud documentation, "TPU v5e"
}


def peak_flops_for(device) -> Optional[float]:
    """bf16 peak of ``device``: None on a CPU (no utilization is reported
    there), the table's figure on a known accelerator, ValueError on an
    unknown one."""
    if device.platform == "cpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device.device_kind!r}: add "
            "it to observability.compile_tracker.PEAK_BF16_FLOPS with its "
            "source, or set DL4J_PEAK_FLOPS") from None


def _abstractify_for_lowering(x: Any) -> Any:
    """Array leaves -> ShapeDtypeStruct so a compiled program can be
    re-lowered for cost analysis without keeping live buffers alive."""
    import jax

    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return x


def _abstract(x: Any) -> Any:
    """Abstract one argument leaf the way jit's cache does: arrays by
    (shape, dtype), everything else by value (static/hashable) or type."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("array", tuple(x.shape), str(x.dtype))
    try:
        hash(x)
        return x
    except TypeError:
        return type(x).__name__


def _signature(args: tuple, kwargs: dict) -> Tuple:
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (tuple(_abstract(l) for l in leaves), str(treedef))


def _policy_key() -> Tuple:
    from deeplearning4j_tpu import common

    return common.policy_key()


def _normalize_cost(analysis: Any) -> Optional[dict]:
    """XLA cost_analysis() -> {str: number} (it returns a list on some
    backends/versions, a mapping on others)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if analysis is None:
        return None
    return {str(k): v for k, v in dict(analysis).items()
            if isinstance(v, (int, float))}


def cost_analysis_flops(fn: Callable, *args, **kwargs) -> float:
    """One-dispatch FLOP count of ``fn`` for these (possibly abstract)
    args, without a second backend compile. Shared by the MFU path and
    bench.py. Accepts a compile_cache ``CachedProgram`` (reuses its
    resolved executable), or any lowerable (jitted) fn — the cost comes
    from ``Lowered.cost_analysis()``; ``.compile()`` only as API-drift
    fallback. Returns 0.0 when no analysis is available."""
    try:
        if hasattr(fn, "cost_flops"):
            flops = fn.cost_flops(*args, **kwargs)
            if flops is not None:
                return max(0.0, float(flops))
        lowered = fn.lower(*args, **kwargs)
        try:
            cost = _normalize_cost(lowered.cost_analysis())
        except Exception:
            cost = _normalize_cost(lowered.compile().cost_analysis())
        return max(0.0, float((cost or {}).get("flops", 0.0)))
    except Exception:
        return 0.0


class CompileTracker:
    """Records compile events and watches for retrace storms.

    One process-global instance (``global_tracker()``) is shared by every
    seam; tests may construct private ones and lower the storm knobs.
    """

    def __init__(self, registry=None, storm_threshold: int = STORM_THRESHOLD,
                 storm_window_steps: int = STORM_WINDOW_STEPS):
        self._lock = threading.Lock()
        self._registry = registry
        self.storm_threshold = storm_threshold
        self.storm_window_steps = storm_window_steps
        self._step = 0
        #: fn name -> deque of step indices at which it compiled
        self._compile_steps: Dict[str, deque] = {}
        #: fn name -> step of last storm warning (rate limit)
        self._last_warned: Dict[str, int] = {}
        self.events: deque = deque(maxlen=_MAX_EVENTS)
        #: fn name -> (jitted fn, abstract args, abstract kwargs) captured at
        #: first call, so cost analysis can be computed lazily without live
        #: buffers
        self._lowerable: Dict[str, Tuple] = {}
        #: fn name -> cost_analysis dict (None caches "analysis unavailable"
        #: so a failing lower is attempted once, not every step)
        self._cost: Dict[str, Optional[dict]] = {}
        #: fn name -> last Compiled executable noted by an AOT seam
        #: (compile_cache), so flops_for reads its cost_analysis directly
        #: instead of re-lowering
        self._executables: Dict[str, Any] = {}
        #: fn name -> perf_counter of the previous note_step(fn=...) — the
        #: rolling-MFU time base
        self._mfu_last: Dict[str, float] = {}
        self._backend_peak: Optional[float] = None
        self._backend_peak_resolved = False
        # thread-local stack of active tracked calls, so jax.monitoring
        # compile-duration events can be attributed to the right function
        self._active = threading.local()
        self._monitoring_hooked = False

    # ------------------------------------------------------------ registry
    @property
    def registry(self):
        return self._registry if self._registry is not None else global_registry()

    def _metrics(self):
        reg = self.registry
        return (
            reg.counter(JIT_COMPILE_TOTAL,
                        "jit/pjit compiles recorded at framework seams"),
            reg.histogram(JIT_COMPILE_SECONDS,
                          "wall time of first-call trace+lower+compile"),
            reg.histogram(JIT_BACKEND_COMPILE_SECONDS,
                          "backend compile time from jax.monitoring events"),
            reg.counter(RECOMPILE_STORM_WARNINGS_TOTAL,
                        "rate-limited retrace-storm warnings emitted"),
        )

    # ------------------------------------------------------------ stepping
    def note_step(self, n: int = 1, fn: Optional[str] = None) -> None:
        """Advance the training-step clock (fit loops call this; a K-step
        fused dispatch advances by K). When ``fn`` names the wrapped program
        that just dispatched, a rolling MFU sample is also recorded — see
        ``_note_mfu``."""
        with self._lock:
            self._step += n
        if fn is not None:
            self._note_mfu(fn, n)

    @property
    def step(self) -> int:
        return self._step

    # ----------------------------------------------------------------- mfu
    def peak_flops(self) -> Optional[float]:
        """Accelerator peak FLOP/s for MFU: ``DL4J_PEAK_FLOPS`` if set, else
        the default device's entry in :data:`PEAK_BF16_FLOPS`
        (:func:`peak_flops_for`) — None on CPU, where the MFU gauge
        deliberately stays silent rather than report a meaningless ratio,
        and an error on an accelerator the table does not know."""
        env = os.environ.get("DL4J_PEAK_FLOPS")
        if env:
            return float(env)
        if not self._backend_peak_resolved:
            import jax

            self._backend_peak = peak_flops_for(jax.devices()[0])
            self._backend_peak_resolved = True
        return self._backend_peak

    def note_executable(self, name: str, compiled: Any) -> None:
        """An AOT seam (compile_cache) built or loaded an executable for
        ``name``: keep it so ``flops_for`` reads its cost analysis directly
        — no second lowering, no second compile."""
        with self._lock:
            self._executables[name] = compiled
            self._cost.pop(name, None)

    def executable(self, name: str) -> Optional[Any]:
        """The executable last noted for ``name`` (its ``as_text()`` and
        ``memory_analysis()`` say what the compiler did), or None."""
        with self._lock:
            return self._executables.get(name)

    def flops_for(self, name: str) -> Optional[float]:
        """FLOPs of ONE training step of the wrapped program ``name``.
        Preference order: a noted executable's own ``cost_analysis()``
        (zero extra work), else the lowering captured at first call —
        ``Lowered.cost_analysis()`` never triggers a second backend
        compile; ``.compile()`` remains only as an API-drift fallback.
        Computed lazily once per (re)compile and cached; XLA counts a scan
        body once regardless of trip count (pinned by test), so the value is
        per-step even for the K-step fused programs. Returns None when no
        analysis is available (never retried until the next compile)."""
        with self._lock:
            if name in self._cost:
                cost = self._cost[name]
                return None if cost is None else cost.get("flops")
            exe = self._executables.get(name)
            lowerable = self._lowerable.get(name)
        cost = None
        if exe is not None:
            try:
                cost = _normalize_cost(exe.cost_analysis())
            except Exception as e:
                log.debug("executable cost analysis failed for %s: %r",
                          name, e)
        if cost is None and lowerable is not None:
            fn, aargs, akwargs = lowerable
            try:
                lowered = fn.lower(*aargs, **akwargs)
                try:
                    cost = _normalize_cost(lowered.cost_analysis())
                except Exception:
                    cost = _normalize_cost(lowered.compile().cost_analysis())
            except Exception as e:  # non-jit wrappee, API drift: MFU off
                log.debug("cost analysis unavailable for %s: %r", name, e)
        with self._lock:
            self._cost[name] = cost
        return None if cost is None else cost.get("flops")

    def _note_mfu(self, fn_name: str, n: int) -> None:
        now = time.perf_counter()
        last = self._mfu_last.get(fn_name)
        self._mfu_last[fn_name] = now
        if last is None:
            return
        elapsed = now - last
        peak = self.peak_flops()
        if elapsed <= 0 or not peak:
            return
        flops = self.flops_for(fn_name)
        if not flops:
            return
        mfu = min(1.0, (flops * n) / (elapsed * peak))
        self.registry.gauge(
            STEP_MFU, "rolling model FLOP utilization per dispatched "
            "program").labels(fn=fn_name).set(mfu)

    # -------------------------------------------------- monitoring bridge
    def _ensure_monitoring(self) -> None:
        if self._monitoring_hooked:
            return
        self._monitoring_hooked = True
        try:
            from jax import monitoring as jmon

            def _on_duration(event: str, duration: float, **kw):
                if "compile" not in event and "lower" not in event:
                    return
                stack = getattr(self._active, "stack", None)
                if not stack:
                    return
                name = stack[-1]
                _, _, backend_hist, _ = self._metrics()
                backend_hist.labels(fn=name).observe(duration)

            jmon.register_event_duration_secs_listener(_on_duration)
        except Exception:  # pragma: no cover - monitoring API moved/absent  # lint: swallowed-exception-ok (tracker degrades to wall timing only)
            pass

    # ------------------------------------------------------------ tracking
    def record_compile(self, name: str, *, cache_key: Any = None,
                       wall_s: float = 0.0, shapes: Any = None,
                       policy: Any = None, cache_hit: bool = False,
                       span: Optional[Tuple[int, int]] = None,
                       **span_fields) -> dict:
        """Record one compile event (the wrap() path calls this; seams that
        build executables eagerly may call it directly). ``cache_hit=True``
        marks a warm load from the executable cache: counted and flight-
        recorded like any compile, but excluded from storm accounting —
        warm loads are the fix for compile storms, not a symptom of one.

        ``span``: the resolution's ``(t0_ns, t1_ns)`` on ``time.time_ns()``'s
        clock where the seam took them (``CachedProgram._build``): the
        ring's one ``compile`` record is then the span ``compile.resolve``,
        with ``span_fields`` beside the event's own."""
        total, wall_hist, _, storm_total = self._metrics()
        total.labels(fn=name).inc()
        if wall_s:
            wall_hist.labels(fn=name).observe(wall_s)
        if policy is None:
            try:
                policy = _policy_key()
            except Exception:
                policy = None
        with self._lock:
            step = self._step
            event = {"fn": name, "step": step, "wall_s": wall_s,
                     "cache_key": repr(cache_key), "shapes": repr(shapes),
                     "policy": repr(policy), "cache_hit": cache_hit}
            self.events.append(event)
            storm = False
            if not cache_hit:
                dq = self._compile_steps.setdefault(
                    name, deque(maxlen=max(64, self.storm_threshold * 4)))
                dq.append(step)
                lo = step - self.storm_window_steps
                recent = sum(1 for s in dq if s >= lo)
                warned = self._last_warned.get(name)
                storm = (recent >= self.storm_threshold
                         and (warned is None
                              or step - warned > self.storm_window_steps))
                if storm:
                    self._last_warned[name] = step
        try:
            from .flight_recorder import global_recorder

            if span is None:
                global_recorder().record("compile", **event)
            else:
                global_recorder().record_span(
                    "compile.resolve", *span, kind="compile", **event,
                    **span_fields)
        except Exception:  # pragma: no cover - recorder import cycle guard  # lint: swallowed-exception-ok (recorder forwarding is best-effort)
            pass
        if storm:
            storm_total.labels(fn=name).inc()
            log.warning(
                "recompile storm: %s compiled %d times in the last %d steps "
                "(step %d, policy=%s) — check for shape churn or dtype-policy "
                "flips; further warnings suppressed for %d steps",
                name, recent, self.storm_window_steps, step, event["policy"],
                self.storm_window_steps)
        return event

    def wrap(self, name: str, fn: Callable, *,
             cache_key: Any = None) -> Callable:
        """Wrap a freshly-built jitted callable. The first call for each new
        abstract argument signature is timed and recorded as a compile; later
        calls with a seen signature pay one dict lookup and a tree-flatten.

        Seams create a NEW wrap per cache entry (``LazyScore._jit`` et al.),
        so a dtype-policy flip — which changes the cache key and rebuilds the
        jit — naturally lands here again and is counted as a fresh compile
        of the same ``name``, which is exactly what the storm detector
        watches for.
        """
        self._ensure_monitoring()
        seen: Dict[Tuple, bool] = {}
        tracker = self

        def tracked(*args, **kwargs):
            try:
                sig = _signature(args, kwargs)
            except Exception:
                sig = None
            if sig is not None and sig in seen:
                return fn(*args, **kwargs)
            stack = getattr(tracker._active, "stack", None)
            if stack is None:
                stack = tracker._active.stack = []
            stack.append(name)
            import time as _time
            t0 = _time.perf_counter()
            try:
                out = fn(*args, **kwargs)
            finally:
                stack.pop()
            wall = _time.perf_counter() - t0
            if sig is not None:
                seen[sig] = True
            tracker._capture_lowerable(name, fn, args, kwargs)
            tracker.record_compile(name, cache_key=cache_key, wall_s=wall,
                                   shapes=None if sig is None else sig[0])
            return out

        tracked.__wrapped__ = fn  # type: ignore[attr-defined]
        tracked.__name__ = getattr(fn, "__name__", name)
        return tracked

    def _capture_lowerable(self, name: str, fn: Callable, args: tuple,
                           kwargs: dict) -> None:
        """Remember the abstract signature of a freshly-compiled program so
        ``flops_for`` can re-lower it later; invalidates any cached cost
        analysis for the name (shapes may have changed)."""
        try:
            import jax

            aargs, akwargs = jax.tree_util.tree_map(
                _abstractify_for_lowering, (args, kwargs))
        except Exception:  # unflattenable args: cost analysis just stays off  # lint: swallowed-exception-ok (MFU degrades to unavailable for this program)
            return
        with self._lock:
            self._lowerable[name] = (fn, aargs, akwargs)
            self._cost.pop(name, None)

    # ------------------------------------------------------------ export
    def snapshot_events(self) -> list:
        with self._lock:
            return list(self.events)

    def snapshot_cost_analyses(self) -> dict:
        """Cached per-program cost analyses (no new lowering/compiling —
        safe to call from a crash dump)."""
        with self._lock:
            return {name: (dict(cost) if cost else None)
                    for name, cost in self._cost.items()}


_GLOBAL = CompileTracker()


def global_tracker() -> CompileTracker:
    """THE process-global tracker the framework seams report into."""
    return _GLOBAL
