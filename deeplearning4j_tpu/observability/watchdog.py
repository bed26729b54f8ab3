"""Step watchdog: a background thread that notices when training stops.

A hung device is the motivating incident: every step call blocks forever,
and without the framework having a notion of "a step should have finished
by now" the hang can only be diagnosed from outside the process. The watchdog is that notion. Fit loops call
``beat(step)`` after every completed dispatch (a near-zero no-op when no
watchdog is installed); the watchdog thread wakes every ``poll_s`` and, once
the wall time since the last beat crosses ``threshold_s``, it

* logs every thread's Python stack at ERROR level (so the hang site is in
  the training log even if the process is later SIGKILLed),
* dumps the flight recorder (reason ``watchdog-stall``), and
* increments ``dl4j_watchdog_stalls_total``

— once per stall: the alarm re-arms on the next heartbeat, so a recovered
run that stalls again is reported again, but a single wedged step produces a
single bundle, not one per poll.
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Optional

from .metrics import global_registry
from .names import WATCHDOG_STALLS_TOTAL

log = logging.getLogger(__name__)

#: default stall threshold — generous enough that a cold-start compile of a
#: large model does not trip it; tune down for small-step production loops
DEFAULT_THRESHOLD_S = 300.0


class StepWatchdog:
    """Watches wall time since the last completed training step.

    The watchdog only arms after the first ``heartbeat()`` — an installed
    but idle watchdog (before ``fit`` is entered, or after it returns) never
    fires. ``start()``/``stop()`` manage the daemon thread; the instance is
    also a context manager.
    """

    def __init__(self, threshold_s: float = DEFAULT_THRESHOLD_S, *,
                 poll_s: Optional[float] = None, recorder=None,
                 registry=None):
        self.threshold_s = float(threshold_s)
        self.poll_s = max(0.01, float(poll_s) if poll_s is not None
                          else min(self.threshold_s / 4.0, 5.0))
        self._recorder = recorder
        self._registry = registry
        self._last_beat: Optional[float] = None
        self._last_step = None
        self._fired = False
        self.stalls = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- wiring
    @property
    def registry(self):
        return self._registry if self._registry is not None \
            else global_registry()

    def _recorder_or_global(self):
        if self._recorder is not None:
            return self._recorder
        from .flight_recorder import global_recorder

        return global_recorder()

    # ---------------------------------------------------------- heartbeat
    def heartbeat(self, step=None) -> None:
        """Record that a training step just completed. Cheap and lock-free
        (two attribute stores); the monitor thread tolerates torn reads."""
        self._last_beat = time.monotonic()
        self._last_step = step
        self._fired = False  # re-arm: training made progress

    # ------------------------------------------------------------ thread
    def start(self) -> "StepWatchdog":
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="dl4j-step-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=max(1.0, self.poll_s * 4))
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            last = self._last_beat
            if last is None or self._fired:
                continue
            stalled = time.monotonic() - last
            if stalled >= self.threshold_s:
                self._fired = True
                self._on_stall(stalled)

    def _on_stall(self, stalled_s: float) -> None:
        self.stalls += 1
        self.registry.counter(
            WATCHDOG_STALLS_TOTAL,
            "training stalls detected by the step watchdog").inc()
        from .flight_recorder import thread_stacks

        log.error(
            "watchdog: no training step completed for %.1fs "
            "(threshold %.1fs, last step %s); all-thread stacks follow\n%s",
            stalled_s, self.threshold_s, self._last_step, thread_stacks())
        rec = self._recorder_or_global()
        rec.record("watchdog_stall", stalled_s=stalled_s,
                   threshold_s=self.threshold_s, step=self._last_step)
        try:
            rec.dump(reason="watchdog-stall")
        except Exception:
            log.exception("watchdog: flight recorder dump failed")


_GLOBAL: Optional[StepWatchdog] = None


def install_watchdog(threshold_s: float = DEFAULT_THRESHOLD_S,
                     **kwargs) -> StepWatchdog:
    """Create, start, and register the process watchdog the fit loops beat.
    Replaces (and stops) any previously installed one."""
    global _GLOBAL
    if _GLOBAL is not None:
        _GLOBAL.stop()
    _GLOBAL = StepWatchdog(threshold_s, **kwargs).start()
    return _GLOBAL


def uninstall_watchdog() -> None:
    global _GLOBAL
    if _GLOBAL is not None:
        _GLOBAL.stop()
        _GLOBAL = None


def global_watchdog() -> Optional[StepWatchdog]:
    return _GLOBAL


def beat(step=None) -> None:
    """Heartbeat hook for the fit loops: one global read + an early return
    when no watchdog is installed, so always-on call sites cost nothing."""
    wd = _GLOBAL
    if wd is not None:
        wd.heartbeat(step)
