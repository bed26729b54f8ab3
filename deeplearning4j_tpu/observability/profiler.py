"""Process-global trace capture engine: TraceSession + capture triggers.

``jax.profiler.start_trace`` is a process singleton — two owners (a
``ProfilerListener`` window and a bench/script capture, say) calling it
concurrently raise from inside a fit loop. This module is the single locked
door in front of it:

* :class:`TraceSession` — one capture at a time, enforced with a lock;
  a collision logs a warning, bumps ``dl4j_profile_collisions_total`` and
  no-ops (``start`` returns None) instead of raising. Every completed
  capture is summarized by :mod:`.xplane` into ``attribution.json`` next to
  the trace, mirrored into ``dl4j_profile_*`` gauges, recorded in the
  flight-recorder ring, and registered in a persistent sqlite index
  (:class:`~deeplearning4j_tpu.ui.storage.FileStatsStorage`) so profiles
  survive process death the way flight-recorder bundles do.
* :class:`StepAnomalyWatcher` — the ``DL4J_PROFILE_TRIGGER=anomaly`` mode:
  watches per-dispatch wall times (the ``dl4j_fit_phase_seconds`` dispatch
  phase, fed via :func:`note_dispatch` from the fit loops), and when a step
  exceeds ``k x rolling-p50`` starts a capture over the next dispatches —
  once per cool-down, so a pathological run cannot trace itself to death.

Env knobs: ``DL4J_PROFILE_TRIGGER`` (off | anomaly),
``DL4J_PROFILE_DIR`` (base directory, default ``profiles/``),
``DL4J_PROFILE_ANOMALY_K`` (default 3.0), ``DL4J_PROFILE_COOLDOWN_S``
(default 600), ``DL4J_PROFILE_WINDOW`` (dispatches per capture, default 2).
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import statistics
import threading
import time
from collections import deque
from typing import Optional

from . import xplane
from .metrics import global_registry
from .names import (PROFILE_ACTIVE, PROFILE_CAPTURE_SECONDS,
                    PROFILE_CAPTURES_TOTAL, PROFILE_CATEGORY_SHARE,
                    PROFILE_COLLISIONS_TOTAL)

log = logging.getLogger(__name__)

TRIGGER_ENV = "DL4J_PROFILE_TRIGGER"
DIR_ENV = "DL4J_PROFILE_DIR"
ANOMALY_K_ENV = "DL4J_PROFILE_ANOMALY_K"
COOLDOWN_ENV = "DL4J_PROFILE_COOLDOWN_S"
WINDOW_ENV = "DL4J_PROFILE_WINDOW"

DEFAULT_BASE_DIR = "profiles"
ATTRIBUTION_FILE = "attribution.json"
INDEX_DB = "profile_index.db"

#: index keying: one fixed session so every process appends to the same
#: stream; the worker id is the pid, the row timestamp orders entries
_INDEX_SESSION = "profiles"
_INDEX_TYPE = "ProfileRecord"


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


class ProfileRecord:
    """Persistable wrapper over one capture's JSON payload (duck-typed to
    ui.storage.Persistable so the sqlite index is the same machinery that
    stores training stats)."""

    def __init__(self, payload: dict):
        self.payload = payload

    def get_session_id(self) -> str:
        return _INDEX_SESSION

    def get_type_id(self) -> str:
        return _INDEX_TYPE

    def get_worker_id(self) -> str:
        return str(self.payload.get("pid", 0))

    def get_timestamp(self) -> int:
        return int(float(self.payload.get("ts", 0.0)) * 1000)

    def encode(self) -> bytes:
        return json.dumps(self.payload).encode("utf-8")

    @classmethod
    def decode(cls, data: bytes) -> "ProfileRecord":
        return cls(json.loads(data.decode("utf-8")))


class TraceSession:
    """Single-owner lock over the process-global jax profiler.

    ``start()`` claims the profiler (returns the trace directory, or None on
    collision/failure — never raises); ``stop()`` ends the trace, writes
    ``attribution.json``, updates gauges/counters, records a flight-recorder
    event and appends to the persistent index. The ``capture()`` context
    manager pairs them for exact windows.
    """

    def __init__(self, base_dir: Optional[str] = None, registry=None,
                 recorder=None):
        self.base_dir = base_dir or os.environ.get(DIR_ENV) \
            or DEFAULT_BASE_DIR
        self._lock = threading.Lock()
        self._current: Optional[dict] = None
        self._registry = registry
        self._recorder = recorder
        self._index = None

    # ------------------------------------------------------------- plumbing
    def _reg(self):
        return self._registry if self._registry is not None \
            else global_registry()

    def _rec(self):
        if self._recorder is not None:
            return self._recorder
        from .flight_recorder import global_recorder
        return global_recorder()

    @property
    def active(self) -> Optional[str]:
        """Trigger name of the live capture, or None when idle."""
        cur = self._current
        return cur["trigger"] if cur else None

    # -------------------------------------------------------------- capture
    def start(self, trigger: str = "manual",
              logdir: Optional[str] = None) -> Optional[str]:
        """Claim the profiler and start tracing into ``logdir`` (default: a
        fresh ``<base_dir>/<trigger>-<stamp>`` directory). Returns the trace
        directory, or None when another capture owns the profiler or jax
        refuses — callers inside fit loops need never guard this."""
        with self._lock:
            if self._current is not None:
                log.warning(
                    "TraceSession: %r capture already active; ignoring "
                    "%r capture request", self._current["trigger"], trigger)
                self._reg().counter(
                    PROFILE_COLLISIONS_TOTAL,
                    "trace capture requests refused because one was live"
                ).labels(trigger=trigger).inc()
                return None
            # claim before releasing the lock so a racing start() collides
            self._current = {"trigger": trigger, "logdir": None,
                             "t0": time.time()}
        sub = logdir or os.path.join(
            self.base_dir,
            f"{trigger}-{time.strftime('%Y%m%d-%H%M%S')}-p{os.getpid()}")
        try:
            os.makedirs(sub, exist_ok=True)
            import jax
            jax.profiler.start_trace(sub)
        except Exception as e:  # profiler/FS refusal must not kill a fit loop
            log.warning("TraceSession: start_trace(%s) failed: %r", sub, e)
            with self._lock:
                self._current = None
            self._reg().counter(
                PROFILE_COLLISIONS_TOTAL,
                "trace capture requests refused because one was live"
            ).labels(trigger=trigger).inc()
            return None
        # the collision guard means one live capture, but stop() hands
        # _current off under the lock — mutate it under the same lock so
        # a concurrent stop never sees a half-written record
        with self._lock:
            if self._current is not None:
                self._current["logdir"] = sub
        self._reg().gauge(PROFILE_ACTIVE,
                          "1 while a profiler trace is being captured").set(1)
        rec = self._rec()
        if rec is not None:
            rec.record("profile_start", trigger=trigger, logdir=sub)
        log.info("TraceSession: capturing %r trace into %s", trigger, sub)
        return sub

    def stop(self, summarize: bool = True) -> Optional[dict]:
        """End the live capture. Returns the attribution summary (or an
        ``{"error": ...}`` record when parsing failed, or None when no
        capture was live / ``summarize=False``). Never raises."""
        cur = self._current
        if cur is None or cur["logdir"] is None:
            log.warning("TraceSession.stop: no active capture")
            return None
        trigger, logdir = cur["trigger"], cur["logdir"]
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:  # a failed stop still releases the session
            log.warning("TraceSession: stop_trace failed: %r", e)
        duration_s = time.time() - cur["t0"]
        summary = None
        summary_path = None
        if summarize:
            summary = xplane.summarize(logdir)
            summary_path = os.path.join(logdir, ATTRIBUTION_FILE)
            try:
                with open(summary_path, "w") as f:
                    json.dump(summary, f, indent=1)
                    f.write("\n")
            except OSError as e:
                log.warning("TraceSession: could not write %s: %r",
                            summary_path, e)
                summary_path = None
            for cat, pct in (summary.get("categories_pct") or {}).items():
                self._reg().gauge(
                    PROFILE_CATEGORY_SHARE,
                    "per-category %% of self time in the latest trace"
                ).labels(category=cat).set(pct)
        reg = self._reg()
        reg.counter(PROFILE_CAPTURES_TOTAL,
                    "completed profiler trace captures").labels(
                        trigger=trigger).inc()
        reg.histogram(PROFILE_CAPTURE_SECONDS,
                      "wall seconds each trace capture stayed open").observe(
                          duration_s)
        reg.gauge(PROFILE_ACTIVE,
                  "1 while a profiler trace is being captured").set(0)
        entry = {
            "ts": cur["t0"], "pid": os.getpid(), "trigger": trigger,
            "logdir": logdir, "duration_s": round(duration_s, 3),
            "summary_path": summary_path,
            "error": (summary or {}).get("error"),
            "categories_pct": (summary or {}).get("categories_pct"),
        }
        self._index_put(entry)
        rec = self._rec()
        if rec is not None:
            rec.record("profile_capture", trigger=trigger, logdir=logdir,
                       duration_s=round(duration_s, 3),
                       error=entry["error"])
        with self._lock:
            self._current = None
        return summary

    @contextlib.contextmanager
    def capture(self, trigger: str = "manual", logdir: Optional[str] = None):
        """``with session.capture("bench") as logdir:`` — exact windows;
        yields None (and skips the stop) when the session was busy."""
        got = self.start(trigger, logdir)
        try:
            yield got
        finally:
            if got is not None:
                self.stop()

    # ---------------------------------------------------------------- index
    def _index_storage(self):
        if self._index is None:
            os.makedirs(self.base_dir, exist_ok=True)
            from ..ui.storage import FileStatsStorage
            self._index = FileStatsStorage(
                os.path.join(self.base_dir, INDEX_DB))
        return self._index

    def _index_put(self, entry: dict) -> None:
        try:
            self._index_storage().put_update(ProfileRecord(entry))
        except Exception as e:  # index damage must not fail the capture path
            log.warning("TraceSession: could not index capture: %r", e)

    def index_entries(self) -> list:
        """All captures ever indexed under ``base_dir``, newest first —
        across process restarts (the ``/train/profiles`` payload)."""
        try:
            st = self._index_storage()
            entries = []
            for wid in st.list_worker_ids_for_session(_INDEX_SESSION):
                for blob in st.get_all_updates_after(
                        _INDEX_SESSION, _INDEX_TYPE, wid, -1):
                    try:
                        entries.append(ProfileRecord.decode(blob).payload)
                    except (ValueError, UnicodeDecodeError):
                        continue
        except Exception as e:  # a corrupt index reads as empty, not a crash
            log.warning("TraceSession: could not read index: %r", e)
            return []
        entries.sort(key=lambda e: -float(e.get("ts") or 0.0))
        return entries


_GLOBAL_SESSION: Optional[TraceSession] = None
_GLOBAL_SESSION_LOCK = threading.Lock()


def global_trace_session() -> TraceSession:
    """THE session every capture path shares — ProfilerListener windows,
    bench attribution, the anomaly watcher, scripts."""
    global _GLOBAL_SESSION
    with _GLOBAL_SESSION_LOCK:
        if _GLOBAL_SESSION is None:
            _GLOBAL_SESSION = TraceSession()
        return _GLOBAL_SESSION


def set_global_trace_session(
        session: Optional[TraceSession]) -> Optional[TraceSession]:
    """Swap the global session (tests); returns the previous one."""
    global _GLOBAL_SESSION
    with _GLOBAL_SESSION_LOCK:
        prev, _GLOBAL_SESSION = _GLOBAL_SESSION, session
        return prev


# ------------------------------------------------------------ anomaly trigger
class StepAnomalyWatcher:
    """Auto-capture when a dispatch exceeds ``k x rolling-p50``.

    ``observe(seconds)`` is called once per fit-loop dispatch (via
    :func:`note_dispatch`). It keeps a rolling window of recent dispatch
    times; once ``min_samples`` have accumulated, a dispatch slower than
    ``k`` times the median starts an ``anomaly`` capture spanning the next
    ``capture_dispatches`` dispatches, then stops and summarizes. At most
    one capture per ``cooldown_s`` (the clock is injectable for tests).
    Anomalous and traced dispatches are excluded from the baseline so one
    stall cannot drag the median up and mask the next one. Nothing in here
    may raise into the fit loop.
    """

    def __init__(self, session: Optional[TraceSession] = None,
                 k: Optional[float] = None, window: int = 128,
                 min_samples: int = 16,
                 cooldown_s: Optional[float] = None,
                 capture_dispatches: Optional[int] = None,
                 clock=time.monotonic):
        self.session = session
        self.k = k if k is not None else _env_float(ANOMALY_K_ENV, 3.0)
        self.cooldown_s = cooldown_s if cooldown_s is not None \
            else _env_float(COOLDOWN_ENV, 600.0)
        self.capture_dispatches = capture_dispatches \
            if capture_dispatches is not None else _env_int(WINDOW_ENV, 2)
        self.min_samples = max(2, int(min_samples))
        self._times: deque = deque(maxlen=max(self.min_samples, int(window)))
        self._clock = clock
        self._cooldown_until = float("-inf")
        self._capturing_left = 0
        self.fired = 0  #: anomaly captures started (tests / debugging)

    def _session(self) -> TraceSession:
        return self.session if self.session is not None \
            else global_trace_session()

    def observe(self, seconds: float) -> None:
        try:
            self._observe(float(seconds))
        except Exception:  # lint: swallowed-exception-ok (trigger failure must never propagate into the fit loop; the log line is the record)
            log.exception("StepAnomalyWatcher: observe failed")

    def _observe(self, seconds: float) -> None:
        if self._capturing_left > 0:
            # dispatches running under the trace: count down, then close the
            # window; traced steps never feed the baseline (trace overhead)
            self._capturing_left -= 1
            if self._capturing_left == 0:
                self._session().stop()
            return
        if len(self._times) >= self.min_samples:
            p50 = statistics.median(self._times)
            if p50 > 0 and seconds > self.k * p50 \
                    and self._clock() >= self._cooldown_until:
                self._cooldown_until = self._clock() + self.cooldown_s
                logdir = self._session().start("anomaly")
                if logdir is not None:
                    self.fired += 1
                    self._capturing_left = max(1, self.capture_dispatches)
                    log.warning(
                        "StepAnomalyWatcher: dispatch %.3fs > %.1fx p50 "
                        "%.3fs; capturing %d dispatches into %s",
                        seconds, self.k, p50, self._capturing_left, logdir)
                    rec = self._session()._rec()
                    if rec is not None:
                        # bundle-link: when a flight-recorder dump dir is
                        # armed the anomaly also writes a bundle whose ring
                        # holds the slow step + the profile_start event
                        rec.dump(reason="profile-anomaly",
                                 extra={"logdir": logdir,
                                        "dispatch_s": seconds,
                                        "p50_s": p50, "k": self.k})
                return  # the anomalous sample never enters the baseline
        self._times.append(seconds)


# The fit-loop hook resolves its watcher lazily from the environment exactly
# once, so the disabled case (no DL4J_PROFILE_TRIGGER) costs two global
# reads per dispatch — inside the telemetry overhead budget.
_WATCHER: Optional[StepAnomalyWatcher] = None
_WATCHER_RESOLVED = False
_WATCHER_LOCK = threading.Lock()


def install_anomaly_watcher(watcher: StepAnomalyWatcher) -> None:
    """Explicitly install a watcher (tests; overrides env resolution)."""
    global _WATCHER, _WATCHER_RESOLVED
    with _WATCHER_LOCK:
        _WATCHER = watcher
        _WATCHER_RESOLVED = True


def uninstall_anomaly_watcher() -> None:
    """Remove the watcher and re-arm env resolution for the next dispatch."""
    global _WATCHER, _WATCHER_RESOLVED
    with _WATCHER_LOCK:
        _WATCHER = None
        _WATCHER_RESOLVED = False


def _resolve_watcher() -> Optional[StepAnomalyWatcher]:
    global _WATCHER, _WATCHER_RESOLVED
    with _WATCHER_LOCK:
        if not _WATCHER_RESOLVED:
            if os.environ.get(TRIGGER_ENV, "").strip() == "anomaly":
                _WATCHER = StepAnomalyWatcher()
            _WATCHER_RESOLVED = True
        return _WATCHER


def note_dispatch(seconds: float) -> None:
    """Fit-loop hook: feed one dispatch wall time to the anomaly trigger
    (no-op unless ``DL4J_PROFILE_TRIGGER=anomaly`` or a watcher was
    installed). Never raises."""
    w = _WATCHER
    if w is None:
        if _WATCHER_RESOLVED:
            return
        w = _resolve_watcher()
        if w is None:
            return
    w.observe(seconds)
