"""Flight recorder: a bounded in-memory event log with crash-time egress.

The metrics registry answers "what is the current value of X"; it cannot
answer "what was the training loop doing in the seconds before it died".
A device that drops mid-run makes the gap concrete: the only record is
whatever an outside observer kept — the framework itself has nothing to
say. The flight recorder is that memory: every fit
path appends cheap, structured step events (step index, dispatch wall time,
batch size, K-group size) to a process-global ring buffer, the compile
tracker appends compile events, and the health monitor / watchdog append
alarms. When something goes wrong — an exception escapes a fit loop, a
health alarm fires, the watchdog detects a stall, or an operator sends
SIGUSR1 — ``dump()`` writes a self-contained diagnostic bundle.

Design constraints:

* **Hot-path cost.** ``record()`` (and ``record_span()``, its form for a
  finished interval) is one dict build + a locked deque append
  — no registry traffic, no device syncs, no I/O. Call sites record once
  per *dispatch* (per K-step group), not per iteration. Events must carry
  host values only (ints/floats/strings); recording a device array would
  make ``dump()`` block on the device, which is exactly what a hang dump
  must never do.
* **Dump never touches the device.** The bundle is assembled entirely from
  host state: the ring buffer, the registry snapshot, cached compile/cost
  data, and ``sys._current_frames()``. Device info is included only when
  the JAX backend was already initialized by the process — ``dump()`` never
  initializes (or waits on) a backend, so it is safe to call from a signal
  handler while the device is wedged.
* **Kill switch.** ``set_enabled(False)`` turns ``record()`` into a no-op,
  mirroring the registry's switch; ``dump()`` still works on whatever was
  recorded.
"""
from __future__ import annotations

import functools
import json
import logging
import os
import re
import signal
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Dict, List, Optional

from .metrics import global_registry
from .names import FLIGHT_DUMPS_TOTAL

log = logging.getLogger(__name__)

#: default ring capacity — at one event per K-step dispatch this is hours of
#: training history for a few hundred KB of host memory
DEFAULT_CAPACITY = 4096

#: environment variable configuring the default dump directory (the same
#: knob --flight-recorder-dir sets on bench.py / cli.py)
DUMP_DIR_ENV = "DL4J_FLIGHT_RECORDER_DIR"

#: environment variables worth snapshotting into the bundle (prefix match)
_ENV_PREFIXES = ("JAX_", "XLA_", "DL4J_", "PALLAS_", "BENCH_", "TPU_",
                 "LIBTPU_")


def _slug(text: str, max_len: int = 48) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]+", "-", str(text)).strip("-")[:max_len] \
        or "dump"


def thread_stacks() -> str:
    """Per-thread Python stack dump from ``sys._current_frames()`` — the
    'where is everyone stuck' section of the bundle, also logged verbatim by
    the watchdog when a stall fires."""
    names = {t.ident: t.name for t in threading.enumerate()}
    lines: List[str] = []
    for ident, frame in sorted(sys._current_frames().items()):
        lines.append(f"--- thread {names.get(ident, '<unknown>')} "
                     f"(ident {ident}) ---")
        lines.extend(s.rstrip("\n") for s in traceback.format_stack(frame))
        lines.append("")
    return "\n".join(lines) + "\n"


def _backend_initialized() -> bool:
    """True only if a JAX backend ALREADY exists in this process. Never
    initializes one — a dump from a process whose device link is dead (the
    bench parent after an outage) must not block dialing the backend."""
    mods = sys.modules
    if "jax" not in mods:
        return False
    try:
        xb = mods.get("jax._src.xla_bridge")
        backends = getattr(xb, "_backends", None)
        return bool(backends)
    except Exception:  # pragma: no cover - private API moved  # lint: swallowed-exception-ok (environment capture degrades to host-only info)
        return False


def collect_environment() -> dict:
    """Host + (when safely available) device environment for the bundle."""
    info: Dict[str, Any] = {
        "time": time.time(),
        "pid": os.getpid(),
        "argv": list(sys.argv),
        "cwd": os.getcwd(),
        "python": sys.version,
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(_ENV_PREFIXES)},
    }
    try:
        import platform

        info["platform"] = platform.platform()
    except Exception:  # pragma: no cover  # lint: swallowed-exception-ok (platform string is best-effort decoration)
        pass
    if "jax" in sys.modules:
        try:
            import jax

            info["jax_version"] = jax.__version__
        except Exception:  # pragma: no cover  # lint: swallowed-exception-ok (version capture is best-effort)
            pass
    if _backend_initialized():
        try:
            import jax

            devs = jax.devices()
            info["backend"] = devs[0].platform if devs else None
            info["device_count"] = len(devs)
            info["local_device_count"] = jax.local_device_count()
            info["devices"] = [
                {"id": d.id, "platform": d.platform,
                 "process_index": d.process_index,
                 "kind": getattr(d, "device_kind", "")} for d in devs]
        except Exception as e:  # backend present but unhealthy — say so
            info["devices_error"] = repr(e)
        try:
            from deeplearning4j_tpu import common

            info["dtype_policy"] = repr(common.policy_key())
        except Exception:  # pragma: no cover  # lint: swallowed-exception-ok (policy key is best-effort decoration)
            pass
    return info


def _jsonable(obj):
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        return repr(obj)


class FlightRecorder:
    """Process-global, thread-safe ring buffer of structured events with a
    ``dump()`` that writes a self-contained diagnostic bundle.

    One global instance (``global_recorder()``) is shared by the fit loops,
    compile tracker, health monitor, and watchdog; tests construct private
    ones with small capacities.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 dump_dir: Optional[str] = None, registry=None):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max(1, int(capacity)))
        self._enabled = True
        self._dropped = 0
        self._dump_seq = 0
        self._registry = registry
        self.dump_dir = dump_dir if dump_dir is not None \
            else os.environ.get(DUMP_DIR_ENV) or None

    # -------------------------------------------------------------- control
    @property
    def capacity(self) -> int:
        return self._events.maxlen

    def set_enabled(self, flag: bool) -> None:
        """Kill switch: False turns every ``record()`` into a no-op
        (mirrors MetricsRegistry.set_enabled; dump still works)."""
        self._enabled = bool(flag)

    @property
    def enabled(self) -> bool:
        return self._enabled

    def set_dump_dir(self, path: Optional[str]) -> None:
        """Configure where unhandled-exception / alarm / signal dumps land.
        None disables automatic dumps (explicit ``dump(dir=...)`` still
        works)."""
        self.dump_dir = path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    # ------------------------------------------------------------ recording
    def record(self, kind: str, **fields) -> None:
        """Append one structured event. Host values only (ints, floats,
        strings) — never device arrays; see the module docstring."""
        if not self._enabled:
            return
        self._append({"kind": kind, "ts": time.time(), **fields})

    def _append(self, event: dict) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self._dropped += 1
            self._events.append(event)

    def record_span(self, name: str, t0_ns: int, t1_ns: int, *,
                    kind: str = "span", **fields) -> None:
        """Append one finished span, written once, at its end: ``[t0_ns,
        t1_ns]`` on ``time.time_ns()``'s clock (``CLOCK_REALTIME``, the clock
        a profiler trace's ``profile_start_time`` is on, so a reader lays the
        span over the device's timeline with one addition) and the thread
        it ran on. ``group`` is the staged K-step group the work belongs to,
        ``cause`` the name of the span of that group it followed from."""
        if not self._enabled:
            return
        self._append({"kind": kind, "ts": t1_ns * 1e-9, "name": name,
                      "t0_ns": t0_ns, "t1_ns": t1_ns,
                      "thread": threading.current_thread().name, **fields})

    def snapshot(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        """Events evicted by the ring bound since the last clear()."""
        return self._dropped

    # ---------------------------------------------------------------- dump
    def _registry_or_global(self):
        return self._registry if self._registry is not None \
            else global_registry()

    def dump(self, dir: Optional[str] = None, reason: str = "manual",
             extra: Optional[dict] = None) -> Optional[str]:
        """Write a diagnostic bundle; returns its path, or None when no
        directory is configured (automatic dump sites are then free no-ops).

        Bundle contents (every section is always written, so consumers can
        rely on the file set): ``manifest.json``, ``events.jsonl``,
        ``metrics.json``, ``environment.json``, ``threads.txt``,
        ``cost_analysis.json``, and ``extra.json`` when ``extra`` is given.
        """
        base = dir or self.dump_dir
        if base is None:
            return None
        with self._lock:
            self._dump_seq += 1
            seq = self._dump_seq
            events = list(self._events)
            dropped = self._dropped
        stamp = time.strftime("%Y%m%d-%H%M%S")
        name = f"flight-{stamp}-p{os.getpid()}-{seq:03d}-{_slug(reason)}"
        path = os.path.join(base, name)
        try:
            os.makedirs(path, exist_ok=True)
            files = []

            def write_json(fname, obj):
                with open(os.path.join(path, fname), "w") as f:
                    json.dump(obj, f, indent=2, default=repr)
                    f.write("\n")
                files.append(fname)

            with open(os.path.join(path, "events.jsonl"), "w") as f:
                for ev in events:
                    f.write(json.dumps(
                        {k: _jsonable(v) for k, v in ev.items()}) + "\n")
            files.append("events.jsonl")
            write_json("metrics.json", self._registry_or_global().snapshot())
            write_json("environment.json", collect_environment())
            with open(os.path.join(path, "threads.txt"), "w") as f:
                f.write(thread_stacks())
            files.append("threads.txt")
            write_json("cost_analysis.json", self._cost_analysis_section())
            if extra is not None:
                write_json("extra.json",
                           {k: _jsonable(v) for k, v in extra.items()})
            write_json("manifest.json", {
                "reason": reason, "ts": time.time(), "pid": os.getpid(),
                "events": len(events), "events_dropped": dropped,
                "capacity": self.capacity, "files": files + ["manifest.json"],
            })
        except OSError as e:
            log.error("flight recorder could not write bundle %s: %r",
                      path, e)
            return None
        self._registry_or_global().counter(
            FLIGHT_DUMPS_TOTAL,
            "flight-recorder diagnostic bundles written").labels(
                reason=_slug(reason)).inc()
        log.warning("flight recorder: wrote diagnostic bundle %s (%s)",
                    path, reason)
        return path

    @staticmethod
    def _cost_analysis_section() -> dict:
        """Cached compile/cost data only — computing a fresh cost analysis
        would compile, and a dump taken during a hang must not."""
        try:
            from .compile_tracker import global_tracker

            t = global_tracker()
            return {"step": t.step,
                    "compile_events": t.snapshot_events(),
                    "cost_analyses": t.snapshot_cost_analyses()}
        except Exception as e:  # tracker import/shape drift must not kill a crash dump
            return {"error": repr(e)}

    def list_bundles(self, dir: Optional[str] = None) -> List[dict]:
        """Manifests of the bundles under the dump directory, newest first
        (the UI server's ``/train/health/bundles`` payload)."""
        base = dir or self.dump_dir
        out: List[dict] = []
        if not base or not os.path.isdir(base):
            return out
        for entry in sorted(os.listdir(base), reverse=True):
            manifest = os.path.join(base, entry, "manifest.json")
            if not os.path.isfile(manifest):
                continue
            try:
                with open(manifest) as f:
                    m = json.load(f)
            except (OSError, ValueError):
                m = {"error": "unreadable manifest"}
            m["path"] = os.path.join(base, entry)
            out.append(m)
        return out


_GLOBAL = FlightRecorder()


def global_recorder() -> FlightRecorder:
    """THE process-global recorder the fit loops and alarm paths write to."""
    return _GLOBAL


# ------------------------------------------------------- exception egress
def dump_on_unhandled(site: str):
    """Decorator for the fit entry points: an exception escaping the wrapped
    call records an event and (when a dump dir is configured) writes one
    bundle, then re-raises. Nested decorated frames (fit -> fit_iterator)
    dump once — the exception object is marked after the first bundle.
    ``{cls}`` in ``site`` is the class of the object the method was called
    on, for a method that several classes share."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            try:
                return fn(*args, **kwargs)
            except Exception as e:
                _note_unhandled(
                    site.format(cls=type(args[0]).__name__) if args else site,
                    e)
                raise
        return wrapper

    return deco


def _note_unhandled(site: str, e: BaseException) -> None:
    rec = global_recorder()
    rec.record("exception", site=site, error=repr(e)[:500])
    if getattr(e, "_dl4j_recorder_dumped", False):
        return
    try:
        if rec.dump(reason=f"exception-{site}") is not None:
            e._dl4j_recorder_dumped = True
    except Exception:
        # the dump must never mask the training error being propagated
        log.exception("flight recorder dump failed while handling an "
                      "exception from %s", site)


# --------------------------------------------------------- signal egress
def install_signal_handlers(recorder: Optional[FlightRecorder] = None,
                            signals: Optional[tuple] = None) -> dict:
    """Opt-in SIGTERM/SIGUSR1 dump hooks (main thread only — CPython signal
    rule). SIGUSR1 is a live diagnostic poke: dump and keep running. SIGTERM
    dumps, then chains to the previous handler (or re-raises the default
    termination) so orchestrator kills still terminate the process. Returns
    the {signum: previous_handler} map for ``uninstall_signal_handlers``."""
    # explicit None check: an EMPTY recorder is falsy (__len__ == 0)
    rec = recorder if recorder is not None else global_recorder()
    sigs = signals or (signal.SIGTERM, signal.SIGUSR1)
    previous: dict = {}

    def handler(signum, frame):
        try:
            sig_name = signal.Signals(signum).name
        except ValueError:
            sig_name = str(signum)
        rec.record("signal", signum=signum, name=sig_name)
        try:
            rec.dump(reason=f"signal-{sig_name}")
        except Exception:
            log.exception("flight recorder dump failed in %s handler",
                          sig_name)
        prev = previous.get(signum)
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL and signum != signal.SIGUSR1:
            # restore the default disposition and re-deliver so SIGTERM
            # still terminates after the dump
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)

    for s in sigs:
        previous[s] = signal.signal(s, handler)
    return previous


def uninstall_signal_handlers(previous: dict) -> None:
    for signum, prev in previous.items():
        signal.signal(signum, prev)
