"""Warm-start compile plane: persistent executable cache + AOT warmup.

Every cold path in the system is compile-bound — elastic respawn recovery,
replica spawn and hot swap, decode-engine bucket growth all stall on XLA
rebuilding programs it has already built in a previous process (or an
earlier version of the same model). The reference stack never pays this
tax twice: cuDNN persists its algorithm-selection cache and DL4J
pre-allocates workspaces before training starts. This module is that
analog for the jit seams.

Two halves:

* ``CompileCache`` — a bounded on-disk store of serialized XLA executables
  (``jax.experimental.serialize_executable``), keyed by a fingerprint of
  everything that could change the compiled program: abstract input
  signature, donation config, seam cache key (dtype policy et al.), model
  config hash, jax version, backend platform/device kind/device count.
  Writes are atomic (tmp + ``os.replace``); torn, truncated, or
  version-mismatched entries are quarantined and fall back to a normal
  compile — corruption is never an error, only a cache miss.

* ``CachedProgram`` — the callable the three compile seams hand out
  (``LazyScore._jit``, ``compile_seam.compile_step``, and through them the
  decode engine's per-bucket step builders). Per abstract signature it
  resolves ONE executable: disk hit -> ``deserialize_and_load`` (recorded
  as a cache-hit compile so storm warnings don't fire), miss ->
  ``jitted.lower().compile()`` AOT, serialized back to disk. Dispatch
  after resolution is a dict lookup + the executable call — measured at
  parity with jit's own dispatch on CPU. ``warm()`` resolves a signature
  from ShapeDtypeStructs without executing, which is what parallel AOT
  warmup (ModelRegistry pin, ReplicaSet construction, decode pre-warm)
  builds on.

Placement (:func:`cache_root`): the store is the ``executables``
subdirectory of JAX's own persistent compilation cache, so one directory
holds everything a warm start needs. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, that is the root and JAX is left to read its variable; where it is
not, both go to ``.jax_cache`` at the root of the checkout — a fixed path,
because the path is part of what a cache key means (a directory that moves
never hits). The test suite repoints the variable per test; elastic ships
the resolved root to spawned workers.

An executable is bound to the devices it was compiled for (on JAX 0.9 a
serialized executable carries its device assignment and cannot be moved):
the fingerprint includes the device ids of its inputs' placement, the entry
records the assignment, and a hit is loaded onto exactly those devices. A
replica on a second chip therefore compiles once for that chip and
warm-starts there from then on.

Kill switch: ``DL4J_COMPILE_CACHE=0`` makes ``build_program`` return the
exact pre-existing ``tracker.wrap(jax.jit(...))`` path — no disk, no AOT.
``DL4J_COMPILE_CACHE_EPOCH`` salts the fingerprint for manual invalidation
without deleting files.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile
import threading
import time
import zlib
from typing import Any, Callable, Dict, Optional, Tuple

from deeplearning4j_tpu.datasets.prefetch import current_group
from deeplearning4j_tpu.observability.compile_tracker import (_signature,
                                                              global_tracker)
from deeplearning4j_tpu.observability.flight_recorder import global_recorder
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.observability.names import (
    COMPILE_CACHE_BYTES, COMPILE_CACHE_HITS_TOTAL, COMPILE_CACHE_LOAD_SECONDS,
    COMPILE_CACHE_MISSES_TOTAL, WARMUP_SECONDS)

log = logging.getLogger(__name__)

#: on-disk entry format: MAGIC + sha256(body) + body, body = zlib(pickle).
#: Serialized TPU executables compress about threefold (ResNet-50's K-step
#: train program: 126 MB raw), which is what keeps a chip machine's cache
#: directory under its size limit. Bump the magic when the layout changes —
#: old entries then read as version-mismatched and are quarantined on first
#: touch.
MAGIC = b"DL4JXC02"
_DIGEST_LEN = 32

#: part of every fingerprint; bump it when the seams' programs change in a
#: way that no argument, option or configuration shows. Operation metadata is
#: such a change: the ``jax.named_scope`` names a profile's reader looks for
#: are in neither this store's key nor (by default) JAX's own, so an entry
#: written before the scopes existed would be loaded for the program that has
#: them, and every scope would read nothing.
PROGRAM_REV = "named-scopes-12-mamba-core"

_DEFAULT_MAX_MB = 512.0


def enabled() -> bool:
    """The kill switch: ``DL4J_COMPILE_CACHE=0`` restores the plain
    ``tracker.wrap(jax.jit(...))`` compile path everywhere."""
    return os.environ.get("DL4J_COMPILE_CACHE", "1").lower() \
        not in ("0", "off", "false")


#: home of both persistent caches when JAX_COMPILATION_CACHE_DIR is unset
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_root() -> str:
    """THE placement helper: root directory of JAX's persistent compilation
    cache and (in ``executables/``) of this module's store. A set
    ``JAX_COMPILATION_CACHE_DIR`` is returned as is — JAX reads the variable
    itself, nothing here repoints it. Unset, JAX's cache is pointed at the
    checkout's ``.jax_cache`` and that is returned."""
    root = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if root:
        return root
    import jax

    if jax.config.jax_compilation_cache_dir != _CHECKOUT_CACHE:
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE


def cache_dir() -> str:
    """Resolved executable-store directory (not necessarily created yet)."""
    return os.path.join(cache_root(), "executables")


def _max_bytes() -> int:
    try:
        mb = float(os.environ.get("DL4J_COMPILE_CACHE_MAX_MB",
                                  _DEFAULT_MAX_MB))
    except ValueError:
        mb = _DEFAULT_MAX_MB
    return int(mb * 1024 * 1024)


def _backend_key() -> Tuple:
    """Everything about the runtime that invalidates an executable: jax
    version, backend platform, device kind, visible device count (a parent
    on an 8-device host mesh and its 1-device elastic child must never
    share entries), and the JAX settings that change what a trace lowers to
    without changing any argument's shape or dtype."""
    import jax

    devs = jax.devices()
    return (jax.__version__, jax.default_backend(),
            devs[0].device_kind if devs else "none", len(devs),
            jax.config.jax_default_matmul_precision,
            jax.config.jax_enable_x64)


def _trace_env() -> Tuple:
    """Every ``DL4J_*`` switch in the environment, the store's own knobs
    aside. Kernel gates and dtype knobs are read at trace time and baked
    into the program, so an executable traced under one setting must never
    be handed to a process running under another (an A/B that flips
    ``DL4J_TPU_DISABLE_PALLAS`` would otherwise measure one side twice)."""
    return tuple(sorted(
        (k, v) for k, v in os.environ.items()
        if k.startswith("DL4J_") and not k.startswith("DL4J_COMPILE_CACHE")))


def conf_fingerprint(conf: Any) -> str:
    """Stable hash of a model configuration (serde JSON when available).
    Two structurally identical models hit each other's entries; any config
    edit — layer sizes, updater, loss — misses."""
    if conf is None:
        return "none"
    try:
        from deeplearning4j_tpu.nn.conf import serde

        return hashlib.sha256(
            serde.to_json(conf).encode("utf-8")).hexdigest()[:16]
    except Exception:
        try:
            return hashlib.sha256(repr(conf).encode("utf-8")).hexdigest()[:16]
        except Exception:
            return type(conf).__name__


def _placement_key(args: tuple, kwargs: dict) -> Optional[Tuple]:
    """Per-leaf input placement: sharding repr plus the ids of the devices
    it spans. An AOT ``Compiled`` strictly requires the placements it was
    built with — where jit would quietly re-dispatch (and recompile) for a
    resharded input, the cache must resolve a sibling executable — and it
    runs only on the devices it was compiled for, so two replicas of one
    program on two chips are siblings too. Kept separate from the tracker's
    shape/dtype ``_signature`` so compile-storm accounting granularity is
    unchanged."""
    try:
        import jax

        default = {jax.devices()[0]}
        leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
        # this runs on every dispatch: a parameter tree's leaves share a few
        # sharding objects, each resolved once
        keys = {id(None): None}
        out = []
        for leaf in leaves:
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                s = getattr(leaf, "sharding", None)
                if id(s) not in keys:
                    # placement on the default device normalizes to None: a
                    # host numpy array and the device array a step handed
                    # back are the same program to jit AND to the strict
                    # Compiled check
                    keys[id(s)] = None if s.device_set == default else (
                        repr(s), tuple(d.id for d in s._device_assignment))
                out.append(keys[id(s)])
        return tuple(out)
    except Exception:
        return None


def _flight(kind: str, **fields) -> None:
    global_recorder().record(kind, **fields)


def _resolve_part(name: str, t0_ns: int, **fields) -> None:
    """One part of a program's resolution, ended now: a ``compile.*`` span
    under the ``compile.resolve`` that ``CachedProgram._build`` writes when
    it returns, in the calling thread's staged group (a resolution inside a
    fit loop's dispatch is that group's)."""
    global_recorder().record_span(name, t0_ns, time.time_ns(),
                                  group=current_group(),
                                  cause="compile.resolve", **fields)


_MEMORY_FIGURES = (("temp_bytes", "temp_size_in_bytes"),
                   ("argument_bytes", "argument_size_in_bytes"),
                   ("output_bytes", "output_size_in_bytes"),
                   ("alias_bytes", "alias_size_in_bytes"),
                   ("code_bytes", "generated_code_size_in_bytes"))


def _memory_figures(compiled) -> dict:
    """The executable's own memory figures (``memory_analysis()``), under
    the names the ``compile.resolve`` span carries; empty where the runtime
    gives none for this executable."""
    try:
        stats = compiled.memory_analysis()
        return {name: int(getattr(stats, attr))
                for name, attr in _MEMORY_FIGURES}
    except Exception:  # lint: swallowed-exception-ok (a runtime without the analysis leaves the figures out)
        return {}


def observe_warmup(site: str, seconds: float) -> None:
    """Record one warmup pass in ``dl4j_warmup_seconds{site=}``."""
    global_registry().histogram(
        WARMUP_SECONDS,
        "wall time of one AOT warmup pass (all buckets, cache-backed)"
    ).labels(site=site).observe(seconds)


def warm_parallel(thunks, *, site: str, workers: int = 4) -> float:
    """Run warmup thunks concurrently (thread pool — compiles release the
    GIL inside XLA) and observe the total in ``dl4j_warmup_seconds``.
    Individual thunk failures are logged and swallowed: warmup is an
    optimization, never a correctness gate. Returns elapsed seconds."""
    from concurrent.futures import ThreadPoolExecutor

    thunks = list(thunks)
    t0 = time.perf_counter()
    if thunks:
        with ThreadPoolExecutor(
                max_workers=max(1, min(workers, len(thunks))),
                thread_name_prefix="dl4j-warmup") as ex:
            for fut in [ex.submit(t) for t in thunks]:
                try:
                    fut.result()
                except Exception as e:
                    log.debug("warmup thunk failed: %r", e)
    elapsed = time.perf_counter() - t0
    observe_warmup(site, elapsed)
    return elapsed


class CompileCache:
    """Bounded on-disk store of serialized executables.

    All operations are best-effort and never raise into the compile path:
    a failed read is a miss, a failed write is a no-op, a corrupt entry is
    deleted and flight-recorded.
    """

    def __init__(self, directory: Optional[str] = None,
                 max_bytes: Optional[int] = None):
        self.directory = directory or cache_dir()
        self.max_bytes = _max_bytes() if max_bytes is None else max_bytes
        self._lock = threading.Lock()

    def entry_path(self, fp_hex: str) -> str:
        return os.path.join(self.directory, fp_hex + ".xc")

    # ------------------------------------------------------------- read
    def get(self, fp_hex: str, name: str) -> Optional[tuple]:
        """-> (payload, in_tree, out_tree, meta) or None. Any validation
        failure (bad magic, truncation, digest mismatch, unpicklable body)
        quarantines the entry and reads as a miss. An entry that is returned
        leaves the span ``compile.store_read`` (open, read, digest,
        decompress, unpickle) with the file's ``bytes``."""
        path = self.entry_path(fp_hex)
        t0_ns = time.time_ns()
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
        why = None
        if len(raw) < len(MAGIC) + _DIGEST_LEN:
            why = "truncated"
        elif not raw.startswith(MAGIC):
            why = "version-mismatch"
        else:
            body = raw[len(MAGIC) + _DIGEST_LEN:]
            digest = raw[len(MAGIC):len(MAGIC) + _DIGEST_LEN]
            if hashlib.sha256(body).digest() != digest:
                why = "digest-mismatch"
            else:
                try:
                    entry = pickle.loads(zlib.decompress(body))
                    _resolve_part("compile.store_read", t0_ns, bytes=len(raw))
                    return entry
                except Exception as e:
                    why = f"unpicklable: {e!r}"
        self.quarantine(fp_hex, name=name, why=why)
        return None

    def quarantine(self, fp_hex: str, *, name: str, why: str) -> None:
        """Delete a bad entry and leave a flight-recorder trail; the caller
        falls back to a normal compile."""
        log.warning("compile cache entry %s for %s is unusable (%s); "
                    "falling back to fresh compile", fp_hex[:12], name, why)
        _flight("compile_cache_fallback", fn=name, fingerprint=fp_hex,
                why=why)
        try:
            os.remove(self.entry_path(fp_hex))
        except OSError:  # lint: swallowed-exception-ok (entry already gone or unremovable — either way it reads as a miss)
            pass

    # ------------------------------------------------------------ write
    def put(self, fp_hex: str, payload: bytes, in_tree, out_tree,
            meta: dict) -> int:
        """Write one entry; returns the bytes of its file, 0 where the write
        failed (a failed write is a no-op)."""
        try:
            body = zlib.compress(
                pickle.dumps((payload, in_tree, out_tree, meta)), 1)
            os.makedirs(self.directory, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.directory, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(MAGIC)
                    f.write(hashlib.sha256(body).digest())
                    f.write(body)
                os.replace(tmp, self.entry_path(fp_hex))
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:  # lint: swallowed-exception-ok (tmp-file cleanup on a failed write; the write error itself is re-raised)
                    pass
                raise
            self._prune()
            return len(MAGIC) + _DIGEST_LEN + len(body)
        except Exception as e:
            log.debug("compile cache write failed for %s: %r", fp_hex, e)
            return 0

    def _prune(self) -> None:
        """Keep the store under ``max_bytes`` by evicting oldest-mtime
        entries; publishes the resulting size gauge."""
        with self._lock:
            try:
                entries = []
                total = 0
                with os.scandir(self.directory) as it:
                    for de in it:
                        if not de.name.endswith(".xc"):
                            continue
                        st = de.stat()
                        entries.append((st.st_mtime, st.st_size, de.path))
                        total += st.st_size
                if total > self.max_bytes:
                    for _mt, size, path in sorted(entries):
                        if total <= self.max_bytes:
                            break
                        try:
                            os.remove(path)
                            total -= size
                        except OSError:  # lint: swallowed-exception-ok (concurrent prune/eviction races are benign — the entry is gone either way)
                            pass
                global_registry().gauge(
                    COMPILE_CACHE_BYTES,
                    "on-disk size of the executable cache").set(total)
            except OSError:  # lint: swallowed-exception-ok (size accounting is best-effort; a vanished dir must not fail a compile)
                pass


_instances_lock = threading.Lock()
_instances: Dict[str, CompileCache] = {}


def global_cache() -> CompileCache:
    """Store for the currently-resolved directory (env-sensitive: tests
    repoint ``JAX_COMPILATION_CACHE_DIR`` per test and get a fresh store)."""
    d = cache_dir()
    with _instances_lock:
        cache = _instances.get(d)
        if cache is None:
            cache = _instances[d] = CompileCache(d)
        return cache


class CachedProgram:
    """Callable seam product: per (abstract signature, input placement),
    one executable — disk-hit deserialized, or AOT-compiled and serialized
    back. Falls back to a plain tracked jit call if anything in the AOT
    path fails."""

    def __init__(self, name: str, jitted: Callable, *,
                 fingerprint: Optional[str] = None, cache_key: Any = None,
                 conf: Any = None, extra: Tuple = (),
                 tracker=None, cache: Optional[CompileCache] = None):
        self._name = name
        self._jitted = jitted
        #: fingerprint identity is deliberately separate from the display
        #: name: hot-swap versions (``@v2``) and replica ranks (``~r1``)
        #: decorate the name but must share warm entries
        self._fingerprint_name = fingerprint or name
        self._cache_key = cache_key
        self._extra = extra
        self._conf_fp = conf_fingerprint(conf)
        self._tracker = tracker
        self._cache = cache
        self._ready: Dict[Tuple, Callable] = {}
        self._lock = threading.Lock()
        self._sig_locks: Dict[Tuple, threading.Lock] = {}
        self._fallback: Optional[Callable] = None
        #: whether the LAST executable resolve was a persistent-cache hit
        #: (None until a signature resolves); dispatch trace spans read it
        self.cache_hit: Optional[bool] = None
        self.__name__ = getattr(jitted, "__name__", name)
        self.__wrapped__ = jitted

    # ---------------------------------------------------------- plumbing
    def _tr(self):
        return self._tracker if self._tracker is not None else global_tracker()

    def _store(self) -> CompileCache:
        return self._cache if self._cache is not None else global_cache()

    def _sig_lock(self, sig: Tuple) -> threading.Lock:
        with self._lock:
            lock = self._sig_locks.get(sig)
            if lock is None:
                lock = self._sig_locks[sig] = threading.Lock()
            return lock

    def _plain(self) -> Callable:
        """Shared tracked-jit fallback for unhashable signatures or AOT
        failures — identical to the kill-switch path."""
        with self._lock:
            if self._fallback is None:
                self._fallback = self._tr().wrap(
                    self._name, self._jitted, cache_key=self._cache_key)
            return self._fallback

    def _fp_hex(self, sig: Tuple, pk: Optional[Tuple] = None) -> Optional[str]:
        # cache_key is deliberately NOT part of the material: seams build it
        # from display names that carry per-instance decoration (@version,
        # ~replica). Fingerprint-relevant key parts (dtype policy, rule set,
        # donation, specs) arrive via ``extra``; ``pk`` keeps differently
        # placed (sharded) callers on sibling entries.
        try:
            material = repr((MAGIC, PROGRAM_REV, _backend_key(), _trace_env(),
                             os.environ.get("DL4J_COMPILE_CACHE_EPOCH", ""),
                             self._fingerprint_name, sig, pk,
                             self._conf_fp, self._extra))
            return hashlib.sha256(material.encode("utf-8")).hexdigest()
        except Exception:
            return None

    # ---------------------------------------------------------- resolve
    def _entry(self, args: tuple,
               kwargs: dict) -> Tuple[Optional[Tuple], Callable]:
        try:
            sig = _signature(args, kwargs)
        except Exception:
            sig = None
        if sig is None:
            return None, self._plain()
        key = (sig, _placement_key(args, kwargs))
        entry = self._ready.get(key)
        if entry is not None:
            return key, entry
        with self._sig_lock(key):
            entry = self._ready.get(key)
            if entry is None:
                entry = self._build(sig, key[1], args, kwargs)
                # lint: lockguard-ok (one writer per key under its per-signature lock; the dict store is GIL-atomic and the lock-free fast path tolerates a miss)
                self._ready[key] = entry
        return key, entry

    def _build(self, sig: Tuple, pk: Optional[Tuple], args: tuple,
               kwargs: dict) -> Callable:
        """Resolve one signature's executable, once: from the store, else
        compiled and written back. The ring's ``compile`` record of it is
        the span ``compile.resolve`` (entry to return; ``fn``, ``hit``,
        ``payload_bytes``, the executable's memory figures), caused by the
        ``fit.dispatch`` of the calling thread's group where it has one,
        with its parts under it: ``compile.store_read`` and
        ``compile.deserialize`` on a hit, ``compile.lower``,
        ``compile.backend`` and ``compile.store_write`` on a miss."""
        from deeplearning4j_tpu.ops.pallas_kernels import (
            recorded_dispatch, replay_dispatch)

        t0_ns = time.time_ns()
        tracker = self._tr()
        tracker._ensure_monitoring()
        fp = self._fp_hex(sig, pk)
        store = self._store()
        reg = global_registry()
        group = current_group()

        def resolved(compiled, *, hit: bool, wall_s: float,
                     payload_bytes: int) -> Callable:
            tracker.note_executable(self._name, compiled)
            self.cache_hit = hit
            tracker.record_compile(
                self._name, cache_key=self._cache_key, wall_s=wall_s,
                shapes=sig[0], cache_hit=hit, span=(t0_ns, time.time_ns()),
                group=group, cause=None if group is None else "fit.dispatch",
                hit=hit, payload_bytes=payload_bytes,
                **_memory_figures(compiled))
            return compiled

        # disk hit: deserialize instead of compiling
        if fp is not None:
            t0 = time.perf_counter()
            got = store.get(fp, self._name)
            if got is not None:
                try:
                    import jax
                    from jax.experimental import serialize_executable as se

                    # onto the devices it was compiled for — the default,
                    # every local device, is wrong for all but a program
                    # that spans the host
                    payload, in_tree, out_tree, meta = got
                    by_id = {d.id: d for d in jax.devices()}
                    d0_ns = time.time_ns()
                    compiled = se.deserialize_and_load(
                        payload, in_tree, out_tree, execution_devices=[
                            by_id[i] for i in meta["device_ids"]])
                    _resolve_part("compile.deserialize", d0_ns)
                    replay_dispatch(meta["dispatch"])
                    load_s = time.perf_counter() - t0
                    reg.counter(
                        COMPILE_CACHE_HITS_TOTAL,
                        "executables loaded from the compile cache"
                    ).labels(fn=self._name).inc()
                    reg.histogram(
                        COMPILE_CACHE_LOAD_SECONDS,
                        "wall time of a cache hit: the store's read (open, "
                        "digest, decompress, unpickle) + deserialize_and_load"
                    ).labels(fn=self._name).observe(load_s)
                    return resolved(compiled, hit=True, wall_s=load_s,
                                    payload_bytes=len(payload))
                except Exception as e:
                    store.quarantine(fp, name=self._name,
                                     why=f"deserialize failed: {e!r}")

        # miss: AOT compile, then persist. The jit dispatch cache is NOT
        # populated by AOT compilation, so the Compiled object itself is
        # what dispatches from here on (parity measured with jit dispatch).
        stack = getattr(tracker._active, "stack", None)
        if stack is None:
            stack = tracker._active.stack = []
        stack.append(self._name)
        t0 = time.perf_counter()
        try:
            with recorded_dispatch() as dispatch_notes:
                l0_ns = time.time_ns()
                lowered = self._jitted.lower(*args, **kwargs)
                _resolve_part("compile.lower", l0_ns)
                b0_ns = time.time_ns()
                compiled = lowered.compile()
                _resolve_part("compile.backend", b0_ns)
        except Exception as e:
            log.debug("AOT compile failed for %s (%r); using plain jit",
                      self._name, e)
            return self._plain()
        finally:
            stack.pop()
        wall = time.perf_counter() - t0
        reg.counter(COMPILE_CACHE_MISSES_TOTAL,
                    "compile-cache misses (fresh XLA compiles)"
                    ).labels(fn=self._name).inc()
        payload_bytes = 0
        if fp is not None:
            try:
                from jax.experimental import serialize_executable as se

                w0_ns = time.time_ns()
                payload, in_tree, out_tree = se.serialize(compiled)
                payload_bytes = len(payload)
                written = store.put(
                    fp, payload, in_tree, out_tree,
                    {"fn": self._fingerprint_name,
                     "wall_s": wall, "shapes": repr(sig[0]),
                     "dispatch": dispatch_notes,
                     "device_ids": [
                         d.id for d in compiled.runtime_executable()
                         .local_devices()]})
                _resolve_part("compile.store_write", w0_ns, bytes=written)
            except Exception as e:
                log.debug("serialize failed for %s: %r", self._name, e)
        return resolved(compiled, hit=False, wall_s=wall,
                        payload_bytes=payload_bytes)

    # ------------------------------------------------------------ public
    def __call__(self, *args, **kwargs):
        key, entry = self._entry(args, kwargs)
        try:
            return entry(*args, **kwargs)
        except ValueError as e:
            msg = str(e)
            if key is None or ("sharding" not in msg and "layout" not in msg):
                raise
            # the AOT Compiled's strict input check tripped on a placement
            # drift the placement key could not see (committed-ness,
            # layout). Poison this key to the plain tracked jit — never an
            # error, at worst a lost warm start for this one signature.
            _flight("compile_cache_fallback", fn=self._name,
                    why="strict-input-mismatch")
            log.debug("AOT strict input check failed for %s (%s); "
                      "pinning signature to plain jit", self._name, msg)
            plain = self._plain()
            with self._lock:
                self._ready[key] = plain
            return plain(*args, **kwargs)

    def warm(self, *args, **kwargs) -> None:
        """Resolve the executable for this signature without executing it.
        Args may be concrete arrays or ``ShapeDtypeStruct``s — both lower
        identically."""
        self._entry(args, kwargs)

    def cost_flops(self, *args, **kwargs) -> Optional[float]:
        """FLOPs from the resolved executable's own cost analysis (no
        re-lowering)."""
        _key, entry = self._entry(args, kwargs)
        analysis = getattr(entry, "cost_analysis", None)
        if analysis is None:
            return None
        try:
            cost = analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            if cost is None:
                return None
            return float(dict(cost).get("flops", 0.0))
        except Exception:
            return None


def build_program(name: str, jitted: Callable, *,
                  fingerprint: Optional[str] = None, cache_key: Any = None,
                  conf: Any = None, extra: Tuple = (),
                  tracker=None) -> Callable:
    """The factory every compile seam calls on a freshly-built jitted fn.
    Cache enabled -> a ``CachedProgram``; kill switch -> exactly the
    pre-existing ``tracker.wrap`` path."""
    tr = tracker if tracker is not None else global_tracker()
    if not enabled():
        return tr.wrap(name, jitted, cache_key=cache_key)
    return CachedProgram(name, jitted, fingerprint=fingerprint,
                         cache_key=cache_key, conf=conf, extra=extra,
                         tracker=tr)
