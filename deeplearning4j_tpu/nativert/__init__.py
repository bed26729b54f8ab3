"""Native runtime bindings (C++ host-side IO/staging pipeline).

The reference reaches all native code through JavaCPP bindings (SURVEY.md
§2.10): libnd4j tensor backends, cuDNN helpers, HDF5. Its data path runs
through AsyncDataSetIterator (background prefetch thread + blocking queue,
reference deeplearning4j-nn datasets/iterator/AsyncDataSetIterator.java:36)
and MagicQueue (parallelism/MagicQueue.java:21). Here the equivalent host
runtime is ``native/src/dl4j_runtime.cpp`` — IDX/CIFAR parsers, an async
producer-thread batch loader, a numeric CSV reader, and the binary stats
codec (SBE-codec equivalent, reference ui-model ui/stats/sbe/*) — consumed
via ctypes. Device compute stays in XLA; this layer only stages host memory.

The shared library is built on demand with g++ from the one input git
commits (``native/src/dl4j_runtime.cpp``), once however many processes ask
at the same time (:func:`_build`); every entry point degrades to
``None``/pure-Python when the build is unavailable so the framework never
hard-requires the native path. A degraded load is not silent:
:func:`load_error` says why, and ``chip_smoke.py`` fails on it wherever a
compiler exists.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import threading
from pathlib import Path
from typing import List, Optional, Sequence

import numpy as np

_REPO_ROOT = Path(__file__).resolve().parent.parent.parent
_NATIVE_DIR = _REPO_ROOT / "native"
_LIB_PATH = _NATIVE_DIR / "libdl4j_runtime.so"
_SRC_PATH = _NATIVE_DIR / "src" / "dl4j_runtime.cpp"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_load_error: Optional[str] = None

c_i64 = ctypes.c_int64
c_f32p = ctypes.POINTER(ctypes.c_float)
c_u8p = ctypes.POINTER(ctypes.c_uint8)
c_i32p = ctypes.POINTER(ctypes.c_int32)
c_i64p = ctypes.POINTER(ctypes.c_int64)


def _stale(src: Path, lib: Path) -> bool:
    return not lib.exists() or (src.exists()
                                and src.stat().st_mtime > lib.stat().st_mtime)


def _build(src: Path = _SRC_PATH, lib: Path = _LIB_PATH) -> bool:
    """Compile ``src`` to ``lib`` where ``lib`` is missing or older, one
    process at a time: an ``flock`` on ``<lib>.lock`` serialises the builds
    of every process that asks at once, and the one that holds it checks
    again, since another may have built it meanwhile. The compiler writes a
    temporary name beside ``lib`` that ``os.replace`` moves onto it, so no
    process loads a half-written library."""
    global _load_error
    if not src.exists():
        _load_error = f"{src} is missing"
        return False
    tmp = lib.with_name(f"{lib.name}.{os.getpid()}.tmp")
    try:
        with open(lib.with_name(lib.name + ".lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)  # released as the file closes
            if _stale(src, lib):
                subprocess.run(
                    ["g++", "-O3", "-std=c++17", "-fPIC", "-shared",
                     "-pthread", str(src), "-o", str(tmp)],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, lib)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        _load_error = f"build failed: {e!r}"
        # leave a post-mortem breadcrumb (worker_exit-style): a silent False
        # here used to mean "mysteriously slow Python paths" with no trace
        try:
            from deeplearning4j_tpu.observability.flight_recorder import (
                global_recorder)
            stderr = getattr(e, "stderr", b"") or b""
            global_recorder().record(
                "native_build_failed", src=str(src), error=repr(e),
                stderr=stderr[-500:].decode("utf-8", "replace")
                if isinstance(stderr, bytes) else str(stderr)[-500:])
        except Exception:  # lint: swallowed-exception-ok (telemetry must not turn a degraded build into a crash)
            pass
        return False
    finally:
        tmp.unlink(missing_ok=True)


def _declare(lib: ctypes.CDLL) -> None:
    lib.dl4j_idx_open.restype = ctypes.c_void_p
    lib.dl4j_idx_open.argtypes = [ctypes.c_char_p]
    lib.dl4j_idx_ndim.restype = ctypes.c_int
    lib.dl4j_idx_ndim.argtypes = [ctypes.c_void_p]
    lib.dl4j_idx_dims.argtypes = [ctypes.c_void_p, c_i64p]
    lib.dl4j_idx_read.argtypes = [ctypes.c_void_p, c_u8p]
    lib.dl4j_idx_close.argtypes = [ctypes.c_void_p]

    lib.dl4j_loader_create_from_arrays.restype = ctypes.c_void_p
    lib.dl4j_loader_create_from_arrays.argtypes = [
        c_u8p, c_u8p, c_i64, c_i64, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_uint64, ctypes.c_int]
    lib.dl4j_mnist_loader_create.restype = ctypes.c_void_p
    lib.dl4j_mnist_loader_create.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_uint64, ctypes.c_int]
    lib.dl4j_cifar_loader_create.restype = ctypes.c_void_p
    lib.dl4j_cifar_loader_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64]
    for name in ("dl4j_loader_num_examples", "dl4j_loader_feature_size"):
        getattr(lib, name).restype = c_i64
        getattr(lib, name).argtypes = [ctypes.c_void_p]
    for name in ("dl4j_loader_num_classes", "dl4j_loader_batch_size"):
        getattr(lib, name).restype = ctypes.c_int
        getattr(lib, name).argtypes = [ctypes.c_void_p]
    lib.dl4j_loader_next.restype = ctypes.c_int
    lib.dl4j_loader_next.argtypes = [ctypes.c_void_p, c_f32p, c_f32p]
    lib.dl4j_loader_reset.argtypes = [ctypes.c_void_p]
    lib.dl4j_loader_close.argtypes = [ctypes.c_void_p]

    lib.dl4j_csv_open.restype = ctypes.c_void_p
    lib.dl4j_csv_open.argtypes = [ctypes.c_char_p, ctypes.c_char, ctypes.c_int]
    lib.dl4j_csv_open2.restype = ctypes.c_void_p
    lib.dl4j_csv_open2.argtypes = [ctypes.c_char_p, ctypes.c_char,
                                   ctypes.c_int, ctypes.c_int]
    lib.dl4j_csv_rows.restype = c_i64
    lib.dl4j_csv_rows.argtypes = [ctypes.c_void_p]
    lib.dl4j_csv_cols.restype = c_i64
    lib.dl4j_csv_cols.argtypes = [ctypes.c_void_p]
    lib.dl4j_csv_read.argtypes = [ctypes.c_void_p, c_f32p]
    lib.dl4j_csv_close.argtypes = [ctypes.c_void_p]

    lib.dl4j_stats_begin.restype = ctypes.c_void_p
    lib.dl4j_stats_begin.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, c_i64, ctypes.c_int32,
        ctypes.c_double, ctypes.c_double, ctypes.c_double, c_i64, c_i64]
    lib.dl4j_stats_add.restype = ctypes.c_int
    lib.dl4j_stats_add.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_double,
        ctypes.c_double, ctypes.c_double, c_i32p, ctypes.c_int]
    lib.dl4j_stats_finish.restype = c_i64
    lib.dl4j_stats_finish.argtypes = [ctypes.c_void_p, c_u8p, c_i64]
    lib.dl4j_stats_abort.argtypes = [ctypes.c_void_p]
    lib.dl4j_runtime_version.restype = ctypes.c_int

    lib.dl4j_vocab_count_file.restype = ctypes.c_void_p
    lib.dl4j_vocab_count_file.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                          ctypes.c_int]
    lib.dl4j_vocab_num_words.restype = c_i64
    lib.dl4j_vocab_num_words.argtypes = [ctypes.c_void_p]
    lib.dl4j_vocab_total_tokens.restype = c_i64
    lib.dl4j_vocab_total_tokens.argtypes = [ctypes.c_void_p]
    lib.dl4j_vocab_entry.restype = c_i64
    lib.dl4j_vocab_entry.argtypes = [ctypes.c_void_p, c_i64, ctypes.c_char_p,
                                     c_i64]
    lib.dl4j_vocab_close.argtypes = [ctypes.c_void_p]

    lib.dl4j_ingest_decode.restype = c_i64
    lib.dl4j_ingest_decode.argtypes = [c_u8p, c_i64, ctypes.c_int, c_f32p,
                                       c_i64]
    lib.dl4j_ingest_create.restype = ctypes.c_void_p
    lib.dl4j_ingest_create.argtypes = [ctypes.c_int]
    lib.dl4j_ingest_submit.restype = ctypes.c_int
    lib.dl4j_ingest_submit.argtypes = [ctypes.c_void_p, c_u8p, c_i64,
                                       ctypes.c_int]
    lib.dl4j_ingest_next.restype = c_i64
    lib.dl4j_ingest_next.argtypes = [ctypes.c_void_p, c_f32p, c_i64]
    lib.dl4j_ingest_close.argtypes = [ctypes.c_void_p]


def get_runtime() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native runtime; None when unavailable.
    Set DL4J_TPU_DISABLE_NATIVE=1 to force the pure-Python paths."""
    global _lib, _load_attempted
    if os.environ.get("DL4J_TPU_DISABLE_NATIVE") == "1":
        return None
    with _lock:
        if not _load_attempted:
            _load_attempted = True
            _lib = _load(_SRC_PATH, _LIB_PATH)
        return _lib


def _load(src: Path, path: Path) -> Optional[ctypes.CDLL]:
    """The runtime at ``path``, built from ``src`` first where it is missing
    or older; None, with :func:`load_error` saying why, where it cannot be
    had."""
    global _load_error
    if _stale(src, path) and not _build(src, path) and not path.exists():
        return None
    try:
        lib = ctypes.CDLL(str(path))
        _declare(lib)
        if lib.dl4j_runtime_version() != 4:
            _load_error = (f"{path} is runtime version "
                           f"{lib.dl4j_runtime_version()}, expected 4")
            return None
        return lib
    except (OSError, AttributeError) as e:
        # AttributeError: a stale older-version .so whose rebuild failed
        # is missing current-version symbols — fall back to pure Python
        # rather than raising out of native_available()
        _load_error = f"load failed: {e!r}"
        return None


def load_error() -> Optional[str]:
    """Why :func:`get_runtime` came back empty (None while it has not, or
    when the kill switch asked for the Python paths)."""
    return _load_error


def native_available() -> bool:
    return get_runtime() is not None


# ---------------------------------------------------------------------------
# IDX
# ---------------------------------------------------------------------------

def read_idx(path: str) -> Optional[np.ndarray]:
    """Parse an IDX (MNIST-format) file with the native parser; None on any
    failure (missing lib, bad file)."""
    lib = get_runtime()
    if lib is None:
        return None
    h = lib.dl4j_idx_open(str(path).encode())
    if not h:
        return None
    try:
        ndim = lib.dl4j_idx_ndim(h)
        dims = np.zeros(ndim, np.int64)
        lib.dl4j_idx_dims(h, dims.ctypes.data_as(c_i64p))
        out = np.empty(int(dims.prod()), np.uint8)
        lib.dl4j_idx_read(h, out.ctypes.data_as(c_u8p))
        return out.reshape(dims.tolist())
    finally:
        lib.dl4j_idx_close(h)


# ---------------------------------------------------------------------------
# CSV
# ---------------------------------------------------------------------------

def read_csv_numeric(path: str, delimiter: str = ",", skip_lines: int = 0,
                     strict: bool = False) -> Optional[np.ndarray]:
    """Fast numeric CSV → float32 [rows, cols].

    ``strict=False``: non-numeric fields become 0 (lenient legacy behavior).
    ``strict=True``: one native pass validates WHILE parsing — returns None
    on any empty/non-numeric field or ragged row so the caller can fall back
    to its general string-preserving reader. Also None when the native
    runtime is unavailable or the file can't be read."""
    lib = get_runtime()
    if lib is None:
        return None
    h = lib.dl4j_csv_open2(str(path).encode(), delimiter.encode()[:1],
                           int(skip_lines), 1 if strict else 0)
    if not h:
        return None
    try:
        rows, cols = lib.dl4j_csv_rows(h), lib.dl4j_csv_cols(h)
        out = np.empty((int(rows), int(cols)), np.float32)
        if rows and cols:
            lib.dl4j_csv_read(h, out.ctypes.data_as(c_f32p))
        return out
    finally:
        lib.dl4j_csv_close(h)


# ---------------------------------------------------------------------------
# Async prefetch loader
# ---------------------------------------------------------------------------

class AsyncNativeLoader:
    """Native async batch loader: a C++ producer thread assembles normalized
    float32 batches (one-hot labels) into a bounded queue; iteration here
    blocks on the queue (reference AsyncDataSetIterator semantics: prefetch
    depth = ``capacity``, reset() reshuffles and restarts the epoch)."""

    def __init__(self, handle, lib):
        if not handle:
            raise ValueError("native loader creation failed")
        self._h = handle
        self._lib = lib
        self.batch = lib.dl4j_loader_batch_size(handle)
        self.feature_size = int(lib.dl4j_loader_feature_size(handle))
        self.num_classes = lib.dl4j_loader_num_classes(handle)
        self.num_examples = int(lib.dl4j_loader_num_examples(handle))

    @classmethod
    def from_arrays(cls, features: np.ndarray, labels: np.ndarray,
                    num_classes: int, batch: int, capacity: int = 4,
                    shuffle: bool = True, seed: int = 0,
                    normalize: bool = True) -> "AsyncNativeLoader":
        lib = get_runtime()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        f = np.ascontiguousarray(features, np.uint8).reshape(len(features), -1)
        l = np.ascontiguousarray(labels, np.uint8).ravel()
        h = lib.dl4j_loader_create_from_arrays(
            f.ctypes.data_as(c_u8p), l.ctypes.data_as(c_u8p),
            f.shape[0], f.shape[1], num_classes, batch, capacity,
            int(shuffle), seed, int(normalize))
        return cls(h, lib)

    @classmethod
    def mnist(cls, images_path: str, labels_path: str, batch: int,
              capacity: int = 4, shuffle: bool = True, seed: int = 0,
              normalize: bool = True) -> "AsyncNativeLoader":
        lib = get_runtime()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        h = lib.dl4j_mnist_loader_create(
            str(images_path).encode(), str(labels_path).encode(), batch,
            capacity, int(shuffle), seed, int(normalize))
        return cls(h, lib)

    @classmethod
    def cifar(cls, paths: Sequence[str], batch: int, capacity: int = 4,
              shuffle: bool = True, seed: int = 0) -> "AsyncNativeLoader":
        lib = get_runtime()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        arr = (ctypes.c_char_p * len(paths))(
            *[str(p).encode() for p in paths])
        h = lib.dl4j_cifar_loader_create(arr, len(paths), batch, capacity,
                                         int(shuffle), seed)
        return cls(h, lib)

    def next(self) -> Optional[tuple]:
        """Next (features [B, F] f32, one-hot labels [B, C] f32), or None at
        end of epoch."""
        if not self._h:
            raise ValueError("loader is closed")
        x = np.empty((self.batch, self.feature_size), np.float32)
        y = np.empty((self.batch, self.num_classes), np.float32)
        ok = self._lib.dl4j_loader_next(
            self._h, x.ctypes.data_as(c_f32p), y.ctypes.data_as(c_f32p))
        return (x, y) if ok else None

    def reset(self) -> None:
        if not self._h:
            raise ValueError("loader is closed")
        self._lib.dl4j_loader_reset(self._h)

    def __iter__(self):
        while True:
            b = self.next()
            if b is None:
                return
            yield b

    def close(self) -> None:
        if self._h:
            self._lib.dl4j_loader_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        # lint: swallowed-exception-ok (destructor must not raise during interpreter teardown)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Stats codec
# ---------------------------------------------------------------------------

def encode_stats_native(session_id: str, worker_id: str, timestamp: int,
                        iteration: int, score: float, iter_time_ms: float,
                        samples_per_sec: float, mem_rss: int, device_mem: int,
                        sections: List[dict]) -> Optional[bytes]:
    """Encode a StatsReport with the native codec (same DLTS wire format as
    the Python encoder in ui/stats.py). ``sections`` is
    [params, gradients, updates], each name -> (mean_mag, hist, (lo, hi))."""
    lib = get_runtime()
    if lib is None:
        return None
    h = lib.dl4j_stats_begin(session_id.encode(), worker_id.encode(),
                             timestamp, iteration, score, iter_time_ms,
                             samples_per_sec, mem_rss, device_mem)
    if not h:
        return None
    try:
        for si, section in enumerate(sections[:3]):
            for name, (mm, hist, (lo, hi)) in section.items():
                ha = np.asarray(hist, np.int32)
                lib.dl4j_stats_add(h, si, name.encode(), float(mm), float(lo),
                                   float(hi), ha.ctypes.data_as(c_i32p),
                                   len(ha))
        n = lib.dl4j_stats_finish(h, None, 0)
        out = np.empty(int(n), np.uint8)
        written = lib.dl4j_stats_finish(h, out.ctypes.data_as(c_u8p), n)
        h = None  # finish with a large-enough buffer frees the builder
        if written != n:
            return None
        return out.tobytes()
    finally:
        if h:
            lib.dl4j_stats_abort(h)


# ---------------------------------------------------------------------------
# Batched ingest decode (zero-copy host data plane): raw record bytes -> f32.
# ctypes releases the GIL for the whole native call, and IngestDecoder adds a
# C++ producer thread so decode overlaps the training step (the
# AsyncDataSetIterator role, on the consume side of the broker).
# ---------------------------------------------------------------------------

#: codec ids shared with dl4j_runtime.cpp (kIngestF32/Bf16/U8)
INGEST_CODECS = {"f32": 0, "none": 0, "bf16": 1, "u8": 2}

#: floats produced per input byte, by codec id
_INGEST_WIDTH = {0: 4, 1: 2, 2: 1}  # bytes per element


def _ingest_counter():
    from deeplearning4j_tpu.observability.metrics import global_registry
    from deeplearning4j_tpu.observability.names import (
        INGEST_DECODE_BYTES_TOTAL)
    return global_registry().counter(
        INGEST_DECODE_BYTES_TOTAL,
        "raw record bytes decoded to f32 batches, by path (native/python)")


def decode_records_py(buf, codec: str = "f32") -> np.ndarray:
    """Pure-Python fallback decoder (also the bench baseline): one record's
    bytes -> f32 vector."""
    cid = INGEST_CODECS[codec]
    _ingest_counter().labels(path="python").inc(len(buf))
    if cid == 0:
        return np.frombuffer(buf, np.float32).copy()  # lint: hot-path-copy-ok (fallback path by definition; native is the hot path)
    if cid == 1:
        import ml_dtypes
        return np.frombuffer(buf, ml_dtypes.bfloat16).astype(np.float32)
    # multiply by the f32 reciprocal, exactly like the native decoder (and
    # the native Loader's normalize path) — bitwise parity across paths
    return (np.frombuffer(buf, np.uint8).astype(np.float32)
            * np.float32(1.0 / 255.0))


def decode_records(buf, codec: str = "f32") -> Optional[np.ndarray]:
    """One-shot native decode of a record's bytes; None when the native
    runtime is unavailable or the length is ragged for the codec (callers
    fall back to ``decode_records_py``)."""
    lib = get_runtime()
    if lib is None:
        return None
    cid = INGEST_CODECS[codec]
    raw = np.frombuffer(buf, np.uint8)  # lint: hot-path-copy-ok (view, no .copy(): zero-copy reinterpret of the input bytes)
    n = len(raw) // _INGEST_WIDTH[cid]
    out = np.empty(n, np.float32)
    wrote = lib.dl4j_ingest_decode(
        raw.ctypes.data_as(c_u8p), len(raw), cid,
        out.ctypes.data_as(c_f32p), n)
    if wrote != n:
        return None
    _ingest_counter().labels(path="native").inc(len(raw))
    return out


class IngestDecoder:
    """Pipelined native decoder: ``submit()`` stages raw record bytes into a
    bounded native queue, a C++ worker thread decodes them to f32, ``next()``
    collects finished records in submission order.

    The staging queue is BOUNDED: ``submit()`` blocks once ``capacity``
    records are in flight, so interleave submits with ``next()`` when
    streaming more than ``capacity`` records (the producer/consumer shape
    DevicePrefetcher already has). Raises RuntimeError at construction when
    the native runtime is unavailable — callers that want graceful
    degradation use ``decode_records``/``decode_records_py``."""

    def __init__(self, capacity: int = 8):
        lib = get_runtime()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.dl4j_ingest_create(int(capacity))
        if not self._h:
            raise RuntimeError("native ingest creation failed")
        self._sizes: List[int] = []  # FIFO of expected output lengths

    def submit(self, buf, codec: str = "f32") -> None:
        if not self._h:
            raise ValueError("decoder is closed")
        cid = INGEST_CODECS[codec]
        raw = np.frombuffer(buf, np.uint8)  # lint: hot-path-copy-ok (view, no .copy(): the native side stages its own copy off-GIL)
        if len(raw) % _INGEST_WIDTH[cid]:
            raise ValueError(f"ragged record: {len(raw)} bytes is not a "
                             f"whole number of {codec} elements")
        rc = self._lib.dl4j_ingest_submit(
            self._h, raw.ctypes.data_as(c_u8p), len(raw), cid)
        if rc != 0:
            raise RuntimeError("ingest pipeline poisoned by a bad record")
        self._sizes.append(len(raw) // _INGEST_WIDTH[cid])
        _ingest_counter().labels(path="native").inc(len(raw))

    def next(self) -> Optional[np.ndarray]:
        """Next decoded f32 record (submission order), or None when every
        submitted record has been collected."""
        if not self._h:
            raise ValueError("decoder is closed")
        if not self._sizes:
            return None
        n = self._sizes.pop(0)
        out = np.empty(n, np.float32)
        wrote = self._lib.dl4j_ingest_next(
            self._h, out.ctypes.data_as(c_f32p), n)
        if wrote != n:
            raise RuntimeError(f"ingest decode returned {wrote}, "
                               f"expected {n}")
        return out

    def close(self) -> None:
        if self._h:
            self._lib.dl4j_ingest_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        # lint: swallowed-exception-ok (destructor must not raise during interpreter teardown)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Vocabulary counting (parallel token counts, reference VocabConstructor.java)
# ---------------------------------------------------------------------------

def count_tokens_file(path: str, common_preprocess: bool = False,
                      nthreads: int = 0) -> Optional[List[tuple]]:
    """Count whitespace tokens in an ASCII text file with worker threads.

    Returns [(word, count), ...] ordered by count desc then word asc, or
    None when the native runtime is unavailable, the file can't be read, or
    it contains non-ASCII bytes (the Python tokenizer pipeline has unicode
    semantics this fast path intentionally does not replicate).
    ``common_preprocess`` applies the CommonPreprocessor rules (strip
    punctuation/digits, lowercase) inline during the scan.
    """
    lib = get_runtime()
    if lib is None:
        return None
    h = lib.dl4j_vocab_count_file(path.encode(), 1 if common_preprocess else 0,
                                  int(nthreads))
    if not h:
        return None
    try:
        n = lib.dl4j_vocab_num_words(h)
        cap = 65536
        buf = ctypes.create_string_buffer(cap)
        out = []
        for i in range(int(n)):
            cnt = lib.dl4j_vocab_entry(h, i, buf, cap)
            if cnt < 0:
                return None
            word = buf.value.decode("ascii")
            if len(word) >= cap - 1:
                # possible truncation (undetectable through the C ABI):
                # decline and let the Python pipeline keep the full token
                return None
            out.append((word, int(cnt)))
        return out
    finally:
        lib.dl4j_vocab_close(h)
