"""Dynamic micro-batching with padded shape buckets.

The single biggest serving win on an accelerator: instead of dispatching
one tiny forward per request, coalesce concurrent requests into ONE padded
batch so the device runs a large fused program. The policy:

- requests group by ``(model, per-example shape, dtype)`` — only
  shape-compatible rows share a dispatch;
- a group dispatches when it reaches ``max_batch`` OR its oldest request
  has waited ``max_latency_s`` (the latency/throughput knob);
- the concatenated rows are zero-padded up to the next **power-of-two
  batch bucket** (capped at ``max_batch``), so the compiled-program cache
  holds at most ``log2(max_batch)+1`` executables per input signature —
  steady-state serving NEVER recompiles, whatever request sizes arrive.
  Compiles are visible in the compile tracker under ``serve_predict@…``
  (``dl4j_jit_compile_total``), which is how the load test pins
  ``recompiles == bucket count``.

Padding is semantics-free: rows are independent under inference-mode
forward (running BN statistics, no dropout), so the sliced-back outputs
are **bitwise identical** to the same rows dispatched in an unpadded batch
of the bucket's size, and equal to a per-request dispatch to float32
rounding — XLA picks its matmul kernel by batch extent, so a row alone and
the same row in a bucket of 4 may differ in the last ulp (nn/inference.py,
"The serving equality contract"). Pinned across bucket boundaries by
tests/test_serving.py.

PR 2/5/7 infrastructure rides on the dispatch loop wholesale: per-batch
latency histograms and occupancy/queue gauges (``dl4j_serve_*``), a
flight-recorder event per dispatch plus a dump on dispatch failure,
watchdog heartbeats so a wedged device yields a thread-stack bundle, and
``note_dispatch`` so the anomaly trigger can capture an XPlane trace of a
slow serve batch.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.observability import names as _n
from deeplearning4j_tpu.observability.compile_tracker import (
    global_tracker as _compile_tracker,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.observability.profiler import (
    note_dispatch as _profile_note_dispatch,
)
from deeplearning4j_tpu.observability.tracing import start_span
from deeplearning4j_tpu.observability.watchdog import beat as _wd_beat

from .admission import AdmissionController, RejectedError  # noqa: F401
from .registry import ModelRegistry


def batch_bucket(n: int, max_batch: int) -> int:
    """Next power-of-two >= n, capped at max_batch."""
    if n >= max_batch:
        return max_batch
    b = 1
    while b < n:
        b <<= 1
    return min(b, max_batch)


class _Request:
    __slots__ = ("model", "xs", "n", "key", "future", "t_enqueue", "span")

    def __init__(self, model: str, xs: Tuple[np.ndarray, ...], key: Tuple,
                 t_enqueue: float):
        self.model = model
        self.xs = xs
        self.n = int(xs[0].shape[0])
        self.key = key
        self.future: Future = Future()
        self.t_enqueue = t_enqueue
        # queue-wait span, started on the submitting thread (where the
        # request's trace context is ambient) and finished by the
        # dispatcher — contextvars don't cross threads, the slot does
        self.span = start_span("batch.queue", model=model,
                               rows=self.n)


class MicroBatcher:
    """Coalesces concurrent predict requests into padded micro-batches.

    ``submit()`` is the producer side (HTTP handler threads); one daemon
    dispatcher thread drains the queue. ``max_batch=1`` degenerates to
    unbatched serving — the load test's A/B baseline.
    """

    def __init__(self, registry: ModelRegistry, *, max_batch: int = 32,
                 max_latency_s: float = 0.002, max_queue: int = 256,
                 admission: Optional[AdmissionController] = None,
                 metrics=None, replica: Optional[int] = None):
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.registry = registry
        self.max_batch = int(max_batch)
        self.max_latency_s = float(max_latency_s)
        #: ReplicaSet member index, or None for a standalone batcher — only
        #: adds the per-replica gauge labels and the result-dict field
        self.replica = replica
        self.admission = admission or AdmissionController(
            max_pending=max_queue, expected_latency_s=max_latency_s)
        m = metrics or global_registry()
        self._g_replica_queue = self._g_replica_occ = None
        if replica is not None:
            self._g_replica_queue = m.gauge(
                _n.SERVE_REPLICA_QUEUE_DEPTH,
                "admitted-but-unanswered requests per replica")
            self._g_replica_occ = m.gauge(
                _n.SERVE_REPLICA_OCCUPANCY,
                "rows/bucket of the replica's last dispatch")
        self._c_requests = m.counter(
            _n.SERVE_REQUESTS_TOTAL, "predict requests admitted")
        self._c_errors = m.counter(
            _n.SERVE_ERRORS_TOTAL, "predict requests failed in dispatch")
        self._c_batches = m.counter(
            _n.SERVE_BATCHES_TOTAL, "micro-batches dispatched")
        self._h_dispatch = m.histogram(
            _n.SERVE_BATCH_DISPATCH_SECONDS, "device time per micro-batch")
        self._g_occupancy = m.gauge(
            _n.SERVE_BATCH_OCCUPANCY,
            "real rows / padded bucket size of the last dispatch")
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: List[_Request] = []
        self._closed = False
        self._dispatches = 0
        self._occupancy_sum = 0.0
        self._buckets_seen: set = set()
        self._thread = threading.Thread(
            target=self._loop, name="serve-microbatcher", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- producer
    @staticmethod
    def _group_key(model: str, xs: Tuple[np.ndarray, ...]) -> Tuple:
        return (model,) + tuple((x.shape[1:], str(x.dtype)) for x in xs)

    def submit(self, model: str, x, *, priority: str = "high",
               tenant: str = "-") -> Future:
        """Queue one request (``x`` carries a leading batch axis; a single
        example must arrive as shape ``[1, ...]``; a multi-input graph
        takes a list/tuple of arrays sharing the leading axis). Raises
        :class:`RejectedError` when admission refuses (HTTP 429).
        ``priority``/``tenant`` flow to admission: under saturation, low
        priorities are shed before high ones (see ``admission.py``)."""
        if isinstance(x, (list, tuple)):
            xs = tuple(np.asarray(a) for a in x)
            if not xs:
                raise ValueError("empty input list")
        else:
            xs = (np.asarray(x),)
        for a in xs:
            if a.ndim < 2:
                raise ValueError(
                    f"request needs a leading batch axis, got shape "
                    f"{a.shape}")
        if len({a.shape[0] for a in xs}) != 1:
            raise ValueError(
                "multi-input request arrays must share the leading batch "
                f"axis, got {[a.shape[0] for a in xs]}")
        if xs[0].shape[0] > self.max_batch:
            raise ValueError(
                f"request batch {xs[0].shape[0]} exceeds max_batch "
                f"{self.max_batch}; split it client-side")
        self.admission.admit(priority=priority, tenant=tenant)
        self._c_requests.labels(model=model).inc()
        req = _Request(model, xs, self._group_key(model, xs),
                       time.perf_counter())
        with self._cond:
            if self._closed:
                self.admission.release()
                req.span.set_status("error").finish()
                raise RuntimeError("MicroBatcher is closed")
            self._queue.append(req)
            self._cond.notify()
        if self._g_replica_queue is not None:
            self._g_replica_queue.labels(
                replica=str(self.replica)).set(self.admission.pending)
        return req.future

    # ------------------------------------------------------------ dispatcher
    #: requires-lock: _cond
    def _take_group(self) -> Optional[List[_Request]]:
        """Under the lock: wait for work, honor the fill-or-deadline policy,
        then cut one shape-compatible group from the queue."""
        while True:
            if self._closed and not self._queue:
                return None
            if not self._queue:
                self._cond.wait(0.05)
                continue
            head = self._queue[0]
            rows = 0
            group: List[_Request] = []
            for r in self._queue:
                if r.key == head.key and rows + r.n <= self.max_batch:
                    group.append(r)
                    rows += r.n
                    if rows == self.max_batch:
                        break
            deadline = head.t_enqueue + self.max_latency_s
            now = time.perf_counter()
            if rows < self.max_batch and now < deadline \
                    and not self._closed:
                self._cond.wait(deadline - now)
                continue
            # one O(queue) rebuild, not O(queue) remove() per member — at
            # saturation depth the quadratic scan would eat the GIL budget
            # the batching is supposed to win back
            taken = set(map(id, group))
            self._queue = [r for r in self._queue if id(r) not in taken]
            return group

    def _dispatch(self, group: List[_Request]) -> None:
        rows = sum(r.n for r in group)
        bucket = batch_bucket(rows, self.max_batch)
        # close each member's queue-wait span at the group cut, then open
        # ONE dispatch span on its own trace that *links* the N member
        # traces (OTel batch-consumer fan-in: no single parent is honest)
        links = []
        for r in group:
            r.span.set_attr(bucket=bucket)
            ref = r.span.ref()
            if ref is not None:
                links.append(ref)
            r.span.finish()
        dspan = start_span("batch.dispatch", links=tuple(links),
                           model=group[0].model, rows=rows, bucket=bucket,
                           requests=len(group))
        if self.replica is not None:
            dspan.set_attr(replica=self.replica)
        try:
            self._dispatch_inner(group, rows, bucket, dspan)
        finally:
            dspan.finish()

    def _dispatch_inner(self, group: List[_Request], rows: int,
                        bucket: int, dspan) -> None:
        try:
            # (replica, version) resolve HERE, at dispatch time: the atomic
            # active pointer means a group enqueued against version N can
            # legally dispatch against N+1 — each is internally consistent
            mv = self.registry.active(group[0].model)
            dspan.set_attr(
                version=mv.version,
                compile_cache_hit=getattr(mv.predict_fn, "cache_hit", None))
            n_inputs = len(group[0].xs)
            xs = []
            for j in range(n_inputs):
                x = np.concatenate([r.xs[j] for r in group], axis=0)
                if bucket > rows:
                    pad = np.zeros((bucket - rows,) + x.shape[1:], x.dtype)
                    x = np.concatenate([x, pad], axis=0)
                xs.append(x)
            t0 = time.perf_counter()
            raw = mv.predict_fn(*xs)
            multi_out = isinstance(raw, (list, tuple))
            if not multi_out:
                raw = [raw]
            # lint: host-sync-in-hot-loop-ok (serving must materialize the response; the sync IS the dispatch being timed)
            outs = [np.asarray(o) for o in raw]
            dt = time.perf_counter() - t0
        except Exception as e:
            self._c_errors.inc(len(group))
            dspan.set_status("error").set_attr(error=repr(e))
            _flight_recorder().dump(
                reason="serve-dispatch-error",
                extra={"model": group[0].model, "rows": rows,
                       "bucket": bucket, "error": repr(e)})
            for r in group:
                r.future.set_exception(e)
            return
        finally:
            self.admission.release(len(group))
            if self._g_replica_queue is not None:
                self._g_replica_queue.labels(
                    replica=str(self.replica)).set(self.admission.pending)
        occupancy = rows / bucket
        dspan.set_attr(dispatch_s=round(dt, 6), occupancy=round(occupancy, 4))
        # a serve dispatch advances the step clock like a fit dispatch, so
        # the recompile-storm window is measured in dispatches (bucket
        # warm-up compiles are expected; steady-state compiles are the bug)
        _compile_tracker().note_step()
        self._c_batches.labels(model=mv.name).inc()
        self._h_dispatch.observe(dt)
        self._g_occupancy.set(occupancy)
        if self._g_replica_occ is not None:
            self._g_replica_occ.labels(
                replica=str(self.replica)).set(occupancy)
        _profile_note_dispatch(dt)
        with self._lock:
            self._dispatches += 1
            self._occupancy_sum += occupancy
            self._buckets_seen.add((group[0].key, bucket))
            n_dispatch = self._dispatches
        _flight_recorder().record(
            "serve_batch", model=mv.name, version=mv.version, rows=rows,
            bucket=bucket, requests=len(group), dispatch_s=dt,
            **({"replica": self.replica} if self.replica is not None else {}))
        _wd_beat(n_dispatch)
        off = 0
        for r in group:
            pred = [o[off:off + r.n] for o in outs]
            r.future.set_result(
                {"predictions": pred if multi_out else pred[0],
                 "model": mv.name, "version": mv.version,
                 "batch_rows": rows, "bucket": bucket,
                 "replica": self.replica})
            off += r.n

    def _loop(self) -> None:
        while True:
            with self._cond:
                group = self._take_group()
            if group is None:
                return
            self._dispatch(group)

    # -------------------------------------------------------------- control
    def stats(self) -> dict:
        with self._lock:
            return {
                "queue_depth": len(self._queue),
                "pending": self.admission.pending,
                "max_queue": self.admission.max_pending,
                "rejected": self.admission.rejected,
                "dispatches": self._dispatches,
                "mean_occupancy": (self._occupancy_sum / self._dispatches
                                   if self._dispatches else 0.0),
                "buckets": sorted(
                    (list(map(str, key)), bucket)
                    for key, bucket in self._buckets_seen),
                "bucket_count": len(self._buckets_seen),
                "max_batch": self.max_batch,
                "max_latency_s": self.max_latency_s,
                "replica": self.replica,
            }

    def close(self, timeout_s: float = 5.0) -> None:
        """Stop accepting work; the dispatcher drains the queue first."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout_s)
