"""Device prefetch: overlap host->device transfer with the running dispatch.

Reference AsyncDataSetIterator.java:36 prefetches *host* batches on a worker
thread. On TPU that is only half the win: the staging phase the telemetry
attributes per step (``dl4j_fit_phase_seconds{phase="staging"}``) is the host
stack + ``stage_dtype`` cast + transfer SUBMIT, and in the synchronous fit
loops it ran serially before every donated dispatch. ``DevicePrefetcher``
moves that work to a background thread: while step *n*'s dispatch executes,
the producer pulls the next K-step group from the iterator, stages it, and
issues a **non-blocking** ``jax.device_put`` — so batch *n+1* is in flight to
HBM behind the compute (the tf.data/GPipe input-pipeline overlap pattern).

Donation safety — the ownership hand-off, explicitly:

* The jitted train steps donate ONLY ``(params, states, updater_state)``
  (``donate_argnums=(0, 1, 2)``); batch inputs are never donated, so XLA
  never reuses a staged batch buffer for step outputs.
* Every staged item is produced from host numpy by ``jax.device_put`` /
  ``make_array_from_callback`` — a FRESH *device* buffer per group, never a
  view of a buffer an in-flight step reads. Only the host side is reused:
  the networks' stage function (``nn.multilayer.stage_group``) writes each
  group into a slot of a ``HostGroupRing`` and puts the slot. The runtime
  reads a slot until the copy is done, so a slot is rewritten only after
  ``block_until_ready()`` on the device arrays last made from it; and where
  the backend wrapped the slot's memory instead of copying it (the CPU
  backend does for a 64-byte-aligned buffer) the device arrays own that
  memory from then on and the ring allocates a new slot in its place.
  ``ParallelWrapper``'s synchronous loop stages through the same function
  with the put laid out over its mesh: every device is sent its shard as a
  view of the slot, so the slot belongs to every shard of the arrays made
  from it. It is rewritten only when all of them, on every device, are
  ready (``block_until_ready`` of the whole array), and given away if any
  shard reads its memory. The wrapper keeps its ring between ``fit`` calls
  and drains it when one returns (``drain``), so the ring holds host
  memory then and no device array.
* Each queue slot is consumed by exactly one dispatch: the consumer pops an
  item, hands it to the train step, and drops its reference. The ring keeps
  one to the group's device arrays only until their copy has finished (it
  looks at every staging call). Nothing ever aliases the donated
  params/state buffers, so depth-2 prefetch cannot trigger a
  "deleted buffer" error (pinned by tests/test_prefetch.py).

Bounded depth (default 2 = double buffering) caps HBM held by queued batches
at ``depth * group_bytes``; the networks' fit loops and the wrapper's keep
at most two dispatched groups unfinished besides
(``nn.multilayer.wait_for_step``, span ``fit.step_wait``). depth <= 0
degrades to synchronous inline staging (the pre-prefetch behavior, used by
the numerical-equivalence tests and the bench A/B).

Spans. Every staged item gets a process-wide sequence number, its ``group``,
when the producer starts to pull it; the number travels with the item through
the queue, and ``current_group()`` answers it on whichever thread is working
on the item (the producer while it pulls and stages, the fit loop from the
moment it receives it). The prefetcher writes ``input.pull`` and ``fit.wait``
into the flight recorder's ring; the stage function writes its own
(``nn.multilayer.stage_group``: ``input.stack``, ``input.cast``,
``input.h2d``), and the dispatch its ``fit.step_wait`` and ``fit.dispatch``,
all under that number.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Callable, Iterable, Optional

import jax
import numpy as np

from deeplearning4j_tpu.observability.flight_recorder import (
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.names import (
    PREFETCH_BYTES_TOTAL, PREFETCH_DEPTH,
    PREFETCH_SLOT_WAIT_SECONDS_TOTAL, PREFETCH_STAGE_SLOTS_TOTAL,
    PREFETCH_STAGING_SECONDS_TOTAL, PREFETCH_WAIT_SECONDS_TOTAL,
)
from deeplearning4j_tpu.observability.metrics import (
    global_registry as _obs_registry, tree_nbytes as _tree_nbytes,
)

# families resolved once at import; one series per `path` label (which fit
# loop is prefetching). Budget pinned by test_telemetry_overhead_budget.
_depth_gauge = _obs_registry().gauge(
    PREFETCH_DEPTH,
    "staged items currently queued ahead of the dispatch loop, by fit path")
_bytes_total = _obs_registry().counter(
    PREFETCH_BYTES_TOTAL,
    "bytes of staged device arrays handed to the prefetch queue, by fit path")
_staging_total = _obs_registry().counter(
    PREFETCH_STAGING_SECONDS_TOTAL,
    "producer-thread seconds spent pulling + staging items (the work hidden "
    "behind dispatch when overlap works), by fit path")
_wait_total = _obs_registry().counter(
    PREFETCH_WAIT_SECONDS_TOTAL,
    "consumer seconds blocked waiting for a staged item (staging NOT hidden "
    "behind dispatch), by fit path")

_slots_total = _obs_registry().counter(
    PREFETCH_STAGE_SLOTS_TOTAL,
    "staged groups by where their host buffers came from: a ring slot written "
    "before (reused) or a new one (allocated), by fit path")
_slot_wait_total = _obs_registry().counter(
    PREFETCH_SLOT_WAIT_SECONDS_TOTAL,
    "staging seconds blocked until the previous transfer out of a ring slot "
    "had finished, by fit path")

_DONE = object()  # queue sentinel: producer finished (or was stopped)

_group_seq = itertools.count()  # next() is one bytecode: atomic under the GIL
_working_on = threading.local()


def begin_group() -> int:
    """A new group number, made this thread's current one."""
    _working_on.group = group = next(_group_seq)
    return group


def end_group() -> None:
    """This thread is working on no group any more (a fit call returned)."""
    _working_on.group = None


def current_group() -> Optional[int]:
    """The staged item this thread is working on (module docstring), or None
    where the thread has touched none."""
    return getattr(_working_on, "group", None)


def _reads_host_memory(dev, host) -> bool:
    """Whether ``dev``, the array a put of ``host`` returned, reads
    ``host``'s own memory: only a CPU device can, and then its buffer, or
    that of one of its shards, lies inside the numpy array's."""
    if all(d.platform != "cpu" for d in dev.devices()):
        return False
    lo = host.ctypes.data
    parts = ([dev] if len(dev.devices()) == 1
             else [s.data for s in dev.addressable_shards])
    return any(lo <= a.unsafe_buffer_pointer() < lo + host.nbytes
               for a in parts)


#: a slot's memory: untouched pages, so a slot costs nothing until a group is
#: written into it
_host_buffer = np.empty


class _Slot:
    """One group's host buffers, ``(capacity, *shape)`` per leaf, and the
    device arrays last made from them whose copy may still be running."""
    __slots__ = ("buffers", "in_flight")

    def __init__(self, buffers):
        self.buffers = buffers
        self.in_flight = []


class HostGroupRing:
    """``size`` reusable host slots for staged K-step groups (donation
    safety, module docstring). A slot is allocated on first use and holds
    one group: per leaf an array ``(capacity, *shape)`` of the staged dtype.
    ``stage`` takes the next slot round the ring, first waiting for the
    transfer last made from it, has the caller write the group into it, and
    puts it. A group of another per-batch shape or dtype, or longer than the
    capacity, drops every slot and starts anew. One stager at a time: a
    network's only one is its fit loop's producer.
    """

    def __init__(self, size: int, path: str):
        self.size = size
        self._spec = None
        self._capacity = 0
        self._slots: list = []
        self._next = 0
        self._m_slots = {o: _slots_total.labels(path=path, outcome=o)
                         for o in ("reused", "allocated")}
        self._m_wait = _slot_wait_total.labels(path=path)

    def stage(self, spec: tuple, n: int, fill: Callable,
              shardings: Optional[list] = None) -> list:
        """Stage a group of ``n`` batches; ``spec`` is the ``(shape, dtype)``
        of each leaf of one batch. ``fill`` is called with the host arrays
        ``(n, *shape)`` to write the group into, one per leaf. Returns the
        device arrays put from them, leaf by leaf: on the default device,
        or with ``shardings`` (one ``Sharding`` a leaf) laid out over a
        mesh, every addressable device sent its shard as a view of the
        filled array (nothing lands whole on one device first, and the
        processes of a cluster exchange nothing). A slot whose memory one
        of them reads is given away."""
        if spec != self._spec or n > self._capacity:
            # in-flight transfers keep their buffers alive themselves
            self._spec, self._capacity = spec, n
            self._slots = [None] * self.size
            self._next = 0
        for slot in self._slots:
            if slot is not None and slot.in_flight:
                slot.in_flight = [d for d in slot.in_flight
                                  if not d.is_ready()]
        i = self._next
        self._next = (i + 1) % self.size
        slot = self._slots[i]
        if slot is None:
            slot = self._slots[i] = _Slot(
                [_host_buffer((self._capacity,) + shape, dtype)
                 for shape, dtype in spec])
            self._m_slots["allocated"].inc()
        else:
            if slot.in_flight:
                t0 = time.perf_counter()
                for d in slot.in_flight:
                    d.block_until_ready()
                self._m_wait.inc(time.perf_counter() - t0)
            self._m_slots["reused"].inc()
        staged = [b[:n] for b in slot.buffers]
        fill(staged)
        if shardings is None:
            device = [jax.device_put(a) for a in staged]
        else:
            device = [jax.make_array_from_callback(a.shape, s, a.__getitem__)
                      for a, s in zip(staged, shardings)]
        if any(_reads_host_memory(d, b)
               for d, b in zip(device, slot.buffers)):
            self._slots[i] = None
        else:
            slot.in_flight = device
        return device

    def drain(self) -> None:
        """Wait for every transfer still running out of a slot and let go of
        the device arrays: for an owner that keeps the ring between fits,
        which would else hold the last groups' device memory through it."""
        for slot in self._slots:
            if slot is not None:
                for d in slot.in_flight:
                    d.block_until_ready()
                slot.in_flight = []


class DevicePrefetcher:
    """Pull items from ``source`` on a background thread, run ``stage`` on
    each (stack + cast + non-blocking ``jax.device_put`` — staging decides
    the sharding, e.g. a ``NamedSharding`` from ParallelWrapper._batch_spec),
    and yield staged items in order through a bounded queue.

    Single-use iterable. Errors raised by the iterator or by ``stage``
    propagate to the consumer AFTER every item staged before them — the
    consumer observes the same prefix of work as the synchronous loop.
    ``close()`` (also called when iteration ends or the consumer's for-loop
    exits early) shuts the producer down deterministically; the thread never
    stays blocked on a full queue.

    ``wait_series``: optional histogram series (e.g. the fit loops'
    ``dl4j_fit_phase_seconds{phase="staging"}``) observing what the consumer
    actually waited per item — under working overlap it collapses toward 0.
    ``path=None`` disables all metrics and spans (host-only use,
    AsyncDataSetIterator).
    """

    def __init__(self, source: Iterable, stage: Optional[Callable] = None,
                 *, depth: int = 2, path: Optional[str] = "default",
                 wait_series=None):
        self._source = source
        self._stage = stage
        self._depth = depth
        self._wait_series = wait_series
        self._path = path
        if path is not None:
            self._m_depth = _depth_gauge.labels(path=path)
            self._m_bytes = _bytes_total.labels(path=path)
            self._m_staging = _staging_total.labels(path=path)
            self._m_wait = _wait_total.labels(path=path)
        else:
            self._m_depth = self._m_bytes = None
            self._m_staging = self._m_wait = None
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self.thread: Optional[threading.Thread] = None

    # ---------------------------------------------------------------- producer
    def _put(self, item) -> bool:
        """Bounded put that polls the stop flag — a consumer that went away
        can never strand the producer on a full queue (the reference
        AsyncDataSetIterator leak)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self, it):
        """Pull the next item and stage it on the calling thread, under a
        new group number. Returns ``(group, item, seconds)``; the item is
        ``_DONE`` at the source's end."""
        # a prefetcher without a path (AsyncDataSetIterator) numbers nothing:
        # it runs inside a fit loop's pull, on that loop's producer thread,
        # whose current group it must leave alone
        group = begin_group() if self._path is not None else None
        t0, w0 = time.perf_counter(), time.time_ns()
        try:
            item = next(it)
        except StopIteration:
            return group, _DONE, 0.0
        w1 = time.time_ns()
        if self._stage is not None:
            item = self._stage(item)
        dt = time.perf_counter() - t0
        if self._path is not None:
            _flight_recorder().record_span("input.pull", w0, w1, group=group,
                                           path=self._path)
        if self._m_staging is not None:
            self._m_staging.inc(dt)
            nbytes = _tree_nbytes(item)
            if nbytes:
                self._m_bytes.inc(nbytes)
        return group, item, dt

    def _run(self) -> None:
        try:
            it = iter(self._source)
            while not self._stop.is_set():
                group, item, _ = self._produce(it)
                if item is _DONE or not self._put((group, item)):
                    return
                if self._m_depth is not None:
                    self._m_depth.set(self._q.qsize())
        except BaseException as e:  # propagate into the consumer, in order
            self._error = e
        finally:
            self._put(_DONE)

    # ---------------------------------------------------------------- consumer
    def __iter__(self):
        if self._depth <= 0:
            yield from self._iter_sync()
            return
        self.thread = threading.Thread(
            target=self._run, daemon=True,
            name="dl4j-prefetch" if self._wait_series is None
            else "dl4j-prefetch-staging")
        self.thread.start()
        try:
            while True:
                t0, w0 = time.perf_counter(), time.time_ns()
                got = self._q.get()
                wait = time.perf_counter() - t0
                if got is _DONE:
                    if self._error is not None:
                        raise self._error
                    return
                group, item = got
                if self._m_wait is not None:
                    _working_on.group = group
                    _flight_recorder().record_span(
                        "fit.wait", w0, time.time_ns(), group=group,
                        path=self._path)
                    self._m_wait.inc(wait)
                    self._m_depth.set(self._q.qsize())
                if self._wait_series is not None:
                    self._wait_series.observe(wait)
                yield item
        finally:
            self.close()

    def _iter_sync(self):
        """depth <= 0: the exact pre-prefetch behavior — pull and stage
        inline on the consumer thread, the full cost visible in
        ``wait_series`` and as the group's ``fit.wait``."""
        it = iter(self._source)
        while True:
            w0 = time.time_ns()
            group, item, dt = self._produce(it)
            if item is _DONE:
                return
            if self._path is not None:
                _flight_recorder().record_span("fit.wait", w0, time.time_ns(),
                                               group=group, path=self._path)
            if self._wait_series is not None:
                self._wait_series.observe(dt)
            yield item

    def close(self) -> None:
        """Deterministic shutdown: stop the producer, unblock it by draining
        the queue, and join. Safe to call more than once."""
        self._stop.set()
        if self.thread is None:
            return
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self.thread.join(timeout=5.0)

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
