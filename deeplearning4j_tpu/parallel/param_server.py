"""Staleness-bounded asynchronous parameter-server training.

Reference: deeplearning4j-scaleout ParameterServerParallelWrapper.java — embeds
an Aeron media driver + ParameterServerNode (:159-161); worker threads
pushNDArray(model.params()) (:328) and re-fetch the global array (:305),
training asynchronously between syncs.

TPU-native redesign (stale-synchronous-parallel, not a thread toy):

* **Server** (`ParameterServer`): the canonical parameters live as ONE flat
  float32 vector behind a lock, with a monotonically increasing *version*.
  Workers push **deltas** (local params minus the base they pulled), not raw
  params; the server applies each delta through a server-side optimizer and
  bumps the version. A push whose base version is ``s`` behind is
  down-weighted by ``1/(1+s)``; pushes staler than ``staleness_cap`` are
  hard-rejected, forcing the worker to re-pull and rebase. This replaces the
  old ``(a+b)/2`` soft-average, where the *last* pusher always owned half
  the model regardless of worker count.

* **Transport** (`parallel/ps_transport.py`): one `Transport` API with two
  backends — ``inproc`` (direct calls, worker threads; deterministic tests)
  and ``tcp`` (stdlib sockets, length-prefixed frames, workers in separate
  OS processes so the GIL cannot mask the straggler win). Pushed deltas can
  ride the wire as bf16; canonical server state stays f32.

* **Overlap**: a double-buffered background pull (`_BackgroundPuller`, the
  DevicePrefetcher philosophy from datasets/prefetch.py) fetches fresh
  global params while the worker computes, so mid-window catch-up costs no
  worker wall-clock and staleness stays low.

The wrapper keeps the reference Builder API and grows it:
``.staleness(cap)``, ``.compression("bf16"|"none")``,
``.transport("inproc"|"tcp")``. Worker train steps compile through the
partition-rule seam (parallel/compile_seam.py) so they share CompileTracker
attribution with every other fit path.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.observability.compile_tracker import (
    global_tracker as _compile_tracker,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    dump_on_unhandled as _dump_on_unhandled,
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.metrics import (
    global_registry as _obs_registry,
)
from deeplearning4j_tpu.observability.names import (
    ELASTIC_FENCED_PUSHES_TOTAL, PS_PULLS_TOTAL, PS_PUSHES_TOTAL,
    PS_PUSH_WEIGHT, PS_STALENESS, PS_VERSION, PS_WORKER_STEPS_TOTAL,
)
from deeplearning4j_tpu.observability.tracing import (
    current_span as _current_span,
    trace_span as _trace_span,
)
from deeplearning4j_tpu.observability.watchdog import beat as _wd_beat

#: default hard staleness bound: a push based >8 versions back is rejected
DEFAULT_STALENESS_CAP = 8

_pushes = _obs_registry().counter(
    PS_PUSHES_TOTAL, "delta pushes by outcome (applied|rejected)")
_pushes_applied = _pushes.labels(outcome="applied")
_pushes_rejected = _pushes.labels(outcome="rejected")
_pulls = _obs_registry().counter(PS_PULLS_TOTAL,
                                 "server param pulls").labels()
_staleness_hist = _obs_registry().histogram(
    PS_STALENESS, "versions behind head at push time",
    buckets=(0, 1, 2, 4, 8, 16, 32, 64)).labels()
_weight_hist = _obs_registry().histogram(
    PS_PUSH_WEIGHT, "staleness down-weight 1/(1+s) applied to each delta",
    buckets=(0.05, 0.1, 0.2, 0.3, 0.5, 0.8, 1.0)).labels()
_version_gauge = _obs_registry().gauge(
    PS_VERSION, "server param version (total applied pushes)").labels()
_worker_steps = _obs_registry().counter(
    PS_WORKER_STEPS_TOTAL, "local train steps by PS workers")
_fenced_pushes = _obs_registry().counter(
    ELASTIC_FENCED_PUSHES_TOTAL,
    "pushes rejected because the worker's membership epoch is dead "
    "(zombie fencing)").labels()


# --------------------------------------------------------------------------
# flat-vector codec: the whole param pytree as one contiguous f32 vector
# (what rides the wire and what the server owns)

@dataclass(frozen=True)
class TreeSpec:
    treedef: object
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[object, ...]
    sizes: Tuple[int, ...]


def flatten_tree(tree) -> Tuple[np.ndarray, TreeSpec]:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    host = [np.asarray(leaf) for leaf in leaves]
    spec = TreeSpec(treedef=treedef,
                    shapes=tuple(a.shape for a in host),
                    dtypes=tuple(a.dtype for a in host),
                    sizes=tuple(a.size for a in host))
    if not host:
        return np.zeros(0, np.float32), spec
    vec = np.concatenate([a.astype(np.float32, copy=False).ravel()
                          for a in host])
    return vec, spec


def unflatten_tree(vec: np.ndarray, spec: TreeSpec, *, as_jax: bool = False):
    leaves, off = [], 0
    for shape, dtype, size in zip(spec.shapes, spec.dtypes, spec.sizes):
        a = vec[off:off + size].reshape(shape).astype(dtype, copy=False)
        leaves.append(jnp.asarray(a) if as_jax else a)
        off += size
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# --------------------------------------------------------------------------
# server

@dataclass
class PushResult:
    """Outcome of one delta push. ``params``/``version`` always carry the
    post-push server state (a rejected push's forced re-pull rides the same
    round trip). ``fenced`` marks an epoch-fenced rejection: the pusher's
    membership lease is dead and no rebase/retry can ever succeed."""
    accepted: bool
    version: int
    staleness: int
    weight: float
    params: Optional[np.ndarray] = None
    fenced: bool = False


class StaleEpochFenced(RuntimeError):
    """The worker's membership epoch was fenced: its lease lapsed (or was
    superseded) and the server rejects its pushes permanently. The worker
    must exit; a replacement re-registers with a fresh epoch."""


class _ServerOptimizer:
    """Server-side update rule for pushed deltas (the PS analog of the
    reference ParameterServerNode's updater): plain SGD applies
    ``lr * weight * delta``; momentum folds deltas into a velocity first,
    smoothing bursty async arrivals."""

    def __init__(self, kind: str = "sgd", lr: float = 1.0,
                 momentum: float = 0.9):
        if kind not in ("sgd", "momentum"):
            raise ValueError(f"unknown server optimizer {kind!r}; "
                             "expected 'sgd' or 'momentum'")
        self.kind, self.lr, self.momentum = kind, lr, momentum
        self._vel: Optional[np.ndarray] = None

    def apply(self, params: np.ndarray, delta: np.ndarray,
              weight: float) -> np.ndarray:
        if self.kind == "sgd":
            params += (self.lr * weight) * delta
        else:
            if self._vel is None:
                self._vel = np.zeros_like(params)
            self._vel *= self.momentum
            self._vel += weight * delta
            params += self.lr * self._vel
        return params


class ParameterServer:
    """Versioned canonical param store (reference ParameterServerNode role).

    All mutation happens under one lock; ``version`` counts applied pushes.
    Thread-safe; the TCP front-end (`parallel/ps_transport.py`) serves the
    same object to out-of-process workers.
    """

    def __init__(self, initial_params, *,
                 staleness_cap: int = DEFAULT_STALENESS_CAP,
                 optimizer: str = "sgd", server_lr: float = 1.0,
                 momentum: float = 0.9, membership=None):
        vec, spec = flatten_tree(initial_params)
        self._vec = vec
        self._spec = spec
        self._opt = _ServerOptimizer(optimizer, server_lr, momentum)
        self._lock = threading.Lock()
        self.staleness_cap = int(staleness_cap)
        self.version = 0
        self.pushes = 0          # applied (legacy counter, kept public)
        self.rejected = 0
        #: cloud.MembershipOracle (or None): when set, pushes carrying a
        #: (member, epoch) identity are epoch-fenced against its leases
        self.membership = membership
        self.fenced = 0

    @property
    def spec(self) -> TreeSpec:
        return self._spec

    # ------------------------------------------------------------- core API
    def push_delta(self, delta: np.ndarray, base_version: int, *,
                   member: Optional[int] = None,
                   epoch: Optional[int] = None) -> PushResult:
        """Apply a worker delta computed against ``base_version``.

        staleness s = version - base_version; weight = 1/(1+s). A push with
        s > staleness_cap is rejected (weight 0) and the caller must rebase
        onto the returned fresh state before retrying.

        When a membership oracle is attached and the push carries a
        ``(member, epoch)`` identity, a dead/superseded epoch is fenced:
        rejected with ``fenced=True``, permanently — the zombie's delta must
        never land after its shard was handed off.
        """
        delta = np.asarray(delta, np.float32)
        if (self.membership is not None and member is not None
                and not self.membership.validate(member, epoch)):
            with self._lock:
                self.fenced += 1
                self.rejected += 1
                _fenced_pushes.inc()
                _pushes_rejected.inc()
                _flight_recorder().record(
                    "ps_push_fenced", member=member, epoch=epoch,
                    version=self.version)
                return PushResult(False, self.version,
                                  self.version - int(base_version), 0.0,
                                  np.copy(self._vec), fenced=True)
        with self._lock:
            staleness = self.version - int(base_version)
            _staleness_hist.observe(staleness)
            if staleness > self.staleness_cap:
                self.rejected += 1
                _pushes_rejected.inc()
                _flight_recorder().record(
                    "ps_push_rejected", staleness=staleness,
                    cap=self.staleness_cap, version=self.version)
                return PushResult(False, self.version, staleness, 0.0,
                                  np.copy(self._vec))
            weight = 1.0 / (1.0 + max(0, staleness))
            self._vec = self._opt.apply(self._vec, delta, weight)
            self.version += 1
            self.pushes += 1
            _pushes_applied.inc()
            _weight_hist.observe(weight)
            _version_gauge.set(self.version)
            _wd_beat(self.version)
            return PushResult(True, self.version, staleness, weight,
                              np.copy(self._vec))

    def pull_flat(self) -> Tuple[int, np.ndarray]:
        _pulls.inc()
        with self._lock:
            return self.version, np.copy(self._vec)

    # ------------------------------------------------- legacy pytree facade
    def push(self, params, base_version: Optional[int] = None) -> PushResult:
        """Full-param push (the pre-engine API): converted to a delta against
        the caller's base — or, when no base version is known, against the
        current head (last-writer-wins at weight 1, staleness 0)."""
        vec, _ = flatten_tree(params)
        with self._lock:
            head = np.copy(self._vec)
            base = self.version if base_version is None else base_version
        return self.push_delta(vec - head, base)

    def pull(self):
        _, vec = self.pull_flat()
        return unflatten_tree(vec, self._spec)


# --------------------------------------------------------------------------
# hooks (unchanged SPI)

class ParameterServerTrainingHook:
    """Training-hook SPI (reference dl4j-spark-parameterserver
    ParameterServerTrainingHook.java): callbacks around each worker's local
    update so custom logic (gradient compression, auditing, custom sync) can
    interpose on the async training path."""

    def pre_update(self, dataset, model) -> None:
        pass

    def post_update(self, dataset, model) -> None:
        pass


# --------------------------------------------------------------------------
# background pull: double-buffered fetch that overlaps local compute

class _BackgroundPuller:
    """Fetch fresh (version, params) on a daemon thread while the worker
    computes (DevicePrefetcher philosophy: the transfer hides behind the
    step). `latest()` is non-blocking; `request()` forces an immediate
    fetch (the wake event fires regardless of where the thread is in its
    wait); between requests the thread polls at ``poll_interval_s``,
    doubling the interval up to ``idle_backoff_cap_s`` while the server
    version is NOT advancing — an idle fleet stops burning CPU on no-change
    pulls — and snapping back to the base interval on any fresh version or
    explicit request."""

    def __init__(self, pull_fn: Callable[[], Tuple[int, np.ndarray]],
                 poll_interval_s: float = 0.05,
                 idle_backoff_cap_s: float = 0.8):
        self._pull = pull_fn
        self._interval = poll_interval_s
        self._idle_cap = max(poll_interval_s, idle_backoff_cap_s)
        self._buf: Optional[Tuple[int, np.ndarray]] = None
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        wait = self._interval
        last_version = -1
        while True:
            requested = self._wake.wait(wait)
            self._wake.clear()
            if self._stop:
                return
            try:
                got = self._pull()
            except (OSError, RuntimeError) as e:
                # transport teardown race at fit() shutdown: the worker
                # falls back to its push-ack state; nothing to propagate
                _flight_recorder().record("ps_bg_pull_error", error=str(e))
                continue
            fresh = got[0] > last_version
            last_version = max(last_version, got[0])
            with self._lock:
                if self._buf is None or got[0] > self._buf[0]:
                    self._buf = got
            # exponential idle backoff: only stale no-request polls widen
            # the interval; data or a request() resets it immediately
            if requested or fresh:
                wait = self._interval
            else:
                wait = min(wait * 2.0, self._idle_cap)

    def request(self) -> None:
        self._wake.set()

    def latest(self) -> Optional[Tuple[int, np.ndarray]]:
        with self._lock:
            buf, self._buf = self._buf, None
        return buf

    def stop(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=10)


# --------------------------------------------------------------------------
# worker loop (shared by in-process threads and `python -m ...ps_worker`)

def run_worker_loop(*, transport, replica, step_fn, next_batch,
                    push_frequency: int,
                    hooks: Sequence[ParameterServerTrainingHook] = (),
                    delay_s: float = 0.0, worker_id: int = 0,
                    background_pull: bool = True,
                    on_push: Optional[Callable[[bool], None]] = None) -> dict:
    """Train ``replica`` on batches from ``next_batch()`` (None = done),
    pushing a delta every ``push_frequency`` steps; returns worker stats.

    ``step_fn(params, states, upd, x, y, rng, it) -> (params, states, upd,
    loss)`` is the compiled train step; pass None to fall back to
    ``replica.fit`` (non-MultiLayerNetwork models).
    ``delay_s`` is the per-step fault-injection sleep used by the straggler
    benchmarks/tests.
    ``on_push(accepted)`` fires after each push window resolves — the
    elastic worker commits its broker offsets there, so samples are marked
    consumed only once their delta landed (at-least-once accounting).
    An epoch-fenced push raises ``StaleEpochFenced`` immediately: the
    worker's lease is dead, retrying cannot help, and training on must not
    continue (its shard now belongs to a replacement).
    """
    spec = None
    version, base_vec = transport.pull()
    steps = pushes = rejected = rebased = 0
    steps_since_push = 0
    step_series = _worker_steps.labels(worker=str(worker_id))

    def _set_replica(vec: np.ndarray) -> None:
        nonlocal spec
        if spec is None:
            _, spec = flatten_tree(replica.params_list)
        replica.params_list = unflatten_tree(vec, spec, as_jax=True)

    _set_replica(base_vec)
    # the puller gets its OWN connection when the transport supports it
    # (tcp), so background fetches genuinely overlap pushes on the wire
    bg_transport = (transport.clone() if background_pull
                    and hasattr(transport, "clone") else transport)
    puller = (_BackgroundPuller(bg_transport.pull)
              if background_pull else None)
    if puller is not None:
        puller.request()

    def _push_window() -> None:
        # nest the window's push RPC(s) under one span parented by the
        # batch's consume span (bound on the transport by the elastic
        # worker); with no parent, open no span — a static worker would
        # only mint root-trace noise
        parent = _current_span() or getattr(transport, "trace_parent", None)
        if parent is None:
            return _push_window_inner()
        with _trace_span("ps.push_window", parent=parent,
                         worker=str(worker_id)):
            return _push_window_inner()

    def _push_window_inner() -> None:
        nonlocal version, base_vec, steps_since_push, pushes, rejected
        local, _ = flatten_tree(replica.params_list)
        delta = local - base_vec
        # pre-push rebase: a delta is position-independent (the server
        # applies head + w*delta), so the freshest background-pulled
        # version is this window's honest base — global progress the
        # worker has already seen must not count against it as staleness
        if puller is not None:
            got = puller.latest()
            if got is not None and got[0] > version:
                version = got[0]
        res = transport.push(delta, version)
        if getattr(res, "fenced", False):
            raise StaleEpochFenced(
                f"worker {worker_id}: push fenced at version {res.version}")
        if not res.accepted:
            # hard-rejected: rebase the local window onto the forced
            # re-pull state, then re-push at ~zero staleness
            rejected += 1
            res2 = transport.push(delta, res.version)
            if getattr(res2, "fenced", False):
                raise StaleEpochFenced(
                    f"worker {worker_id}: push fenced at version "
                    f"{res2.version}")
            res = res2 if res2.accepted else res
        if res.accepted:
            pushes += 1
        version, base_vec = res.version, res.params
        _set_replica(base_vec)
        steps_since_push = 0
        if on_push is not None:
            on_push(res.accepted)
        if puller is not None:
            puller.request()

    try:
        while True:
            ds = next_batch()
            if ds is None:
                break
            if delay_s > 0.0:
                time.sleep(delay_s)
            # mid-window catch-up from the background pull: fold fresh
            # global progress under the local window without blocking or
            # re-counting it
            if puller is not None and steps_since_push > 0:
                got = puller.latest()
                if got is not None and got[0] > version:
                    local, _ = flatten_tree(replica.params_list)
                    version, fresh = got
                    _set_replica(fresh + (local - base_vec))
                    base_vec = fresh
                    rebased += 1
                    puller.request()
            for hook in hooks:
                hook.pre_update(ds, replica)
            if step_fn is not None:
                p, s, u, loss = step_fn(
                    replica.params_list, replica.state_list,
                    replica.updater_state, jnp.asarray(ds.features),
                    jnp.asarray(ds.labels), replica._next_rng(),
                    jnp.int32(replica.iteration))
                replica.params_list, replica.state_list = p, s
                replica.updater_state = u
                replica.score_value = loss
            else:
                replica.fit(ds.features, ds.labels)
            replica.iteration += 1
            for hook in hooks:
                hook.post_update(ds, replica)
            steps += 1
            steps_since_push += 1
            step_series.inc()
            _compile_tracker().note_step(fn=f"ps_worker[{worker_id}]")
            if steps_since_push >= push_frequency:
                _push_window()
        # flush ONLY a partial window: a worker that pushed at the boundary
        # has nothing left, and re-pushing its last delta would double-count
        # it (the pre-engine shutdown bug)
        if steps_since_push > 0:
            _push_window()
    finally:
        # the puller must die even on a fenced/crashed exit, or its daemon
        # thread keeps hammering the transport after the worker is gone
        if puller is not None:
            puller.stop()
            if bg_transport is not transport:
                bg_transport.close()
    return {"worker_id": worker_id, "steps": steps, "pushes": pushes,
            "rejected": rejected, "rebased": rebased,
            "final_version": version}


def make_compiled_worker_step(net, *, transport: str):
    """Compile the replica train step through the partition-rule seam
    (single-replica program: every input replicated on the worker's device;
    CompileTracker attribution rides the seam). Returns None for models
    without a MultiLayerNetwork-style train step — the worker loop then
    falls back to ``replica.fit``."""
    from deeplearning4j_tpu.nn.multilayer import (MultiLayerNetwork,
                                                  make_train_step)
    if not isinstance(net, MultiLayerNetwork):
        return None
    from deeplearning4j_tpu.parallel.compile_seam import compile_step
    from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh
    return compile_step(
        "ParameterServerParallelWrapper.worker_step",
        make_train_step(net.conf), mesh=data_parallel_mesh(),
        rule_set="ps_async", strategy="jit",
        cache_key=(transport,), conf=net.conf)


# --------------------------------------------------------------------------
# wrapper

class ParameterServerParallelWrapper:
    """Async-DP trainer (reference ParameterServerParallelWrapper.java).

    Where the workers compute: ``transport="inproc"`` runs worker threads in
    this process, on whatever device this process holds. ``"tcp"`` and
    ``"shm"`` start worker PROCESSES with ``JAX_PLATFORMS=cpu`` — a chip
    belongs to one process, so a fleet of processes trains **on the CPU by
    construction**, also on a chip machine. What those modes offer is
    host-side orchestration (staleness, stragglers, transports), not chip
    throughput."""

    def __init__(self, model, workers: int = 2, push_frequency: int = 4,
                 prefetch: int = 2,
                 training_hooks: Optional[List[ParameterServerTrainingHook]] = None,
                 staleness: int = DEFAULT_STALENESS_CAP,
                 compression: str = "none",
                 transport: str = "inproc",
                 server_optimizer: str = "sgd", server_lr: float = 1.0,
                 worker_delays: Optional[Sequence[float]] = None):
        if transport not in ("inproc", "tcp", "shm"):
            raise ValueError(f"unknown transport {transport!r}; "
                             "expected 'inproc', 'tcp' or 'shm'")
        if compression not in ("none", "bf16"):
            raise ValueError(f"unknown compression {compression!r}; "
                             "expected 'none' or 'bf16'")
        if transport in ("tcp", "shm") and training_hooks:
            raise ValueError(
                "training hooks run in the worker's interpreter; the tcp "
                "transport trains in separate processes — use inproc")
        self.model = model
        self.workers = workers
        self.push_frequency = max(1, push_frequency)
        self.prefetch = prefetch
        self.training_hooks = list(training_hooks or [])
        self.staleness = int(staleness)
        self.compression = compression
        self.transport = transport
        self.server_optimizer = server_optimizer
        self.server_lr = server_lr
        self.worker_delays = list(worker_delays or [])
        self.worker_stats: List[dict] = []
        self.server: Optional[ParameterServer] = None
        self._compiled_step = None  # one program per wrapper: repeated
        # fit() calls must not re-trace (recompile-storm hygiene)

    class Builder:
        def __init__(self, model):
            self._model = model
            self._kw = {}

        def workers(self, n: int):
            self._kw["workers"] = n
            return self

        def push_frequency(self, n: int):
            self._kw["push_frequency"] = n
            return self

        def training_hooks(self, *hooks):
            self._kw["training_hooks"] = list(hooks)
            return self

        def staleness(self, cap: int):
            """Hard staleness bound τ: pushes based more than τ versions
            behind are rejected (weight already decays as 1/(1+s))."""
            self._kw["staleness"] = cap
            return self

        def compression(self, codec: str):
            """Wire codec for pushed deltas: "bf16" halves push bytes."""
            self._kw["compression"] = codec
            return self

        def transport(self, kind: str):
            """"inproc" (worker threads), "tcp" (worker processes over
            loopback sockets), or "shm" (worker processes; tensor bytes in
            shared-memory rings, control verbs on the socket — falls back
            to tcp frames when segments can't attach)."""
            self._kw["transport"] = kind
            return self

        def server_optimizer(self, kind: str, lr: float = 1.0):
            self._kw["server_optimizer"] = kind
            self._kw["server_lr"] = lr
            return self

        def worker_delays(self, *delays: float):
            """Fault injection for benchmarks/tests: worker i sleeps
            delays[i] seconds before every local step (straggler model)."""
            self._kw["worker_delays"] = list(delays)
            return self

        def build(self) -> "ParameterServerParallelWrapper":
            return ParameterServerParallelWrapper(self._model, **self._kw)

    @staticmethod
    def builder(model) -> "ParameterServerParallelWrapper.Builder":
        return ParameterServerParallelWrapper.Builder(model)

    # ------------------------------------------------------------------ fit
    @_dump_on_unhandled("ParameterServerParallelWrapper.fit")
    def fit(self, iterator, epochs: int = 1) -> None:
        self.server = ParameterServer(
            self.model.params_list, staleness_cap=self.staleness,
            optimizer=self.server_optimizer, server_lr=self.server_lr)
        if self.transport in ("tcp", "shm"):
            self._fit_tcp(iterator, epochs)
        else:
            self._fit_inproc(iterator, epochs)
        self.model.params_list = unflatten_tree(
            self.server.pull_flat()[1], self.server.spec, as_jax=True)
        # lint: host-sync-in-hot-loop-ok (one trusted LazyScore sync after the workers join)
        self.model.score_value = float(self.model.score_value)

    def _delay(self, worker_id: int) -> float:
        if worker_id < len(self.worker_delays):
            return float(self.worker_delays[worker_id])
        return 0.0

    def _fit_inproc(self, iterator, epochs: int) -> None:
        import queue as _queue

        model = self.model
        server = self.server
        if self._compiled_step is None:
            self._compiled_step = make_compiled_worker_step(
                model, transport="inproc")
        step = self._compiled_step
        q: _queue.Queue = _queue.Queue(maxsize=self.workers * max(
            1, self.prefetch))
        failed: List[BaseException] = []
        self.worker_stats = [None] * self.workers

        def make_worker(worker_id: int):
            def run():
                from deeplearning4j_tpu.parallel.ps_transport import (
                    InprocTransport)
                replica = model.clone() if hasattr(model, "clone") else model

                def next_batch():
                    ds = q.get()
                    q.task_done()
                    return ds

                try:
                    self.worker_stats[worker_id] = run_worker_loop(
                        transport=InprocTransport(server), replica=replica,
                        step_fn=(step.fn if step is not None else None),
                        next_batch=next_batch,
                        push_frequency=self.push_frequency,
                        hooks=self.training_hooks,
                        delay_s=self._delay(worker_id),
                        worker_id=worker_id)
                except BaseException as e:
                    failed.append(e)
                    _flight_recorder().record(
                        "ps_worker_crash", worker=worker_id, error=repr(e))
                    raise
            return threading.Thread(target=run, daemon=True,
                                    name=f"ps-worker-{worker_id}")

        threads = [make_worker(i) for i in range(self.workers)]
        for t in threads:
            t.start()
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            for ds in iterator:
                while not failed:
                    try:
                        q.put(ds, timeout=1.0)
                        break
                    except _queue.Full:
                        continue
                if failed:
                    break
        for _ in threads:
            q.put(None)
        for t in threads:
            t.join()
        if failed:
            raise RuntimeError("parameter-server worker crashed") from failed[0]

    def _fit_tcp(self, iterator, epochs: int) -> None:
        """Separate-process workers over loopback TCP (the pattern proven by
        tests/test_distributed_multiprocess.py): the iterator's batches are
        materialized, round-robin partitioned, and shipped to each worker —
        through a shared-memory segment on the "shm" transport (no
        compression, no filesystem round-trip; npz tempfile fallback if the
        host has no usable /dev/shm), as an .npz otherwise; model config
        rides as JSON; workers pull initial params from this process's
        server."""
        import json
        import os
        import subprocess
        import sys
        import tempfile

        from deeplearning4j_tpu.nn.conf.serde import to_json
        from deeplearning4j_tpu.parallel import ps_transport as _pst

        batches = []
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            batches.extend(iterator)
        shards = [batches[i::self.workers] for i in range(self.workers)]

        frontend = _pst.ParameterServerTcpFrontend(self.server).start()
        procs = []
        segments: List[str] = []
        try:
            with tempfile.TemporaryDirectory(prefix="dl4j_ps_") as tmp:
                conf_path = os.path.join(tmp, "conf.json")
                with open(conf_path, "w") as f:
                    f.write(to_json(self.model.conf))
                env = os.environ.copy()
                # workers train on the CPU: a chip belongs to one process
                env["JAX_PLATFORMS"] = "cpu"
                env.pop("XLA_FLAGS", None)  # one CPU device per process
                repo_root = os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))))
                env["PYTHONPATH"] = (repo_root + os.pathsep
                                     + env.get("PYTHONPATH", ""))
                for i, shard in enumerate(shards):
                    x = np.stack([np.asarray(d.features)  # lint: host-sync-in-hot-loop-ok (one-time shard serialization before workers spawn, not a train loop)
                                  for d in shard])
                    y = np.stack([np.asarray(d.labels)  # lint: host-sync-in-hot-loop-ok (one-time shard serialization before workers spawn, not a train loop)
                                  for d in shard])
                    data_path = None
                    if self.transport == "shm":
                        try:
                            seg = _pst.write_shard_segment(
                                {"x": x, "y": y}, kind=f"shard{i}")
                            segments.append(seg)
                            data_path = "shm://" + seg
                        except OSError:
                            data_path = None  # fall through to npz
                    if data_path is None:
                        data_path = os.path.join(tmp, f"worker{i}.npz")
                        np.savez(data_path, x=x, y=y)
                    cmd = [sys.executable, "-m",
                           "deeplearning4j_tpu.parallel.ps_worker",
                           "--addr", f"127.0.0.1:{frontend.port}",
                           "--conf", conf_path, "--data", data_path,
                           "--worker-id", str(i),
                           "--push-frequency", str(self.push_frequency),
                           "--codec", self.compression,
                           "--ps-transport", self.transport,
                           "--delay", str(self._delay(i))]
                    procs.append(subprocess.Popen(
                        cmd, env=env, stdout=subprocess.PIPE,
                        stderr=subprocess.PIPE, text=True))
                self.worker_stats = []
                for i, p in enumerate(procs):
                    stdout, stderr = p.communicate(timeout=600)
                    if p.returncode != 0:
                        raise RuntimeError(
                            f"tcp PS worker {i} failed (rc={p.returncode}):\n"
                            + stderr[-2000:])
                    self.worker_stats.append(
                        json.loads(stdout.strip().splitlines()[-1]))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            frontend.stop()
            for seg in segments:
                _pst.release_segment_by_name(seg)
