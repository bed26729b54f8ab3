"""Elastic preemption-tolerant training over the async parameter server.

Production accelerator pools preempt workers without warning (the reference
shipped deeplearning4j-aws + Spark TrainingMaster for exactly this). The
``ElasticTrainer`` composes three engines that already work alone into a
fleet that survives a worker dying mid-``fit()``:

* **Membership** — ``cloud.MembershipOracle`` (TpuProvisioner grown into
  the membership authority): workers register over the PS transport seam,
  drawing a member id + globally monotonic *fencing epoch* + lease;
  heartbeats renew the lease; a lapsed lease is declared dead server-side.
  The ``ParameterServer`` fences pushes by epoch, so a zombie resumed after
  expiry cannot corrupt the model — its deltas are rejected permanently.

* **Shard handoff** — data assignment flows through the loopback broker's
  committed-offset consumer groups (`streaming/broker.py`): shard *i* is a
  topic consumed under group *i*, workers commit offsets only after a push
  window lands on the PS, and a replacement simply resumes the same group
  at committed+1. At-least-once: a crash redelivers at most one window;
  nothing is ever silently skipped. The coordinator keeps NO assignment
  bookkeeping beyond comparing the group's committed offset to the shard
  topic's ``fin`` marker.

* **Restore-on-join** — a joining worker warm-starts by pulling the current
  ``(version, params)`` from the PS (the normal worker bootstrap). When the
  PS itself restarts, ``fit()`` warm-starts the server from the committed
  async checkpoint sidecar (`utils/sharded_checkpoint.py`) before any
  worker joins — the sidecar-as-commit-marker contract guarantees a torn
  save is never restored.

The coordinator monitors worker processes: a dead process whose shard has
uncommitted samples is replaced (``shard_handoff``); a process whose lease
lapsed while it still runs is a zombie and is SIGKILLed before its
replacement spawns, so one live worker per shard is an invariant.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.cloud import MembershipOracle
from deeplearning4j_tpu.observability.federation import (
    FederatedRegistry, FleetCollector, register_status_provider,
    set_global_federation, set_global_fleet_collector,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    dump_on_unhandled as _dump_on_unhandled,
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.metrics import (
    global_registry as _obs_registry,
)
from deeplearning4j_tpu.observability.names import ELASTIC_HANDOFFS_TOTAL
from deeplearning4j_tpu.observability.tracing import (
    trace_span as _trace_span,
)
from deeplearning4j_tpu.observability.watchdog import beat as _wd_beat
from deeplearning4j_tpu.parallel.param_server import (
    DEFAULT_STALENESS_CAP, ParameterServer, unflatten_tree,
)
from deeplearning4j_tpu.parallel.ps_transport import (
    ParameterServerTcpFrontend,
)
from deeplearning4j_tpu.streaming.broker import BrokerProducer, LoopbackBroker

_handoffs = _obs_registry().counter(
    ELASTIC_HANDOFFS_TOTAL,
    "shard handoffs to a replacement worker after a worker died").labels()


class _Shard:
    """Coordinator-side view of one shard: its topic/group, its fin-marker
    offset, and the worker process generation currently owning it."""

    def __init__(self, shard: int):
        self.shard = shard
        self.topic = f"shard-{shard}"
        self.group = f"shard-{shard}"
        self.fin_offset = -1
        self.committed = -1
        self.gen = 0
        self.name = ""
        self.proc: Optional[subprocess.Popen] = None
        self.done = False
        self.handoffs = 0


class ElasticTrainer:
    """Preemption-tolerant async-PS trainer (the reference's Spark
    TrainingMaster fault-tolerance role, rebuilt on lease epochs + broker
    offsets). Workers are separate OS processes; kill one mid-fit and its
    shard hands off to a freshly registered replacement.

    The workers are started with ``JAX_PLATFORMS=cpu``: a chip belongs to
    one process, so this fleet trains **on the CPU by construction**, also
    on a chip machine. It is the membership / handoff / recovery plane that
    is exercised here, not chip throughput."""

    def __init__(self, model, workers: int = 2, push_frequency: int = 4,
                 staleness: int = DEFAULT_STALENESS_CAP,
                 compression: str = "none",
                 transport: str = "tcp",
                 server_optimizer: str = "sgd", server_lr: float = 1.0,
                 lease_timeout_s: float = 15.0,
                 respawn: bool = True, max_handoffs_per_shard: int = 4,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_interval_s: float = 30.0,
                 worker_delays: Optional[Sequence[float]] = None,
                 fit_timeout_s: float = 900.0):
        if compression not in ("none", "bf16"):
            raise ValueError(f"unknown compression {compression!r}; "
                             "expected 'none' or 'bf16'")
        if transport not in ("tcp", "shm"):
            raise ValueError(f"unknown transport {transport!r}; "
                             "expected 'tcp' or 'shm'")
        self.model = model
        self.workers = int(workers)
        self.push_frequency = max(1, push_frequency)
        self.staleness = int(staleness)
        self.compression = compression
        self.transport = transport
        self.server_optimizer = server_optimizer
        self.server_lr = server_lr
        self.lease_timeout_s = float(lease_timeout_s)
        self.respawn = bool(respawn)
        self.max_handoffs_per_shard = int(max_handoffs_per_shard)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_interval_s = float(checkpoint_interval_s)
        self.worker_delays = list(worker_delays or [])
        self.fit_timeout_s = float(fit_timeout_s)
        self.server: Optional[ParameterServer] = None
        self.oracle: Optional[MembershipOracle] = None
        self.federation: Optional[FederatedRegistry] = None
        self.collector: Optional[FleetCollector] = None
        self.worker_stats: List[dict] = []
        self.published = 0
        self.restored_from_checkpoint = False
        self._shards: List[_Shard] = []
        self._proc_lock = threading.Lock()
        self._env_conf: Dict[str, object] = {}
        self._ps_port = self._broker_port = 0

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

        def staleness(self, cap: int):
            self._kw["staleness"] = cap
            return self

        def compression(self, codec: str):
            self._kw["compression"] = codec
            return self

        def transport(self, kind: str):
            """"tcp" (framed sockets) or "shm" (tensor bytes in per-worker
            shared-memory rings; control verbs stay on the socket;
            auto-falls back to tcp frames when segments can't attach)."""
            self._kw["transport"] = kind
            return self

        def server_optimizer(self, kind: str, lr: float = 1.0):
            self._kw["server_optimizer"] = kind
            self._kw["server_lr"] = lr
            return self

        def lease_timeout(self, seconds: float):
            """Heartbeat lease: a worker silent this long is declared dead,
            its epoch fenced, its shard handed off."""
            self._kw["lease_timeout_s"] = seconds
            return self

        def respawn(self, enabled: bool, max_per_shard: int = 4):
            """Spawn a replacement for a dead worker whose shard still has
            uncommitted samples (the handoff)."""
            self._kw["respawn"] = enabled
            self._kw["max_handoffs_per_shard"] = max_per_shard
            return self

        def checkpoint(self, directory: str, interval_s: float = 30.0):
            """Async sharded checkpoints for PS-restart warm start: fit()
            restores the committed sidecar state before workers join."""
            self._kw["checkpoint_dir"] = directory
            self._kw["checkpoint_interval_s"] = interval_s
            return self

        def worker_delays(self, *delays: float):
            """Fault injection: shard i's worker sleeps delays[i] seconds
            per step (paces chaos tests and the kill benchmark)."""
            self._kw["worker_delays"] = list(delays)
            return self

        def fit_timeout(self, seconds: float):
            self._kw["fit_timeout_s"] = seconds
            return self

        def build(self) -> "ElasticTrainer":
            return ElasticTrainer(self._model, **self._kw)

    @staticmethod
    def builder(model) -> "ElasticTrainer.Builder":
        return ElasticTrainer.Builder(model)

    # ----------------------------------------------------------------- fit
    @_dump_on_unhandled("ElasticTrainer.fit")
    def fit(self, iterator, epochs: int = 1) -> None:
        self._maybe_restore()
        self.oracle = MembershipOracle(
            preemptible=True, lease_timeout_s=self.lease_timeout_s)
        self.server = ParameterServer(
            self.model.params_list, staleness_cap=self.staleness,
            optimizer=self.server_optimizer, server_lr=self.server_lr,
            membership=self.oracle)
        # fleet observability plane: workers push cumulative metric frames
        # over the same PS seam; the oracle's side-effect-free validate
        # fences a zombie's frames exactly like its deltas
        self.federation = FederatedRegistry(validate=self.oracle.validate)
        self.collector = FleetCollector(federation=self.federation)
        set_global_federation(self.federation)
        set_global_fleet_collector(self.collector)
        register_status_provider("elastic", lambda: self.stats)
        frontend = ParameterServerTcpFrontend(
            self.server, federation=self.federation,
            collector=self.collector).start()
        broker = LoopbackBroker().start()
        self._ps_port, self._broker_port = frontend.port, broker.port
        saver = None
        if self.checkpoint_dir is not None:
            from deeplearning4j_tpu.utils.sharded_checkpoint import (
                AsyncShardedSaver)
            saver = AsyncShardedSaver()
        self.worker_stats = []
        self._shards = [_Shard(i) for i in range(self.workers)]
        try:
            with tempfile.TemporaryDirectory(prefix="dl4j_elastic_") as tmp:
                self._publish_shards(broker, iterator, epochs)
                self._write_conf(tmp)
                for shard in self._shards:
                    self._spawn(shard)
                self._monitor(broker, saver)
        finally:
            with self._proc_lock:
                for shard in self._shards:
                    if shard.proc is not None and shard.proc.poll() is None:
                        shard.proc.kill()
            frontend.stop()
            broker.stop()
        self.model.params_list = unflatten_tree(
            self.server.pull_flat()[1], self.server.spec, as_jax=True)
        if saver is not None:
            # final committed state: the next fit()'s PS-restart warm start
            saver.save(self.checkpoint_dir, self.model,
                       step=self.server.version)
            saver.close()

    # ------------------------------------------------------------ restore
    def _maybe_restore(self) -> None:
        """PS-restart warm start: only a COMMITTED checkpoint (sidecar
        present) is restored; a torn async save is ignored by contract."""
        if self.checkpoint_dir is None:
            return
        if not os.path.exists(os.path.join(self.checkpoint_dir,
                                           "meta.json")):
            return
        from deeplearning4j_tpu.utils.sharded_checkpoint import (
            restore_sharded)
        restore_sharded(self.checkpoint_dir, self.model)
        self.restored_from_checkpoint = True
        _flight_recorder().record(
            "elastic_restore", directory=self.checkpoint_dir,
            iteration=self.model.iteration)

    # ------------------------------------------------------------- publish
    def _publish_shards(self, broker: LoopbackBroker, iterator,
                        epochs: int) -> None:
        batches = []
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            batches.extend(iterator)
        producer = BrokerProducer(broker.address)
        try:
            for shard in self._shards:
                # one trace root per shard: every message carries this
                # span's traceparent, so consume + push stitch under it
                with _trace_span("shard.publish", topic=shard.topic,
                                 shard=shard.shard):
                    for ds in batches[shard.shard::self.workers]:
                        producer.publish(
                            shard.topic,
                            {"x": np.asarray(ds.features),  # lint: host-sync-in-hot-loop-ok (one-time shard publication before workers spawn, not a train loop)
                             "y": np.asarray(ds.labels)})  # lint: host-sync-in-hot-loop-ok (one-time shard publication before workers spawn, not a train loop)
                        self.published += 1
                    # the fin marker closes the shard: a group whose
                    # committed offset reaches it has consumed every sample
                    # at least once
                    shard.fin_offset = producer.publish(
                        shard.topic, {}, meta={"fin": True})
        finally:
            producer.close()

    def _write_conf(self, tmp: str) -> None:
        from deeplearning4j_tpu.nn.conf.serde import to_json
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
        from deeplearning4j_tpu.nn import compile_cache
        if compile_cache.enabled():
            # pin the resolved cache root so every worker generation
            # shares it: gen-0 writes the step executable, a respawned
            # replacement warm-loads it and skips XLA
            env["JAX_COMPILATION_CACHE_DIR"] = compile_cache.cache_root()
        rec = _flight_recorder()
        if rec.dump_dir:
            # same pinning for the flight-recorder dir: a set_dump_dir()
            # call on the coordinator never reaches os.environ, so without
            # this a dead worker's last bundle lands nowhere the fleet
            # collector can find it
            env["DL4J_FLIGHT_RECORDER_DIR"] = rec.dump_dir
        self._env_conf = {"env": env, "conf": conf_path}

    def _delay(self, shard: int) -> float:
        if shard < len(self.worker_delays):
            return float(self.worker_delays[shard])
        return 0.0

    # --------------------------------------------------------------- spawn
    def _spawn(self, shard: _Shard) -> None:
        shard.name = f"shard{shard.shard}-gen{shard.gen}"
        cmd = [sys.executable, "-m",
               "deeplearning4j_tpu.parallel.ps_worker",
               "--addr", f"127.0.0.1:{self._ps_port}",
               "--conf", self._env_conf["conf"],
               "--broker", f"127.0.0.1:{self._broker_port}",
               "--topic", shard.topic, "--group", shard.group,
               "--shard", str(shard.shard),
               "--worker-name", shard.name,
               "--push-frequency", str(self.push_frequency),
               "--codec", self.compression,
               "--ps-transport", self.transport,
               "--delay", str(self._delay(shard.shard))]
        with self._proc_lock:
            shard.proc = subprocess.Popen(
                cmd, env=self._env_conf["env"], stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True)
        shard.gen += 1

    def chaos_kill(self, shard: int) -> bool:
        """Fault injection for tests/benchmarks: SIGKILL (never graceful)
        the process currently owning ``shard``. Returns True if a live
        process was killed."""
        with self._proc_lock:
            s = self._shards[shard]
            if s.proc is None or s.proc.poll() is not None:
                return False
            s.proc.kill()
        _flight_recorder().record("elastic_chaos_kill", shard=shard,
                                  worker=s.name)
        return True

    # -------------------------------------------------------------- monitor
    def _monitor(self, broker: LoopbackBroker, saver) -> None:
        deadline = time.time() + self.fit_timeout_s
        last_ckpt = time.time()
        while not all(s.done for s in self._shards):
            if time.time() > deadline:
                raise RuntimeError(
                    f"elastic fit exceeded {self.fit_timeout_s:.0f}s; "
                    f"shards done: {[s.done for s in self._shards]}")
            self.oracle.expire()
            for shard in self._shards:
                if shard.done:
                    continue
                self._tend(shard, broker)
            _wd_beat(self.server.version)
            if (saver is not None
                    and time.time() - last_ckpt
                    > self.checkpoint_interval_s):
                self._snapshot(saver)
                last_ckpt = time.time()
            time.sleep(0.05)

    def _tend(self, shard: _Shard, broker: LoopbackBroker) -> None:
        lease = self.oracle.member_by_name(shard.name)
        rc = shard.proc.poll()
        if rc is None:
            if (lease is not None and not lease.alive
                    and lease.reason == "lease-lapsed"):
                # zombie: the oracle declared it dead but the process still
                # runs (wedged/paused). Its pushes are already fenced; kill
                # the body so exactly one worker owns the shard before the
                # replacement spawns.
                shard.proc.kill()
            return
        stdout, stderr = shard.proc.communicate()
        committed = shard.committed = broker.committed(shard.topic,
                                                       shard.group)
        if rc == 0:
            shard.done = True
            try:
                self.worker_stats.append(
                    json.loads(stdout.strip().splitlines()[-1]))
            except (ValueError, IndexError):
                _flight_recorder().record(
                    "elastic_stats_unparsed", worker=shard.name)
            return
        if lease is not None and lease.alive:
            self.oracle.evict(lease.member, reason=f"exit-rc{rc}")
        if committed >= shard.fin_offset:
            # died after committing its fin marker: every sample in the
            # shard is consumed; no replacement needed
            shard.done = True
            return
        if self.respawn and shard.handoffs < self.max_handoffs_per_shard:
            shard.handoffs += 1
            _handoffs.inc()
            _flight_recorder().record(
                "shard_handoff", shard=shard.shard, gen=shard.gen,
                committed=committed, fin=shard.fin_offset, rc=rc)
            if self.collector is not None:
                # a handoff is exactly the moment one process's ring is not
                # enough: capture the whole fleet's view of the death
                self.collector.dump(reason="shard-handoff")
            self._spawn(shard)
            return
        raise RuntimeError(
            f"elastic worker for shard {shard.shard} died (rc={rc}) with "
            f"uncommitted samples and no respawn budget:\n" + stderr[-2000:])

    def _snapshot(self, saver) -> None:
        # the coordinator's model object is a snapshot vehicle: restore the
        # server's current vector into it, then async-save (the sidecar
        # commits only after the background write lands)
        self.model.params_list = unflatten_tree(
            self.server.pull_flat()[1], self.server.spec, as_jax=True)
        saver.save(self.checkpoint_dir, self.model,
                   step=self.server.version)

    # ----------------------------------------------------------- accessors
    @property
    def handoffs(self) -> int:
        return sum(s.handoffs for s in self._shards)

    @property
    def shard_commits(self) -> List[dict]:
        """Per-shard accounting after fit(): the group's final committed
        offset vs the topic's fin marker. ``committed >= fin`` is the
        no-window-silently-dropped proof (the chaos test's acceptance)."""
        return [{"shard": s.shard, "committed": s.committed,
                 "fin": s.fin_offset, "handoffs": s.handoffs}
                for s in self._shards]

    @property
    def stats(self) -> dict:
        return {
            "published": self.published,
            "steps": sum(int(s.get("steps", 0)) for s in self.worker_stats),
            "handoffs": self.handoffs,
            "fenced": self.server.fenced if self.server else 0,
            "lease_expiries": (self.oracle.lease_expiries
                               if self.oracle else 0),
            "joins": self.oracle.joins if self.oracle else 0,
            "restored": self.restored_from_checkpoint,
        }
