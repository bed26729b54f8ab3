"""SLO-driven autoscaling fleet: the ISSUE-18 acceptance set.

Pinned contracts:
- ``add_replica()`` / ``remove_replica()`` mutate the set atomically:
  monotonic never-reused indices, the new replica pre-registers the whole
  catalog before it becomes routable, the primary and the last replica
  cannot be removed;
- scale-in is drain-without-loss: every request admitted to a replica
  before its removal completes with a correct answer;
- a replica whose membership lease was evicted is fenced out of the
  router, and the autoscaler's zombie sweep evicts-and-backfills it
  outside the hysteresis window;
- hysteresis holds: at most ONE scale event per cooldown window, one step
  at a time, bounds respected, scale-in only after ``headroom_ticks``
  consecutive low-pressure ticks;
- priority shedding order: under saturation ``low`` is refused (with
  ``dl4j_serve_shed_total{tenant,priority}`` accounting) while ``high``
  still admits — a high-priority 429 means the queue is hard-full;
- warm scale-up: with the persistent compile cache populated,
  ``add_replica()`` onto a device that has run the model resolves every
  bucket program from disk — zero fresh XLA compiles on a hot scale-up;
  the first replica on a new device compiles for it (an executable is
  bound to its device);
- the HTTP front door exposes the autoscaler block and honors the
  priority/tenant headers; the CLI grows the --autoscale axis;
- ``run_ramp_ab`` produces the full A/B record shape with zero lost
  requests (the strict auto<static violation floor is asserted on the
  capture host's record, not re-measured here — wall-clock SLO math on a
  loaded CI box is noise).
"""
import json
import time

import numpy as np
import pytest

from deeplearning4j_tpu.cloud import MembershipOracle
from deeplearning4j_tpu.keras_server import Autoscaler, ReplicaSet
from deeplearning4j_tpu.keras_server.admission import (
    PRIORITY_FLOORS, PRIORITY_LEVELS, AdmissionController, RejectedError,
    normalize_priority,
)
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import names as _n
from deeplearning4j_tpu.observability.metrics import global_registry

N_IN, N_OUT = 12, 3


def _mlp(seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=N_OUT, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _x(n=2, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, N_IN)).astype(np.float32)


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _FakeSLO:
    """Duck-typed SLOEngine: the autoscaler only reads evaluate()."""

    def __init__(self, burn=0.0, alerting=False):
        self.burn = burn
        self.alerting = alerting

    def evaluate(self):
        return [{"name": "latency", "alerting": self.alerting,
                 "windows": [{"burn_rate": self.burn}]}]


def _counter_value(name, **labels):
    series = global_registry().snapshot().get(name, {}).get("series", [])
    for s in series:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return 0


# ------------------------------------------------------- fleet mutation API

def test_add_remove_replica_atomic():
    rs = ReplicaSet(2, max_batch=8, max_latency_s=0.001, max_queue=32)
    try:
        rs.register("mlp", _mlp(), version="v1")

        r2 = rs.add_replica(reason="t-atomic")
        assert r2.index == 2 and rs.n_replicas == 3
        # catalog seeded BEFORE the replica became routable: it serves the
        # registered model at the active version immediately
        assert r2.registry.active("mlp").version == "v1"
        out = r2.batcher.submit("mlp", _x()).result(timeout=30)
        assert np.asarray(out["predictions"]).shape == (2, N_OUT)
        assert out["version"] == "v1" and out["replica"] == 2
        assert _counter_value(_n.SERVE_SCALE_EVENTS_TOTAL,
                              direction="out", reason="t-atomic") == 1

        # a later register() rolls onto the added replica too
        rs.register("mlp", _mlp(seed=9), version="v2")
        for r in rs.replicas:
            assert r.registry.active("mlp").version == "v2"

        # default removal takes the highest-index non-primary replica
        assert rs.remove_replica(reason="t-atomic") is True
        assert rs.n_replicas == 2
        assert sorted(r.index for r in rs.replicas) == [0, 1]
        assert _counter_value(_n.SERVE_SCALE_EVENTS_TOTAL,
                              direction="in", reason="t-atomic") == 1
        # unknown index: soft miss; primary: hard refusal
        assert rs.remove_replica(index=99) is False
        with pytest.raises(ValueError):
            rs.remove_replica(index=0)
        assert rs.remove_replica(index=1) is True
        with pytest.raises(ValueError):
            rs.remove_replica()
        # indices are never reused across churn
        assert rs.add_replica(reason="t-atomic").index == 3
        # the fleet gauge tracks the live count
        assert _counter_value(_n.SERVE_FLEET_SIZE) == rs.n_replicas == 2
    finally:
        rs.close()


def test_scale_in_drains_without_loss():
    # a generous batching window keeps singles queued long enough that the
    # removal genuinely races in-flight work
    rs = ReplicaSet(2, max_batch=8, max_latency_s=0.05, max_queue=64)
    try:
        rs.register("mlp", _mlp(), version="v1")
        victim = [r for r in rs.replicas if r.index == 1][0]
        futures = [victim.batcher.submit("mlp", _x(1, seed=i))
                   for i in range(12)]
        assert rs.remove_replica(index=1, reason="t-drain") is True
        for f in futures:
            out = f.result(timeout=30)
            assert np.asarray(out["predictions"]).shape == (1, N_OUT)
            assert out["replica"] == 1
        assert victim.batcher.admission.rejected == 0
        assert rs.n_replicas == 1
    finally:
        rs.close()


# ----------------------------------------------------------- zombie fencing

def test_zombie_lease_fencing_and_backfill():
    oracle = MembershipOracle(role="replica", lease_timeout_s=60.0)
    rs = ReplicaSet(2, max_batch=8, max_latency_s=0.001, max_queue=32,
                    membership=oracle)
    try:
        rs.register("mlp", _mlp(), version="v1")
        zombie = [r for r in rs.replicas if r.index == 1][0]
        assert oracle.evict(zombie.lease.member, reason="chaos") is True
        assert [r.index for r in rs.fenced_replicas()] == [1]

        # the router never dispatches to a fenced replica
        for i in range(6):
            rs.submit("mlp", _x(1, seed=i)).result(timeout=30)
        routed = {s["replica"]: s["routed"] for s in rs.stats()["replicas"]}
        assert routed[0] == 6 and routed[1] == 0
        assert [s["replica"] for s in rs.stats()["replicas"]
                if s["fenced"]] == [1]

        # the autoscaler sweep evicts the zombie and backfills to
        # min_replicas outside the cooldown window
        asc = Autoscaler(rs, min_replicas=2, max_replicas=4,
                         cooldown_s=300.0)
        asc.tick()
        assert rs.n_replicas == 2
        assert rs.fenced_replicas() == []
        assert sorted(r.index for r in rs.replicas) == [0, 2]
        # the backfilled replica carries the catalog and a fresh lease
        fresh = [r for r in rs.replicas if r.index == 2][0]
        assert fresh.registry.active("mlp").version == "v1"
        assert oracle.validate(fresh.lease.member, fresh.lease.epoch)
        assert _counter_value(_n.SERVE_SCALE_EVENTS_TOTAL, direction="in",
                              reason="lease-fenced") >= 1
        assert _counter_value(_n.SERVE_SCALE_EVENTS_TOTAL, direction="out",
                              reason="replace-fenced") >= 1
        # heartbeat cannot resurrect the evicted lease
        rs.heartbeat()
        assert not oracle.validate(zombie.lease.member, zombie.lease.epoch)
    finally:
        rs.close()


# --------------------------------------------------------------- hysteresis

def test_hysteresis_one_event_per_cooldown_window():
    clock = _Clock()
    slo = _FakeSLO(burn=5.0)
    rs = ReplicaSet(1, max_batch=4, max_latency_s=0.001, max_queue=16)
    try:
        asc = Autoscaler(rs, slo_engine=slo, min_replicas=1, max_replicas=3,
                         cooldown_s=10.0, headroom_ticks=3, clock=clock)
        assert asc.tick() == "out" and rs.n_replicas == 2
        # burning hard the whole window: every tick inside the cooldown is
        # a no-op — at most one scale event per cooldown_s
        for _ in range(9):
            clock.advance(1.0)
            assert asc.tick() == "none"
        assert rs.n_replicas == 2
        clock.advance(1.0)
        assert asc.tick() == "out" and rs.n_replicas == 3
        # max bound: still burning, but the fleet never exceeds max_replicas
        clock.advance(11.0)
        assert asc.tick() == "none" and rs.n_replicas == 3

        # scale-in needs headroom_ticks CONSECUTIVE low ticks, then one
        # step per cooldown window
        slo.burn = 0.0
        clock.advance(11.0)
        assert asc.tick() == "none"      # low tick 1
        clock.advance(1.0)
        assert asc.tick() == "none"      # low tick 2
        slo.burn = 5.0                   # blip resets the streak but the
        clock.advance(1.0)               # fleet is at max: no event
        assert asc.tick() == "none"
        slo.burn = 0.0
        for _ in range(2):
            clock.advance(1.0)
            assert asc.tick() == "none"
        clock.advance(1.0)
        assert asc.tick() == "in" and rs.n_replicas == 2

        st = asc.status()
        assert st["n_replicas"] == 2
        assert st["last_decision"] == "in"
        assert st["last_reason"] == "headroom"
        assert st["min_replicas"] == 1 and st["max_replicas"] == 3
        assert st["last_scale_out_latency_s"] is not None
        assert st["events"] and st["events"][-1]["direction"] == "in"
    finally:
        rs.close()


def test_autoscaler_bounds_validation():
    rs = ReplicaSet(1, max_batch=4, max_queue=16)
    try:
        with pytest.raises(ValueError):
            Autoscaler(rs, min_replicas=0)
        with pytest.raises(ValueError):
            Autoscaler(rs, min_replicas=4, max_replicas=2)
    finally:
        rs.close()


# --------------------------------------------------------- priority shedding

def test_priority_shed_order_low_before_high():
    assert PRIORITY_LEVELS == ("low", "normal", "high")
    assert normalize_priority(None) == "high"
    assert normalize_priority("LOW") == "low"
    assert normalize_priority("gibberish") == "high"

    ac = AdmissionController(max_pending=10, expected_latency_s=0.01)
    assert ac.limit_for("low") == 5
    assert ac.limit_for("normal") == 7
    assert ac.limit_for("high") == 10

    ac.admit(5, priority="high", tenant="acme-18")
    # past low's floor: low is shed while normal and high still admit
    with pytest.raises(RejectedError) as ei:
        ac.admit(priority="low", tenant="free-18")
    assert ei.value.shed is True and ei.value.priority == "low"
    ac.admit(2, priority="normal", tenant="acme-18")     # 7 pending
    with pytest.raises(RejectedError) as ei:
        ac.admit(priority="normal", tenant="acme-18")
    assert ei.value.shed is True
    # high admits to the hard cap; only THEN does it see a 429, and that
    # refusal is a hard-full reject, not a shed
    ac.admit(3, priority="high", tenant="acme-18")       # 10 pending
    with pytest.raises(RejectedError) as ei:
        ac.admit(priority="high", tenant="acme-18")
    assert ei.value.shed is False and ei.value.priority == "high"

    assert ac.shed == 2 and ac.rejected == 3
    assert _counter_value(_n.SERVE_SHED_TOTAL,
                          tenant="free-18", priority="low") == 1
    assert _counter_value(_n.SERVE_SHED_TOTAL,
                          tenant="acme-18", priority="normal") == 1
    # the hard-full high reject never lands in the shed counter
    assert _counter_value(_n.SERVE_SHED_TOTAL,
                          tenant="acme-18", priority="high") == 0


def test_priority_flows_through_router():
    rs = ReplicaSet(2, max_batch=8, max_latency_s=0.001, max_queue=32)
    try:
        rs.register("mlp", _mlp(), version="v1")
        out = rs.submit("mlp", _x(), priority="low",
                        tenant="acme-18").result(timeout=30)
        assert np.asarray(out["predictions"]).shape == (2, N_OUT)
    finally:
        rs.close()


# ----------------------------------------------------------- warm scale-up

@pytest.mark.parametrize("same_device", [True, False],
                         ids=["device-seen-before", "new-device"])
def test_scale_out_warm_hits_compile_cache(monkeypatch, same_device):
    import jax

    from deeplearning4j_tpu.observability.compile_tracker import (
        global_tracker,
    )
    monkeypatch.setenv("DL4J_COMPILE_CACHE", "1")
    devs = jax.devices()[:1] if same_device else jax.devices()[:2]
    rs = ReplicaSet(1, max_batch=8, max_latency_s=0.001, max_queue=32,
                    warmup=True, devices=devs)
    try:
        # cold: replica 0's warmup populates the persistent cache with
        # every bucket program
        rs.register("mlp", _mlp(), version="v1")
        n0 = len(global_tracker().snapshot_events())
        r = rs.add_replica(reason="t-warm")
        ev = global_tracker().snapshot_events()[n0:]
        assert ev, "scale-out must warm every bucket program"
        if same_device:
            # the pinned acceptance: a hot scale-up onto a device that has
            # run the model resolves EVERY program from disk (the
            # fingerprint sheds the ~r<i> decoration) — no fresh XLA
            # compile stands between the decision and a routable replica
            assert all(e.get("cache_hit") for e in ev), \
                f"fresh compile on hot scale-up: {ev}"
        else:
            # an executable is bound to the device it was compiled for: the
            # first replica on a second device compiles for it, once
            assert not any(e.get("cache_hit") for e in ev), \
                f"device 0's executable was loaded for device 1: {ev}"
        out = r.batcher.submit("mlp", _x()).result(timeout=30)
        assert np.asarray(out["predictions"]).shape == (2, N_OUT)
    finally:
        rs.close()


# ------------------------------------------------------- names, HTTP, CLI

def test_autoscale_metric_names_registered():
    for name in (_n.SERVE_FLEET_SIZE, _n.SERVE_SCALE_EVENTS_TOTAL,
                 _n.SERVE_SHED_TOTAL):
        assert name in _n.ALL_METRIC_NAMES
        assert name.startswith("dl4j_serve_")


def test_http_autoscaler_status_and_priority_headers():
    import http.client

    from deeplearning4j_tpu.keras_server import InferenceServer
    from deeplearning4j_tpu.keras_server.serving import (
        PRIORITY_HEADER, TENANT_HEADER,
    )

    srv = InferenceServer(autoscale=True, min_replicas=1, max_replicas=2,
                          autoscale_cooldown_s=300.0, max_batch=8,
                          max_latency_s=0.002, max_queue=64)
    srv.register("mlp", _mlp(), version="v1")
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        x = np.zeros((2, N_IN), np.float32)
        conn.request("POST", "/v1/predict",
                     body=json.dumps({"model": "mlp",
                                      "inputs": x.tolist()}),
                     headers={"Content-Type": "application/json",
                              PRIORITY_HEADER: "low",
                              TENANT_HEADER: "acme-18"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert np.asarray(body["predictions"]).shape == (2, N_OUT)

        conn.request("GET", "/serve/status")
        st = json.loads(conn.getresponse().read())
        asc = st["autoscaler"]
        assert asc["running"] is True
        assert asc["min_replicas"] == 1 and asc["max_replicas"] == 2
        assert asc["n_replicas"] >= 1 and "cooldown_s" in asc
        assert "last_scale_out_latency_s" in asc
    finally:
        srv.stop()


def test_cli_serve_autoscale_parser():
    from deeplearning4j_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["serve", "--model", "m.zip", "--autoscale", "--min-replicas", "1",
         "--max-replicas", "4", "--autoscale-cooldown-s", "5", "--port",
         "0"])
    assert args.autoscale is True
    assert args.min_replicas == 1 and args.max_replicas == 4
    assert args.autoscale_cooldown_s == 5.0
    # the axis is opt-in: a bare serve invocation stays static
    base = build_parser().parse_args(["serve", "--model", "m.zip"])
    assert base.autoscale is False
    assert base.min_replicas is None and base.max_replicas is None


# ------------------------------------------------------------ ramp A/B shape

def test_ramp_ab_record_shape(tmp_path):
    from deeplearning4j_tpu.keras_server import run_ramp_ab

    rec_path = tmp_path / "ramp.jsonl"
    rec = run_ramp_ab(
        _mlp(), model="mlp", qps_low=15.0, segment_s=0.6, slo_ms=1000.0,
        min_replicas=1, max_replicas=2, cooldown_s=0.5, interval_s=0.1,
        max_batch=8, max_latency_s=0.002, max_queue=64,
        example=np.zeros((1, N_IN), np.float32), workers=4,
        record_path=str(rec_path))

    assert rec["harness"] == "keras_server.loadgen.run_ramp_ab"
    assert rec["model"] == "mlp"
    assert rec["qps_high"] == pytest.approx(150.0)
    assert rec["min_replicas"] == 1 and rec["max_replicas"] == 2
    assert rec["avg_replicas_auto"] >= 1.0
    assert rec["static_replicas"] >= 1
    for phase in ("auto", "static"):
        ph = rec[phase]
        assert ph["requests"] > 0 and ph["ok"] > 0
        assert ph["p99_ms"] >= ph["p50_ms"] >= 0.0
        assert "slo_violation_seconds" in ph and "rejected" in ph
    # the acceptance floor fields the capture host asserts on
    assert rec["slo_violation_seconds_auto"] == \
        rec["auto"]["slo_violation_seconds"]
    assert rec["slo_violation_seconds_static"] == \
        rec["static"]["slo_violation_seconds"]
    assert isinstance(rec["auto_beats_static"], bool)
    assert "scale_out_latency_s" in rec and "scale_events" in rec
    # zero lost in-flight requests across the whole autoscaled ramp — the
    # drain-without-loss contract under real churn
    assert rec["lost_requests"] == 0
    assert rec["auto"]["lost"] == 0

    lines = rec_path.read_text().strip().splitlines()
    assert len(lines) == 1 and json.loads(lines[0])["model"] == "mlp"
