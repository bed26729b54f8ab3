"""Sharded multi-replica serving: the ISSUE-12 acceptance set.

Pinned contracts:
- a ``sharding="dp_tp"`` PredictFn on the 8-device virtual mesh is
  **bitwise-identical** to the single-device program applied to each
  device's slice of the batch, at every batch size including ones the data
  axis doesn't divide (gather-at-use: the params shard at rest, no
  cross-device arithmetic), and equal to it on the whole batch to float32
  rounding — XLA's kernels are not batch-size invariant on JAX 0.9
  (nn/inference.py, "The serving equality contract");
- the per-device resident bytes really drop (shard check on the weight
  buffers) and the ``dl4j_sharded_param_bytes_per_device`` gauge agrees
  with ``partition.per_device_bytes``;
- int8 quantization composes with sharding (the codes shard);
- multi-input ComputationGraphs serve through PredictFn AND the
  MicroBatcher (per-position concat/pad, one group per input signature);
- a rolling hot swap across 3 replicas loses zero in-flight requests;
- the least-queue-depth router shifts traffic off a slow replica;
- the HTTP front door exposes per-replica status and metrics.
"""
import json
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from deeplearning4j_tpu.keras_server import (
    MicroBatcher, ModelRegistry, ReplicaSet,
)
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization, DenseLayer, OutputLayer,
)
from deeplearning4j_tpu.nn.graph_network import ComputationGraph
from deeplearning4j_tpu.nn.inference import make_predict_fn
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import names as _n
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.parallel import partition
from deeplearning4j_tpu.parallel.mesh import build_mesh

N_IN, N_OUT = 16, 4


def _mlp(seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=32, activation="relu"))
            .layer(BatchNormalization(n_in=32))
            .layer(OutputLayer(n_in=32, n_out=N_OUT, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _two_input_graph(seed=5):
    from deeplearning4j_tpu.nn.conf.vertices import MergeVertex
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .graph_builder()
            .add_inputs("a", "b")
            .add_layer("da", DenseLayer(n_in=4, n_out=6, activation="tanh"),
                       "a")
            .add_layer("db", DenseLayer(n_in=3, n_out=6, activation="tanh"),
                       "b")
            .add_vertex("merged", MergeVertex(), "da", "db")
            .add_layer("out", OutputLayer(n_in=12, n_out=2, loss="mse",
                                          activation="identity"), "merged")
            .set_outputs("out")
            .build())
    return ComputationGraph(conf).init()


def _sharded_gauge():
    snap = global_registry().snapshot()
    series = snap[_n.SHARDED_PARAM_BYTES_PER_DEVICE]["series"]
    return {s["labels"]["rule_set"]: s["value"] for s in series}


# ------------------------------------------------------- sharded PredictFn

def test_sharded_predict_bitwise_and_per_device_bytes():
    net = _mlp()
    mesh = build_mesh({"data": 4, "model": 2})
    ref = make_predict_fn(net)
    pf = make_predict_fn(net, sharding="dp_tp", mesh=mesh)
    rng = np.random.default_rng(0)
    # batch sizes the data axis divides AND ones it doesn't (3, 1): the
    # odd tails dispatch replicated via partition.batch_spec
    for n in (1, 2, 3, 4, 8, 32):
        x = rng.normal(size=(n, N_IN)).astype(np.float32)
        a, b = np.asarray(ref(x)), np.asarray(pf(x))
        assert a.shape == (n, N_OUT)
        # bitwise against the single-device program on each device's slice
        split = partition.batch_spec(mesh, n) != partition.pspec()
        per = n // 4 if split else n
        sliced = np.concatenate([np.asarray(ref(x[i:i + per]))
                                 for i in range(0, n, per)])
        assert np.array_equal(sliced, b), f"sharded output drifted at batch {n}"
        # and to float32 rounding against it on the whole batch
        np.testing.assert_allclose(b, a, rtol=4e-6, atol=1e-7)
    # the params really live split: the 16x32 weight holds half its bytes
    # per device on the model=2 axis
    import jax
    leaves = [leaf for leaf in jax.tree_util.tree_leaves(
        pf.params_snapshot()) if leaf.nbytes == N_IN * 32 * 4]
    assert leaves, "expected the 16x32 f32 dense kernel in the snapshot"
    w = leaves[0]
    assert w.addressable_shards[0].data.nbytes * 2 == w.nbytes
    # per-device accounting: property == partition math == recorded gauge
    per_dev = pf.per_device_param_bytes
    assert per_dev is not None and per_dev < pf.param_bytes
    assert per_dev == partition.per_device_bytes(
        pf.params_snapshot(), pf.param_specs, mesh)
    assert _sharded_gauge()["dp_tp"] == per_dev
    assert ref.per_device_param_bytes is None


def test_batch_spec_odd_tail_replicates():
    mesh = build_mesh({"data": 4, "model": 2})
    assert partition.batch_spec(mesh, 8) == partition.pspec("data")
    assert partition.batch_spec(mesh, 4) == partition.pspec("data")
    # not divisible by the data factor -> replicated, never an error
    assert partition.batch_spec(mesh, 3) == partition.pspec()
    assert partition.batch_spec(mesh, 1) == partition.pspec()


def test_sharded_int8_composes_bitwise():
    # wide enough that the dense kernels clear ops.quant.MIN_QUANT_ELEMS
    conf = (NeuralNetConfiguration.builder()
            .seed(11).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=64, activation="relu"))
            .layer(DenseLayer(n_in=64, n_out=64, activation="relu"))
            .layer(OutputLayer(n_in=64, n_out=N_OUT, loss="mcxent",
                               activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    mesh = build_mesh({"data": 4, "model": 2})
    q_ref = make_predict_fn(net, quant="int8")
    q_pf = make_predict_fn(net, quant="int8", sharding="dp_tp", mesh=mesh)
    assert q_pf.name.endswith("+int8")
    rng = np.random.default_rng(1)
    for n in (2, 8):
        x = rng.normal(size=(n, N_IN)).astype(np.float32)
        assert np.array_equal(np.asarray(q_ref(x)), np.asarray(q_pf(x)))
    # int8 codes shard too: the quantized pin stays below the f32 pin
    assert q_pf.param_bytes < make_predict_fn(net).param_bytes


def test_predictfn_placement_validation():
    net = _mlp()
    mesh = build_mesh({"data": 4, "model": 2})
    with pytest.raises(ValueError, match="mesh"):
        make_predict_fn(net, sharding="dp_tp")
    import jax
    with pytest.raises(ValueError, match="not both"):
        make_predict_fn(net, sharding="dp_tp", mesh=mesh,
                        device=jax.devices()[0])


# ----------------------------------------------------- multi-input serving

def test_multi_input_graph_through_predictfn_and_batcher():
    net = _two_input_graph()
    rng = np.random.default_rng(3)
    a = rng.normal(size=(3, 4)).astype(np.float32)
    b = rng.normal(size=(3, 3)).astype(np.float32)
    want = np.asarray(net.output(a, b)[0])

    pf = make_predict_fn(net)
    assert pf.n_inputs == 2
    assert np.array_equal(np.asarray(pf(a, b)), want)
    with pytest.raises(ValueError, match="2 input"):
        pf(a)

    registry = ModelRegistry()
    registry.register("g", net, version="v1")
    batcher = MicroBatcher(registry, max_batch=8, max_latency_s=0.002)
    try:
        futs = [batcher.submit("g", [a[i:i + 1], b[i:i + 1]])
                for i in range(3)]
        for i, f in enumerate(futs):
            res = f.result(timeout=30)
            assert np.allclose(np.asarray(res["predictions"]),
                               want[i:i + 1], atol=1e-6)
        # mismatched leading dims are an input error, not a dispatch crash
        with pytest.raises(ValueError):
            batcher.submit("g", [a, b[:2]])
    finally:
        batcher.close()


# ----------------------------------------------------- replica set + router

def test_replica_set_sharded_placement_disjoint():
    import jax
    rs = ReplicaSet(4, sharding="dp_tp", max_latency_s=0.001)
    try:
        assert rs.n_replicas == 4
        seen = []
        for r in rs.replicas:
            devs = r.devices()
            assert len(devs) == 2  # 8 virtual devices / 4 replicas
            seen.extend(devs)
        assert len(seen) == len(set(seen)) == len(jax.devices())
        rs.register("m", _mlp(), version="v1")
        x = np.zeros((2, N_IN), np.float32)
        res = rs.submit("m", x).result(timeout=60)
        assert res["version"] == "v1" and res["replica"] in range(4)
    finally:
        rs.close()


def test_rolling_hot_swap_three_replicas_zero_loss():
    rs = ReplicaSet(3, max_latency_s=0.001, drain_timeout_s=30.0)
    try:
        rs.register("m", _mlp(seed=1), version="v1")
        x = np.zeros((1, N_IN), np.float32)
        results, errors = [], []
        done = threading.Event()

        def client():
            got = []
            while not (done.is_set() and len(got) >= 100):
                try:
                    got.append(rs.submit("m", x).result(timeout=60))
                except Exception as e:  # any loss fails the test
                    errors.append(e)
                    break
                time.sleep(0.0005)
            results.extend(got)

        threads = [threading.Thread(target=client) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # let v1 traffic establish
        rs.register("m", _mlp(seed=2), version="v2")
        done.set()
        for t in threads:
            t.join(timeout=120)
        assert not errors, f"requests lost during the roll: {errors[:3]}"
        assert len(results) >= 300
        versions = {r["version"] for r in results}
        assert versions <= {"v1", "v2"} and "v1" in versions \
            and "v2" in versions
        # every replica ends on v2 (the roll visited the whole fleet)
        for r in rs.replicas:
            assert r.registry.active("m").version == "v2"
            assert not r.draining
        # the active-version gauge flipped series: v1 -> 0, v2 -> 1
        snap = global_registry().snapshot()
        series = snap[_n.SERVE_REPLICA_ACTIVE_VERSION]["series"]
        active = {(s["labels"]["replica"], s["labels"]["version"]):
                  s["value"] for s in series
                  if s["labels"]["model"] == "m"}
        for i in range(3):
            assert active[(str(i), "v1")] == 0
            assert active[(str(i), "v2")] == 1
        # versions are immutable at set level
        with pytest.raises(ValueError, match="immutable"):
            rs.register("m", _mlp(), version="v2")
    finally:
        rs.close()


def test_router_prefers_shorter_queue_under_slow_replica():
    rs = ReplicaSet(2, max_batch=1, max_latency_s=0.0)
    try:
        rs.register("m", _mlp(), version="v1")
        x = np.zeros((1, N_IN), np.float32)
        # warm both replicas' bucket-1 programs so compile time doesn't
        # masquerade as queue depth
        for r in rs.replicas:
            r.batcher.submit("m", x).result(timeout=60)
        # wedge replica 0: every dispatch sleeps, so its queue stays deep
        mv0 = rs.replicas[0].registry.active("m")
        real = mv0.predict_fn

        def slow(*xs):
            time.sleep(0.05)
            return real(*xs)

        mv0.predict_fn = slow
        # paced offered load: the fast replica drains between arrivals, so
        # queue depth — the router's signal — tracks service rate, and the
        # wedged replica's depth pins at 1 while it sleeps
        futs = []
        for _ in range(40):
            futs.append(rs.submit("m", x))
            time.sleep(0.002)
        by_replica = {0: 0, 1: 0}
        for f in futs:
            by_replica[f.result(timeout=60)["replica"]] += 1
        assert by_replica[1] > by_replica[0], by_replica
        st = rs.stats()
        routed = {r["replica"]: r["routed"] for r in st["replicas"]}
        assert routed[1] > routed[0]
    finally:
        rs.close()


# ------------------------------------------------------------ HTTP + names

def test_http_replica_mode_status_and_metrics():
    import http.client

    from deeplearning4j_tpu.keras_server import InferenceServer

    srv = InferenceServer(replicas=2, max_batch=8, max_latency_s=0.002,
                          max_queue=64)
    srv.register("mlp", _mlp(), version="v1")
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        x = np.zeros((2, N_IN), np.float32)
        conn.request("POST", "/v1/predict",
                     body=json.dumps({"model": "mlp",
                                      "inputs": x.tolist()}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert body["version"] == "v1" and body["replica"] in (0, 1)
        assert np.asarray(body["predictions"]).shape == (2, N_OUT)

        conn.request("GET", "/serve/status")
        st = json.loads(conn.getresponse().read())
        assert st["replicas"]["n_replicas"] == 2
        assert len(st["replicas"]["replicas"]) == 2
        assert st["queue"]["replicas"] == 2 and "queue_depth" in st["queue"]
        for rep in st["replicas"]["replicas"]:
            assert rep["active"] == {"mlp": "v1"}

        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        conn.close()
        assert _n.SERVE_REPLICA_QUEUE_DEPTH in text
        assert _n.SERVE_REPLICA_ACTIVE_VERSION in text
        assert _n.SERVE_REPLICA_ROUTED_TOTAL in text
    finally:
        srv.stop()


def test_replica_mode_refuses_external_registry():
    from deeplearning4j_tpu.keras_server import InferenceServer

    with pytest.raises(ValueError, match="replica mode"):
        InferenceServer(ModelRegistry(), replicas=2)


def test_new_metric_names_registered():
    for name in (_n.SERVE_REPLICA_QUEUE_DEPTH, _n.SERVE_REPLICA_OCCUPANCY,
                 _n.SERVE_REPLICA_ACTIVE_VERSION,
                 _n.SERVE_REPLICA_ROUTED_TOTAL):
        assert name in _n.ALL_METRIC_NAMES
        assert name.startswith("dl4j_serve_replica_")


def test_cli_serve_parser():
    from deeplearning4j_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["serve", "--model", "m.zip", "--replicas", "4",
         "--sharding", "dp_tp", "--quant", "int8", "--port", "0"])
    assert args.replicas == 4 and args.sharding == "dp_tp"
    assert args.quant == "int8" and args.max_batch == 32
    assert args.name == "default" and args.max_latency_ms == 2.0


def test_fleet_reads_race_free_under_churn():
    """Regression: n_replicas and primary_registry read _replicas bare
    while remove_replica rebinds the list under _lock. Readers could see
    a mid-rebind list (or index an empty snapshot during construction of
    the rebound one). Hammer both read paths while the fleet churns; the
    primary (index 0) is never removable, so primary_registry must stay
    valid through every mutation."""
    rs = ReplicaSet(2, max_batch=4, max_latency_s=0.001, max_queue=8)
    stop = threading.Event()
    errors = []

    def reader():
        try:
            while not stop.is_set():
                n = rs.n_replicas
                assert n >= 1
                assert rs.primary_registry is rs.replicas[0].registry
        except Exception as e:  # pragma: no cover - the regression itself
            errors.append(e)

    readers = [threading.Thread(target=reader) for _ in range(4)]
    try:
        for t in readers:
            t.start()
        # churn: grow to 4, shrink back to 2, five times over. The empty
        # catalog keeps add_replica cheap (no programs to warm).
        for _ in range(5):
            rs.add_replica(reason="t-churn")
            rs.add_replica(reason="t-churn")
            assert rs.remove_replica(reason="t-churn") is True
            assert rs.remove_replica(reason="t-churn") is True
    finally:
        stop.set()
        for t in readers:
            t.join()
        rs.close()
    assert errors == []
    assert rs.n_replicas == 2
