"""Serving-engine semantics: non-donation, bucketed batching, backpressure.

The contracts pinned here are the ISSUE-9 acceptance set:
- the inference dispatch path never donates inputs or params (100 served
  requests leave every parameter buffer bit-identical);
- batched-and-padded output bitwise-equals per-request output across
  bucket boundaries (batch 1, boundary, boundary+1);
- the compile cache stays bounded under 1k mixed-shape requests
  (CompileTracker event count == bucket count);
- admission overflow rejects AND the queue-depth gauge agrees;
- hot-swapping the active version mid-flight loses zero requests.
"""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.keras_server import (
    AdmissionController, MicroBatcher, ModelRegistry, RejectedError,
    batch_bucket)
from deeplearning4j_tpu.keras_server.streaming import StreamSessions
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization, DenseLayer, GravesLSTM, OutputLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.inference import (
    PREDICT_PROGRAM_NAME, make_predict_fn,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.compile_tracker import global_tracker
from deeplearning4j_tpu.observability.metrics import MetricsRegistry
from deeplearning4j_tpu.observability import names as _n

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


def _lstm(seed=3):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .list()
            .layer(GravesLSTM(n_in=5, n_out=8, activation="tanh"))
            .layer(RnnOutputLayer(n_in=8, n_out=2, loss="mcxent",
                                  activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _params_bytes(tree) -> bytes:
    import jax
    return b"".join(np.asarray(leaf).tobytes()
                    for leaf in jax.tree_util.tree_leaves(tree))


def _serve_compiles() -> int:
    return sum(1 for e in global_tracker().snapshot_events()
               if PREDICT_PROGRAM_NAME in e.get("fn", ""))


def _x(rng, n):
    return rng.normal(size=(n, N_IN)).astype(np.float32)


# --------------------------------------------------------------- bucketing
def test_batch_bucket_powers_of_two():
    assert [batch_bucket(n, 8) for n in (1, 2, 3, 4, 5, 7, 8, 9, 100)] \
        == [1, 2, 4, 4, 8, 8, 8, 8, 8]
    assert batch_bucket(1, 1) == 1


# ------------------------------------------------------------ non-donation
def test_serving_100_requests_params_bit_identical():
    """Satellite 2: the serving dispatch never donates params or inputs."""
    net = _mlp()
    registry = ModelRegistry()
    mv = registry.register("m", net, version="v1")
    before_pinned = _params_bytes(mv.predict_fn.params_snapshot())
    before_source = _params_bytes(net.params_list)
    batcher = MicroBatcher(registry, max_batch=8, max_latency_s=0.001)
    try:
        rng = np.random.default_rng(0)
        futs = [batcher.submit("m", _x(rng, 1 + i % 4)) for i in range(100)]
        outs = [f.result(timeout=30) for f in futs]
    finally:
        batcher.close()
    assert len(outs) == 100
    assert all(o["version"] == "v1" for o in outs)
    assert _params_bytes(mv.predict_fn.params_snapshot()) == before_pinned
    assert _params_bytes(net.params_list) == before_source


def test_predict_fn_isolated_from_training_donation():
    """fit() after pinning must not corrupt the serving snapshot."""
    net = _mlp()
    pf = make_predict_fn(net)
    rng = np.random.default_rng(1)
    x = _x(rng, 4)
    before = np.asarray(pf(x))
    pinned = _params_bytes(pf.params_snapshot())
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 4)]
    for _ in range(3):
        net.fit(x, y)  # donates the NET's buffers, not the snapshot
    assert _params_bytes(pf.params_snapshot()) == pinned
    assert np.array_equal(np.asarray(pf(x)), before)


# ------------------------------------------------- padded batch semantics
def test_batched_padded_output_equals_per_request():
    """Across bucket boundaries: coalesced+padded == served alone, to
    float32 rounding (XLA's kernels are not batch-size invariant on JAX
    0.9: a row alone and the same row in a bucket of 4 may differ in the
    last ulp) — and padding itself is bitwise semantics-free."""
    net = _mlp()
    registry = ModelRegistry()
    mv = registry.register("m", net, version="v1")
    rng = np.random.default_rng(2)
    boundary = 4  # max_batch=4: buckets 1,2,4
    for k in (1, boundary, boundary + 1):
        xs = [_x(rng, 1) for _ in range(k)]
        refs = [np.asarray(mv.predict_fn(x)) for x in xs]  # per-request
        batcher = MicroBatcher(registry, max_batch=boundary,
                               max_latency_s=0.25)
        try:
            futs = [batcher.submit("m", x) for x in xs]
            outs = [f.result(timeout=30) for f in futs]
        finally:
            batcher.close()
        if k > 1:
            # the high max_latency guarantees the first `boundary` requests
            # coalesced into one padded dispatch — the property under test
            assert max(o["batch_rows"] for o in outs) > 1
        for o, ref in zip(outs, refs):
            got = np.asarray(o["predictions"])
            if o["batch_rows"] == 1:
                assert np.array_equal(got, ref), f"bitwise mismatch at k={k}"
            np.testing.assert_allclose(got, ref, rtol=4e-6, atol=1e-7,
                                       err_msg=f"mismatch at k={k}")
    # padding adds nothing: 3 rows padded to the bucket of 4 are bitwise the
    # first 3 rows of the same 4-row batch with the zero row written out
    x3 = _x(rng, 3)
    batcher = MicroBatcher(registry, max_batch=boundary, max_latency_s=0.001)
    try:
        got = np.asarray(batcher.submit("m", x3).result(timeout=30)
                         ["predictions"])
    finally:
        batcher.close()
    x4 = np.concatenate([x3, np.zeros_like(x3[:1])])
    assert np.array_equal(got, np.asarray(mv.predict_fn(x4))[:3])


# ------------------------------------------------------ bounded compile cache
def test_compile_cache_bounded_under_1k_mixed_shape_requests():
    net = _mlp(seed=11)
    registry = ModelRegistry()
    registry.register("m", net, version="v1")
    batcher = MicroBatcher(registry, max_batch=8, max_latency_s=0.0005,
                           max_queue=2000)
    compiles_before = _serve_compiles()
    try:
        rng = np.random.default_rng(3)
        futs = [batcher.submit("m", _x(rng, int(rng.integers(1, 9))))
                for _ in range(1000)]
        for f in futs:
            f.result(timeout=60)
        stats = batcher.stats()
    finally:
        batcher.close()
    compiles = _serve_compiles() - compiles_before
    # the pinned bound: one compile per padded bucket, nothing else — with
    # max_batch=8 the buckets are {1,2,4,8}, so at most 4 compiles for 1000
    # mixed-shape requests, and every compile is a bucket actually used
    assert compiles == stats["bucket_count"], stats
    assert compiles <= 4, f"{compiles} compiles for 1000 requests"


# ------------------------------------------------------------- backpressure
def test_backpressure_rejects_and_queue_depth_gauge_agrees():
    net = _mlp(seed=5)
    registry = ModelRegistry()
    mv = registry.register("m", net, version="v1")
    release = threading.Event()
    real_pf = mv.predict_fn

    class _Blocking:
        calls = 0

        def __call__(self, x):
            release.wait(timeout=30)
            return real_pf(x)

    mv.predict_fn = _Blocking()
    metrics = MetricsRegistry()
    admission = AdmissionController(max_pending=4, metrics=metrics)
    batcher = MicroBatcher(registry, max_batch=1, max_latency_s=0.0,
                           admission=admission, metrics=metrics)
    try:
        rng = np.random.default_rng(4)
        futs = [batcher.submit("m", _x(rng, 1)) for _ in range(4)]
        with pytest.raises(RejectedError) as exc:
            batcher.submit("m", _x(rng, 1))
        assert exc.value.pending == 4
        assert exc.value.limit == 4
        assert exc.value.retry_after_s > 0

        def _gauge():
            snap = metrics.snapshot()[_n.SERVE_QUEUE_DEPTH]
            return snap["series"][0]["value"]

        # the gauge must agree with what the 429 claimed
        assert _gauge() == 4
        assert admission.pending == 4
        release.set()
        for f in futs:
            f.result(timeout=30)
        deadline = time.time() + 10
        while admission.pending and time.time() < deadline:
            time.sleep(0.01)
        assert _gauge() == 0
        snap = metrics.snapshot()[_n.SERVE_REJECTED_TOTAL]
        assert snap["series"][0]["value"] == 1
    finally:
        release.set()
        batcher.close()


# ----------------------------------------------------------------- hot swap
def test_hot_swap_mid_flight_loses_zero_requests():
    registry = ModelRegistry()
    registry.register("m", _mlp(seed=21), version="v1")
    batcher = MicroBatcher(registry, max_batch=8, max_latency_s=0.001,
                           max_queue=512)
    results, errors = [], []
    lock = threading.Lock()

    def client(seed):
        rng = np.random.default_rng(seed)
        for _ in range(50):
            try:
                fut = batcher.submit("m", _x(rng, 1))
                out = fut.result(timeout=30)
                with lock:
                    results.append(out["version"])
            except Exception as e:  # any loss/failure fails the test
                with lock:
                    errors.append(repr(e))
            time.sleep(0.001)
    try:
        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.04)  # mid-flight
        registry.register("m", _mlp(seed=22), version="v2")
        for t in threads:
            t.join()
    finally:
        batcher.close()
    assert not errors, errors
    assert len(results) == 200
    assert "v2" in set(results)  # the swap actually took effect mid-run
    assert registry.active("m").version == "v2"


def test_registry_versioning_and_rollback():
    registry = ModelRegistry()
    registry.register("m", _mlp(seed=31))
    registry.register("m", _mlp(seed=32))
    assert registry.active("m").version == "v2"
    registry.set_active("m", "v1")  # rollback
    assert registry.active("m").version == "v1"
    with pytest.raises(ValueError):
        registry.register("m", _mlp(seed=33), version="v1")
    with pytest.raises(KeyError):
        registry.active("nope")
    st = registry.status()
    assert sorted(st["models"]["m"]["versions"]) == ["v1", "v2"]


# ---------------------------------------------------------------- streaming
def test_streaming_sessions_match_full_sequence():
    net = _lstm()
    registry = ModelRegistry()
    registry.register("rnn", net, version="v1")
    sessions = StreamSessions(registry)
    rng = np.random.default_rng(6)
    seq = rng.normal(size=(1, 6, 5)).astype(np.float32)
    full = np.asarray(net.output(seq))  # [B,T,O]
    streamed = []
    for t in range(6):
        out = sessions.step("rnn", "s1", seq[:, t:t + 1, :])
        streamed.append(out["output"][:, -1, :])
    streamed = np.stack(streamed, axis=1)
    assert np.allclose(streamed, full, atol=1e-5), \
        np.max(np.abs(streamed - full))
    # state is per-session: a fresh session re-starts from zero state
    out2 = sessions.step("rnn", "s2", seq[:, 0:1, :])
    assert np.allclose(out2["output"][:, -1, :], full[:, 0, :], atol=1e-5)
    assert sessions.reset("rnn", "s1")
    assert not sessions.reset("rnn", "s1")


def test_model_serializer_zip_roundtrip_serves():
    import tempfile, os
    from deeplearning4j_tpu.utils.model_serializer import write_model
    net = _mlp(seed=41)
    registry = ModelRegistry()
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.zip")
        write_model(net, path)
        mv = registry.load("m", path)
    rng = np.random.default_rng(7)
    x = _x(rng, 2)
    assert np.allclose(np.asarray(mv.predict_fn(x)),
                       np.asarray(net.output(x)), atol=1e-6)
    assert mv.source.endswith("model.zip")
