"""HTTP front-end tests: /v1/predict, 429 backpressure, streaming, status.

Runs the real stdlib server stack on loopback (same as tests/test_ui.py);
every test binds port 0 so parallel runs never collide.
"""
import http.client
import json
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.keras_server import (
    InferenceServer, ModelRegistry, set_global_model_registry,
)
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    DenseLayer, GravesLSTM, OutputLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

N_IN, N_OUT = 12, 3


def _mlp(seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=24, activation="relu"))
            .layer(OutputLayer(n_in=24, n_out=N_OUT, loss="mcxent",
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


def _post(port, path, obj, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(obj),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


@pytest.fixture()
def server():
    registry = ModelRegistry()
    registry.register("mlp", _mlp(), version="v1")
    registry.register("rnn", _lstm(), version="v1")
    srv = InferenceServer(registry, max_batch=8, max_latency_s=0.002,
                          max_queue=64).start()
    yield srv
    srv.stop()


def test_predict_roundtrip_and_status(server):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, N_IN)).astype(np.float32)
    status, _, body = _post(server.port, "/v1/predict",
                            {"model": "mlp", "inputs": x.tolist()})
    assert status == 200
    out = json.loads(body)
    assert np.asarray(out["predictions"]).shape == (3, N_OUT)
    assert out["model"] == "mlp" and out["version"] == "v1"
    # per-request vs HTTP-batched: same numbers end to end. The 3 rows ride
    # the bucket of 4: bitwise the same 4-row batch with the zero row written
    # out, and equal to the unpadded 3-row dispatch to float32 rounding
    # (nn/inference.py, "The serving equality contract")
    got = np.asarray(out["predictions"], np.float32)
    predict = server.registry.active("mlp").predict_fn
    padded = np.concatenate([x, np.zeros_like(x[:1])])
    assert np.array_equal(got, np.asarray(predict(padded), np.float32)[:3])
    np.testing.assert_allclose(got, np.asarray(predict(x), np.float32),
                               rtol=4e-6, atol=1e-7)

    status, body = _get(server.port, "/serve/status")
    st = json.loads(body)
    assert status == 200
    assert st["models"]["mlp"]["active"] == "v1"
    assert st["queue"]["dispatches"] >= 1
    assert "max_batch" in st["queue"]


def test_unknown_model_404_malformed_400(server):
    status, _, body = _post(server.port, "/v1/predict",
                            {"model": "nope", "inputs": [[0.0] * N_IN]})
    assert status == 404
    status, _, body = _post(server.port, "/v1/predict", {"model": "mlp"})
    assert status == 400
    status, body = _get(server.port, "/no/such/route")
    assert status == 404


def test_http_429_backpressure_and_gauge_agree():
    registry = ModelRegistry()
    mv = registry.register("mlp", _mlp(seed=9), version="v1")
    release = threading.Event()
    real_pf = mv.predict_fn

    class _Blocking:
        calls = 0

        def __call__(self, x):
            release.wait(timeout=30)
            return real_pf(x)

    srv = InferenceServer(registry, max_batch=1, max_latency_s=0.0,
                          max_queue=3).start()
    mv.predict_fn = _Blocking()
    statuses, lock = [], threading.Lock()

    def client():
        s, headers, body = _post(
            srv.port, "/v1/predict",
            {"model": "mlp", "inputs": [[0.0] * N_IN]})
        with lock:
            statuses.append((s, headers, body))
    try:
        threads = [threading.Thread(target=client) for _ in range(8)]
        for t in threads:
            t.start()
        deadline = time.time() + 10
        while srv.batcher.admission.rejected == 0 and time.time() < deadline:
            time.sleep(0.005)
        # while wedged: what 429s claim and what the gauge says must agree
        assert srv.batcher.admission.pending == 3
        metrics_text = None
        status, body = _get(srv.port, "/metrics")
        assert status == 200
        for line in body.decode().splitlines():
            if line.startswith("dl4j_serve_queue_depth"):
                metrics_text = float(line.rsplit(" ", 1)[1])
        assert metrics_text == 3.0
        release.set()
        for t in threads:
            t.join()
    finally:
        release.set()
        srv.stop()
    got = sorted(s for s, _, _ in statuses)
    assert got.count(200) == 3
    assert got.count(429) == 5
    for s, headers, body in statuses:
        if s == 429:
            assert float(headers["Retry-After"]) > 0
            err = json.loads(body)
            assert err["pending"] == 3 and err["limit"] == 3


def test_stream_sessions_persist_across_requests(server):
    rng = np.random.default_rng(1)
    seq = rng.normal(size=(1, 4, 5)).astype(np.float32)
    # one request, 4 timesteps, session A
    status, _, body = _post(server.port, "/v1/stream",
                            {"model": "rnn", "session": "A",
                             "inputs": seq.tolist()})
    assert status == 200
    lines = [json.loads(l) for l in body.decode().strip().splitlines()]
    assert lines[-1]["done"] and lines[-1]["timesteps"] == 4
    steps_a = [l["output"] for l in lines[:-1]]
    assert len(steps_a) == 4
    # two requests, 2 timesteps each, session B: state must carry over
    _post(server.port, "/v1/stream",
          {"model": "rnn", "session": "B", "inputs": seq[:, :2].tolist()})
    status, _, body = _post(server.port, "/v1/stream",
                            {"model": "rnn", "session": "B",
                             "inputs": seq[:, 2:].tolist()})
    lines = [json.loads(l) for l in body.decode().strip().splitlines()]
    steps_b = [l["output"] for l in lines[:-1]]
    assert np.allclose(np.asarray(steps_b), np.asarray(steps_a[2:]),
                       atol=1e-5)
    # reset drops the parked state
    status, _, body = _post(server.port, "/v1/stream/reset",
                            {"model": "rnn", "session": "B"})
    assert json.loads(body)["reset"] is True


def test_ui_server_serve_status_route():
    from deeplearning4j_tpu.ui.server import UIServer

    registry = ModelRegistry()
    registry.register("uim", _mlp(seed=13), version="v7")
    prev = set_global_model_registry(registry)
    ui = UIServer(port=0)
    try:
        status, body = _get(ui.port, "/serve/status")
        assert status == 200
        st = json.loads(body)
        assert st["models"]["uim"]["active"] == "v7"
    finally:
        ui.stop()
        set_global_model_registry(prev)
