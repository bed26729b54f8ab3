"""2-process jax.distributed execution proof (VERDICT round-2 item 4).

The reference proves its cluster semantics by running distributed logic in a
local[N] Spark context (reference BaseSparkTest.java:90); the TPU-native
equivalent is two OS processes, each owning one CPU device, joined into one
JAX cluster by `init_distributed` (parallel/mesh.py:26) — the same code path
a real multi-host TPU pod uses, with DCN collectives replaced by local
transport. One synchronous-DP step over the 2-process mesh must produce the
same parameters as a single-process step on the full batch.
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np

_WORKER = os.path.join(os.path.dirname(__file__), "_dist_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(mode: str):
    port = _free_port()
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one CPU device per process
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return [subprocess.Popen(
        [sys.executable, _WORKER, str(i), str(port), mode], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in (0, 1)]


def test_two_process_sync_dp_matches_single_process():
    procs = _run_workers("step")
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=180)
        assert p.returncode == 0, f"worker failed:\n{stderr[-2000:]}"
        rec = json.loads(stdout.strip().splitlines()[-1])
        outs.append(rec)

    # result is replicated: both processes must report identical params
    assert outs[0]["psum"] == outs[1]["psum"]
    assert outs[0]["head"] == outs[1]["head"]
    assert abs(outs[0]["loss"] - outs[1]["loss"]) < 1e-7

    # single-process reference on the full batch
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import (
        MultiLayerNetwork, make_train_step)

    conf = (NeuralNetConfiguration.builder()
            .seed(9).learning_rate(0.1).updater("sgd")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                               activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    B = 8
    x = rng.normal(size=(B, 4)).astype(np.float32)
    y = np.zeros((B, 3), np.float32)
    y[np.arange(B), rng.integers(0, 3, B)] = 1
    step = jax.jit(make_train_step(conf))
    params, _, _, loss = step(net.params_list, net.state_list,
                              net.updater_state, jnp.asarray(x),
                              jnp.asarray(y), jax.random.PRNGKey(0),
                              jnp.int32(0))
    flat = np.concatenate([np.ravel(np.asarray(leaf)) for leaf in
                           jax.tree_util.tree_leaves(params)])
    np.testing.assert_allclose(outs[0]["psum"], float(flat.sum()),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[0]["head"], flat[:5],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[0]["loss"], float(loss),
                               rtol=1e-5, atol=1e-6)


def test_two_process_parallel_wrapper_fit_matches_single_process():
    """The PRODUCTION ParallelWrapper.fit over a 2-process jax.distributed
    mesh == single-process fit on the same batches (multi-host batch staging
    via make_array_from_callback; reference analog: the same Spark job giving
    the same model regardless of executor count)."""
    procs = _run_workers("wrapper")
    outs = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=240)
        assert p.returncode == 0, f"worker failed:\n{stderr[-2000:]}"
        outs.append(json.loads(stdout.strip().splitlines()[-1]))

    assert outs[0]["psum"] == outs[1]["psum"]
    assert outs[0]["head"] == outs[1]["head"]

    # single-process oracle: same net, same 4 batches, plain fit_iterator
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .seed(9).learning_rate(0.1).updater("sgd")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                               activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    B = 8
    x = rng.normal(size=(B, 4)).astype(np.float32)
    y = np.zeros((B, 3), np.float32)
    y[np.arange(B), rng.integers(0, 3, B)] = 1
    net.fit_iterator(ListDataSetIterator(
        [DataSet(x.copy(), y.copy()) for _ in range(4)]))

    import jax
    flat = np.concatenate([np.ravel(np.asarray(leaf)) for leaf in
                           jax.tree_util.tree_leaves(net.params_list)])
    np.testing.assert_allclose(outs[0]["psum"], float(flat.sum()),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[0]["head"], flat[:5],
                               rtol=1e-5, atol=1e-6)
