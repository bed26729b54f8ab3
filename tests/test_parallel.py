"""Data-parallel training tests on the virtual 8-device CPU mesh.

The gold-standard pattern is the reference's
TestCompareParameterAveragingSparkVsSingleMachine (SURVEY.md §4): distributed training
must equal single-device training for matched configs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import build_mesh, data_parallel_mesh
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper


def _conf(seed=1, lr=0.1, updater="sgd"):
    return (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(lr).updater(updater)
            .list()
            .layer(DenseLayer(n_in=6, n_out=10, activation="tanh"))
            .layer(OutputLayer(n_in=10, n_out=3, loss="mcxent", activation="softmax"))
            .build())


def _batches(n_batches=6, batch=32, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        x = rng.normal(size=(batch, 6)).astype(np.float32)
        y = np.zeros((batch, 3), np.float32)
        y[np.arange(batch), rng.integers(0, 3, batch)] = 1
        out.append(DataSet(x, y))
    return out


def test_sync_dp_equals_single_device():
    """averaging_frequency=1 DP over 8 devices == plain single-device fit on the
    same global batches (reference TestCompareParameterAveragingSparkVsSingleMachine)."""
    batches = _batches()

    single = MultiLayerNetwork(_conf()).init()
    for ds in batches:
        single.fit(ds.features, ds.labels)

    dp_net = MultiLayerNetwork(_conf()).init()
    pw = (ParallelWrapper.builder(dp_net)
          .workers(8).prefetch_buffer(0).averaging_frequency(1)
          .build())
    pw.fit(ListDataSetIterator(batches))

    np.testing.assert_allclose(np.asarray(single.params()),
                               np.asarray(dp_net.params()), atol=2e-6)


def test_sync_dp_adam_equals_single_device():
    batches = _batches(4)
    single = MultiLayerNetwork(_conf(updater="adam")).init()
    for ds in batches:
        single.fit(ds.features, ds.labels)
    dp_net = MultiLayerNetwork(_conf(updater="adam")).init()
    ParallelWrapper.builder(dp_net).workers(8).prefetch_buffer(0).build() \
        .fit(ListDataSetIterator(batches))
    np.testing.assert_allclose(np.asarray(single.params()),
                               np.asarray(dp_net.params()), atol=2e-6)


def test_sync_dp_compiles_its_step_once():
    """A network fresh from ``init()`` lies on one device; the wrapper lays
    its state out over the mesh before the first dispatch, so the second
    group of K steps meets the placement the first left and compiles
    nothing (handed over as it was, the K-step program compiled twice)."""
    net = MultiLayerNetwork(_conf()).init()
    net.dispatch_ksteps = 2
    pw = (ParallelWrapper.builder(net)
          .workers(8).prefetch_buffer(0).averaging_frequency(1).build())
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if event.endswith("backend_compile_duration") else None)
    pw.fit(ListDataSetIterator(_batches(2)))
    first = len(compiles)
    assert first > 0
    pw.fit(ListDataSetIterator(_batches(4, seed=1)))
    assert len(compiles) == first
    for leaf in jax.tree_util.tree_leaves(net.params_list):
        assert len(leaf.sharding.device_set) == 8


def test_local_sgd_averaging():
    """averaging_frequency=4 local-SGD: runs, stays finite, and final params are
    synchronized across replicas (reference ParallelWrapper averaging :179-212)."""
    batches = _batches(8)
    net = MultiLayerNetwork(_conf()).init()
    p0 = np.asarray(net.params())
    pw = (ParallelWrapper.builder(net)
          .workers(8).prefetch_buffer(0).averaging_frequency(4)
          .build())
    pw.fit(ListDataSetIterator(batches))
    p1 = np.asarray(net.params())
    assert np.isfinite(p1).all()
    assert not np.allclose(p0, p1)  # actually trained


def test_local_sgd_freq1_equals_sync():
    """local-SGD path with freq=1 must equal the fused sync path (same math,
    different transport) — validates the shard_map implementation."""
    batches = _batches(3)
    netA = MultiLayerNetwork(_conf()).init()
    ParallelWrapper.builder(netA).workers(8).prefetch_buffer(0) \
        .averaging_frequency(1).build().fit(ListDataSetIterator(batches))

    netB = MultiLayerNetwork(_conf()).init()
    pw = ParallelWrapper(netB, workers=8, prefetch=0, averaging_frequency=2)
    # force the local-SGD machinery even for freq comparison: use freq=1 via local path
    pw.averaging_frequency = 1
    pw._fit_local_sgd(ListDataSetIterator(batches), epochs=1)
    np.testing.assert_allclose(np.asarray(netA.params()),
                               np.asarray(netB.params()), atol=1e-5)


def test_tensor_parallel_sharding_applies():
    from deeplearning4j_tpu.parallel.mesh import shard_params_for_tp

    mesh = build_mesh({"data": 4, "model": 2})
    net = MultiLayerNetwork(_conf()).init()
    sharded = shard_params_for_tp(net.params_list, net.conf, mesh)
    # dense W sharded over model axis on output dim
    w = sharded[0]["W"]
    assert w.shape == (6, 10)
    # forward still correct under sharding
    x = np.random.default_rng(0).normal(size=(8, 6)).astype(np.float32)
    ref = np.asarray(net.output(x))
    net.params_list = sharded
    net._jit_cache.clear()
    out = np.asarray(net.output(x))
    np.testing.assert_allclose(ref, out, atol=1e-6)


def test_local_sgd_multi_io_graph():
    """Multi-input/multi-output CG local-SGD (closes the round-2 wrapper
    NotImplementedError gate; reference ParallelWrapper handles MultiDataSet
    fit, ParallelWrapper.java:117): runs with averaging_frequency>1, params
    stay finite, and the model still learns."""
    from deeplearning4j_tpu.nn.conf.vertices import MergeVertex
    from deeplearning4j_tpu.nn.graph_network import (
        ComputationGraph, MultiDataSet)

    conf = (NeuralNetConfiguration.builder()
            .seed(4).learning_rate(0.1).updater("sgd")
            .graph_builder()
            .add_inputs("a", "b")
            .add_layer("da", DenseLayer(n_in=3, n_out=6, activation="tanh"),
                       "a")
            .add_layer("db", DenseLayer(n_in=2, n_out=6, activation="tanh"),
                       "b")
            .add_vertex("m", MergeVertex(), "da", "db")
            .add_layer("out", OutputLayer(n_in=12, n_out=2, loss="mcxent",
                                          activation="softmax"), "m")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(8):
        xa = rng.normal(size=(16, 3)).astype(np.float32)
        xb = rng.normal(size=(16, 2)).astype(np.float32)
        labels = (xa[:, 0] + xb[:, 0] > 0).astype(int)
        y = np.zeros((16, 2), np.float32)
        y[np.arange(16), labels] = 1
        batches.append(MultiDataSet([xa, xb], [y]))
    mds = MultiDataSet([np.concatenate([b.features[0] for b in batches])[:32],
                        np.concatenate([b.features[1] for b in batches])[:32]],
                       [np.concatenate([b.labels[0] for b in batches])[:32]])
    s0 = net.score(mds)
    pw = (ParallelWrapper.builder(net)
          .workers(8).prefetch_buffer(0).averaging_frequency(2)
          .build())
    for _ in range(6):
        pw.fit(ListDataSetIterator(batches))
    s1 = net.score(mds)
    assert np.isfinite(s1)
    assert s1 < s0, (s0, s1)


def test_hybrid_mesh_single_slice_fallback():
    """build_hybrid_mesh degrades to a plain product mesh on one slice (the
    CPU test environment) with identical axis names, and a DP-over-dcn x
    TP-over-ici sharded step still executes."""
    from deeplearning4j_tpu.parallel.mesh import build_hybrid_mesh

    mesh = build_hybrid_mesh({"data": 2, "model": 2}, {"data": 2})
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2

    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    w = jnp.ones((4, 4), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, "model")))
    y = jax.jit(jnp.matmul)(xs, ws)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) @ np.asarray(w))

    with pytest.raises(ValueError, match="not present"):
        build_hybrid_mesh({"data": 2}, {"expert": 2})
