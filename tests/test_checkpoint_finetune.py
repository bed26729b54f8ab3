"""Checkpoint/resume determinism, NaN guard, and the VGG-16-style Keras
import fine-tune path (BASELINE config 5 at test scale)."""
import json

import numpy as np
import pytest

from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.optimize.listeners import (
    CheckpointListener, NanScoreWatcher,
)
from deeplearning4j_tpu.utils.model_serializer import (
    restore_multi_layer_network, write_model,
)


def _net(seed=0, lr=0.05):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(lr)
            .updater("adam")
            .list().layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=2, loss="mcxent",
                               activation="softmax")).build())
    return MultiLayerNetwork(conf).init()


def _data(n=32, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 2, n)
    x = rng.normal(0, 0.3, (n, 4)).astype(np.float32)
    x[np.arange(n), labels] += 2.0
    return x, np.eye(2, dtype=np.float32)[labels]


def test_resume_from_checkpoint_is_deterministic(tmp_path):
    x, y = _data()
    # train 4 steps, checkpoint, then 4 more
    a = _net()
    for i in range(4):
        a.fit(x, y)
    ckpt = str(tmp_path / "mid.zip")
    write_model(a, ckpt)
    for i in range(4):
        a.fit(x, y)

    # restore at step 4 and replay the last 4 steps: updater state is in the
    # checkpoint so the trajectory must match exactly (SURVEY.md §5)
    b = restore_multi_layer_network(ckpt)
    for i in range(4):
        b.fit(x, y)
    np.testing.assert_allclose(np.asarray(a.params()), np.asarray(b.params()),
                               rtol=1e-6, atol=1e-7)


def test_checkpoint_listener_rotation(tmp_path):
    net = _net()
    x, y = _data()
    lst = CheckpointListener(str(tmp_path), every_n_iterations=1,
                             every_n_epochs=None, keep_last=2)
    net.set_listeners(lst)
    for _ in range(5):
        net.fit(x, y)
    zips = sorted(p.name for p in tmp_path.glob("checkpoint_*.zip"))
    assert len(zips) == 2  # rotated
    assert CheckpointListener.last_checkpoint(str(tmp_path)) is not None
    restored = restore_multi_layer_network(
        CheckpointListener.last_checkpoint(str(tmp_path)))
    np.testing.assert_allclose(np.asarray(restored.params()),
                               np.asarray(net.params()), rtol=1e-6)


def test_nan_watcher_raises():
    net = _net(lr=0.05)
    net.set_listeners(NanScoreWatcher())
    x, y = _data()
    net.fit(x, y)  # healthy step passes
    x_bad = x.copy()
    x_bad[0, 0] = np.nan
    with pytest.raises(FloatingPointError):
        net.fit(x_bad, y)


@pytest.mark.skipif(
    not __import__("deeplearning4j_tpu.modelimport.hdf5",
                   fromlist=["hdf5_available"]).hdf5_available(),
    reason="libhdf5 not present")
def test_vgg_style_keras_import_finetune(tmp_path):
    """BASELINE config 5 shape: import a (tiny) VGG-16-style conv archive and
    fine-tune with data-parallel averaging."""
    from deeplearning4j_tpu.modelimport.hdf5 import H5File
    from deeplearning4j_tpu.modelimport.keras_import import KerasModelImport
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.datasets.iterators import ArrayDataSetIterator

    rng = np.random.default_rng(0)
    size, nc = 16, 4
    # VGG topology at toy scale: conv-conv-pool / conv-pool / flatten-dense
    layers = [
        ("Convolution2D", {"name": "block1_conv1", "nb_filter": 4,
                           "nb_row": 3, "nb_col": 3, "border_mode": "same",
                           "dim_ordering": "tf", "activation": "relu",
                           "batch_input_shape": [None, size, size, 3]}),
        ("Convolution2D", {"name": "block1_conv2", "nb_filter": 4,
                           "nb_row": 3, "nb_col": 3, "border_mode": "same",
                           "dim_ordering": "tf", "activation": "relu"}),
        ("MaxPooling2D", {"name": "block1_pool", "pool_size": [2, 2]}),
        ("Convolution2D", {"name": "block2_conv1", "nb_filter": 8,
                           "nb_row": 3, "nb_col": 3, "border_mode": "same",
                           "dim_ordering": "tf", "activation": "relu"}),
        ("MaxPooling2D", {"name": "block2_pool", "pool_size": [2, 2]}),
        ("Flatten", {"name": "flatten"}),
        ("Dense", {"name": "fc1", "output_dim": 16, "activation": "relu"}),
        ("Dense", {"name": "predictions", "output_dim": nc,
                   "activation": "softmax"}),
    ]
    mc = {"class_name": "Sequential",
          "config": [{"class_name": c, "config": cfg} for c, cfg in layers]}
    weights = {}
    shapes = {"block1_conv1": [(3, 3, 3, 4), (4,)],
              "block1_conv2": [(3, 3, 4, 4), (4,)],
              "block2_conv1": [(3, 3, 4, 8), (8,)],
              "fc1": [(4 * 4 * 8, 16), (16,)],
              "predictions": [(16, nc), (nc,)]}
    for lname, (ws, bs) in shapes.items():
        weights[lname] = [
            (f"{lname}_W", rng.normal(0, 0.1, ws).astype(np.float32)),
            (f"{lname}_b", np.zeros(bs, np.float32))]
    p = tmp_path / "vgg_tiny.h5"
    with H5File(str(p), "w") as f:
        f.write_attr("/", "model_config", json.dumps(mc))
        f.write_attr("/", "training_config",
                     json.dumps({"loss": "categorical_crossentropy"}))
        f.create_group("/model_weights")
        f.write_attr("/model_weights", "layer_names", list(weights))
        for lname, ws in weights.items():
            f.create_group(f"/model_weights/{lname}")
            f.write_attr(f"/model_weights/{lname}", "weight_names",
                         [wn for wn, _ in ws])
            for wn, arr in ws:
                f.write_dataset(f"/model_weights/{lname}/{wn}", arr)

    net = KerasModelImport.import_keras_sequential_model_and_weights(str(p))
    # fine-tune data-parallel: class = dominant color channel pattern
    n = 64
    labels = rng.integers(0, nc, n)
    x = rng.normal(0, 0.2, (n, size, size, 3)).astype(np.float32)
    for i in range(n):
        x[i, :, :, labels[i] % 3] += 1.0 + (labels[i] // 3)
    y = np.eye(nc, dtype=np.float32)[labels]
    it = ArrayDataSetIterator(x, y, batch=16, shuffle=True, seed=0)
    wrapper = ParallelWrapper(net, workers=2, prefetch=0)
    first = None
    for _ in range(6):
        wrapper.fit(it, epochs=1)
        if first is None:
            first = net.score_value
    assert net.score_value < first
    assert np.asarray(net.output(x[:2])).shape == (2, nc)


def test_crash_resume_matches_uninterrupted_run(tmp_path):
    """Fault injection (SURVEY §5): a training process that dies hard
    (os._exit mid-fit, simulating host preemption) resumes from the
    CheckpointListener's latest.zip and reproduces the uninterrupted
    trajectory exactly — the reference's deterministic-restart contract
    (ModelSerializer zips include updater state)."""
    import os
    import subprocess
    import sys

    worker = os.path.join(os.path.dirname(__file__), "_crash_worker.py")
    ckpt_dir = str(tmp_path / "ckpts")
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, worker, ckpt_dir], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 17, proc.stderr[-1500:]  # died as planned
    assert "CRASHING at iteration 5" in proc.stdout

    latest = CheckpointListener.last_checkpoint(ckpt_dir)
    assert latest is not None
    resumed = restore_multi_layer_network(latest, load_updater=True)
    assert resumed.iteration == 5

    x, y = _data()
    for _ in range(5):
        resumed.fit(x, y)

    # oracle: uninterrupted 10 steps in this process
    oracle = _net()
    for _ in range(10):
        oracle.fit(x, y)
    np.testing.assert_allclose(np.asarray(resumed.params()),
                               np.asarray(oracle.params()),
                               rtol=1e-6, atol=1e-7)
