"""Device-prefetch pipeline tests (datasets/prefetch.py + rewired fit loops).

Pins the three ISSUE-level guarantees on the CPU mesh:
  * overlap ordering — the next group's ``jax.device_put`` is issued before
    the previous dispatch's host-side completion (listener phase),
  * prefetch-on (default) vs prefetch-off numerical equivalence over
    ``fit_iterator`` — bit-identical params,
  * donation safety — depth-2 prefetch over reused host buffers never
    trips a deleted-buffer error (batch inputs are not in donate_argnums),
plus the AsyncDataSetIterator producer-thread-leak regression and the
prefetch metric families.
"""
import threading

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator, ListDataSetIterator,
)
from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    DenseLayer, GravesLSTM, OutputLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.metrics import global_registry


def _mlp_net(seed=12, lr=0.1):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(lr)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init(seed=seed)


def _batches(n, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.normal(size=(batch, 4)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)]
        out.append(DataSet(x, y))
    return out


def _leaves(net):
    return [np.asarray(p) for p in jax.tree_util.tree_leaves(net.params_list)]


# ------------------------------------------------------------- DevicePrefetcher
def test_prefetcher_orders_and_stages():
    pf = DevicePrefetcher(iter(range(10)), lambda i: i * 2, depth=2, path=None)
    assert list(pf) == [i * 2 for i in range(10)]
    assert not pf.thread.is_alive()


def test_depth_zero_is_synchronous_inline():
    pf = DevicePrefetcher(iter(range(5)), lambda i: i + 1, depth=0, path=None)
    assert list(pf) == [1, 2, 3, 4, 5]
    assert pf.thread is None  # no producer thread at all


def test_error_propagates_after_prior_items():
    def src():
        yield 1
        yield 2
        raise RuntimeError("boom")

    got = []
    with pytest.raises(RuntimeError, match="boom"):
        for v in DevicePrefetcher(src(), None, depth=2, path=None):
            got.append(v)
    # same observable prefix as the synchronous loop
    assert got == [1, 2]


def test_stage_error_propagates_after_prior_items():
    def stage(i):
        if i == 2:
            raise ValueError("bad batch")
        return i

    got = []
    with pytest.raises(ValueError, match="bad batch"):
        for v in DevicePrefetcher(iter(range(5)), stage, depth=2, path=None):
            got.append(v)
    assert got == [0, 1]


def test_producer_runs_ahead_of_consumer():
    """While the consumer holds item 0, the producer stages item 1 in the
    background — the overlap DevicePrefetcher exists for."""
    staged_next = threading.Event()

    def stage(i):
        if i == 1:
            staged_next.set()
        return i

    pf = DevicePrefetcher(iter(range(4)), stage, depth=2, path=None)
    it = iter(pf)
    assert next(it) == 0
    # the consumer is "computing" on item 0 right now; item 1 must get
    # staged concurrently without another next() call
    assert staged_next.wait(timeout=10.0)
    assert list(it) == [1, 2, 3]


def test_close_unblocks_full_queue_producer():
    """A consumer that abandons iteration must not strand the producer on a
    full queue (the reference AsyncDataSetIterator leak)."""
    pf = DevicePrefetcher(iter(range(100)), None, depth=1, path=None)
    it = iter(pf)
    assert next(it) == 0  # producer now refilling a full queue
    pf.close()
    pf.thread.join(timeout=5.0)
    assert not pf.thread.is_alive()
    pf.close()  # idempotent


def test_async_iterator_early_exit_no_thread_leak():
    """Regression: breaking out of an AsyncDataSetIterator loop used to leave
    the producer thread blocked forever on its bounded queue."""
    ait = AsyncDataSetIterator(ListDataSetIterator(_batches(50)), queue_size=2)
    for _ in ait:
        break  # abandon mid-iteration
    ait.close()
    t = ait._pf.thread
    t.join(timeout=5.0)
    assert not t.is_alive()
    # the iterator is reusable after the abandoned pass
    assert sum(1 for _ in ait) == 50
    ait.close()


def test_async_iterator_reset_joins_producer():
    ait = AsyncDataSetIterator(ListDataSetIterator(_batches(20)), queue_size=2)
    it = iter(ait)
    next(it)
    old = ait._pf.thread
    ait.reset()
    old.join(timeout=5.0)
    assert not old.is_alive()
    assert sum(1 for _ in ait) == 20
    ait.close()


# ------------------------------------------------------------ fit-path overlap
def test_overlap_ordering_put_before_host_completion(monkeypatch):
    """The ordering the tentpole promises: the NEXT group's device_put is
    issued while the PREVIOUS dispatch's host-side completion (listener
    phase) is still pending."""
    next_group_in_flight = threading.Event()
    n_puts = [0]
    real_put = jax.device_put

    def spy(x, *a, **kw):
        n_puts[0] += 1
        # group 1 stages via puts 1-2 (xs, ys); put 3 = group 2 in flight
        if n_puts[0] >= 3:
            next_group_in_flight.set()
        return real_put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", spy)

    overlap = []

    class BlockingListener:
        def iteration_done(self, model, iteration):
            if not overlap:
                # we are inside dispatch 1's host-side completion; a working
                # prefetcher issues group 2's transfer concurrently
                overlap.append(next_group_in_flight.wait(timeout=30.0))

    net = _mlp_net(seed=3)
    net.dispatch_ksteps = 2
    net.prefetch_depth = 2
    net.set_listeners(BlockingListener())
    net.fit_iterator(ListDataSetIterator(_batches(8)))
    assert overlap and overlap[0], (
        "next group's device_put was not issued before the previous "
        "dispatch's host-side completion")


# -------------------------------------------------------- numerical equivalence
def test_prefetch_on_off_bit_identical_params():
    """Default prefetch (depth 2) must produce BIT-identical params to the
    synchronous depth-0 path over fit_iterator, including the ragged tail
    that flushes a short group."""
    data = _batches(7) + _batches(1, batch=5, seed=99)

    def run(depth):
        net = _mlp_net(seed=7)
        net.dispatch_ksteps = 2
        net.prefetch_depth = depth
        net.fit_iterator(ListDataSetIterator(data), epochs=2)
        return _leaves(net)

    on, off = run(2), run(0)
    assert len(on) == len(off)
    for a, b in zip(on, off):
        assert np.array_equal(a, b)


def test_prefetch_equivalence_with_masked_fallback():
    """Masked batches route through the per-batch fallback mid-stream; the
    grouped/fallback interleaving must be order-identical with and without
    prefetch (bit-identical params)."""
    B, T, C = 4, 5, 3
    rng = np.random.default_rng(3)

    def seq_ds(masked=False):
        x = rng.normal(size=(B, T, C)).astype(np.float32)
        y = np.eye(C, dtype=np.float32)[rng.integers(0, C, (B, T))]
        lm = None
        if masked:
            lm = np.ones((B, T), np.float32)
            lm[:, T // 2:] = 0
        return DataSet(x, y, labels_mask=lm)

    data = [seq_ds(), seq_ds(), seq_ds(masked=True), seq_ds(), seq_ds()]
    conf_b = (NeuralNetConfiguration.builder().seed(5).learning_rate(0.1)
              .list()
              .layer(GravesLSTM(n_in=C, n_out=6, activation="tanh"))
              .layer(RnnOutputLayer(n_in=6, n_out=C, loss="mcxent",
                                    activation="softmax")))

    def run(depth):
        net = MultiLayerNetwork(conf_b.build()).init(seed=5)
        net.dispatch_ksteps = 2
        net.prefetch_depth = depth
        net.fit_iterator(ListDataSetIterator(data))
        return _leaves(net)

    for a, b in zip(run(2), run(0)):
        assert np.array_equal(a, b)


def test_wrapper_prefetch_equivalence():
    """ParallelWrapper sync DP with device prefetch == without (same sharded
    staging, same order), bit-for-bit."""
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    def conf():
        return (NeuralNetConfiguration.builder()
                .seed(1).learning_rate(0.1)
                .list()
                .layer(DenseLayer(n_in=6, n_out=10, activation="tanh"))
                .layer(OutputLayer(n_in=10, n_out=3, loss="mcxent",
                                   activation="softmax"))
                .build())

    rng = np.random.default_rng(0)
    data = []
    for _ in range(6):
        x = rng.normal(size=(32, 6)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
        data.append(DataSet(x, y))

    def run(prefetch):
        net = MultiLayerNetwork(conf()).init(seed=1)
        (ParallelWrapper.builder(net)
         .workers(8).prefetch_buffer(prefetch).averaging_frequency(1)
         .build()).fit(ListDataSetIterator(data))
        return _leaves(net)

    for a, b in zip(run(2), run(0)):
        assert np.array_equal(a, b)


# -------------------------------------------------------------- donation safety
def test_donation_safety_under_depth2_prefetch():
    """Depth-2 prefetch stages batches from the SAME host arrays every step
    while the donated (params/states/updater) dispatch is in flight. Staged
    buffers are fresh, non-donated device arrays, so nothing may raise a
    deleted-buffer error and the net stays usable."""
    net = _mlp_net(seed=5)
    net.dispatch_ksteps = 2
    net.prefetch_depth = 2
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    data = [DataSet(x, y) for _ in range(8)]  # shared backing buffers
    net.fit_iterator(ListDataSetIterator(data), epochs=2)
    for p in _leaves(net):
        assert np.isfinite(p).all()
    out = np.asarray(net.output(x))
    assert np.isfinite(out).all()


# ------------------------------------------------------------------- telemetry
def test_prefetch_metric_families_exposed():
    net = _mlp_net(seed=9)
    net.dispatch_ksteps = 2
    net.fit_iterator(ListDataSetIterator(_batches(6)))
    snap = global_registry().snapshot()
    for fam in ("dl4j_prefetch_depth", "dl4j_prefetch_bytes_total",
                "dl4j_prefetch_staging_seconds_total",
                "dl4j_prefetch_wait_seconds_total"):
        assert fam in snap, fam
    by_path = {s["labels"].get("path"): s
               for s in snap["dl4j_prefetch_bytes_total"]["series"]}
    assert by_path["multilayer"]["value"] > 0
    # the share of staging hidden behind dispatch is 1 - wait / staging of
    # the two counters (no gauge of its own)
    wait, staging = (
        next(s["value"] for s in snap[fam]["series"]
             if s["labels"].get("path") == "multilayer")
        for fam in ("dl4j_prefetch_wait_seconds_total",
                    "dl4j_prefetch_staging_seconds_total"))
    assert wait >= 0.0 and staging > 0.0
