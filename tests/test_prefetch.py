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
import types

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator, ListDataSetIterator,
)
from deeplearning4j_tpu.datasets import prefetch as prefetch_mod
from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher, HostGroupRing
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    DenseLayer, GravesLSTM, OutputLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.nn import multilayer as multilayer_mod
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, stage_group
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


# ------------------------------------------------------- host ring (stage_group)
def _host_group(kind, n, seed=0, batch=6):
    """``n`` distinct host batches: arrays, or a graph's lists of streams
    (two feature streams, one label stream)."""
    rng = np.random.default_rng(seed)

    def one():
        x = rng.normal(size=(batch, 5)).astype(np.float32) * 3.0
        x[0, 0] = np.inf
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)]
        if kind == "array":
            return x, y
        x2 = rng.integers(-9, 9, size=(batch, 2, 3)).astype(np.int32)
        return [x, x2], [y]

    return [one() for _ in range(n)]


def _as_bits(a):
    a = np.asarray(a)
    return a.view(np.uint8).reshape(a.shape + (a.dtype.itemsize,))


def _slot_counts(path):
    out = {"reused": 0, "allocated": 0, "wait": 0.0}
    snap = global_registry().snapshot()
    for s in snap.get("dl4j_prefetch_stage_slots_total", {"series": []})[
            "series"]:
        if s["labels"].get("path") == path:
            out[s["labels"]["outcome"]] = s["value"]
    for s in snap.get("dl4j_prefetch_slot_wait_seconds_total",
                      {"series": []})["series"]:
        if s["labels"].get("path") == path:
            out["wait"] = s["value"]
    return out


def _buffer_at(offset):
    """An allocator of memory ``offset`` bytes past a 64-byte boundary: at 0
    the CPU backend wraps it without a copy, at 16 it has to copy."""
    def host_buffer(shape, dtype):
        dtype = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dtype.itemsize
        raw = np.empty(nbytes + 128, np.uint8)
        start = -raw.ctypes.data % 64 + offset
        return raw[start:start + nbytes].view(dtype).reshape(shape)
    return host_buffer


@pytest.fixture
def copied_slots(monkeypatch):
    """Slots the CPU backend copies out of, as a TPU does (for arrays as
    small as a test's, whether ``np.empty``'s memory gets wrapped is
    chance)."""
    monkeypatch.setattr(prefetch_mod, "_host_buffer", _buffer_at(16))


def _group_sharding(mesh_shape):
    """What ``ParallelWrapper._fit_sync`` hands ``stage_group``: a batch's
    leaf to the sharding of its stacked group, on a mesh ``(data,)`` or
    ``(data, sp)`` of the virtual devices (with ``sp``, a ``[B, T, ...]``
    leaf's time axis is split too, as ``_batch_spec`` does)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    count = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(jax.devices()[:count]).reshape(mesh_shape),
                ("data", "sp")[:len(mesh_shape)])

    def sharding(leaf):
        seq = len(mesh_shape) == 2 and np.ndim(leaf) == 3
        return NamedSharding(mesh, P(None, "data", "sp") if seq
                             else P(None, "data"))
    return sharding


@pytest.mark.parametrize("n", [4, 2])
@pytest.mark.parametrize("kind", ["array", "streams"])
@pytest.mark.parametrize("dtype", ["bfloat16", None])
@pytest.mark.parametrize("mesh_shape", [None, (4,), (2, 2)])
def test_stage_group_bits_equal_stack_then_astype(copied_slots, mesh_shape,
                                                  dtype, kind, n):
    """One pass into a reused slot gives the bytes ``np.stack(...).astype``
    gave: full group, then a short one (``buf[:n]``) into the same slots,
    then round the ring so that every slot is written a second time. On the
    default device, and laid out over a mesh as the wrapper's groups are
    (every device sent its shard as a view of the slot): over the batch
    alone, and with the sequence axis or replicas besides."""
    import jax.numpy as jnp

    dtype = getattr(jnp, dtype) if dtype else None
    sharding = _group_sharding(mesh_shape) if mesh_shape else None
    ring = HostGroupRing(2, "test_bits")
    before = _slot_counts("test_bits")
    for turn, length in enumerate([4, n, 4, n]):
        group = _host_group(kind, length, seed=turn,
                            batch=8 if mesh_shape else 6)
        xs, ys = stage_group(group, dtype, ring, sharding)
        tree = jax.tree_util
        want_x = tree.tree_map(lambda *a: np.stack(a), *[b[0] for b in group])
        want_y = tree.tree_map(lambda *a: np.stack(a), *[b[1] for b in group])
        if dtype is not None:
            # an integer stream keeps its dtype: bfloat16 cannot hold an id
            want_x = tree.tree_map(
                lambda a: a if np.issubdtype(a.dtype, np.integer)
                else a.astype(dtype), want_x)
        assert tree.tree_structure((xs, ys)) == tree.tree_structure(
            (want_x, want_y))
        for got, want in zip(tree.tree_leaves((xs, ys)),
                             tree.tree_leaves((want_x, want_y))):
            assert isinstance(got, jax.Array)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(_as_bits(got), _as_bits(want))
            if sharding is not None:
                assert got.sharding.is_equivalent_to(sharding(want[0]),
                                                     want.ndim)
                assert len(got.sharding.device_set) == 4
    after = _slot_counts("test_bits")
    assert after["allocated"] - before["allocated"] == 2
    assert after["reused"] - before["reused"] == 2


@pytest.mark.parametrize("aligned", [False, True])
@pytest.mark.parametrize("depth", [2, 0])
def test_slot_reuse_trains_the_same_as_fresh_slots(monkeypatch, depth,
                                                   aligned):
    """Every slot of the ring is rewritten twice while earlier groups are
    queued, dispatched or still being copied (the CPU runtime reads a put's
    host buffer after ``device_put`` has returned, and wraps an aligned one
    for good): params bit for bit as with a stager that never reuses one."""
    import jax.numpy as jnp

    k = 2
    data = _batches((2 * (depth + 2) + 1) * k, seed=4)

    def run(reuse):
        monkeypatch.setattr(prefetch_mod, "_host_buffer",
                            _buffer_at(0 if aligned else 16))
        if not reuse:  # a ring that never comes round
            monkeypatch.setattr(
                multilayer_mod, "HostGroupRing",
                lambda size, path: HostGroupRing(10 ** 6, path))
        net = _mlp_net(seed=11)
        net.dispatch_ksteps = k
        net.prefetch_depth = depth
        net.stage_dtype = jnp.bfloat16
        before = _slot_counts("multilayer")
        net.fit_iterator(ListDataSetIterator(data))
        after = _slot_counts("multilayer")
        monkeypatch.undo()
        return _leaves(net), {o: after[o] - before[o] for o in after}

    fresh, fresh_counts = run(reuse=False)
    reused, counts = run(reuse=True)
    groups = 2 * (depth + 2) + 1
    assert fresh_counts["reused"] == 0 and fresh_counts["allocated"] == groups
    if aligned:
        # the backend wrapped every slot, so each was given away
        assert counts["reused"] == 0 and counts["allocated"] == groups
    else:
        assert counts["allocated"] == depth + 2
        assert counts["reused"] == groups - (depth + 2)
    for a, b in zip(fresh, reused):
        assert np.array_equal(a, b)


class _StubDeviceArray:
    """What ``device_put`` returns, for a ring that cannot tell: a transfer
    that has not finished until someone waits for it, on a device whose
    memory is its own (``platform``) or the host's."""

    def __init__(self, host, log, platform="tpu"):
        self.host, self.log, self.platform = host, log, platform
        self.sent = host.copy()
        self.ready = False
        self.nbytes, self.shape, self.dtype = host.nbytes, host.shape, host.dtype

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        # the runtime was still reading: the slot must hold what was put
        self.log.append(("waited", np.array_equal(_as_bits(self.host),
                                                  _as_bits(self.sent))))
        self.ready = True
        return self

    def devices(self):
        return [types.SimpleNamespace(platform=self.platform)]

    def unsafe_buffer_pointer(self):
        return self.host.ctypes.data


@pytest.mark.parametrize("sharded", [False, True])
def test_slot_is_not_rewritten_before_its_transfer_has_finished(monkeypatch,
                                                                sharded):
    """``sharded``: the group is laid out over a mesh, as the wrapper's are;
    the slot then belongs to an array with a shard on every device, which is
    waited for as a whole."""
    log = []
    monkeypatch.setattr(
        jax, "device_put", lambda a, *r, **kw: _StubDeviceArray(a, log))
    monkeypatch.setattr(
        jax, "make_array_from_callback",
        lambda shape, sharding, shard_of: _StubDeviceArray(
            shard_of((slice(None),) * len(shape)), log))
    sharding = _group_sharding((4,)) if sharded else None

    def stage(seed):
        return stage_group(_host_group("array", 3, seed=seed, batch=8),
                           None, ring, sharding)

    ring = HostGroupRing(2, "test_wait")
    before = _slot_counts("test_wait")
    first = stage(1)
    stage(2)
    assert log == []                      # two slots, nothing to wait for
    third = stage(3)
    # both transfers out of slot 0 (features, labels) were waited for, and
    # at that moment the slot still held the first group
    assert log == [("waited", True), ("waited", True)]
    assert all(d.ready for d in first) and not any(d.ready for d in third)
    assert first[0].host.ctypes.data == third[0].host.ctypes.data
    after = _slot_counts("test_wait")
    assert after["reused"] - before["reused"] == 1
    assert after["wait"] >= before["wait"]
    # a transfer seen finished at a later staging call is let go of unwaited
    for d in third:
        d.ready = True
    stage(4)   # slot 1
    fifth = stage(5)   # slot 0
    assert len(log) == 4                  # slot 1's two, none for slot 0
    # an owner that keeps the ring between fits drains it: every transfer
    # waited for, no device array kept
    assert not any(d.ready for d in fifth)
    ring.drain()
    assert all(d.ready for d in fifth) and len(log) == 8
    assert all(slot.in_flight == [] for slot in ring._slots)


def test_a_shard_that_reads_the_slot_is_seen_on_every_device():
    """A slot laid out over a mesh is given away if ANY device's shard reads
    its memory: the CPU backend wraps an aligned buffer whole for every
    device of a replicated leaf, and copies out of an unaligned one."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    everywhere = NamedSharding(Mesh(np.array(jax.devices()[:4]), ("data",)),
                               P())
    for offset, aliased in ((0, True), (16, False)):
        host = _buffer_at(offset)((4, 64), np.float32)
        host[:] = 1.0
        dev = jax.make_array_from_callback(host.shape, everywhere,
                                           host.__getitem__)
        assert len(dev.addressable_shards) == 4
        assert prefetch_mod._reads_host_memory(dev, host) == aliased


def test_aliased_put_gives_the_slot_away(monkeypatch):
    log = []
    monkeypatch.setattr(
        jax, "device_put",
        lambda a, *r, **kw: _StubDeviceArray(a, log, platform="cpu"))
    ring = HostGroupRing(1, "test_alias")
    before = _slot_counts("test_alias")
    staged = [stage_group(_host_group("streams", 2, seed=s), None, ring)
              for s in range(3)]
    after = _slot_counts("test_alias")
    # a ring of one would have reused its slot twice; every put read the
    # slot's own memory, so each group got memory of its own and none waited
    assert after["allocated"] - before["allocated"] == 3
    assert after["reused"] == before["reused"] and log == []
    firsts = [jax.tree_util.tree_leaves(s)[0] for s in staged]
    assert len({d.host.ctypes.data for d in firsts}) == 3
    for s, d in enumerate(firsts):        # and nobody wrote over it
        want = np.stack([b[0][0] for b in _host_group("streams", 2, seed=s)])
        assert np.array_equal(_as_bits(d.host), _as_bits(want))


def test_slot_counters_follow_a_known_sequence(copied_slots):
    import jax.numpy as jnp

    ring = HostGroupRing(2, "test_counts")
    seen = []

    def stage(group, dtype=jnp.bfloat16):
        before = _slot_counts("test_counts")
        out = stage_group(group, dtype, ring)
        after = _slot_counts("test_counts")
        seen.extend(o for o in ("reused", "allocated")
                    if after[o] != before[o])
        return out

    def bf16_bits(group):
        return _as_bits(np.stack([b[0] for b in group]).astype(jnp.bfloat16))

    a = lambda s: _host_group("array", 4, seed=s)                 # noqa: E731
    stage(a(0)); stage(a(1)); stage(a(2))        # two slots, then round
    stage(a(3)[:2])                              # short: same slots
    stage(_host_group("array", 4, seed=4, batch=3))   # ragged tail: new ring
    stage(a(5))                                  # back: the ring was dropped
    # one path whatever the leaf: a jax.Array and an object array are cast
    # into the slot like any other
    xs, _ = stage([(jnp.asarray(x), y) for x, y in a(6)])
    assert xs.dtype == jnp.bfloat16 and xs.shape == (4, 6, 5)
    assert np.array_equal(_as_bits(xs), bf16_bits(a(6)))
    xs, _ = stage([(x.astype(object), y) for x, y in a(7)])
    assert np.array_equal(_as_bits(xs), bf16_bits(a(7)))
    stage(a(8), dtype=None)                      # another staged dtype
    assert seen == ["allocated", "allocated", "reused", "reused",
                    "allocated", "allocated", "allocated", "reused",
                    "allocated"]


def test_fit_loop_keeps_one_group_queued_behind_the_running_step(monkeypatch):
    """Flow control: a staged group is dispatched once the step of the group
    two before it has finished, so staged groups cannot pile up in HBM and
    the dispatch call is still hidden behind a running step. What the loop
    kept between groups goes when ``fit_iterator`` returns."""
    order = []

    class Losses:
        def __init__(self, i, real):
            self.i, self.real = i, real

        def block_until_ready(self):
            order.append(("waited", self.i))

        def __getitem__(self, j):
            return self.real[j]

    net = _mlp_net(seed=2)
    net.dispatch_ksteps = 2
    run = type(net)._run_steps

    def spy(self, kind, n, xs, ys, after=None, **kw):
        assert kind == "multistep"
        i = sum(1 for o in order if o[0] == "dispatch")
        order.append(("dispatch", i, after.i if after else None))
        assert self._host_ring is not None
        return Losses(i, run(self, kind, n, xs, ys, after=after, **kw))

    monkeypatch.setattr(type(net), "_run_steps", spy)
    net.fit_iterator(ListDataSetIterator(_batches(8)))
    assert order == [("dispatch", 0, None), ("dispatch", 1, None),
                     ("dispatch", 2, 0), ("waited", 0),
                     ("dispatch", 3, 1), ("waited", 1)]
    assert net._host_ring is None and net._staged_losses == (None, None)


# ------------------------------------ ParallelWrapper's synchronous loop (ring)
def _wrapped_net(kind, seed=7):
    """A network of either type under a 4-device synchronous wrapper,
    ``prefetch`` 2 (a ring of four slots), K=2, staging cast on."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph_network import ComputationGraph
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    b = NeuralNetConfiguration.builder().seed(seed).learning_rate(0.1)
    dense = DenseLayer(n_in=4, n_out=8, activation="tanh")
    out = OutputLayer(n_in=8, n_out=3, loss="mcxent", activation="softmax")
    if kind == "multilayer":
        net = MultiLayerNetwork(b.list().layer(dense).layer(out).build())
    else:
        net = ComputationGraph(
            b.graph_builder().add_inputs("in")
            .add_layer("dense", dense, "in").add_layer("out", out, "dense")
            .set_outputs("out").build())
    net = net.init(seed=seed)
    net.dispatch_ksteps = 2
    net.stage_dtype = jnp.bfloat16
    wrapper = (ParallelWrapper.builder(net).workers(4).prefetch_buffer(2)
               .averaging_frequency(1).build())
    return net, wrapper


@pytest.mark.parametrize("kind", ["multilayer", "graph"])
def test_wrapper_slot_reuse_trains_the_same_as_fresh_slots(monkeypatch, kind):
    """The wrapper's groups go through ``stage_group`` into a ring of
    ``prefetch + 2`` slots that outlives ``fit``: cast to ``stage_dtype``,
    laid out over the mesh's batch axis, every slot rewritten while earlier
    groups are queued or dispatched, and params bit for bit as with a stager
    that never reuses a slot. A second ``fit`` allocates nothing, and no
    device array is kept between the two."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    k, slots = 2, 4
    groups = 2 * slots + 1
    data = _batches(groups * k, seed=4)
    make = ParallelWrapper._make_sync_multistep

    def run(reuse):
        monkeypatch.setattr(prefetch_mod, "_host_buffer", _buffer_at(16))
        if not reuse:  # a ring that never comes round
            monkeypatch.setattr(
                prefetch_mod, "HostGroupRing",
                lambda size, path: HostGroupRing(10 ** 6, path))
        staged = []

        def spying(self):
            multi = make(self)

            def spy(params, states, upd, xs, ys, rng, it):
                staged.append((xs, ys))
                return multi(params, states, upd, xs, ys, rng, it)
            return spy

        monkeypatch.setattr(ParallelWrapper, "_make_sync_multistep", spying)
        net, wrapper = _wrapped_net(kind)
        counts = [_slot_counts("wrapper_sync")]
        for _ in range(2):
            wrapper.fit(ListDataSetIterator(data))
            counts.append(_slot_counts("wrapper_sync"))
            assert all(slot is None or slot.in_flight == []
                       for slot in wrapper._host_ring._slots)
        monkeypatch.undo()
        deltas = [{o: b[o] - a[o] for o in ("allocated", "reused")}
                  for a, b in zip(counts, counts[1:])]
        return _leaves(net), deltas, staged

    fresh, fresh_counts, _ = run(reuse=False)
    reused, counts, staged = run(reuse=True)
    assert fresh_counts == [{"allocated": groups, "reused": 0}] * 2
    assert counts == [{"allocated": slots, "reused": groups - slots},
                      {"allocated": 0, "reused": groups}]
    for a, b in zip(fresh, reused):
        assert np.array_equal(a, b)
    assert len(staged) == 2 * groups
    for i, (xs, ys) in enumerate(staged):
        want = data[(i % groups) * k:(i % groups + 1) * k]
        for got, host, dtype in (
                (xs, [d.features for d in want], jnp.bfloat16),
                (ys, [d.labels for d in want], np.float32)):
            got, = jax.tree_util.tree_leaves(got)
            assert got.dtype == dtype and got.shape == (k, 8) + host[0].shape[1:]
            assert got.sharding.spec == P(None, "data")
            assert len(got.sharding.device_set) == 4
            assert np.array_equal(_as_bits(got),
                                  _as_bits(np.stack(host).astype(dtype)))


# ------------------------------------------------- one staged loop, three doors
@pytest.mark.parametrize("entry", ["multilayer", "graph", "wrapper"])
def test_every_entry_point_runs_the_one_staged_epoch(monkeypatch, entry):
    """Both networks' ``fit_iterator`` and ``ParallelWrapper``'s synchronous
    loop pass through ``LazyScore._fit_epoch_staged``, the wrapper as a
    ``LoopOwner``. For the networks the loop is what it was: the type's
    K-step program over each stacked, cast group (its one-step program for
    the lone ragged batch), one rng split and K iterations a dispatch —
    replayed here by hand, the parameters agree bit for bit."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.multilayer import LazyScore

    k, epochs = 2, 2
    data = _batches(5, seed=8) + _batches(1, batch=5, seed=9)
    calls = []
    staged_epoch = LazyScore._fit_epoch_staged

    def spy(self, iterator, k, owner=None):
        calls.append((type(self).__name__, k, owner and owner.path))
        return staged_epoch(self, iterator, k, owner)

    monkeypatch.setattr(LazyScore, "_fit_epoch_staged", spy)
    kind = "multilayer" if entry == "wrapper" else entry
    net, wrapper = _wrapped_net(kind)
    name = type(net).__name__
    if entry == "wrapper":
        # the wrapper's loop needs batches its four devices divide
        wrapper.fit(ListDataSetIterator(data[:5]), epochs=epochs)
        assert calls == [(name, k, "wrapper_sync")] * epochs
        assert net.iteration == 5 * epochs
        return
    net.fit_iterator(ListDataSetIterator(data), epochs=epochs)
    assert calls == [(name, k, None)] * epochs

    twin, _ = _wrapped_net(kind)
    multi = jax.jit(type(twin)._multistep_builder(twin.conf))
    step = jax.jit(type(twin)._step_builder(twin.conf))
    tree = (lambda a: a) if kind == "multilayer" else (lambda a: [a])
    state = (twin.params_list, twin.state_list, twin.updater_state)
    it = 0
    for _ in range(epochs):
        for group in (data[0:2], data[2:4], data[4:5], data[5:6]):
            x = np.stack([d.features for d in group])
            y = np.stack([d.labels for d in group])
            if len(group) == 1:      # a lone batch is not cast: _fit_batch
                *state, _ = step(*state, tree(jnp.asarray(x[0])),
                                 tree(jnp.asarray(y[0])), twin._next_rng(),
                                 jnp.int32(it), None, None)
            else:
                *state, _ = multi(*state, tree(x.astype(jnp.bfloat16)),
                                  tree(y), twin._next_rng(), jnp.int32(it))
            it += len(group)
    assert net.iteration == it
    for a, b in zip(_leaves(net), jax.tree_util.tree_leaves(state[0])):
        assert np.array_equal(a, np.asarray(b))


def test_wrapper_sets_last_batch_size_for_a_graph():
    """The loop is the networks': a ``ComputationGraph`` under the wrapper
    knows its batch size like any other (PerformanceListener reads it)."""
    net, wrapper = _wrapped_net("graph")
    assert net.last_batch_size == 0
    wrapper.fit(ListDataSetIterator(_batches(3)))   # a group of 2, a lone one
    assert net.last_batch_size == 8
