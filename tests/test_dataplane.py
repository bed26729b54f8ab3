"""Zero-copy host data plane tests (ISSUE 14): scatter-gather wire codec
fuzz roundtrips, shm segment reaper under SIGKILL chaos (zero orphans),
seqlock ring integrity, ShmTransport negotiate/fallback, shard-segment
shipping, native ingest decode parity, and the three-transport
(inproc/tcp/shm) bitwise fit parity pin."""
import os
import signal
import socket
import struct
import subprocess
import sys
import threading

import numpy as np
import pytest

from deeplearning4j_tpu import nativert
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel import ps_transport as pst
from deeplearning4j_tpu.parallel.param_server import (
    ParameterServer, ParameterServerParallelWrapper,
)
from deeplearning4j_tpu.streaming import wire
from deeplearning4j_tpu.streaming.broker import (
    BrokerIngestSource, BrokerProducer, LoopbackBroker, ReconnectingConsumer,
)

SHM_DIR = "/dev/shm"

needs_shm = pytest.mark.skipif(not os.path.isdir(SHM_DIR),
                               reason="no /dev/shm on this host")
needs_native = pytest.mark.skipif(
    not nativert.native_available(),
    reason=f"native runtime unavailable: {nativert.load_error()}")


def _shm_names():
    try:
        return {n for n in os.listdir(SHM_DIR)
                if n.startswith(pst._SHM_PREFIX)}
    except OSError:
        return set()


# ------------------------------------------------------------- wire codec

_FUZZ_DTYPES = (np.float32, np.float64, np.int32, np.int64, np.uint8)


def _random_arrays(rng, n_arrays):
    out = {}
    for i in range(n_arrays):
        dt = _FUZZ_DTYPES[int(rng.integers(len(_FUZZ_DTYPES)))]
        ndim = int(rng.integers(0, 4))
        # odd/prime extents and occasional zero-length axes on purpose
        shape = tuple(int(rng.integers(0, 8)) for _ in range(ndim))
        if np.dtype(dt).kind == "f":
            a = rng.normal(size=shape).astype(dt)
        else:
            a = rng.integers(0, 200, size=shape).astype(dt)
        out[f"a{i}"] = a
    return out


@pytest.mark.parametrize("codec", ["none", "bf16"])
def test_wire_fuzz_roundtrip_over_socketpair(codec):
    """Random multi-tensor frames (mixed dtypes, empty and odd-length
    shapes) survive pack -> sendmsg scatter-gather -> recv_into -> unpack.
    codec none is bitwise; bf16 widens back exactly (bf16 -> f32 is exact)
    after the documented precision haircut."""
    rng = np.random.default_rng(1234)
    left, right = socket.socketpair()
    try:
        for _ in range(25):
            arrays = _random_arrays(rng, int(rng.integers(1, 5)))
            metas, views = wire.pack_arrays(arrays, codec)
            wire.send_frame(left, {"op": "t", "arrays": metas}, views)
            header, payload = wire.recv_frame(right)
            got = wire.unpack_arrays(header["arrays"], payload)
            assert set(got) == set(arrays)
            for k, a in arrays.items():
                assert got[k].shape == a.shape
                if codec == "bf16" and a.dtype.kind == "f":
                    # the decoded array is the bf16 quantization of a,
                    # widened: re-quantizing a must reproduce it exactly
                    import ml_dtypes
                    expect = np.asarray(a, ml_dtypes.bfloat16).astype(a.dtype)
                    np.testing.assert_array_equal(got[k], expect)
                else:
                    assert got[k].dtype == a.dtype
                    np.testing.assert_array_equal(got[k], a)
    finally:
        left.close()
        right.close()


def test_wire_reusable_buffer_roundtrip():
    left, right = socket.socketpair()
    rbuf = bytearray()
    try:
        for i in range(4):
            a = {"x": np.full((3, 5), float(i), np.float32)}
            metas, views = wire.pack_arrays(a)
            wire.send_frame(left, {"arrays": metas}, views)
            header, payload = wire.recv_frame(right, rbuf)
            got = wire.unpack_arrays(header["arrays"], payload)
            np.testing.assert_array_equal(got["x"], a["x"])
            del got, payload  # release the views so the buffer can be reused
    finally:
        left.close()
        right.close()


def test_wire_truncated_stream_raises():
    """A peer dying mid-frame raises ConnectionError, never returns a short
    read as a frame."""
    # case 1: prefix promises more payload than ever arrives
    left, right = socket.socketpair()
    try:
        hdr = b'{"op":"t"}'
        left.sendall(struct.pack("!II", len(hdr), 64) + hdr + b"\x00" * 10)
        left.close()
        with pytest.raises(ConnectionError):
            wire.recv_frame(right)
    finally:
        right.close()
    # case 2: cut inside the header
    left, right = socket.socketpair()
    try:
        left.sendall(struct.pack("!II", 100, 0) + b'{"op"')
        left.close()
        with pytest.raises(ConnectionError):
            wire.recv_frame(right)
    finally:
        right.close()


def test_wire_unknown_codec_rejected():
    with pytest.raises(ValueError):
        wire.encode_array(np.zeros(3, np.float32), "zstd")


# --------------------------------------------------------- seqlock ring

@needs_shm
def test_shm_ring_roundtrip_and_slot_alternation():
    seg = pst.create_segment(pst.ShmRing.segment_size(64), "ringtest")
    try:
        ring = pst.ShmRing(seg, 64)
        reader = pst.ShmRing(pst.attach_segment(seg.name), 64)
        for i in range(5):
            payload = bytes(range(i, i + 10))
            slot, seq = ring.write(memoryview(payload), version=i)
            assert slot == i % 2  # double buffer alternates
            version, view = reader.read(slot, seq)
            assert version == i
            assert bytes(view) == payload
            del view
        pst.release_segment(reader.shm)
    finally:
        pst.release_segment(seg, unlink=True)


@needs_shm
def test_shm_ring_detects_stale_and_torn_slots():
    seg = pst.create_segment(pst.ShmRing.segment_size(32), "ringtorn")
    try:
        ring = pst.ShmRing(seg, 32)
        slot, seq = ring.write(b"abc", version=1)
        # stale: the control message promised a seq the slot no longer has
        with pytest.raises(ConnectionError):
            ring.read(slot, seq + 2)
        # torn: an odd seq means the writer died mid-write
        pst.ShmRing.SLOT_HDR.pack_into(seg.buf, 0, seq + 1, 1, 3)
        with pytest.raises(ConnectionError, match="torn"):
            ring.read(slot, seq + 1)
        # overflow refuses, never scribbles past the slot
        with pytest.raises(ValueError, match="overflow"):
            ring.write(b"x" * 33, version=2)
    finally:
        pst.release_segment(seg, unlink=True)


# ------------------------------------------------------ reaper + shipping

@needs_shm
def test_shard_segment_roundtrip_owns_data():
    arrays = {"x": np.arange(24, dtype=np.float32).reshape(4, 6),
              "y": np.eye(3, dtype=np.float32)}
    name = pst.write_shard_segment(arrays, kind="t")
    assert name in _shm_names()
    got = pst.read_shard_segment(name)
    assert pst.release_segment_by_name(name)
    assert name not in _shm_names()
    for k in arrays:  # the decoded arrays outlive the unlinked segment
        np.testing.assert_array_equal(got[k], arrays[k])


@needs_shm
def test_reaper_skips_live_owner():
    seg = pst.create_segment(128, "alive")
    try:
        assert pst.reap_orphans() >= 0
        assert seg.name in _shm_names()  # own pid is alive: not garbage
    finally:
        pst.release_segment(seg, unlink=True)


@needs_shm
def test_reaper_collects_sigkilled_creators_segments():
    """SIGKILL chaos: a process that created segments and died without
    atexit (and whose resource tracker died with the group, simulated by
    unregistering) leaves orphans in /dev/shm — reap_orphans() sweeps every
    one of them."""
    child_src = (
        "import os, signal, sys\n"
        "from multiprocessing import resource_tracker\n"
        "from deeplearning4j_tpu.parallel import ps_transport as pst\n"
        "names = []\n"
        "for i in range(3):\n"
        "    seg = pst.create_segment(256, f'chaos{i}')\n"
        "    resource_tracker.unregister(\n"
        "        getattr(seg, '_name', '/' + seg.name), 'shared_memory')\n"
        "    names.append(seg.name)\n"
        "print('\\n'.join(names), flush=True)\n"
        "os.kill(os.getpid(), signal.SIGKILL)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", child_src],
                          capture_output=True, text=True, timeout=120,
                          env=env)
    assert proc.returncode == -signal.SIGKILL
    names = [n for n in proc.stdout.splitlines() if n.strip()]
    assert len(names) == 3, proc.stderr
    live = _shm_names()
    assert all(n in live for n in names), "fixture broke: no orphans to reap"
    assert pst.reap_orphans() >= 3
    left = _shm_names()
    assert not any(n in left for n in names)


# ----------------------------------------------------------- shm transport

@needs_shm
def test_shm_transport_negotiates_and_matches_inproc():
    init = np.zeros(16, np.float32)
    srv = ParameterServer([init.copy()])
    ref = ParameterServer([init.copy()])
    frontend = pst.ParameterServerTcpFrontend(srv).start()
    t = pst.ShmTransport(("127.0.0.1", frontend.port))
    inproc = pst.InprocTransport(ref)
    try:
        v0, vec0 = t.pull()
        assert t.shm_active is True
        rv0, rvec0 = inproc.pull()
        assert (v0, rv0) == (0, 0)
        np.testing.assert_array_equal(vec0, rvec0)
        rng = np.random.default_rng(7)
        for i in range(6):
            delta = rng.normal(size=16).astype(np.float32)
            a = t.push(delta, base_version=i)
            b = inproc.push(delta, base_version=i)
            assert (a.accepted, a.version, a.staleness, a.weight) == \
                   (b.accepted, b.version, b.staleness, b.weight)
            np.testing.assert_array_equal(a.params, b.params)
        seg_names = {t._push_ring.shm.name, t._pull_ring.shm.name}
        assert seg_names <= _shm_names()
    finally:
        t.close()
        frontend.stop()
    # frontend.stop() unlinks the session rings: nothing left behind
    assert not (seg_names & _shm_names())


@needs_shm
def test_shm_transport_falls_back_to_tcp_when_attach_fails(monkeypatch):
    """A peer that can't map the segments (cross-host) degrades permanently
    to the inherited TCP frames with identical results."""
    srv = ParameterServer([np.zeros(8, np.float32)])
    frontend = pst.ParameterServerTcpFrontend(srv).start()
    monkeypatch.setattr(pst, "attach_segment",
                        lambda name: (_ for _ in ()).throw(OSError("nope")))
    t = pst.ShmTransport(("127.0.0.1", frontend.port))
    try:
        version, vec = t.pull()
        assert t.shm_active is False
        assert version == 0 and vec.shape == (8,)
        res = t.push(np.ones(8, np.float32), base_version=0)
        assert res.accepted and res.version == 1
        np.testing.assert_array_equal(res.params, np.ones(8, np.float32))
    finally:
        t.close()
        frontend.stop()


# -------------------------------------------------------- native ingest

def test_ingest_python_decoder_paths():
    raw = np.arange(12, dtype=np.float32)
    np.testing.assert_array_equal(
        nativert.decode_records_py(raw.tobytes(), "f32"), raw)
    u8 = bytes(range(256))
    got = nativert.decode_records_py(u8, "u8")
    np.testing.assert_array_equal(
        got, np.arange(256, dtype=np.float32) * np.float32(1.0 / 255.0))


@needs_native
@pytest.mark.parametrize("codec", ["f32", "bf16", "u8"])
def test_ingest_native_python_bitwise_parity(codec):
    rng = np.random.default_rng(42)
    if codec == "f32":
        buf = rng.normal(size=333).astype(np.float32).tobytes()
    elif codec == "bf16":
        import ml_dtypes
        buf = rng.normal(size=333).astype(ml_dtypes.bfloat16).tobytes()
    else:
        buf = rng.integers(0, 256, 333, dtype=np.uint8).tobytes()
    native = nativert.decode_records(buf, codec)
    assert native is not None
    np.testing.assert_array_equal(native,
                                  nativert.decode_records_py(buf, codec))


@needs_native
def test_ingest_ragged_record_rejected():
    assert nativert.decode_records(b"\x00" * 7, "f32") is None
    dec = nativert.IngestDecoder(capacity=4)
    try:
        with pytest.raises(ValueError, match="ragged"):
            dec.submit(b"\x00" * 7, "f32")
    finally:
        dec.close()


@needs_native
def test_ingest_decoder_pipelines_in_order():
    """Bounded staging queue: interleave submits with next() past the
    capacity and records come back f32-decoded in submission order."""
    rng = np.random.default_rng(3)
    records = [rng.normal(size=int(rng.integers(1, 64))).astype(np.float32)
               for _ in range(10)]
    dec = nativert.IngestDecoder(capacity=4)
    out = []
    try:
        for i, rec in enumerate(records):
            dec.submit(rec.tobytes(), "f32")
            if i >= 3:
                out.append(dec.next())
        while True:
            got = dec.next()
            if got is None:
                break
            out.append(got)
    finally:
        dec.close()
    assert len(out) == len(records)
    for got, rec in zip(out, records):
        np.testing.assert_array_equal(got, rec)


# ------------------------------------------------------ broker integration

def test_broker_native_decode_parity_and_ingest_source():
    """native_decode consumers deliver bitwise the same arrays as the plain
    wire decode, and BrokerIngestSource iterates them prefetcher-shaped
    (ends at the fin marker)."""
    broker = LoopbackBroker().start()
    prod = BrokerProducer(broker.address)
    plain = ReconnectingConsumer(broker.address, "t", group="plain")
    native = ReconnectingConsumer(broker.address, "t", group="native",
                                  native_decode=True)
    try:
        rng = np.random.default_rng(9)
        msgs = [{"x": rng.normal(size=(4, 6)).astype(np.float32),
                 "y": rng.normal(size=(4, 3)).astype(np.float32)}
                for _ in range(3)]
        for m in msgs:
            prod.publish("t", m)
        prod.publish("t", {}, meta={"fin": True})
        for m in msgs:
            _, a = plain.get(timeout=5.0)
            plain.task_done()
            _, b = native.get(timeout=5.0)
            native.task_done()
            for k in m:
                np.testing.assert_array_equal(a[k], m[k])
                np.testing.assert_array_equal(b[k], m[k])
        plain.get(timeout=5.0)  # drain plain's fin
        plain.task_done()
        got = list(BrokerIngestSource(native, idle_timeout_s=5.0))
        assert got == []  # fin already next in line: source stops cleanly
    finally:
        plain.close()
        native.close()
        prod.close()
        broker.stop()


def test_broker_ingest_source_yields_batches():
    broker = LoopbackBroker().start()
    prod = BrokerProducer(broker.address)
    cons = ReconnectingConsumer(broker.address, "t", group="g",
                                native_decode=True)
    try:
        msgs = [{"x": np.full((2, 4), float(i), np.float32)} for i in range(3)]
        for m in msgs:
            prod.publish("t", m)
        prod.publish("t", {}, meta={"fin": True})
        got = list(BrokerIngestSource(cons, idle_timeout_s=5.0))
        assert len(got) == 3
        for g, m in zip(got, msgs):
            np.testing.assert_array_equal(g["x"], m["x"])
    finally:
        cons.close()
        prod.close()
        broker.stop()


# ------------------------------------------------- three-transport parity

def _net(seed=12345, lr=0.1):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(lr).updater("sgd")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(n_batches=8, batch=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        x = rng.normal(size=(batch, 4)).astype(np.float32)
        labels = (x[:, 0] + x[:, 1] > 0).astype(int)
        y = np.zeros((batch, 3), np.float32)
        y[np.arange(batch), labels] = 1
        out.append(DataSet(x, y))
    return out


def _leaves(net):
    import jax
    return [np.array(x) for x in jax.tree_util.tree_leaves(net.params_list)]


def _flushes_in_order(push_delta, workers):
    """A ``ParameterServer.push_delta`` that holds each push until all
    ``workers`` have sent theirs, then applies them in the order of their
    sums: no worker sees another's push while it trains, and which lands
    first (at staleness 0) is fixed by what was pushed, not by a clock.
    (A sum, not the bytes, so that engines whose deltas differ in their
    last bits order them alike.)"""
    turn = threading.Condition()
    sent, applied = [], []

    def ordered(server, delta, base_version, **kw):
        key = float(np.sum(delta, dtype=np.float64))
        with turn:
            sent.append(key)
            turn.notify_all()
            assert turn.wait_for(lambda: len(sent) >= workers, timeout=60), (
                "a worker never flushed")
            assert turn.wait_for(
                lambda: sorted(sent).index(key) == len(applied), timeout=60)
            res = push_delta(server, delta, base_version, **kw)
            applied.append(key)
            turn.notify_all()
            return res
    return ordered


@needs_shm
@pytest.mark.slow
def test_fit_parity_inproc_tcp_shm_bitwise(monkeypatch):
    """2-worker fits over tcp and shm produce bitwise-identical parameters
    when the push schedule is deterministic (one flush push per worker,
    applied in an order ``_flushes_in_order`` fixes, none while a worker
    trains) — the transports move bytes, they don't do arithmetic. The
    threaded inproc engine runs another program on another device count,
    so it anchors within tolerance rather than bitwise. The shm run also
    leaves zero segments behind."""
    data = _batches()
    before = _shm_names()
    push_delta = ParameterServer.push_delta
    results = {}
    for kind in ("inproc", "tcp", "shm"):
        monkeypatch.setattr(ParameterServer, "push_delta",
                            _flushes_in_order(push_delta, 2))
        net = _net()
        wrapper = (ParameterServerParallelWrapper.builder(net)
                   .workers(2).push_frequency(100).transport(kind).build())
        wrapper.fit(ListDataSetIterator(data))
        assert sum(s["steps"] for s in wrapper.worker_stats) == len(data)
        assert [s["rebased"] for s in wrapper.worker_stats] == [0, 0]
        results[kind] = _leaves(net)
    for a, b in zip(results["tcp"], results["shm"]):
        np.testing.assert_array_equal(a, b, err_msg="shm diverged from tcp")
    for a, b in zip(results["inproc"], results["tcp"]):
        np.testing.assert_allclose(
            a, b, atol=5e-2, err_msg="tcp drifted from the inproc anchor")
    assert not (_shm_names() - before), "shm fit leaked segments"
