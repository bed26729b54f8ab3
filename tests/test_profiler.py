"""Trace-attribution engine tests: the stdlib XPlane parser against the
committed golden fixture (top-op ordering, category split closure,
truncation -> error record), the TraceSession single-owner lock +
persistent index, the anomaly trigger (fake clock: fires
once, cool-down re-arm, disabled off), span flight-recorder events, the
/train/profiles endpoints, and an end-to-end CPU trace capture through a
real fit() via ProfilerListener."""
import json
import os
import urllib.parse
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (FlightRecorder, MetricsRegistry,
                                              span)
from deeplearning4j_tpu.observability import profiler as prof_mod
from deeplearning4j_tpu.observability import xplane
from deeplearning4j_tpu.observability.names import (PROFILE_CAPTURES_TOTAL,
                                                    PROFILE_COLLISIONS_TOTAL)
from deeplearning4j_tpu.observability.profiler import (StepAnomalyWatcher,
                                                       TraceSession,
                                                       note_dispatch,
                                                       set_global_trace_session,
                                                       uninstall_anomaly_watcher)
from deeplearning4j_tpu.optimize.listeners import ProfilerListener
from deeplearning4j_tpu.ui import UIServer

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "xplane_golden.pb")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_net():
    conf = (NeuralNetConfiguration.builder()
            .seed(0).learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_in=8, n_out=3, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _xy(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.zeros((n, 3), np.float32)
    y[np.arange(n), rng.integers(0, 3, n)] = 1
    return x, y


def _session(tmp_path, **kw):
    """Private TraceSession: its own registry + recorder, index under tmp."""
    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=64)
    return TraceSession(base_dir=str(tmp_path / "profiles"), registry=reg,
                        recorder=rec, **kw), reg, rec


# ------------------------------------------------------------ XPlane parser

def test_golden_top_op_ordering_and_plane_selection():
    s = xplane.summarize(GOLDEN)
    assert "error" not in s
    # device plane preferred; host plane excluded from the op summary
    assert s["summarized_planes"] == ["/device:TPU:0"]
    assert s["planes"] == ["/device:TPU:0", "/host:CPU"]
    ops = [o["op"].split(" ")[0] for o in s["top_ops"]]
    assert ops == ["%convolution.42", "%dot.3", "%convert_reduce_fusion.7",
                   "%multiply_add_fusion.9", "%all-reduce.1", "%copy.4"]
    assert [o["pct"] for o in s["top_ops"]] == [40.0, 30.0, 20.0, 5.0,
                                                3.0, 2.0]
    # the while wrapper (99ms) and the XLA Modules container line were
    # excluded: counted total is exactly the six real ops
    assert s["total_device_ns"] == 100_000


def test_golden_category_split_sums_to_total():
    s = xplane.summarize(GOLDEN)
    assert s["categories_pct"] == {
        "conv": 40.0, "matmul/custom": 30.0, "fusion:reduce": 20.0,
        "fusion:compute": 5.0, "collective": 3.0, "datamovement": 2.0}
    assert sum(s["categories_pct"].values()) == pytest.approx(100.0, abs=0.1)


def test_golden_fn_share_and_bookkeeping_filter():
    s = xplane.summarize(GOLDEN)
    # host pjit spans -> per-fn share; the $profiler bookkeeping event
    # (4.4s, bigger than everything) is filtered, not attributed
    assert s["fn_pct"] == {"multistep": 70.0, "train_step": 30.0}
    assert not any("start_trace" in o["op"] for o in s["top_ops"])


def test_generator_matches_committed_fixture():
    """The committed binary is exactly what the generator emits — edit the
    generator, rerun it, and commit both or this fails."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "make_xplane_golden",
        os.path.join(os.path.dirname(__file__), "golden",
                     "make_xplane_golden.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(GOLDEN, "rb") as f:
        assert mod.build() == f.read()


def test_truncated_and_malformed_proto_error_record(tmp_path):
    with open(GOLDEN, "rb") as f:
        data = f.read()
    trunc = tmp_path / "t" / "host.xplane.pb"
    trunc.parent.mkdir()
    trunc.write_bytes(data[:len(data) // 2])
    s = xplane.summarize(str(tmp_path / "t"))
    assert "error" in s and "top_ops" not in s  # record, not a crash
    trunc.write_bytes(b"\x0f\xff\xff\xff")  # wire type 7: malformed
    assert "error" in xplane.summarize(str(tmp_path / "t"))
    with pytest.raises(xplane.XPlaneParseError):
        xplane.parse_planes(data[:len(data) // 2])


def test_empty_device_planes_fall_back_to_host_planes(tmp_path, monkeypatch):
    """A process that has described a TPU it does not run on (an AOT compile
    for a topology, tests/test_tpu_aot_compile.py) finds that device's
    planes in its later profiles, without an event: they are not the device."""
    trace = tmp_path / "t" / "host.xplane.pb"
    trace.parent.mkdir()
    trace.write_bytes(b"")
    planes = [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": []}]},
        {"name": "/host:CPU", "lines": [{"name": "tf_XLACpuClient", "events": [
            ("%dot.1 = f32[8]{0} dot(f32[8]{0} %a, f32[8]{0} %b)", 5000)]}]}]
    monkeypatch.setattr(xplane, "parse_planes", lambda data: planes)
    s = xplane.summarize(str(tmp_path / "t"))
    assert s["summarized_planes"] == ["/device:TPU:0", "/host:CPU"]
    assert s["categories_pct"] == {"matmul/custom": 100.0}


def test_summarize_empty_dir_error(tmp_path):
    s = xplane.summarize(str(tmp_path))
    assert "error" in s and "no xplane.pb" in s["error"]


# ------------------------------------------------------------- TraceSession

def test_trace_session_lock_collision_and_index(tmp_path, caplog):
    session, reg, rec = _session(tmp_path)
    logdir = session.start("manual")
    try:
        assert logdir is not None and os.path.isdir(logdir)
        assert session.active == "manual"
        # second owner: warning + no-op + collision counter, never a raise
        with caplog.at_level("WARNING"):
            assert session.start("listener") is None
        assert "already active" in caplog.text
        assert reg.counter(PROFILE_COLLISIONS_TOTAL, "").labels(
            trigger="listener").value == 1
    finally:
        session.stop(summarize=False)
    assert session.active is None
    assert reg.counter(PROFILE_CAPTURES_TOTAL, "").labels(
        trigger="manual").value == 1
    kinds = [e["kind"] for e in rec.snapshot()]
    assert "profile_start" in kinds and "profile_capture" in kinds
    # persistent index: a NEW session over the same base_dir sees the capture
    fresh = TraceSession(base_dir=session.base_dir,
                         registry=MetricsRegistry(), recorder=rec)
    entries = fresh.index_entries()
    assert len(entries) == 1
    assert entries[0]["logdir"] == logdir
    assert entries[0]["trigger"] == "manual"


def test_trace_session_capture_contextmanager_busy(tmp_path):
    session, reg, _ = _session(tmp_path)
    with session.capture("outer") as outer:
        assert outer is not None
        with session.capture("inner") as inner:
            assert inner is None  # busy: yields None, skips the stop
        assert session.active == "outer"  # inner ctx did not stop the outer
    assert session.active is None


def test_trace_session_stop_without_start_is_noop(tmp_path):
    session, _, _ = _session(tmp_path)
    assert session.stop() is None


# ---------------------------------------------------------- anomaly trigger

class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


class _FakeSession:
    """Duck-typed TraceSession: counts starts/stops, no real profiler."""

    def __init__(self):
        self.starts = []
        self.stops = 0

    def start(self, trigger, logdir=None):
        self.starts.append(trigger)
        return f"/fake/{len(self.starts)}"

    def stop(self, summarize=True):
        self.stops += 1
        return {}

    def _rec(self):
        return None


def test_anomaly_fires_once_and_rearms_after_cooldown():
    clock = _FakeClock()
    fake = _FakeSession()
    w = StepAnomalyWatcher(session=fake, k=3.0, min_samples=4,
                           cooldown_s=100.0, capture_dispatches=2,
                           clock=clock)
    for _ in range(4):
        w.observe(0.01)
    w.observe(0.5)  # > 3 x p50: fires
    assert fake.starts == ["anomaly"] and w.fired == 1
    # the next two dispatches run under the trace, then it closes
    w.observe(0.01)
    assert fake.stops == 0
    w.observe(0.01)
    assert fake.stops == 1
    # inside the cool-down: another slow step does NOT re-fire
    w.observe(0.6)
    assert w.fired == 1 and len(fake.starts) == 1
    # past the cool-down: re-arms
    clock.t += 101.0
    w.observe(0.6)
    assert w.fired == 2 and fake.starts == ["anomaly", "anomaly"]


def test_anomaly_quiet_below_threshold_and_never_raises():
    fake = _FakeSession()
    w = StepAnomalyWatcher(session=fake, k=3.0, min_samples=4,
                           cooldown_s=100.0, clock=_FakeClock())
    for _ in range(50):
        w.observe(0.01)
    w.observe(0.029)  # 2.9x p50: below k
    assert fake.starts == [] and w.fired == 0
    w.observe(float("nan"))  # pathological input must not raise
    w.observe("not-a-number")


def test_anomaly_capture_counts_in_registry(tmp_path):
    """Acceptance pin: an injected slow step captures a REAL trace exactly
    once, asserted via dl4j_profile_captures_total{trigger="anomaly"}."""
    session, reg, _ = _session(tmp_path)
    clock = _FakeClock()
    w = StepAnomalyWatcher(session=session, k=3.0, min_samples=4,
                           cooldown_s=3600.0, capture_dispatches=1,
                           clock=clock)
    for _ in range(4):
        w.observe(0.01)
    w.observe(0.5)   # fires: real jax.profiler trace starts
    w.observe(0.01)  # closes the window -> stop + summarize + index
    w.observe(0.5)   # inside cool-down: must not fire again
    assert w.fired == 1
    assert reg.counter(PROFILE_CAPTURES_TOTAL, "").labels(
        trigger="anomaly").value == 1
    entries = session.index_entries()
    assert len(entries) == 1 and entries[0]["trigger"] == "anomaly"


def test_note_dispatch_disabled_off(monkeypatch):
    monkeypatch.delenv(prof_mod.TRIGGER_ENV, raising=False)
    uninstall_anomaly_watcher()
    try:
        note_dispatch(99.0)  # resolves to "off" once...
        assert prof_mod._WATCHER is None and prof_mod._WATCHER_RESOLVED
        note_dispatch(99.0)  # ...then short-circuits forever
        assert prof_mod._WATCHER is None
    finally:
        uninstall_anomaly_watcher()


def test_note_dispatch_env_resolution(monkeypatch):
    monkeypatch.setenv(prof_mod.TRIGGER_ENV, "anomaly")
    monkeypatch.setenv(prof_mod.ANOMALY_K_ENV, "5.5")
    uninstall_anomaly_watcher()
    try:
        note_dispatch(0.01)
        w = prof_mod._WATCHER
        assert isinstance(w, StepAnomalyWatcher) and w.k == 5.5
        assert len(w._times) == 1
    finally:
        uninstall_anomaly_watcher()


def test_fit_loop_feeds_note_dispatch():
    """The multilayer dispatch sites call note_dispatch: an installed
    watcher sees one sample per fit dispatch."""
    fake = _FakeSession()
    w = StepAnomalyWatcher(session=fake, k=1e9, min_samples=2,
                           cooldown_s=1.0, clock=_FakeClock())
    prof_mod.install_anomaly_watcher(w)
    try:
        net = _small_net()
        x, y = _xy()
        net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 5))
        # the multistep engine may coalesce all 5 batches into one dispatch;
        # at least one sample must land either way
        assert len(w._times) >= 1
        assert fake.starts == []  # k=1e9: healthy run never triggers
    finally:
        uninstall_anomaly_watcher()


# ----------------------------------------------- e2e capture through fit()

def test_e2e_cpu_fit_capture_via_profiler_listener(tmp_path):
    """Acceptance pin: a TraceSession capture through a real CPU fit()
    produces a trace dir + attribution JSON whose category shares sum to
    ~100%, with no direct jax.profiler calls in the listener."""
    prev = set_global_trace_session(
        TraceSession(base_dir=str(tmp_path / "profiles")))
    try:
        listener = ProfilerListener(str(tmp_path / "trace"),
                                    start_iteration=2, num_iterations=2)
        net = _small_net()
        net.listeners.append(listener)
        x, y = _xy()
        net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 8))
        assert len(listener.windows) == 1
        logdir = listener.windows[0]
        assert xplane.find_trace(logdir) is not None  # real .xplane.pb
        summary = listener.summaries[0]
        assert summary is not None and "error" not in summary, summary
        shares = summary["categories_pct"]
        assert shares and sum(shares.values()) == pytest.approx(100.0,
                                                                abs=1.0)
        # ...and the attribution JSON sits next to the trace
        with open(os.path.join(logdir, prof_mod.ATTRIBUTION_FILE)) as f:
            assert json.load(f)["categories_pct"] == shares
        # the capture is in the persistent index
        entries = prof_mod.global_trace_session().index_entries()
        assert any(e["logdir"] == logdir and e["trigger"] == "listener"
                   for e in entries)
    finally:
        set_global_trace_session(prev)


def test_no_direct_profiler_calls_outside_engine():
    """profile_flagship.py and ProfilerListener must not drive
    jax.profiler.start_trace/stop_trace themselves — all capture flows
    through the single locked TraceSession."""
    for rel in ("scripts/profile_flagship.py",
                "deeplearning4j_tpu/optimize/listeners.py"):
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        assert "jax.profiler.start_trace" not in src, rel
        assert "jax.profiler.stop_trace" not in src, rel
        assert "profiler.start_trace" not in src, rel


# ------------------------------------------------------------- span events

def test_span_emits_flight_recorder_events():
    rec = FlightRecorder(capacity=16)
    reg = MetricsRegistry()
    with span("epoch/0/fwd", metric_name="epoch", registry=reg,
              recorder=rec):
        pass
    kinds = [(e["kind"], e["name"]) for e in rec.snapshot()]
    assert kinds == [("span_enter", "epoch/0/fwd"),
                     ("span_exit", "epoch/0/fwd")]
    exit_ev = rec.snapshot()[-1]
    assert exit_ev["dur_s"] >= 0.0


def test_span_exit_recorded_on_exception():
    rec = FlightRecorder(capacity=16)
    with pytest.raises(RuntimeError):
        with span("doomed", registry=MetricsRegistry(), recorder=rec):
            raise RuntimeError("boom")
    assert [e["kind"] for e in rec.snapshot()] == ["span_enter", "span_exit"]


# ------------------------------------------------------------ UI endpoints

def test_train_profiles_endpoints(tmp_path):
    session = TraceSession(base_dir=str(tmp_path / "profiles"))
    prev = set_global_trace_session(session)
    server = UIServer(port=0)
    try:
        logdir = session.start("manual")
        assert logdir is not None
        session.stop()  # summarize=True writes attribution.json (even as
        #                 an error record when the trace is host-only/empty)
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(base + "/train/profiles") as r:
            assert r.status == 200
            idx = json.loads(r.read())
        assert idx["active"] is None
        assert len(idx["profiles"]) == 1
        assert idx["profiles"][0]["logdir"] == logdir
        q = urllib.parse.quote(logdir, safe="")
        with urllib.request.urlopen(
                base + f"/train/profiles/summary?trace={q}") as r:
            assert r.status == 200
            summary = json.loads(r.read())
        assert "categories_pct" in summary or "error" in summary
        # unknown trace: the index is the allow-list
        with urllib.request.urlopen(
                base + "/train/profiles/summary?trace=%2Fetc%2Fpasswd") as r:
            assert json.loads(r.read())["error"] == \
                "trace not in the profile index"
    finally:
        server.stop()
        set_global_trace_session(prev)


# -------------------------------------------------------- bench integration

@pytest.mark.slow
def test_bench_xplane_attribution_end_to_end(tmp_path):
    """bench.py --xplane-attribution attaches the category split (or a
    graceful profile_error) to the record without touching the headline."""
    import subprocess
    import sys

    import bench

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DL4J_PROFILE_DIR=str(tmp_path / "profiles"))
    env.pop("DL4J_PROFILE_TRIGGER", None)
    cmd = [sys.executable, os.path.join(os.path.dirname(bench.__file__),
                                        "bench.py"),
           "--model", "lenet", "--batch", "8", "--iters", "2",
           "--ksteps", "1", "--xplane-attribution",
           "--attempts", "1", "--attempt-timeout", "180"]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=200,
                          env=env)
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "error" not in rec, rec
    assert rec["value"] > 0
    detail = rec["detail"]
    if "profile_error" in detail:  # graceful degradation is in-contract
        assert isinstance(detail["profile_error"], str)
    else:
        att = detail["xplane_attribution"]
        assert sum(att["categories_pct"].values()) == pytest.approx(
            100.0, abs=1.0)
        assert detail["profile_trace"].startswith(str(tmp_path))
