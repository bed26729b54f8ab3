"""Failure-diagnostics tests: flight-recorder ring semantics and bundle
completeness, the fused training-health monitor through real fits (NaN
injection), the step watchdog (stall fires once, healthy run silent),
signal/exception dump egress, the MFU gauge, the /train/health endpoints,
and the shared invalid-score predicate."""
import json
import os
import signal
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (
    FlightRecorder, HealthMonitor, MetricsRegistry, NanAlertListener,
    StepWatchdog, TrainingDivergedError, global_recorder, health_terms,
    install_signal_handlers, is_invalid_score, uninstall_signal_handlers,
)
from deeplearning4j_tpu.observability import flight_recorder as fr_mod
from deeplearning4j_tpu.observability.flight_recorder import dump_on_unhandled
from deeplearning4j_tpu.ui import UIServer


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


# ------------------------------------------------------------- ring buffer

def test_ring_buffer_bounds_and_eviction():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("step", it=i)
    assert len(rec) == 4
    assert rec.dropped == 6
    # oldest evicted, newest kept, order preserved
    assert [e["it"] for e in rec.snapshot()] == [6, 7, 8, 9]
    assert all(e["kind"] == "step" and e["ts"] > 0 for e in rec.snapshot())
    rec.clear()
    assert len(rec) == 0 and rec.dropped == 0


def test_ring_buffer_thread_safety():
    rec = FlightRecorder(capacity=64)
    n_threads, n_each = 8, 500

    def writer(tid):
        for i in range(n_each):
            rec.record("step", tid=tid, i=i)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec) == 64
    assert rec.dropped == n_threads * n_each - 64
    assert all(e["kind"] == "step" for e in rec.snapshot())


def test_kill_switch():
    rec = FlightRecorder(capacity=8)
    rec.set_enabled(False)
    rec.record("step", it=0)
    assert len(rec) == 0 and not rec.enabled
    rec.set_enabled(True)
    rec.record("step", it=1)
    assert len(rec) == 1


# ------------------------------------------------------------------ bundles

BUNDLE_FILES = ("manifest.json", "events.jsonl", "metrics.json",
                "environment.json", "threads.txt", "cost_analysis.json")


def _assert_complete_bundle(path, expect_extra=False):
    for fname in BUNDLE_FILES + (("extra.json",) if expect_extra else ()):
        assert os.path.isfile(os.path.join(path, fname)), f"missing {fname}"
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    assert set(manifest["files"]) >= set(BUNDLE_FILES)
    for fname in ("metrics.json", "environment.json", "cost_analysis.json"):
        with open(os.path.join(path, fname)) as f:
            json.load(f)
    with open(os.path.join(path, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    with open(os.path.join(path, "threads.txt")) as f:
        threads_txt = f.read()
    assert "--- thread" in threads_txt
    return manifest, events


def test_dump_bundle_completeness(tmp_path):
    reg = MetricsRegistry()
    reg.counter("dl4j_probe_total", "probe").labels(k="x").inc(3)
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path), registry=reg)
    rec.record("step", it=1, dispatch_s=0.01)
    rec.record("health_alarm", why="nonfinite-grads", iteration=1)
    path = rec.dump(reason="manual test!", extra={"note": "hello"})
    assert path is not None and path.startswith(str(tmp_path))
    manifest, events = _assert_complete_bundle(path, expect_extra=True)
    assert manifest["reason"] == "manual test!"
    assert manifest["events"] == 2 and manifest["events_dropped"] == 0
    assert [e["kind"] for e in events] == ["step", "health_alarm"]
    with open(os.path.join(path, "environment.json")) as f:
        env = json.load(f)
    assert env["pid"] == os.getpid() and "python" in env
    with open(os.path.join(path, "metrics.json")) as f:
        assert "dl4j_probe_total" in json.load(f)
    with open(os.path.join(path, "extra.json")) as f:
        assert json.load(f) == {"note": "hello"}
    # dump bumps its own counter in the bundle's registry
    snap = reg.snapshot()["dl4j_flight_dumps_total"]
    assert snap["series"][0]["value"] == 1.0

    # no directory configured -> automatic dump sites are free no-ops
    assert FlightRecorder(capacity=4).dump(reason="nowhere") is None


def test_bundle_keeps_a_resolution_as_one_compile_record(tmp_path):
    """The crash bundle's readers find a program's resolution where they
    found the ``compile`` event: one record of that kind, with the event's
    fields, now the span ``compile.resolve`` with its interval."""
    from deeplearning4j_tpu.nn import compile_cache as cc
    from deeplearning4j_tpu.observability.compile_tracker import (
        CompileTracker,
    )

    rec = fr_mod.global_recorder()
    rec.clear()
    program = cc.CachedProgram("bundle_probe", jax.jit(lambda a: a - 1),
                               tracker=CompileTracker())
    program(np.ones((3,), np.float32))
    path = rec.dump(dir=str(tmp_path), reason="resolution")
    _, events = _assert_complete_bundle(path, expect_extra=False)
    (compiled,) = [e for e in events if e["kind"] == "compile"]
    assert compiled["name"] == "compile.resolve"
    assert compiled["fn"] == "bundle_probe" and compiled["cache_hit"] is False
    assert {"step", "wall_s", "cache_key", "shapes", "policy"} <= set(compiled)
    assert compiled["t0_ns"] <= compiled["t1_ns"]
    assert compiled["ts"] == pytest.approx(compiled["t1_ns"] / 1e9)
    rec.clear()


def test_list_bundles_newest_first(tmp_path):
    rec = FlightRecorder(capacity=4, dump_dir=str(tmp_path),
                         registry=MetricsRegistry())
    rec.dump(reason="first")
    rec.dump(reason="second")
    bundles = rec.list_bundles()
    assert len(bundles) == 2
    assert bundles[0]["reason"] == "second"  # newest first (seq in dir name)
    assert all(os.path.isdir(b["path"]) for b in bundles)


# --------------------------------------------------------- exception egress

def test_exception_escape_dumps_once(tmp_path, monkeypatch):
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                         registry=MetricsRegistry())
    monkeypatch.setattr(fr_mod, "_GLOBAL", rec)

    @dump_on_unhandled("outer.fit")
    def outer():
        return inner()

    @dump_on_unhandled("inner.fit_iterator")
    def inner():
        raise ValueError("boom")

    with pytest.raises(ValueError, match="boom"):
        outer()
    # both frames record an event, but the exception produces ONE bundle
    kinds = [(e["kind"], e.get("site")) for e in rec.snapshot()]
    assert ("exception", "inner.fit_iterator") in kinds
    assert ("exception", "outer.fit") in kinds
    bundles = rec.list_bundles()
    assert len(bundles) == 1
    assert bundles[0]["reason"] == "exception-inner.fit_iterator"
    _assert_complete_bundle(bundles[0]["path"])


def test_signal_handler_dumps(tmp_path, monkeypatch):
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                         registry=MetricsRegistry())
    previous = install_signal_handlers(rec, signals=(signal.SIGUSR1,))
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        # the interpreter runs the handler at the next bytecode boundary
        deadline = time.time() + 5.0
        while len(rec) == 0 and time.time() < deadline:
            time.sleep(0.01)
        events = rec.snapshot()
        assert any(e["kind"] == "signal" and e["name"] == "SIGUSR1"
                   for e in events)
        bundles = rec.list_bundles()
        assert len(bundles) == 1
        assert bundles[0]["reason"] == "signal-SIGUSR1"
    finally:
        uninstall_signal_handlers(previous)
    assert signal.getsignal(signal.SIGUSR1) == previous[signal.SIGUSR1]


# ------------------------------------------------------------ health monitor

def test_health_terms_values():
    import jax.numpy as jnp

    grads = [jnp.ones((2, 2)), jnp.zeros(3)]
    params = [jnp.zeros((2, 2)), jnp.zeros(3)]
    new_params = [jnp.full((2, 2), 0.5), jnp.zeros(3)]
    packed = np.asarray(jax.jit(health_terms)(grads, params, new_params,
                                              jnp.float32(1.25)))
    grad_norm, upd_norm, nonfinite, loss = [float(v) for v in packed]
    assert grad_norm == pytest.approx(2.0)      # sqrt(4 * 1)
    assert upd_norm == pytest.approx(1.0)       # sqrt(4 * 0.25)
    assert nonfinite == 0.0
    assert loss == pytest.approx(1.25)

    grads[0] = grads[0].at[0, 0].set(jnp.nan)
    packed = np.asarray(jax.jit(health_terms)(grads, params, new_params,
                                              jnp.float32(1.25)))
    assert packed[2] == 1.0  # one non-finite grad element counted


def test_health_cadence_logic():
    hm = HealthMonitor(cadence=50)
    assert hm.due(0) and hm.due(100) and not hm.due(49)
    assert hm.due_index(0, 8) == 0
    assert hm.due_index(48, 8) == 2   # 50 falls in [48, 56)
    assert hm.due_index(51, 8) is None
    assert hm.due_range(96, 8) and not hm.due_range(101, 8)
    assert HealthMonitor(cadence=0).due_index(0, 8) is None


def test_healthy_fit_checks_without_alarm(tmp_path):
    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=32, dump_dir=str(tmp_path), registry=reg)
    net = _small_net()
    hm = HealthMonitor(cadence=4, recorder=rec, registry=reg).attach(net)
    net.set_listeners(NanAlertListener(raise_on_alarm=True))
    x, y = _xy()
    net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 12))
    assert hm.checks > 0
    assert hm.alarms == 0 and hm.alarm is None
    assert hm.last is not None and np.isfinite(hm.last["loss"])
    assert rec.list_bundles() == []  # healthy run writes nothing
    snap = reg.snapshot()
    assert snap["dl4j_health_checks_total"]["series"][0]["value"] == hm.checks
    assert "dl4j_health_grad_norm" in snap
    assert "dl4j_health_loss_ema" in snap


def test_nan_injection_alarms_and_dumps(tmp_path):
    """Forced-NaN acceptance: a NaN in the batch reaches the grads, the
    fused health check catches it on the device, the listener raises, and a
    complete bundle lands on disk."""
    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=32, dump_dir=str(tmp_path), registry=reg)
    net = _small_net()
    hm = HealthMonitor(cadence=1, recorder=rec, registry=reg).attach(net)
    net.set_listeners(NanAlertListener(raise_on_alarm=True))
    x, y = _xy()
    x[0, 0] = np.nan
    with pytest.raises(TrainingDivergedError, match="nonfinite-grads"):
        net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 4))
    assert hm.alarms >= 1
    assert hm.alarm["why"] == "nonfinite-grads"
    assert hm.alarm["nonfinite_grads"] > 0
    snap = reg.snapshot()["dl4j_health_alarms_total"]["series"]
    assert any(dict(s["labels"])["why"] == "nonfinite-grads" for s in snap)
    bundles = rec.list_bundles()
    assert any(b["reason"] == "health-alarm-nonfinite-grads"
               for b in bundles)
    path = [b for b in bundles
            if b["reason"] == "health-alarm-nonfinite-grads"][0]["path"]
    _, events = _assert_complete_bundle(path)
    assert any(e["kind"] == "health_alarm" for e in events)


def test_nan_alert_listener_score_fallback(tmp_path):
    """Without a monitor the listener degrades to the reference
    NanScoreWatcher idiom: it syncs score_value and alarms on NaN."""
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                         registry=MetricsRegistry())

    class FakeModel:
        score_value = float("nan")

    listener = NanAlertListener(raise_on_alarm=True, recorder=rec)
    with pytest.raises(TrainingDivergedError, match="invalid score"):
        listener.iteration_done(FakeModel(), 1)
    assert any(b["reason"] == "health-alarm-invalid-score"
               for b in rec.list_bundles())


def test_invalid_score_predicate_shared():
    from deeplearning4j_tpu.earlystopping.termination import (
        InvalidScoreIterationTerminationCondition,
    )

    cond = InvalidScoreIterationTerminationCondition()
    for bad in (float("nan"), float("inf"), float("-inf")):
        assert cond.terminate(bad) and is_invalid_score(bad)
    for ok in (0.0, -3.5, 1e30):
        assert not cond.terminate(ok) and not is_invalid_score(ok)
    assert is_invalid_score(None) and is_invalid_score("not-a-number")


# ---------------------------------------------------------------- watchdog

def test_watchdog_fires_once_on_stall(tmp_path, caplog):
    import logging

    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path), registry=reg)
    wd = StepWatchdog(threshold_s=0.15, poll_s=0.03, recorder=rec,
                      registry=reg)
    with caplog.at_level(logging.ERROR,
                         logger="deeplearning4j_tpu.observability.watchdog"):
        with wd:
            wd.heartbeat(step=7)
            deadline = time.time() + 5.0
            while wd.stalls == 0 and time.time() < deadline:
                time.sleep(0.02)
            # fired once; no further alarms without a new heartbeat
            time.sleep(0.3)
    assert wd.stalls == 1
    assert reg.snapshot()["dl4j_watchdog_stalls_total"]["series"][0][
        "value"] == 1.0
    assert any(e["kind"] == "watchdog_stall" and e["step"] == 7
               for e in rec.snapshot())
    bundles = rec.list_bundles()
    assert len(bundles) == 1 and bundles[0]["reason"] == "watchdog-stall"
    _assert_complete_bundle(bundles[0]["path"])
    # the hang site is in the training log even if the process dies later
    assert any("all-thread stacks follow" in r.getMessage()
               for r in caplog.records)


def test_watchdog_silent_on_healthy_run(tmp_path):
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path),
                         registry=MetricsRegistry())
    wd = StepWatchdog(threshold_s=0.3, poll_s=0.03, recorder=rec,
                      registry=MetricsRegistry())
    with wd:
        for step in range(10):
            wd.heartbeat(step=step)
            time.sleep(0.05)  # each beat well inside the threshold
    assert wd.stalls == 0
    assert rec.list_bundles() == []


def test_watchdog_unarmed_until_first_beat(tmp_path):
    wd = StepWatchdog(threshold_s=0.05, poll_s=0.02,
                      recorder=FlightRecorder(capacity=4),
                      registry=MetricsRegistry())
    with wd:
        time.sleep(0.2)  # installed but idle: never fires
    assert wd.stalls == 0


def test_global_watchdog_beat_hook():
    from deeplearning4j_tpu.observability import (
        beat, global_watchdog, install_watchdog, uninstall_watchdog,
    )

    assert global_watchdog() is None
    beat(3)  # no-op without an installed watchdog
    wd = install_watchdog(threshold_s=60.0, poll_s=0.05,
                          recorder=FlightRecorder(capacity=4),
                          registry=MetricsRegistry())
    try:
        assert global_watchdog() is wd
        beat(42)
        assert wd._last_step == 42
    finally:
        uninstall_watchdog()
    assert global_watchdog() is None


# -------------------------------------------------------------------- MFU

def test_mfu_gauge_with_peak_override(monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.observability.compile_tracker import (
        CompileTracker,
    )

    monkeypatch.setenv("DL4J_PEAK_FLOPS", "1e12")
    reg = MetricsRegistry()
    tracker = CompileTracker(registry=reg)
    fn = tracker.wrap("mfu_probe", jax.jit(lambda a: a @ a))
    x = jnp.ones((64, 64), jnp.float32)
    fn(x).block_until_ready()
    flops = tracker.flops_for("mfu_probe")
    assert flops and flops > 0
    tracker.note_step(fn="mfu_probe")  # first sample only records the clock
    fn(x).block_until_ready()
    tracker.note_step(fn="mfu_probe")
    series = reg.snapshot()["dl4j_step_mfu"]["series"]
    by_fn = {dict(s["labels"])["fn"]: s["value"] for s in series}
    assert 0.0 < by_fn["mfu_probe"] <= 1.0


def test_mfu_silent_without_peak(monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.observability.compile_tracker import (
        CompileTracker,
    )

    monkeypatch.delenv("DL4J_PEAK_FLOPS", raising=False)
    reg = MetricsRegistry()
    tracker = CompileTracker(registry=reg)
    fn = tracker.wrap("mfu_cpu", jax.jit(lambda a: a + 1))
    x = jnp.ones((8,), jnp.float32)
    fn(x).block_until_ready()
    tracker.note_step(fn="mfu_cpu")
    fn(x).block_until_ready()
    tracker.note_step(fn="mfu_cpu")
    # CPU backend, no override: the gauge deliberately stays unset
    assert "dl4j_step_mfu" not in reg.snapshot()


def test_peak_is_keyed_by_device_kind_and_unknown_is_an_error():
    from types import SimpleNamespace

    from deeplearning4j_tpu.observability.compile_tracker import (
        peak_flops_for,
    )

    assert peak_flops_for(SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite")) == 197e12
    assert peak_flops_for(SimpleNamespace(
        platform="cpu", device_kind="cpu")) is None
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops_for(SimpleNamespace(platform="tpu", device_kind="TPU v9"))


# ---------------------------------------------------------------- UI routes

def test_train_health_endpoints(tmp_path, monkeypatch):
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path))
    monkeypatch.setattr(fr_mod, "_GLOBAL", rec)
    rec.record("step", it=0)
    rec.dump(reason="endpoint test")

    server = UIServer(port=0)
    try:
        base = f"http://127.0.0.1:{server.port}"
        with urllib.request.urlopen(base + "/train/health") as r:
            assert r.status == 200
            health = json.loads(r.read())
        with urllib.request.urlopen(base + "/train/health/bundles") as r:
            assert r.status == 200
            bundles = json.loads(r.read())
    finally:
        server.stop()
    assert health["recorder"]["enabled"] is True
    assert health["recorder"]["events"] >= 1
    assert health["recorder"]["capacity"] == 16
    assert isinstance(health["metrics"], dict)
    assert len(bundles["bundles"]) == 1
    assert bundles["bundles"][0]["reason"] == "endpoint test"


# ------------------------------------------------------------ bench egress

def test_bench_failed_run_writes_bundle(tmp_path):
    """When every bench attempt times out, the parent exits non-zero and,
    given --flight-recorder-dir, writes a flight-recorder bundle carrying
    the env, the retry timeline, and the emitted error record."""
    import subprocess
    import sys

    import bench

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable,
           os.path.join(os.path.dirname(bench.__file__), "bench.py"),
           "--model", "lenet", "--batch", "8", "--iters", "1",
           "--attempts", "1", "--attempt-timeout", "0.01",
           "--flight-recorder-dir", str(tmp_path)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                          env=env)
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "timed out" in rec["error"]
    assert proc.returncode == 1  # no measurement is a failed run
    bundle = rec.get("flight_bundle")
    assert bundle and bundle.startswith(str(tmp_path))
    _assert_complete_bundle(bundle, expect_extra=True)
    with open(os.path.join(bundle, "extra.json")) as f:
        extra = json.load(f)
    assert extra["retry_timeline"][0]["outcome"] == "timeout"
    assert "record" in extra


# ----------------------------------------------------------- fit-path events

def test_fit_records_step_events():
    rec_global = global_recorder()
    before = len(rec_global)
    net = _small_net()
    x, y = _xy()
    net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 4))
    events = rec_global.snapshot()
    assert len(events) > before
    steps = [e for e in events if e["kind"] == "step"
             and "MultiLayerNetwork" in e.get("path", "")]
    assert steps, "fit loop recorded no step events"
    assert all("it" in e and "dispatch_s" in e for e in steps)
