"""Pins the XLA behaviors bench.py's MFU accounting depends on.

bench.py multiplies XLA's cost-analysis flop count by K for K-step scanned
dispatches because cost analysis counts a scan body ONCE, not trip-count
times. If an XLA upgrade changes that, this test fails and bench.py's
`_xla_flops` callers must drop their `* ksteps`.
"""
import jax
import jax.numpy as jnp
import numpy as np


def _cost_flops(jit_fn, *args) -> float:
    cost = jit_fn.lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float((cost or {}).get("flops", 0.0))


def test_cost_analysis_counts_scan_body_once():
    w = jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)),
                    jnp.float32)

    def multi(w, xs):
        def body(carry, x):
            return carry, jnp.sum(jnp.dot(x, w))

        _, outs = jax.lax.scan(body, 0.0, xs)
        return outs

    jit_multi = jax.jit(multi)
    costs = []
    for k in (1, 4):
        xs = jnp.ones((k, 32, 64), jnp.float32)
        costs.append(_cost_flops(jit_multi, w, xs))
    assert costs[0] > 0
    # body counted once: flops near-identical despite 4x the executed steps
    # (a couple of scalar loop-counter flops may differ; 4x would mean XLA
    # started scaling with trip count)
    assert costs[1] < costs[0] * 1.5, (
        "XLA cost analysis now scales scan flops with trip count; "
        "remove the `* ksteps` factors in bench.py::_xla_flops callers")


def test_timed_out_run_exits_nonzero_with_an_error_record():
    """A run that got no measurement is a failed run: one machine-readable
    error record, exit code 1, and no number from an older run in it."""
    import json
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
    # the child cannot even import JAX inside the attempt's time limit
    proc = subprocess.run(
        [sys.executable, bench_py, "--model", "lenet", "--attempts", "1",
         "--attempt-timeout", "0.2"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stderr[-400:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["value"] == 0.0 and "timed out" in rec["error"]
    assert "last_healthy" not in rec


def test_tile_sweep_isolates_failures_and_picks_best():
    """The flash tile sweep runs unattended in the auto-capture window: a
    failing tile shape must record an error string (not kill the bench), the
    best shape is the fastest timed one, and a shape that does not divide
    the sequence is left out."""
    import bench

    calls = []

    def fake_time_tiles(bq, bk):
        calls.append((bq, bk))
        if (bq, bk) == (512, 512):
            raise RuntimeError("VMEM OOM")
        return 0.001 * bq / bk  # fastest: 128x512

    out = bench._sweep_tiles(fake_time_tiles, seq=2048)
    assert out["best_tiles"] == "128x512"  # smallest bq/bk ratio timed
    assert out["best_tiles_ms"] == out["tile_sweep_ms"]["128x512"] == 0.25
    assert out["tile_sweep_ms"]["512x512"].startswith("error:")
    assert len(calls) == 6  # every shape visited despite the failure
    assert set(bench._sweep_tiles(fake_time_tiles, seq=1536)[
        "tile_sweep_ms"]) == {"128x512", "256x256", "512x512"}


def test_swept_tiles_reach_both_kernels():
    """``_flash_at_tiles`` hands its tiles to the forward and the backward
    kernels, and its value and gradients are the XLA math's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    seen = []
    real_fwd, real_bwd = pk._flash_forward, pk._flash_backward

    def fwd(*a, **kw):
        seen.append(("fwd", kw["blk_q"], kw["blk_k"]))
        return real_fwd(*a, interpret=True, **kw)

    def bwd(*a, **kw):
        seen.append(("bwd", kw["blk_q"], kw["blk_k"]))
        return real_bwd(*a, interpret=True, **kw)

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 256, 2, 16)), jnp.float32)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    want = jax.grad(loss(lambda q, k, v: pk._attention_xla(q, k, v, True)),
                    argnums=(0, 1, 2))(q, k, v)
    try:
        pk._flash_forward, pk._flash_backward = fwd, bwd
        got = jax.grad(loss(bench._flash_at_tiles(128, 64)),
                       argnums=(0, 1, 2))(q, k, v)
    finally:
        pk._flash_forward, pk._flash_backward = real_fwd, real_bwd
    assert ("fwd", 128, 64) in seen and ("bwd", 128, 64) in seen
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4, rtol=2e-4)


def test_reduction_dtype_config_resolution():
    """--reduction-dtype resolution and bench_log config matching: explicit
    flag wins; bf16-act defaults to bf16 statistics (round 6); every other
    mode defaults to f32; and rows logged BEFORE the round-6 default change
    are reinterpreted as f32 so an outage can never serve a wrong-reduction
    number as 'the same config'."""
    import bench

    assert bench._reduction_mode("bf16_act", None) == "bf16"
    assert bench._reduction_mode("bf16_act", "f32") == "f32"
    assert bench._reduction_mode("bf16", None) == "f32"
    assert bench._reduction_mode("f32", "bf16") == "bf16"

    # ts after the round-6 change: bare bf16-act rows mean bf16 statistics
    key = bench._config_key("--model resnet50 --bf16-act",
                            ts="2026-08-06T00:00:00Z")
    assert key["rdtype"] == "bf16"
    # ts before the change: the same args ran at-least-f32 statistics
    key = bench._config_key("--model resnet50 --bf16-act",
                            ts="2026-08-01T00:00:00Z")
    assert key["rdtype"] == "f32"
    # an explicit flag is authoritative regardless of age
    key = bench._config_key("--model resnet50 --bf16-act "
                            "--reduction-dtype f32",
                            ts="2026-08-06T00:00:00Z")
    assert key["rdtype"] == "f32"
    # the two reduction modes are DIFFERENT configs for outage matching
    a = bench._config_key("--model resnet50 --bf16-act")
    b = bench._config_key("--model resnet50 --bf16-act --reduction-dtype f32")
    assert a != b


def test_bench_reduction_dtype_flag_end_to_end(tmp_path):
    """bench.py --reduction-dtype runs the flagship recipe clean on CPU and
    stamps the resolved reduction mode into the record (the BASELINE.md
    provenance requirement: every number names its reduction policy)."""
    import json
    import os
    import subprocess
    import sys

    import bench

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(os.path.dirname(bench.__file__),
                                        "bench.py"),
           "--model", "lenet", "--batch", "8", "--iters", "2",
           "--ksteps", "1", "--bf16-act", "--reduction-dtype", "bf16",
           "--attempts", "1", "--attempt-timeout", "180"]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=200,
                          env=env)
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "error" not in rec, rec
    assert rec["value"] > 0
    assert rec["detail"]["dtype"] == "bf16_act"
    assert rec["detail"]["reduction_dtype"] == "bf16"


def test_telemetry_overhead_budget():
    """Telemetry (including the prefetch families AND the training-health
    monitor at its check cadence) must cost <=2% of a LeNet fit step.
    Budget-style rather than a wall-clock A/B (which flakes on shared CI
    hosts): measure the real per-step time of the instrumented loop —
    driven through fit_iterator with device prefetch ON and a HealthMonitor
    + NanAlertListener attached so the health metrics are in the measured
    window — microbenchmark the registry primitives it calls, bound the
    ops issued per step from registry deltas, and require
    ops_per_step * per_op_cost <= 2% of the step time."""
    import time

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.observability import (
        HealthMonitor, MetricsRegistry, NanAlertListener, TelemetryListener,
        global_registry,
    )

    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 784)).astype(np.float32)
    y = np.zeros((8, 10), np.float32)
    y[np.arange(8), rng.integers(0, 10, 8)] = 1
    ksteps = 2
    health_cadence = 4
    net = MultiLayerNetwork(lenet_mnist()).init()
    net.dispatch_ksteps = ksteps
    HealthMonitor(cadence=health_cadence, dump_on_alarm=False).attach(net)
    net.set_listeners(TelemetryListener(sync_every=1, hbm_every=1,
                                        worker_id="overhead_budget"),
                      NanAlertListener())
    # warmup: compile the fused step (both health variants) outside the
    # measured window
    net.fit_iterator(ListDataSetIterator([DataSet(x, y)] * 2 * ksteps))

    def _mutation_count(reg):
        # counter value == #incs (unit increments in the fit path),
        # histogram count == #observes; add every gauge series as one
        # set per step (upper bound: they are set at most once a step).
        # Quantity counters (*_bytes_total / *_seconds_total) increment by
        # measured amounts, not by 1 — their value is NOT an op count, so
        # they are excluded here and charged explicitly below. The health
        # gauges hold arbitrary floats (norms, EMA) rather than op counts,
        # so they too are excluded and charged explicitly per cadence.
        total = 0.0
        for name, fam in reg.snapshot().items():
            if name.endswith(("_bytes_total", "_seconds_total")):
                continue
            for s in fam["series"]:
                if fam["type"] == "gauge" and name.startswith("dl4j_health_"):
                    continue
                total += s["count"] if "count" in s else max(s["value"], 1.0)
        return total

    from deeplearning4j_tpu.observability.flight_recorder import (
        FlightRecorder, global_recorder,
    )

    global_recorder().clear()
    before = _mutation_count(global_registry())
    n_steps = 12
    data = [DataSet(x, y) for _ in range(n_steps)]
    t0 = time.perf_counter()
    net.fit_iterator(ListDataSetIterator(data))
    score = net.score_value
    float(score() if callable(score) else score)
    step_s = (time.perf_counter() - t0) / n_steps
    ops_per_step = (_mutation_count(global_registry()) - before) / n_steps
    # HBM gauges are 0.0 on CPU (memory_stats is None) so their sets are
    # invisible to the value delta — add them explicitly.
    ops_per_step += 2 * len(jax.local_devices()) + 2
    # DevicePrefetcher ops excluded or invisible above, charged per GROUP
    # (k steps): producer staging.inc + bytes.inc + depth.set, consumer
    # wait.inc + depth.set = 5 (the wait_series observe is a histogram
    # count, already in the delta).
    ops_per_step += 5 / ksteps
    # the spans the fit path wrote into the flight recorder's ring: seven a
    # group (input.pull/stack/cast/h2d, fit.wait/dispatch/listeners) and
    # from the call's third group on its fit.step_wait, each two clock reads
    # and one record_span; and one fit.call for the call. The warmed
    # program's dispatches resolve nothing, so no compile.* record comes
    ring = global_recorder().snapshot()
    assert not [e for e in ring if e.get("kind") == "compile"
                or str(e.get("name", "")).startswith("compile.")]
    spans_per_step = sum("t0_ns" in e for e in ring) / n_steps
    groups = n_steps // ksteps
    assert spans_per_step == (7 * groups + groups - 2 + 1) / n_steps
    # health gauges excluded above, charged per CHECK: grad/update/nonfinite
    # norm sets + loss-EMA set = 4 (the checks counter inc is a unit counter,
    # already in the delta). The fused K-group path checks at most once per
    # group, so the effective cadence is max(cadence, ksteps).
    ops_per_step += 4 / max(health_cadence, ksteps)
    assert ops_per_step > 0  # the loop really is instrumented

    probe = MetricsRegistry()
    c = probe.counter("probe_total").labels(k="x")
    h = probe.histogram("probe_seconds").labels(k="x")
    n_probe = 20000
    t0 = time.perf_counter()
    for _ in range(n_probe):
        c.inc()
        h.observe(0.001)
    per_op_s = (time.perf_counter() - t0) / (2 * n_probe)
    probe_ring = FlightRecorder(capacity=64)
    t0 = time.perf_counter()
    for _ in range(n_probe):
        probe_ring.record_span("probe", time.time_ns(), time.time_ns(),
                               group=1, cause="probe")
    per_span_s = (time.perf_counter() - t0) / n_probe

    overhead = ops_per_step * per_op_s + spans_per_step * per_span_s
    assert overhead <= 0.02 * step_s, (
        f"telemetry budget blown: {ops_per_step:.0f} registry ops/step x "
        f"{per_op_s * 1e6:.2f}us + {spans_per_step:.1f} spans/step x "
        f"{per_span_s * 1e6:.2f}us = {overhead * 1e3:.3f}ms vs step "
        f"{step_s * 1e3:.1f}ms")


def test_federation_overhead_budget():
    """The federation publisher must cost <=2% of an elastic worker's
    wall clock. Budget-style like the telemetry test above: the publisher
    is TIME-driven (one flush per DEFAULT_INTERVAL_S on its own thread),
    so its duty cycle is flush_cost / interval regardless of how many fit
    steps land inside an interval — requiring
    ``flush_cost <= 0.02 * DEFAULT_INTERVAL_S`` bounds the overhead at 2%
    of ANY elastic fit step schedule. Measured over a real TcpTransport to
    a live frontend with a representatively-populated registry, so the
    cost includes snapshotting, JSON framing, the socket round trip, and
    the coordinator-side merge."""
    import time

    from deeplearning4j_tpu.observability.federation import (
        DEFAULT_INTERVAL_S, FederatedRegistry, MetricsPublisher,
    )
    from deeplearning4j_tpu.observability.flight_recorder import (
        FlightRecorder,
    )
    from deeplearning4j_tpu.observability.metrics import MetricsRegistry
    from deeplearning4j_tpu.observability.tracing import TraceStore
    from deeplearning4j_tpu.parallel.param_server import ParameterServer
    from deeplearning4j_tpu.parallel.ps_transport import (
        ParameterServerTcpFrontend, TcpTransport,
    )

    # a registry shaped like a real elastic worker's: a handful of counter
    # series, the push/step histograms with spread-out observations, gauges
    reg = MetricsRegistry()
    for i in range(8):
        reg.counter("dl4j_ps_worker_steps_total").labels(
            worker=str(i)).inc(100 + i)
    h = reg.histogram("dl4j_ps_push_seconds").labels()
    hs = reg.histogram("dl4j_step_seconds").labels()
    for i in range(64):
        h.observe(0.001 * (i + 1))
        hs.observe(0.002 * (i + 1))
    reg.gauge("dl4j_ps_version").labels().set(123)
    rec = FlightRecorder(capacity=256, registry=reg)
    for i in range(32):
        rec.record("push_window", window=i)

    fed = FederatedRegistry(registry=MetricsRegistry(),
                            trace_store=TraceStore())
    srv = ParameterServer([np.zeros(8, np.float32)])
    frontend = ParameterServerTcpFrontend(srv, federation=fed).start()
    t = TcpTransport(("127.0.0.1", frontend.port))
    try:
        pub = MetricsPublisher(t, name="budget-w0", interval_s=999.0,
                               registry=reg, recorder=rec,
                               trace_store=TraceStore())
        assert pub.flush()  # warm the path outside the measured window
        n = 50
        t0 = time.perf_counter()
        for i in range(n):
            reg.counter("dl4j_ps_worker_steps_total").labels(
                worker="0").inc()  # the snapshot must not be cached
            assert pub.flush()
        flush_s = (time.perf_counter() - t0) / n
    finally:
        t.close()
        frontend.stop()
    assert flush_s <= 0.02 * DEFAULT_INTERVAL_S, (
        f"federation budget blown: flush costs {flush_s * 1e3:.3f}ms, "
        f"duty cycle {flush_s / DEFAULT_INTERVAL_S * 100:.2f}% of the "
        f"{DEFAULT_INTERVAL_S * 1e3:.0f}ms publish interval (budget 2%)")


def test_grid_rows_vgg16_and_lstm_hidden():
    """The round-6 grid additions are wired end-to-end: vgg16 is a
    first-class model (metric name, defaults, bench fn) and --hidden is a
    config-distinguishing axis for the char_rnn MFU-floor row."""
    import bench

    assert bench._METRICS["vgg16"] == "vgg16_samples_per_sec_per_chip"
    assert "vgg16" in bench._DEFAULTS
    assert "vgg16" in bench._bench_fns()
    # --hidden distinguishes configs in outage matching: the hidden>=1024
    # MFU-floor row must never be served by a hidden=200 capture
    a = bench._config_key("--model char_rnn")
    b = bench._config_key("--model char_rnn --hidden 1024")
    assert a != b and b["hidden"] == "1024"


def test_config_key_lstm_impl_axis():
    """--lstm-impl is config-distinct for char_rnn rows (an explicit scan-
    headline row must not stand in for the auto/fused default), and rows
    logged before the recurrent engine landed reinterpret as the scan path
    they actually measured — the same timestamp-guard pattern as the dtype
    and reduction-dtype default changes."""
    import bench

    a = bench._config_key("--model char_rnn --hidden 1024")
    b = bench._config_key("--model char_rnn --hidden 1024 --lstm-impl scan")
    assert a != b and a["lstm_impl"] == "auto" and b["lstm_impl"] == "scan"
    # non-recurrent models don't grow a phantom axis
    assert bench._config_key("--model resnet50")["lstm_impl"] is None
    # pre-engine bare rows ran the old scan path
    old = bench._config_key("--model char_rnn",
                            ts="2026-08-05T11:59:59Z")
    new = bench._config_key("--model char_rnn",
                            ts="2026-08-05T12:00:01Z")
    assert old["lstm_impl"] == "scan" and new["lstm_impl"] == "auto"


def test_config_key_sharding_axis():
    """--sharding is config-distinct for the flagship fit models (a dp_tp
    row must not stand in for the single-device headline), non-capable
    models don't grow a phantom axis, and rows logged before the sharding
    engine landed reinterpret as the single-device path they actually
    measured — the same timestamp-guard pattern as the other axis gates."""
    import bench

    a = bench._config_key("--model transformer")
    b = bench._config_key("--model transformer --sharding dp_tp")
    assert a != b and a["sharding"] is None and b["sharding"] == "dp_tp"
    assert bench._config_key(
        "--model fit_resnet50 --sharding zero3")["sharding"] == "zero3"
    # non-capable models don't grow a phantom axis
    assert bench._config_key("--model char_rnn")["sharding"] is None
    assert bench._SHARDING_CAPABLE == frozenset(
        {"fit_resnet50", "transformer"})
    # pre-engine rows measured the single-device path, whatever a later
    # reader asks for
    old = bench._config_key("--model transformer --sharding dp",
                            ts="2026-08-05T19:59:59Z")
    new = bench._config_key("--model transformer --sharding dp",
                            ts="2026-08-05T20:00:01Z")
    assert old["sharding"] is None and new["sharding"] == "dp"
    ts = bench._SHARDING_AXIS_LANDED_TS
    assert ts.endswith("Z") and ts > bench._XPLANE_ATTRIBUTION_LANDED_TS


def test_xplane_attribution_contract():
    """xplane attribution is measurement-only and ts-gated: the flag never
    makes a config distinct (a prior healthy row stands in during an
    outage), the landed-ts postdates the lstm-impl gate it stacks on, and
    the attribution field names bench rows carry are pinned."""
    import bench

    a = bench._config_key("--model resnet50")
    b = bench._config_key("--model resnet50 --xplane-attribution")
    assert a == b  # like --telemetry-out: does not change what is measured
    # same measurement-only rule on a recurrent row with its impl axis set
    assert bench._config_key(
        "--model char_rnn --hidden 1024 --xplane-attribution") == \
        bench._config_key("--model char_rnn --hidden 1024")

    ts = bench._XPLANE_ATTRIBUTION_LANDED_TS
    assert ts.endswith("Z") and len(ts) == len("2026-08-05T16:00:00Z")
    assert ts > bench._LSTM_IMPL_DEFAULT_CHANGE_TS  # ISO-8601 sorts

    assert bench.XPLANE_ATTRIBUTION_FIELDS == (
        "xplane_attribution", "profile_trace", "profile_error",
        "profile_variant")
    # the capture-capable set covers every multistep-harness model; models
    # outside it must degrade to profile_error, never crash (pinned so a
    # new model is consciously added or consciously excluded)
    assert bench._PROFILE_CAPABLE == frozenset(
        {"lenet", "resnet50", "vgg16", "char_rnn", "transformer", "moe"})


def test_config_key_serve_axes():
    """The serving A/B's load shape is config-distinct: an explicit
    --serve-qps row must not stand in for the auto-calibrated headline
    (offered rate IS the config under an open-loop client), the coalescing
    window is an axis for the same reason, other models don't grow phantom
    serve axes, and the ts-gate ignores the axes on rows that predate the
    serving engine — the same pattern as the sharding gate."""
    import bench

    a = bench._config_key("--model serve")
    b = bench._config_key("--model serve --serve-qps 800")
    c = bench._config_key("--model serve --serve-latency-ms 8")
    assert a != b and a["serve_qps"] == "auto" and b["serve_qps"] == "800"
    assert a != c and c["serve_latency_ms"] == "8"
    assert a["serve_latency_ms"] == "4"  # the bench_serve default, pinned
    # non-serve models don't grow phantom axes
    r = bench._config_key("--model resnet50")
    assert r["serve_qps"] is None and r["serve_latency_ms"] is None
    # rows logged before the serving engine landed cannot be serve rows;
    # the gate strips the axes rather than invent a config for them
    old = bench._config_key("--model serve --serve-qps 800",
                            ts="2026-08-05T21:59:59Z")
    new = bench._config_key("--model serve --serve-qps 800",
                            ts="2026-08-05T22:00:01Z")
    assert old["serve_qps"] is None and new["serve_qps"] == "800"
    ts = bench._SERVE_AXIS_LANDED_TS
    assert ts.endswith("Z") and ts > bench._SHARDING_AXIS_LANDED_TS


def test_config_key_serve_decode_axes():
    """The decode section's scheduling mode and weight quantization are
    config-distinct serve axes: a static-batching or int8 capture must
    never stand in for the continuous dense row (they measure different
    engines), other models don't grow phantom axes, and the ts-gate
    strips the axes on rows that predate the decode section — those rows
    carry no decode numbers, so normalizing their axes to None (never
    equal to a live request's resolved defaults) keeps an outage from
    serving a decode-less row for a decode-bearing request."""
    import bench

    a = bench._config_key("--model serve")
    b = bench._config_key("--model serve --serve-batching static")
    c = bench._config_key("--model serve --serve-quant int8")
    assert a != b and a["serve_batching"] == "continuous" \
        and b["serve_batching"] == "static"
    assert a != c and a["serve_quant"] == "none" \
        and c["serve_quant"] == "int8"
    # non-serve models don't grow phantom axes
    r = bench._config_key("--model resnet50")
    assert r["serve_batching"] is None and r["serve_quant"] is None
    # rows logged before the decode section landed never match post-landing
    # requests (axes None vs resolved defaults)
    old = bench._config_key("--model serve", ts="2026-08-05T23:29:59Z")
    new = bench._config_key("--model serve", ts="2026-08-05T23:30:01Z")
    assert old["serve_batching"] is None and old["serve_quant"] is None
    assert new["serve_batching"] == "continuous" \
        and new["serve_quant"] == "none"
    assert old != bench._config_key("--model serve")
    ts = bench._SERVE_DECODE_AXIS_LANDED_TS
    assert ts.endswith("Z") and ts > bench._PS_AXIS_LANDED_TS


def test_config_key_serve_replica_axes():
    """The replica-scaling section's fleet size and serving rule set are
    config-distinct serve axes: a 4-replica or dp_tp-sharded capture must
    never stand in for the 2-replica single-device row (they measure
    different serving topologies), other models don't grow phantom axes,
    and the ts-gate strips the axes on rows that predate the ReplicaSet —
    those rows carry no replica-scaling numbers, so normalizing their axes
    to None keeps an outage from serving a replica-less row. The serve
    scenario's sharding rides its OWN ``--serve-sharding`` flag, never the
    fit path's ``--sharding`` axis."""
    import bench

    a = bench._config_key("--model serve")
    b = bench._config_key("--model serve --serve-replicas 4")
    c = bench._config_key("--model serve --serve-sharding dp_tp")
    assert a != b and a["serve_replicas"] == "2" \
        and b["serve_replicas"] == "4"
    assert a != c and a["serve_sharding"] == "none" \
        and c["serve_sharding"] == "dp_tp"
    # non-serve models don't grow phantom axes
    r = bench._config_key("--model resnet50")
    assert r["serve_replicas"] is None and r["serve_sharding"] is None
    # rows logged before the replica section landed never match
    # post-landing requests (axes None vs resolved defaults)
    old = bench._config_key("--model serve", ts="2026-08-05T23:59:59Z")
    new = bench._config_key("--model serve", ts="2026-08-06T00:00:01Z")
    assert old["serve_replicas"] is None and old["serve_sharding"] is None
    assert new["serve_replicas"] == "2" and new["serve_sharding"] == "none"
    assert old != bench._config_key("--model serve")
    ts = bench._SERVE_REPLICA_AXIS_LANDED_TS
    assert ts.endswith("Z") and ts > bench._SERVE_DECODE_AXIS_LANDED_TS
    # serve never joins the fit path's sharding grid
    assert "serve" not in bench._SHARDING_CAPABLE


def test_grid_row_serve():
    """The serve scenario is wired through the whole bench surface: grid
    membership, the requests/sec unit (the one non-samples/sec headline),
    the f32 dtype default (bf16 convert ops would dominate the tiny
    serving model like they do LeNet), and profile-incapable (the A/B
    runs its own servers, not the multistep harness)."""
    import bench

    assert bench._METRICS["serve"] == "serve_batched_requests_per_sec"
    assert "serve" in bench._DEFAULTS and "serve" in bench._bench_fns()
    assert bench._UNITS["serve"] == "requests/sec"
    assert bench._DTYPE_DEFAULT["serve"] == "f32"
    assert "serve" not in bench._PROFILE_CAPABLE
    assert "serve" not in bench._SHARDING_CAPABLE
    batch, iters, _ = bench._DEFAULTS["serve"]
    assert batch >= 8  # max_batch: must exercise multiple pow2 buckets
    assert iters >= 2  # seconds per phase


def test_config_key_ps_axes():
    """The ps_async A/B's straggler shape is config-distinct: a 2-worker or
    8x-straggler capture must never stand in for the standard 4-worker/4x
    row (the barrier cost being measured IS a function of both), other
    models don't grow phantom ps axes, and the ts-gate strips the axes on
    rows that predate the async-PS engine — same pattern as serve."""
    import bench

    a = bench._config_key("--model ps_async")
    b = bench._config_key("--model ps_async --ps-workers 8")
    c = bench._config_key("--model ps_async --ps-straggler 2")
    assert a != b and a["ps_workers"] == "4" and b["ps_workers"] == "8"
    assert a != c and c["ps_straggler"] == "2"
    assert a["ps_straggler"] == "4"  # the bench_ps_async default, pinned
    # non-ps models don't grow phantom axes
    r = bench._config_key("--model lenet")
    assert r["ps_workers"] is None and r["ps_straggler"] is None
    # rows logged before the async-PS engine landed cannot be ps rows
    old = bench._config_key("--model ps_async --ps-workers 8",
                            ts="2026-08-05T22:00:29Z")
    new = bench._config_key("--model ps_async --ps-workers 8",
                            ts="2026-08-05T22:00:31Z")
    assert old["ps_workers"] is None and new["ps_workers"] == "8"
    ts = bench._PS_AXIS_LANDED_TS
    assert ts.endswith("Z") and ts > bench._SERVE_AXIS_LANDED_TS


def test_grid_row_ps_async():
    """The ps_async scenario is wired through the whole bench surface:
    grid membership, samples/sec unit, f32 dtype default (the A/B measures
    host-side barrier vs async orchestration, not MXU width — dtype
    conversion noise would pollute it), and neither profile- nor
    sharding-capable (it runs its own ParallelWrapper/PS harnesses, not
    the multistep harness those frozensets describe)."""
    import bench

    assert bench._METRICS["ps_async"] == "ps_async_samples_per_sec"
    assert "ps_async" in bench._DEFAULTS and "ps_async" in bench._bench_fns()
    assert "ps_async" not in bench._UNITS  # samples/sec, the default unit
    assert bench._DTYPE_DEFAULT["ps_async"] == "f32"
    assert "ps_async" not in bench._PROFILE_CAPABLE
    assert "ps_async" not in bench._SHARDING_CAPABLE
    batch, iters, ksteps = bench._DEFAULTS["ps_async"]
    # enough minibatches that every worker pushes several windows per phase
    # and the loss-parity phase reaches the label-noise plateau
    assert iters * ksteps >= 32


def test_config_key_elastic_axes():
    """The elastic kill A/B's fleet shape is config-distinct: a no-kill or
    8-worker capture must never stand in for the standard 4-worker
    kill-at-50% recovery row (the dip and recovery being measured ARE
    functions of both), other models don't grow phantom elastic axes, and
    the ts-gate strips the axes on rows that predate the elastic trainer —
    same pattern as serve and ps_async."""
    import bench

    a = bench._config_key("--model elastic")
    b = bench._config_key("--model elastic --elastic-workers 8")
    c = bench._config_key("--model elastic --elastic-kill 0")
    assert a != b and a["elastic_workers"] == "4" \
        and b["elastic_workers"] == "8"
    assert a != c and c["elastic_kill"] == "0"
    assert a["elastic_kill"] == "0.5"  # the bench_elastic default, pinned
    # non-elastic models don't grow phantom axes
    r = bench._config_key("--model ps_async")
    assert r["elastic_workers"] is None and r["elastic_kill"] is None
    # rows logged before the elastic trainer landed cannot be elastic rows
    old = bench._config_key("--model elastic --elastic-workers 8",
                            ts="2026-08-06T01:59:59Z")
    new = bench._config_key("--model elastic --elastic-workers 8",
                            ts="2026-08-06T02:00:01Z")
    assert old["elastic_workers"] is None and new["elastic_workers"] == "8"
    ts = bench._ELASTIC_AXIS_LANDED_TS
    assert ts.endswith("Z") and ts > bench._SERVE_REPLICA_AXIS_LANDED_TS


def test_grid_row_elastic():
    """The elastic scenario is wired through the whole bench surface: grid
    membership, samples/sec unit, f32 dtype default (the kill A/B measures
    membership/handoff orchestration on subprocess CPU workers, not MXU
    width), and neither profile- nor sharding-capable (it runs its own
    coordinator + worker-process harness, not the multistep harness those
    frozensets describe)."""
    import bench

    assert bench._METRICS["elastic"] == "elastic_ps_samples_per_sec"
    assert "elastic" in bench._DEFAULTS and "elastic" in bench._bench_fns()
    assert "elastic" not in bench._UNITS  # samples/sec, the default unit
    assert bench._DTYPE_DEFAULT["elastic"] == "f32"
    assert "elastic" not in bench._PROFILE_CAPABLE
    assert "elastic" not in bench._SHARDING_CAPABLE
    batch, iters, ksteps = bench._DEFAULTS["elastic"]
    # enough minibatches that the fit comfortably outlives a worker
    # respawn (~3s): the recovery-to-90% number must be measurable before
    # the surviving shards drain
    assert iters * ksteps >= 128


def test_config_key_dataplane_axes():
    """The host-data-plane axes (ISSUE 14) are config-distinct: an shm
    capture must never stand in for the tcp baseline (the A/B the headline
    compares), an f32 ingest row must never stand in for the default u8
    one, other models don't grow phantom axes, and the ts-gate strips both
    on rows that predate the plane — same pattern as the elastic axes."""
    import bench

    a = bench._config_key("--model ps_async")
    b = bench._config_key("--model ps_async --ps-transport shm")
    assert a != b and a["ps_transport"] == "tcp" \
        and b["ps_transport"] == "shm"
    e = bench._config_key("--model elastic --ps-transport shm")
    assert e["ps_transport"] == "shm"
    i = bench._config_key("--model ingest")
    j = bench._config_key("--model ingest --ingest-codec f32")
    assert i != j and i["ingest_codec"] == "u8" \
        and j["ingest_codec"] == "f32"
    # non-dataplane models don't grow phantom axes (ingest likewise never
    # grows a transport axis: it exercises the decoder, not the PS)
    r = bench._config_key("--model serve")
    assert r["ps_transport"] is None and r["ingest_codec"] is None
    assert i["ps_transport"] is None
    # rows logged before the data plane landed cannot carry its axes
    old = bench._config_key("--model ps_async --ps-transport shm",
                            ts="2026-08-06T05:59:59Z")
    new = bench._config_key("--model ps_async --ps-transport shm",
                            ts="2026-08-06T06:00:01Z")
    assert old["ps_transport"] is None and new["ps_transport"] == "shm"
    ts = bench._DATAPLANE_AXIS_LANDED_TS
    assert ts.endswith("Z") and ts > bench._ELASTIC_AXIS_LANDED_TS


def test_grid_row_ingest():
    """The ingest decode A/B is wired through the whole bench surface:
    grid membership, MB/sec unit (it is a decoder-bandwidth row, not a
    training row), f32 dtype default (no matmuls at all), and neither
    profile- nor sharding-capable (it never enters the multistep
    harness)."""
    import bench

    assert bench._METRICS["ingest"] == "native_ingest_decode_mb_per_sec"
    assert "ingest" in bench._DEFAULTS and "ingest" in bench._bench_fns()
    assert bench._UNITS["ingest"] == "MB/sec"
    assert bench._DTYPE_DEFAULT["ingest"] == "f32"
    assert "ingest" not in bench._PROFILE_CAPABLE
    assert "ingest" not in bench._SHARDING_CAPABLE
    batch, iters, ksteps = bench._DEFAULTS["ingest"]
    # sample-sized records (the regime where the per-record GIL-bound
    # fallback's fixed cost shows) and best-of reps for a stable bandwidth
    assert batch <= 16 and iters >= 2


def test_config_key_compile_cache_axes():
    """The warm-start compile plane's axis (ISSUE 15) is config-distinct
    on BOTH models that report warm numbers: a cold-only --compile-cache
    off capture must never stand in for the warm-headline default serve or
    elastic row; other models don't grow the axis; and the ts-gate strips
    it on rows that predate the plane."""
    import bench

    a = bench._config_key("--model serve")
    b = bench._config_key("--model serve --compile-cache off")
    assert a != b and a["compile_cache"] == "on" \
        and b["compile_cache"] == "off"
    c = bench._config_key("--model elastic")
    d = bench._config_key("--model elastic --compile-cache off")
    assert c != d and c["compile_cache"] == "on" \
        and d["compile_cache"] == "off"
    # no phantom axis on models without a warm-start section
    assert bench._config_key("--model ps_async")["compile_cache"] is None
    assert bench._config_key("--model resnet50")["compile_cache"] is None
    # rows logged before the plane landed cannot carry the axis
    gate = bench._COMPILE_CACHE_AXIS_LANDED_TS
    old = bench._config_key("--model serve --compile-cache off",
                            ts="2026-08-06T09:59:59Z")
    new = bench._config_key("--model serve --compile-cache off",
                            ts="2026-08-06T10:00:01Z")
    assert old["compile_cache"] is None and new["compile_cache"] == "off"
    assert gate.endswith("Z") and gate > bench._DATAPLANE_AXIS_LANDED_TS


def test_config_key_decode_kv_axes():
    """The paged decode memory plane's axes (ISSUE 16) are config-distinct
    serve axes: a dense-KV, odd-page-size, or no-draft capture must never
    stand in for the paged + tiny-draft headline row (they measure
    different decode engines); other models don't grow the axes; and the
    ts-gate strips them on rows that predate the plane — those rows ran
    dense KV with no draft model in the repo at all."""
    import bench

    a = bench._config_key("--model serve")
    b = bench._config_key("--model serve --decode-kv dense")
    c = bench._config_key("--model serve --decode-page-size 32")
    d = bench._config_key("--model serve --decode-spec-draft none")
    assert a != b and a["decode_kv"] == "paged" \
        and b["decode_kv"] == "dense"
    assert a != c and a["decode_page_size"] == "16" \
        and c["decode_page_size"] == "32"
    assert a != d and a["decode_spec_draft"] == "tiny" \
        and d["decode_spec_draft"] == "none"
    # no phantom axes on models without a decode section
    for model in ("resnet50", "ps_async", "elastic"):
        r = bench._config_key(f"--model {model}")
        assert r["decode_kv"] is None and r["decode_page_size"] is None \
            and r["decode_spec_draft"] is None
    # rows logged before the plane landed cannot carry the axes
    gate = bench._PAGED_DECODE_AXIS_LANDED_TS
    old = bench._config_key("--model serve --decode-kv dense",
                            ts="2026-08-07T07:59:59Z")
    new = bench._config_key("--model serve --decode-kv dense",
                            ts="2026-08-07T08:00:01Z")
    assert old["decode_kv"] is None and old["decode_page_size"] is None \
        and old["decode_spec_draft"] is None
    assert new["decode_kv"] == "dense" and new["decode_page_size"] == "16"
    assert old != bench._config_key("--model serve --decode-kv dense")
    assert gate.endswith("Z") \
        and gate > bench._COMPILE_CACHE_AXIS_LANDED_TS


def test_config_key_serve_tracing_axis():
    """--serve-tracing (ISSUE 17) is a config-distinct serve axis: an
    untraced capture must never stand in for the tracing-on default row
    (whose headline carries trace_overhead_pct, the <=2% always-on
    tracing budget); other models don't grow the axis; and the ts-gate
    strips it from rows that predate the tracing plane — those requests
    ran with no tracing code in the repo at all."""
    import bench

    a = bench._config_key("--model serve")
    b = bench._config_key("--model serve --serve-tracing off")
    assert a != b and a["serve_tracing"] == "on" \
        and b["serve_tracing"] == "off"
    # no phantom axis on models without a serve section
    for model in ("resnet50", "ps_async", "char_rnn"):
        assert bench._config_key(f"--model {model}")["serve_tracing"] is None
    # rows logged before the plane landed cannot carry the axis
    gate = bench._SERVE_TRACING_AXIS_LANDED_TS
    old = bench._config_key("--model serve", ts="2026-08-07T11:59:59Z")
    new = bench._config_key("--model serve", ts="2026-08-07T12:00:01Z")
    assert old["serve_tracing"] is None and new["serve_tracing"] == "on"
    assert old != bench._config_key("--model serve")
    assert gate.endswith("Z") and gate > bench._PAGED_DECODE_AXIS_LANDED_TS

def test_config_key_serve_autoscale_axis():
    """--serve-autoscale (ISSUE 18) is a config-distinct serve axis: the
    static default row must never stand in for the open-loop ramp A/B
    capture (whose headline carries ramp_slo_violation_seconds_auto/
    static, the zero-loss count and the warm scale-out latency); other
    models don't grow the axis; and the ts-gate strips it from rows that
    predate the autoscaling fleet."""
    import bench

    a = bench._config_key("--model serve")
    b = bench._config_key("--model serve --serve-autoscale on")
    assert a != b and a["serve_autoscale"] == "off" \
        and b["serve_autoscale"] == "on"
    # no phantom axis on models without a serve section
    for model in ("resnet50", "ps_async", "char_rnn"):
        assert bench._config_key(
            f"--model {model}")["serve_autoscale"] is None
    # rows logged before the plane landed cannot carry the axis
    gate = bench._SERVE_AUTOSCALE_AXIS_LANDED_TS
    old = bench._config_key("--model serve", ts="2026-08-07T15:59:59Z")
    new = bench._config_key("--model serve", ts="2026-08-07T16:00:01Z")
    assert old["serve_autoscale"] is None and new["serve_autoscale"] == "off"
    assert old != bench._config_key("--model serve")
    assert gate.endswith("Z") and gate > bench._SERVE_TRACING_AXIS_LANDED_TS
