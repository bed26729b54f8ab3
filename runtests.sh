#!/usr/bin/env bash
# Run the test suite on a virtual 8-device CPU mesh (reference runtests.sh analog).
#
# Tests never need the chip: every stanza forces JAX_PLATFORMS=cpu (as
# tests/conftest.py does for itself). The chip is reached through the builder's
# tool only: `chiprun -- python3 chip_smoke.py` (README "Testing").
#
#   ./runtests.sh [pytest args]   # the suite
#   ./runtests.sh lint [args]     # graftlint over the package (see docs/GUIDE.md)
#   ./runtests.sh health [args]   # failure-diagnostics suite: flight recorder,
#                                 # health monitor, watchdog, overhead budget
#   ./runtests.sh rnn [args]      # recurrent engine: fused/pallas-vs-scan
#                                 # equivalence, dispatch gate, layer tests
#   ./runtests.sh profile [args]  # trace-attribution engine: XPlane parser
#                                 # golden tests, TraceSession lock, triggers,
#                                 # e2e CPU capture + bench attribution row
#   ./runtests.sh serve [args]    # serving engine: non-donated predict,
#                                 # bucketed micro-batching semantics, 429
#                                 # backpressure, hot swap, streaming, HTTP
#                                 # front-end, bench serve-axis contract
#   ./runtests.sh ps [args]       # async parameter-server engine: staleness
#                                 # math, bf16 wire codec, transport parity,
#                                 # 2-process TCP loss parity, loopback
#                                 # broker reconnect, bench ps-axis contract
#   ./runtests.sh decode [args]   # continuous-batching decode engine:
#                                 # continuous-vs-static bitwise equality,
#                                 # mid-decode admission/eviction, int8
#                                 # drift bounds, compile-per-bucket, the
#                                 # streaming churn regression, /v1/generate
#   ./runtests.sh paged [args]    # paged KV memory plane + speculative
#                                 # decoding: paged-vs-dense bitwise at
#                                 # every bucket, CoW forks, refcount
#                                 # churn, spec-vs-greedy bitwise, pool
#                                 # 429s, the 2x-sessions ratio, bench
#                                 # decode-kv-axis contract
#   ./runtests.sh serve-shard [args]  # sharded multi-replica serving:
#                                 # dp_tp bitwise-vs-single-device, rolling
#                                 # hot swap zero-loss, least-queue router,
#                                 # multi-input graphs, per-replica metrics,
#                                 # bench replica-axis contract
#   ./runtests.sh elastic [args]  # elastic preemption-tolerant training:
#                                 # membership lease math, zombie epoch
#                                 # fencing, half-open-socket retry bounds,
#                                 # broker shard handoff, the slow chaos
#                                 # SIGKILL+respawn loss-parity run, bench
#                                 # elastic-axis contract
#   ./runtests.sh dataplane [args]  # zero-copy host data plane: wire codec
#                                 # fuzz, shm seqlock rings, SIGKILL orphan
#                                 # reaper, shm/tcp transport + fit parity,
#                                 # native ingest decode parity, bench
#                                 # dataplane-axis contract
#   ./runtests.sh compile [args]  # warm-start compile plane: cache-hit
#                                 # bitwise identity (train/predict/decode),
#                                 # corruption quarantine, cross-process
#                                 # reuse, warmup-before-swap ordering,
#                                 # kill switch, bench compile-cache-axis
#                                 # contract
#   ./runtests.sh autoscale [args]  # SLO-driven autoscaling fleet:
#                                 # add/remove replica atomicity, scale-in
#                                 # drain zero-loss, zombie lease fencing,
#                                 # hysteresis (≤1 event per cooldown),
#                                 # priority shedding order, warm scale-up
#                                 # no-fresh-compile pin, bench axis contract
#   ./runtests.sh lock [args]     # concurrency plane: the four lock rules
#                                 # over the package + their fixture suite,
#                                 # then the threaded serve/autoscale/replica
#                                 # suites under the runtime lock-order
#                                 # witness (DL4J_LOCK_WITNESS=1) asserting
#                                 # the executed acquisition graph acyclic
#   ./runtests.sh trace [args]    # request tracing + SLO engine: traceparent
#                                 # propagation through HTTP/batcher/decode/
#                                 # replica, tail sampling (429 always kept),
#                                 # burn-rate math + alert actions, cardinality
#                                 # guard, orphan-span lint rule, the <=2%
#                                 # tracing overhead budget, bench axis contract
#   ./runtests.sh fleet [args]    # fleet observability federation: merge
#                                 # algebra exactness, zombie-gauge fencing,
#                                 # restart-epoch monotonicity, cross-process
#                                 # trace stitching, /fleet/* routes, fleet
#                                 # bundle timeline, fleet-truth lint rule,
#                                 # the <=2% federation overhead budget
set -e
cd "$(dirname "$0")"

if [ "${1-}" = "lint" ]; then
  shift
  JAX_PLATFORMS=cpu \
  exec python -m deeplearning4j_tpu.lint "$@"
fi

if [ "${1-}" = "rnn" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_lstm_fast.py tests/test_layers.py -q "$@"
fi

if [ "${1-}" = "profile" ]; then
  shift
  # includes the slow end-to-end bench --xplane-attribution subprocess row
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_profiler.py \
    tests/test_bench_contract.py::test_xplane_attribution_contract -q "$@"
fi

if [ "${1-}" = "serve" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_serving.py tests/test_serving_http.py \
    tests/test_bench_contract.py::test_config_key_serve_axes \
    tests/test_bench_contract.py::test_grid_row_serve -q "$@"
fi

if [ "${1-}" = "ps" ]; then
  shift
  # includes the slow 2-process TCP loss-parity run
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_param_server.py \
    tests/test_streaming_broker.py \
    tests/test_bench_contract.py::test_config_key_ps_axes \
    tests/test_bench_contract.py::test_grid_row_ps_async -q "$@"
fi

if [ "${1-}" = "decode" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_decode.py \
    tests/test_bench_contract.py::test_config_key_serve_decode_axes -q "$@"
fi

if [ "${1-}" = "paged" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_paged_decode.py \
    tests/test_decode.py \
    tests/test_bench_contract.py::test_config_key_decode_kv_axes -q "$@"
fi

if [ "${1-}" = "serve-shard" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_serving_replica.py \
    tests/test_bench_contract.py::test_config_key_serve_replica_axes -q "$@"
fi

if [ "${1-}" = "elastic" ]; then
  shift
  # includes the slow chaos SIGKILL+respawn loss-parity run
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_elastic.py \
    tests/test_bench_contract.py::test_config_key_elastic_axes \
    tests/test_bench_contract.py::test_grid_row_elastic -q "$@"
fi

if [ "${1-}" = "dataplane" ]; then
  shift
  # includes the slow shm/tcp fit-parity run and the SIGKILL orphan-reaper
  # chaos test; test_param_server/test_streaming_broker ride along because
  # the shm transport and the native broker decode share their surfaces
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_dataplane.py \
    tests/test_param_server.py \
    tests/test_streaming_broker.py \
    tests/test_bench_contract.py::test_config_key_dataplane_axes \
    tests/test_bench_contract.py::test_grid_row_ingest -q "$@"
fi

if [ "${1-}" = "compile" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_compile_cache.py \
    tests/test_bench_contract.py::test_config_key_compile_cache_axes -q "$@"
fi

if [ "${1-}" = "autoscale" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_autoscale.py \
    tests/test_bench_contract.py::test_config_key_serve_autoscale_axis -q "$@"
fi

if [ "${1-}" = "trace" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_tracing.py \
    tests/test_bench_contract.py::test_config_key_serve_tracing_axis -q "$@"
fi

if [ "${1-}" = "lock" ]; then
  shift
  # phase 1: static — the four concurrency rules over the real tree must
  # be clean, and their fixture/witness unit suite must pass
  JAX_PLATFORMS=cpu \
  python -m deeplearning4j_tpu.lint deeplearning4j_tpu \
    --rules lockguard,lock-order,blocking-under-lock,thread-lifecycle
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m pytest tests/test_lint_concurrency.py -q "$@"
  # phase 2: dynamic — the threaded suites under the witness; the
  # session-teardown fixture in conftest.py asserts the lock graph the
  # run actually executed is acyclic
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  DL4J_LOCK_WITNESS=1 \
  exec python -m pytest tests/test_serving.py tests/test_serving_http.py \
    tests/test_serving_replica.py tests/test_autoscale.py -q "$@"
fi

if [ "${1-}" = "health" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_flight_recorder.py \
    tests/test_bench_contract.py::test_telemetry_overhead_budget -q "$@"
fi

if [ "${1-}" = "fleet" ]; then
  shift
  JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  exec python -m pytest tests/test_federation.py \
    tests/test_bench_contract.py::test_federation_overhead_budget -q "$@"
fi

JAX_PLATFORMS=cpu \
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m pytest tests/ -q "$@"
