#!/bin/bash
# Capture the full benchmark grid on the chip in one run: through the builder's
# tool, `chiprun --timeout 3600 -- bash scripts/bench_capture.sh quick`.
# Appends one JSON line per run to chiprun_out/bench_log.jsonl (never
# overwrites; chiprun_out/ is what comes back from the chip machine and is
# git-ignored). Each `run` is its own bench.py parent + child: the parent stays
# off JAX, so the child is the one process that holds the chip.
# Usage: scripts/bench_capture.sh [quick|full]
set -u
cd "$(dirname "$0")/.."
mkdir -p chiprun_out
LOG=chiprun_out/bench_log.jsonl
MODE=${1:-full}

export DL4J_PROFILE_DIR=${DL4J_PROFILE_DIR:-chiprun_out/profiles}

# Only one capture grid at a time: the latecomer exits instead of interleaving
# half-duplicate rows.
exec 9>chiprun_out/.bench_capture.lock
if ! flock -n 9; then
    echo "another bench_capture is running; exiting" >&2
    exit 1
fi

WINDOW_TS=$(date -u +%FT%TZ)

run() {
    echo "--- bench $* $(date -u +%H:%M:%S)" >&2
    out=$(timeout 560 python bench.py "$@" --attempts 1 --attempt-timeout 480 2>/dev/null | tail -1)
    [ -n "$out" ] || out=null   # keep bench_log.jsonl valid per-line JSON
    echo "{\"args\": \"$*\", \"ts\": \"$(date -u +%FT%TZ)\", \"window_start\": \"$WINDOW_TS\", \"rec\": $out}" >> "$LOG"
    echo "$out" | head -c 300 >&2; echo >&2
}

# headline configs: bare = per-model measured-best dtype (round-5);
# --bf16-matmul is the A/B twin
run --model resnet50
run --model resnet50 --bf16-matmul
run --model transformer
run --model transformer --bf16-matmul
# the MFU-floor row (VERDICT #7, ISSUE 6) in the ALWAYS-RUN set: one record
# carries the scan/fused/pallas three-way A/B of the recurrent engine at MXU
# width
run --model char_rnn --hidden 1024
# sharding-engine headline rows (ISSUE 8): the flagship fit paths through
# the partition-rule compile seam — zero3's record must show ~1/N
# param_bytes_per_device, dp_tp prices the Megatron column/row splits
run --model fit_resnet50 --sharding zero3
run --model transformer --sharding dp_tp
# serving-engine headline row (ISSUE 9 + 11): micro-batched vs unbatched
# A/B at the auto-calibrated saturation rate, plus the decode section —
# continuous vs static token streaming and int8 vs dense weights at one
# offered sessions/sec (decode_speedup, decode_ttft_p99_improvement,
# int8_prob_drift ride the row); full records (p50/p99, occupancy,
# recompiles == bucket count) also land in scripts/serve_load.jsonl
run --model serve
# sharded multi-replica serving headline row (ISSUE 12): 4 tensor-parallel
# replicas (8 chips = 4 replicas x 2-way dp_tp slices) behind the least-
# queue router vs the single-replica baseline at the same offered rate —
# replica_speedup and replica_recompiles_match_buckets ride the row (the
# >=1.6x two-replica floor is a capture-host property; single-core CI
# can't exhibit it)
run --model serve --serve-sharding dp_tp --serve-replicas 4
# async-PS headline row (ISSUE 10): straggler A/B — one 4x-slow worker of 4,
# async push/pull vs the sync-DP barrier at equal worker count, plus the
# 2-process TCP loss-parity phase (CPU-measured by design, like serve: the
# win is host-side orchestration, not MXU width)
run --model ps_async
# elastic headline row (ISSUE 13): 4 separate-process workers behind the
# membership oracle, SIGKILL one at 50% of the expected push windows —
# worker_loss_dip_pct and recovery_seconds (time back to 90% of the
# pre-kill rate: lease fence -> shard handoff -> replacement resumes at
# the committed broker offset) ride the row; the same record also lands
# in scripts/ps_ab.jsonl beside the ps_async straggler record
run --model elastic
# host-data-plane rows (ISSUE 14): the shm-transport push-window A/B rides
# the ps_async row (tcp_/shm_push_windows_per_sec + shm_push_speedup — the
# >=1.3x shm floor), and the ingest row A/Bs the batched off-GIL native
# frame decode against the per-record GIL-bound python fallback at
# sample-sized records; both records also land in scripts/ps_ab.jsonl
run --model ps_async --ps-transport shm
run --model ingest
# warm-start compile plane row (ISSUE 15): the default serve and elastic
# rows above already headline the WARM numbers (time_to_ready_s from a
# cache-backed pin, recovery_seconds with the respawned worker loading its
# step executable from disk) with the cold A/B riding along; this cold-only
# row pins the cache-off world as its own config
run --model serve --compile-cache off
# paged decode memory plane row (ISSUE 16): the default serve row above
# already headlines the PAGED numbers (paged_sessions_ratio at equal state
# bytes, paged_bitwise_equal, spec_speedup at the tiny draft's measured
# acceptance); this dense-KV no-draft row pins the old decode world as its
# own config
run --model serve --decode-kv dense --decode-spec-draft none
# autoscaling fleet row (ISSUE 18): the open-loop ramp A/B — SLO-driven
# autoscaled fleet vs a static fleet at the same time-weighted average
# replica count under a 10x offered-load swing; the row carries the
# acceptance floor (ramp_slo_violation_seconds_auto < _static), the
# zero-loss scale-in count and the warm-path scale-out latency. Its own
# config: the default (off) row never stands in for the ramp capture
run --model serve --serve-autoscale on
if [ "$MODE" = full ]; then
    run --model lenet
    run --model lenet --bf16-act
    run --model char_rnn
    run --model char_rnn --bf16-matmul
    # engine A/B at MXU width with the scan oracle as the headline (the
    # hidden-1024 headline row above is auto); speedup fields overlap as a
    # cross-check
    run --model char_rnn --hidden 1024 --lstm-impl scan
    run --model vgg16
    run --model vgg16 --bf16-matmul
    run --model moe
    run --model moe --bf16-matmul
    run --model word2vec
    (export DL4J_FLASH_SWEEP=1; run --model attention)
    # long-context proof: T=16384 runs ONLY via the pallas flash path
    # (bench.py skips the XLA twin past its score-bytes budget)
    run --model attention --seq 16384
    run --model fit_resnet50
    run --model fit_lenet
    # full sharding grid: dp baselines the seam's overhead vs the bare fit
    # rows above; the remaining modes complete the per-rule-set comparison
    run --model fit_resnet50 --sharding dp
    run --model fit_resnet50 --sharding dp_tp
    run --model transformer --sharding dp
    run --model transformer --sharding zero3
    # decode-axis captures: the int8-headlined and static-headlined serve
    # configs (config-distinct from the continuous dense headline row)
    run --model serve --serve-quant int8
    run --model serve --serve-batching static
    # batch sweep for the flagship at the winning dtype
    run --model resnet50 --batch 64
    run --model resnet50 --batch 256
fi
echo "done -> $LOG" >&2
