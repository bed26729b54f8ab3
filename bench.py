"""Benchmark driver: prints ONE JSON line with the headline metric.

Headline (BASELINE.md): ResNet-50 ImageNet-config training throughput
(samples/sec/chip) on one TPU chip — the flagship config from BASELINE.json,
measured the way the reference's PerformanceListener measures throughput
(reference optimize/listeners/PerformanceListener.java). vs_baseline is
reported against the best previously-recorded number in BASELINE.md for the
same config (null when none exists yet).

TPU-first measurement methodology:
 - K train steps run per host dispatch (`lax.scan` inside one XLA program,
   see make_multistep_train_step) so host dispatch latency is amortized;
 - compute dtype defaults to the model's measured-best policy (--f32 /
   --bf16-matmul / --bf16-act force one);
 - inputs are staged device-side once (a (K, B, ...) stack in HBM);
 - the timed region ends in a host read (`float(loss)`); whether
   `block_until_ready` agrees with it on a local chip is to be re-checked
   (ROADMAP S0);
 - model FLOPs come from XLA's own cost analysis of the compiled program, and
   MFU is reported against the chip's published bf16 peak, looked up by
   device_kind (observability/compile_tracker.PEAK_BF16_FLOPS; an unknown
   kind is an error, and a CPU run reports no MFU at all).

Usage: python bench.py [--model lenet|resnet50|char_rnn|transformer|word2vec]
                       [--batch N] [--iters N] [--ksteps K] [--seq T]
                       [--vocab V] [--f32 | --bf16-matmul | --bf16-act]
       (default dtype = each model's measured-best config: bf16 activations
       for the flagships, bf16-matmul for the tiny models — BASELINE.md r5)
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

# Best previously-recorded number per config (BASELINE.md "Measured" table):
# every one measured on a v5e on or before 2026-07-31, on older code, and not
# re-measured since (ROADMAP S0 replaces this table with the ledger).
# vs_baseline is reported against these; None -> no baseline yet and the JSON
# record carries vs_baseline: null (NOT 1.0 — a sentinel a reader could misread
# as parity).
BASELINE_SAMPLES_PER_SEC = {
    "resnet50": 1870.0,    # round 3, bf16 matmul, batch 128 (BASELINE.md)
    "lenet": 702374.8,     # round 2 driver record
    "char_rnn": 16318.1,   # round 3 first recording (BASELINE.md)
    "transformer": 5169.2,  # round 3 first recording
    "word2vec": 940856.4,  # round 3 first recording
    "attention": 1088790.0,  # round 3 first recording (pallas path)
}


def _mfu(flops_per_sec: float):
    """Share of the device's published bf16 peak, or None on a CPU: a CPU
    run never yields a utilization."""
    from deeplearning4j_tpu.observability.compile_tracker import \
        global_tracker
    peak = global_tracker().peak_flops()
    return None if peak is None else round(flops_per_sec / peak, 6)


def _xla_flops(jit_fn, *args) -> float:
    """XLA's own flop count for one dispatch of a compiled jit function.

    CAVEAT (verified on this chip, and pinned by
    tests/test_bench_contract.py::test_cost_analysis_counts_scan_body_once):
    XLA's cost analysis counts a `lax.scan`/while-loop BODY ONCE, not
    trip-count times — flops for a K-step scanned program are identical for
    K=1..8. Callers that scan K steps per dispatch must multiply by K
    themselves. Round 2's recorded "0.3% MFU" for LeNet understated real
    utilization by exactly K for this reason.

    Shares the tracker's ``cost_analysis_flops`` helper, which reads the
    analysis off ``lower()`` WITHOUT a second ``compile()`` — the old
    lower+compile-again path here double-compiled every flagship program
    just to count its flops.
    """
    from deeplearning4j_tpu.observability.compile_tracker import \
        cost_analysis_flops
    return max(0.0, cost_analysis_flops(jit_fn, *args))


#: armed by _child_main when --xplane-attribution asks for a trace:
#: {"trigger": ..., "dispatches": N}. Consumed by
#: the FIRST _measure_multistep call of the run (for char_rnn's three-way
#: A/B that is the scan variant), so one bench row pays for one capture.
_PROFILE_SPEC = None

#: models whose bench path runs through _measure_multistep and can therefore
#: re-dispatch the already-compiled program under a trace; the others get a
#: graceful profile_error field instead of a crash
_PROFILE_CAPABLE = frozenset(
    {"lenet", "resnet50", "vgg16", "char_rnn", "transformer", "moe"})

#: models with a --sharding grid axis: flagship fit paths routed through the
#: partition-rule engine's compile seam (parallel/partition.py rule sets)
_SHARDING_CAPABLE = frozenset({"fit_resnet50", "transformer"})


def _profile_capture(dispatch_once, logdir_hint: str = None) -> dict:
    """Run the armed trace capture around ``dispatch_once`` (a thunk
    re-dispatching the compiled program once, ending on a host sync).
    Returns bench-row fields — xplane_attribution + profile_trace on
    success, profile_error on ANY failure; never raises (the capture is
    measurement decoration, the headline number must survive it)."""
    global _PROFILE_SPEC
    spec, _PROFILE_SPEC = _PROFILE_SPEC, None
    if spec is None:
        return {}
    fields = {}
    try:
        from deeplearning4j_tpu.observability.profiler import \
            global_trace_session
        session = global_trace_session()
        logdir = session.start(spec.get("trigger", "bench"),
                               logdir=logdir_hint)
        if logdir is None:
            return {"profile_error": "trace engine busy or profiler refused"}
        fields["profile_trace"] = logdir
        try:
            for _ in range(max(1, int(spec.get("dispatches", 2)))):
                dispatch_once()
        finally:
            summary = session.stop() or {}
        if summary.get("error"):
            fields["profile_error"] = str(summary["error"])
        else:
            fields["xplane_attribution"] = {
                "categories_pct": summary.get("categories_pct", {}),
                "top_ops": summary.get("top_ops", [])[:5],
                "total_device_ns": summary.get("total_device_ns", 0),
            }
    except Exception as e:  # never let attribution sink the headline row
        fields["profile_error"] = repr(e)[:300]
    return fields


def _measure_multistep(conf, xs, ys, iters: int, warmup: int,
                       graph: bool = False, track_fn: str = None) -> dict:
    """Steady-state throughput of K-step scanned training on stacked batches.

    xs/ys: (K, B, ...) stacks (lists of stacks for graph nets). Each timed
    "iter" is ONE host dispatch running K fused train steps on device. The
    donated-params chain means the final float(loss) waits on every step.

    ``track_fn`` names the program in the CompileTracker so the rolling
    ``dl4j_step_mfu{fn=track_fn}`` gauge populates during the run — the
    per-variant MFU channel for A/B twins (note_step after each timed
    dispatch advances by K, matching the fit loops).
    """
    import jax
    import jax.numpy as jnp

    if graph:
        from deeplearning4j_tpu.nn.graph_network import (
            ComputationGraph, make_graph_multistep_train_step)
        net = ComputationGraph(conf).init()
        multi = make_graph_multistep_train_step(conf)
    else:
        from deeplearning4j_tpu.nn.multilayer import (
            MultiLayerNetwork, make_multistep_train_step)
        net = MultiLayerNetwork(conf).init()
        multi = make_multistep_train_step(conf)

    jit_multi = jax.jit(multi, donate_argnums=(0, 1, 2))
    tracker = None
    dispatch = jit_multi
    if track_fn:
        from deeplearning4j_tpu.observability import global_tracker
        tracker = global_tracker()
        dispatch = tracker.wrap(track_fn, jit_multi)
    key = jax.random.PRNGKey(0)
    params, states, upd = net.params_list, net.state_list, net.updater_state

    ksteps = (xs[0].shape[0] if graph else xs.shape[0])
    batch = (xs[0].shape[1] if graph else xs.shape[1])

    # XLA's flop count covers the scan body ONCE (see _xla_flops caveat), so
    # one K-step dispatch executes ksteps x that count
    flops_per_dispatch = ksteps * _xla_flops(jit_multi, params, states, upd,
                                             xs, ys, key, jnp.int32(0))

    for i in range(warmup):
        params, states, upd, loss = dispatch(params, states, upd, xs, ys,
                                             key, jnp.int32(i * ksteps))
    float(loss[-1])  # hard sync: host read

    t0 = time.perf_counter()
    for i in range(iters):
        params, states, upd, loss = dispatch(
            params, states, upd, xs, ys, key,
            jnp.int32((warmup + i) * ksteps))
        if tracker is not None:
            tracker.note_step(ksteps, fn=track_fn)
    # the donated-params chain makes this final host read wait on every step
    float(loss[-1])
    dt = time.perf_counter() - t0

    n_steps = iters * ksteps
    flops_per_sec = flops_per_dispatch * iters / dt if flops_per_dispatch else 0.0
    r = {
        "samples_per_sec": batch * n_steps / dt,
        "step_time_ms": dt / n_steps * 1000,
        "batch": batch,
        "iters": iters,
        "ksteps": ksteps,
        "tflops_per_sec": round(flops_per_sec / 1e12, 4),
        "mfu": _mfu(flops_per_sec),
    }
    if _PROFILE_SPEC is not None:
        # attribution capture AFTER the timed loop: re-dispatches the
        # already-compiled program (zero extra compiles) under a trace, so
        # the profiled program IS the timed one and the headline number is
        # untouched by trace overhead
        state = {"params": params, "states": states, "upd": upd, "i": 0}

        def dispatch_once():
            state["params"], state["states"], state["upd"], loss = dispatch(
                state["params"], state["states"], state["upd"], xs, ys, key,
                jnp.int32((warmup + iters + state["i"]) * ksteps))
            state["i"] += 1
            float(loss[-1])  # host sync: the trace must contain device work

        r.update(_profile_capture(dispatch_once))
    return r


def _stack(a, k: int):
    import jax.numpy as jnp
    return jnp.broadcast_to(a[None], (k,) + a.shape)


def _onehot_batch(rng, batch: int, n_classes: int):
    y = np.zeros((batch, n_classes), np.float32)
    y[np.arange(batch), rng.integers(0, n_classes, batch)] = 1
    return y


#: LM bench geometry, shared with flagship_setup
LM_VOCAB, LM_SEQ = 256, 256


def flagship_setup(model: str, batch: int, ksteps: int):
    """(conf, xs_stack, ys_stack, is_graph) for a headline config — the ONE
    construction behind both the bench measurements and
    scripts/profile_flagship.py, so the profiled program IS the timed one."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    if model == "resnet50":
        from deeplearning4j_tpu.models.resnet import resnet50
        x = jnp.asarray(rng.normal(size=(batch, 224, 224, 3))
                        .astype(np.float32))
        y = jnp.asarray(_onehot_batch(rng, batch, 1000))
        return (resnet50(n_classes=1000, image_size=224),
                [_stack(x, ksteps)], [_stack(y, ksteps)], True)
    if model == "vgg16":
        from deeplearning4j_tpu.models.vgg import vgg16
        x = jnp.asarray(rng.normal(size=(batch, 224, 224, 3))
                        .astype(np.float32))
        y = jnp.asarray(_onehot_batch(rng, batch, 1000))
        return (vgg16(n_classes=1000, image_size=224),
                _stack(x, ksteps), _stack(y, ksteps), False)
    if model == "lenet":
        from deeplearning4j_tpu.models.lenet import lenet_mnist
        x = jnp.asarray(rng.normal(size=(batch, 784)).astype(np.float32))
        y = jnp.asarray(_onehot_batch(rng, batch, 10))
        return lenet_mnist(), _stack(x, ksteps), _stack(y, ksteps), False
    if model in ("transformer", "moe"):
        from deeplearning4j_tpu.models.transformer import (
            moe_transformer_lm, transformer_lm)
        conf = (transformer_lm(vocab_size=LM_VOCAB, width=256, n_layers=4,
                               n_heads=4, max_len=LM_SEQ)
                if model == "transformer" else
                moe_transformer_lm(vocab_size=LM_VOCAB, width=256, n_layers=4,
                                   n_heads=4, n_experts=8, max_len=LM_SEQ))
        ids = rng.integers(0, LM_VOCAB, (batch, LM_SEQ))
        x = jnp.asarray(np.eye(LM_VOCAB, dtype=np.float32)[ids])
        return conf, _stack(x, ksteps), _stack(x, ksteps), False
    raise ValueError(f"no flagship setup for model '{model}'")


def bench_lenet(batch: int, iters: int, ksteps: int, warmup: int = 2) -> dict:
    conf, xs, ys, graph = flagship_setup("lenet", batch, ksteps)
    return _measure_multistep(conf, xs, ys, iters, warmup, graph=graph)


def bench_resnet50(batch: int, iters: int, ksteps: int, warmup: int = 2) -> dict:
    conf, xs, ys, graph = flagship_setup("resnet50", batch, ksteps)
    return _measure_multistep(conf, xs, ys, iters, warmup, graph=graph)


def bench_vgg16(batch: int, iters: int, ksteps: int, warmup: int = 2) -> dict:
    """VGG-16 single-chip throughput (VERDICT #7 grid completion): the
    classic dense-conv stack — ~4x the per-sample flops of ResNet-50 with no
    BN, so it isolates pure conv/matmul throughput from the norm-reduce
    lever."""
    conf, xs, ys, graph = flagship_setup("vgg16", batch, ksteps)
    return _measure_multistep(conf, xs, ys, iters, warmup, graph=graph)


def bench_char_rnn(batch: int, iters: int, ksteps: int, warmup: int = 2,
                   vocab: int = 64, seq: int = 50,
                   hidden: int = 200, lstm_impl: str = "auto") -> dict:
    """GravesLSTM char-RNN (BASELINE config 3): TBPTT-length sequences.

    ``hidden`` >= 1024 is the grid's worst-number config (0.5%% MFU at the
    default 200) — the row the recurrent engine (ops/lstm.py) exists to move.

    Three-way A/B twin (the word2vec dense/scatter pattern): every record
    carries the scan-oracle and fused-scan timings, plus the Pallas
    persistent-cell timing when the dispatch gate would engage it on this
    backend (None fields on CPU, where the kernel never runs). The headline
    ``samples_per_sec`` is whichever variant ``lstm_impl`` selects — "auto"
    resolves through the production gate, so the headline IS the shipping
    default. Each variant is measured under its own CompileTracker program
    name (``char_rnn[<impl>]``), so per-variant MFU flows through the rolling
    ``dl4j_step_mfu{fn}`` gauge."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm
    from deeplearning4j_tpu.ops import lstm as lstm_engine

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq))
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[ids])

    def measure(impl: str) -> dict:
        # the gate reads DL4J_LSTM_IMPL at trace time; a fresh conf per
        # variant keeps each measurement's trace (and donated buffers) its own
        saved = os.environ.get(lstm_engine.IMPL_ENV)
        os.environ[lstm_engine.IMPL_ENV] = impl
        try:
            conf = char_rnn_lstm(vocab_size=vocab, hidden=hidden,
                                 tbptt_length=seq)
            conf.backprop_type = "Standard"  # one jitted step over the window
            return _measure_multistep(conf, _stack(x, ksteps),
                                      _stack(x, ksteps), iters, warmup,
                                      track_fn=f"char_rnn[{impl}]")
        finally:
            if saved is None:
                os.environ.pop(lstm_engine.IMPL_ENV, None)
            else:
                os.environ[lstm_engine.IMPL_ENV] = saved

    results = {"scan": measure("scan"), "fused": measure("fused")}
    pallas_refusal = lstm_engine.pallas_refusal(hidden, seq, batch, vocab)
    pallas_engages = pallas_refusal is None
    if pallas_engages:
        results["pallas"] = measure("pallas")

    headline = lstm_impl
    if headline == "auto":
        headline = lstm_engine.resolve_impl(hidden, seq, batch, vocab,
                                            impl="auto")[0]
    if headline not in results:
        raise SystemExit(f"--lstm-impl {headline} cannot run here: "
                         f"{pallas_refusal}")
    r = dict(results[headline])
    # an armed attribution capture is consumed by the FIRST variant measured
    # (scan); hoist its fields so the headline row carries them whichever
    # variant wins
    for impl in ("scan", "fused", "pallas"):
        src = results.get(impl, {})
        if any(f in src for f in ("xplane_attribution", "profile_error")):
            for f in ("xplane_attribution", "profile_trace", "profile_error"):
                if f in src:
                    r.setdefault(f, src[f])
            r.setdefault("profile_variant", impl)
            break
    r["chars_per_sec"] = r["samples_per_sec"] * seq
    r["hidden"] = hidden
    r["lstm_impl"] = lstm_impl
    r["lstm_impl_selected"] = headline
    base = results["scan"]["samples_per_sec"]
    r["scan_samples_per_sec"] = round(base, 1)
    r["fused_samples_per_sec"] = round(results["fused"]["samples_per_sec"], 1)
    r["fused_speedup"] = round(results["fused"]["samples_per_sec"] / base, 3)
    if pallas_engages:
        r["pallas_samples_per_sec"] = round(
            results["pallas"]["samples_per_sec"], 1)
        r["pallas_speedup"] = round(
            results["pallas"]["samples_per_sec"] / base, 3)
    else:
        r["pallas_samples_per_sec"] = None
        r["pallas_speedup"] = None
    return r


def _bench_lm(model: str, batch: int, iters: int, ksteps: int,
              warmup: int) -> dict:
    """Shared LM measurement recipe: one-hot [B, T, V] next-token batches
    through the K-step multistep path (used by the transformer and MoE
    benches so the staging/sync methodology cannot diverge)."""
    conf, xs, ys, graph = flagship_setup(model, batch, ksteps)
    r = _measure_multistep(conf, xs, ys, iters, warmup, graph=graph)
    r["tokens_per_sec"] = r["samples_per_sec"] * LM_SEQ
    return r


def bench_transformer(batch: int, iters: int, ksteps: int,
                      warmup: int = 2, sharding: str = None) -> dict:
    """Decoder-only transformer LM over the flash-attention kernel
    (geometry fixed by flagship_setup: LM_VOCAB x LM_SEQ)."""
    if sharding:
        r = _bench_sharded_fit("transformer", batch, iters, ksteps, sharding,
                               warmup)
        r["tokens_per_sec"] = r["samples_per_sec"] * LM_SEQ
        return r
    return _bench_lm("transformer", batch, iters, ksteps, warmup)


def bench_moe(batch: int, iters: int, ksteps: int, warmup: int = 2) -> dict:
    """Switch-style MoE LM (residual attention + top-1 expert FFN blocks,
    load-balance aux loss included in the trained objective; geometry fixed
    by flagship_setup)."""
    return _bench_lm("moe", batch, iters, ksteps, warmup)


def bench_word2vec(batch: int, iters: int, ksteps: int, warmup: int = 2,
                   vocab: int = None, dim: int = 100,
                   negative: int = 5) -> dict:
    """SkipGram negative-sampling pair-kernel throughput (BASELINE config 4).

    Measures the jitted pair update the reference measures as words/sec in
    Word2Vec fit (reference SkipGram.java iterateSample): K scanned batches
    of skip-gram pairs per host dispatch, 5 negatives each.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nlp.learning import PairBatch, make_train_step

    from deeplearning4j_tpu.nlp import learning

    # DL4J_W2V_VOCAB: sweep vocab from the capture harness (the dense/scatter
    # crossover is vocab-dependent — dense rewrites the whole V x D table
    # per chunk; see nlp/learning.DENSE_UPDATE_MAX_VOCAB)
    vocab = vocab or int(os.environ.get("DL4J_W2V_VOCAB", "10000"))
    step = make_train_step(use_hs=False, negative=negative)
    # A/B twin: the opposite embedding-update path (dense one-hot matmul vs
    # XLA scatter) so one record carries both on-chip numbers
    auto_dense = learning.resolve_dense_update(vocab)
    step_alt = make_train_step(use_hs=False, negative=negative,
                               dense_update=not auto_dense)
    rng = np.random.default_rng(0)
    syn0 = jnp.asarray(rng.normal(size=(vocab, dim)).astype(np.float32) * 0.01)
    syn1 = jnp.zeros((1, dim), jnp.float32)  # HS table unused (negative sampling)
    syn1neg = jnp.zeros((vocab, dim), jnp.float32)
    cum_table = jnp.asarray((np.arange(1, vocab + 1) / vocab).astype(np.float32))

    def mk(shape, hi):
        return jnp.asarray(rng.integers(0, hi, shape).astype(np.int32))

    batches = PairBatch(
        ctx=mk((ksteps, batch, 1), vocab),
        ctx_mask=jnp.ones((ksteps, batch, 1), jnp.float32),
        target=mk((ksteps, batch), vocab),
        points=jnp.zeros((ksteps, batch, 1), jnp.int32),
        codes=jnp.zeros((ksteps, batch, 1), jnp.float32),
        code_mask=jnp.zeros((ksteps, batch, 1), jnp.float32),
        pair_mask=jnp.ones((ksteps, batch), jnp.float32),
        update_dest=mk((ksteps, batch, 1), vocab),
    )
    keys = jax.random.split(jax.random.PRNGKey(0), ksteps)

    def make_multi(stepfn):
        def multi(syn0, syn1, syn1neg, batches, keys):
            def body(carry, inp):
                s0, s1, sn = carry
                b, k = inp
                s0, s1, sn = stepfn(s0, s1, sn, cum_table, b,
                                    jnp.float32(0.025), k)
                return (s0, s1, sn), None

            carry, _ = jax.lax.scan(body, (syn0, syn1, syn1neg),
                                    (batches, keys))
            return carry

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def time_path(jit_multi, s0, s1, sn):
        for _ in range(warmup):
            s0, s1, sn = jit_multi(s0, s1, sn, batches, keys)
        float(s0[0, 0])  # hard sync: host read (see module docstring)
        t0 = time.perf_counter()
        for _ in range(iters):
            s0, s1, sn = jit_multi(s0, s1, sn, batches, keys)
        float(s0[0, 0])  # chain-forcing host read through donated buffers
        return time.perf_counter() - t0

    jit_multi = make_multi(step)
    # scan body counted once by cost analysis (see _xla_flops) -> x ksteps
    flops_per_dispatch = ksteps * _xla_flops(jit_multi, syn0, syn1, syn1neg,
                                             batches, keys)
    # copies BEFORE timing: both paths donate their input buffers
    alt0, alt1, altn = syn0.copy(), syn1.copy(), syn1neg.copy()
    dt = time_path(jit_multi, syn0, syn1, syn1neg)
    dt_alt = time_path(make_multi(step_alt), alt0, alt1, altn)
    dense_dt, scatter_dt = (dt, dt_alt) if auto_dense else (dt_alt, dt)
    flops_per_sec = flops_per_dispatch * iters / dt if flops_per_dispatch else 0.0
    pairs = batch * ksteps * iters
    return {
        "samples_per_sec": pairs / dt,
        "step_time_ms": dt / (iters * ksteps) * 1000,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "tflops_per_sec": round(flops_per_sec / 1e12, 4),
        "mfu": _mfu(flops_per_sec),
        "update_path": "dense" if auto_dense else "scatter",
        "dense_pairs_per_sec": round(pairs / dense_dt, 1),
        "scatter_pairs_per_sec": round(pairs / scatter_dt, 1),
        "dense_speedup": round(scatter_dt / dense_dt, 3),
    }


def bench_attention(batch: int, iters: int, ksteps: int, warmup: int = 2,
                    seq: int = None, heads: int = 8, dim: int = 64) -> dict:
    """flash_attention (Pallas) vs the identical XLA math, fwd+bwd, causal.

    Reports both paths' timings so one BASELINE.md line can say which path ran
    on the chip and its speedup (VERDICT round-1 item 3). `value` is the
    tokens/sec of whichever path `use_pallas()` selects in production.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    # DL4J_ATTN_SEQ: sweep the sequence length from the capture harness (the
    # pallas-vs-XLA crossover is seq-dependent; see FLASH_MIN_SEQ)
    seq = seq or int(os.environ.get("DL4J_ATTN_SEQ", "2048"))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, seq, heads, dim)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)

    def time_path(fn, want_flops: bool = False):
        def loss(q, k, v):
            def body(c, _):
                o = fn(c, k, v)
                return o, jnp.float32(0)
            o, _ = jax.lax.scan(body, q, None, length=ksteps)
            return jnp.sum(o * o)

        g = jax.jit(jax.grad(loss))
        # model flops are taken from the XLA path only: the Pallas program's
        # flops hide inside a custom call XLA can't cost, but the math is
        # identical, so the XLA count is the honest numerator for both paths.
        # Cost analysis counts the K-step scan body once (see _xla_flops), so
        # the count is already per-step — no division by ksteps.
        flops = _xla_flops(g, q, k, v) if want_flops else 0.0
        out = g(q, k, v)
        float(jnp.ravel(out)[0])  # hard sync (see module docstring)
        for _ in range(warmup - 1):
            out = g(q, k, v)
        float(jnp.ravel(out)[0])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g(q, k, v)
        float(jnp.ravel(out)[0])
        return (time.perf_counter() - t0) / (iters * ksteps), flops

    # the XLA twin materializes [B, H, T, T] scores; at long-context lengths
    # that alone exceeds HBM (16k: ~64 GiB vs 16 GB on v5e), so past a
    # score-bytes budget only the flash path runs and model flops come from
    # the standard analytic count instead of XLA cost analysis
    xla_score_bytes = 4 * batch * heads * seq * seq * 4  # fwd+bwd tiles, f32
    xla_feasible = xla_score_bytes < 8 * 1024 ** 3
    pallas_engaged = pk.use_pallas()
    if xla_feasible:
        t_xla, flops_per_step = time_path(
            lambda q, k, v: pk._attention_xla(q, k, v, True), want_flops=True)
    else:
        t_xla = None
        # fwd: QK^T + PV = 2 matmuls of 2*B*H*T^2*D flops; bwd ~2.5x fwd;
        # causal halves the realized work; x ksteps per dispatch
        flops_per_step = 3.5 * 2 * 2 * batch * heads * seq * seq * dim / 2 \
            * ksteps
    t_pallas = (time_path(lambda q, k, v: pk.flash_attention(q, k, v, True))[0]
                if pallas_engaged else None)

    t_prod = t_pallas if pallas_engaged else t_xla
    if t_prod is None:
        raise RuntimeError(
            f"seq {seq}: XLA attention infeasible ({xla_score_bytes >> 30} "
            "GiB scores) and pallas not engaged — nothing to measure")
    rec = {
        "samples_per_sec": batch * seq / t_prod,
        "step_time_ms": t_prod * 1000,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "seq": seq, "heads": heads, "head_dim": dim,
        "pallas_engaged": pallas_engaged,
        "xla_ms": round(t_xla * 1000, 3) if t_xla is not None else None,
        "pallas_ms": (round(t_pallas * 1000, 3)
                      if t_pallas is not None else None),
        "pallas_speedup": (round(t_xla / t_pallas, 3)
                           if (t_xla and t_pallas) else None),
        "flops_source": "xla_cost" if xla_feasible else "analytic",
    }

    # DL4J_FLASH_SWEEP=1: time the pallas kernels, forward and backward,
    # across explicit tile shapes, so one run says whether the tiles the
    # kernels choose from the shapes (pk._flash_tiles) are this chip's best
    # at this shape. Each timing call builds a fresh jit program.
    if pallas_engaged and os.environ.get("DL4J_FLASH_SWEEP") == "1":
        rec.update(_sweep_tiles(
            lambda bq, bk: time_path(_flash_at_tiles(bq, bk))[0], seq))
        chosen = pk._flash_tiles(seq, seq, dim, dim, q.dtype, backward=True)
        rec["chosen_tiles"] = "%dx%d" % chosen if chosen else None
    flops_per_sec = flops_per_step / t_prod if flops_per_step else 0.0
    rec["tflops_per_sec"] = round(flops_per_sec / 1e12, 4)
    rec["mfu"] = _mfu(flops_per_sec)
    return rec


def _flash_at_tiles(blk_q: int, blk_k: int):
    """Causal flash attention with the given tiles in the forward and the
    backward kernels (the one-kernel backward where the shape takes it),
    whatever the length gates of ``pk.flash_attention`` say."""
    import jax

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    tiles = {"blk_q": blk_q, "blk_k": blk_k}

    @jax.custom_vjp
    def attend(q, k, v):
        return pk._flash_forward(q, k, v, True, **tiles)[0]

    def fwd(q, k, v):
        out, lse = pk._flash_forward(q, k, v, True, **tiles)
        return out, (q, k, v, out, lse)

    def bwd(res, g):
        return pk._flash_backward(*res, g, True, **tiles)

    attend.defvjp(fwd, bwd)
    return attend


def _sweep_tiles(time_tiles, seq: int) -> dict:
    """Sweep flash tile shapes through ``time_tiles(blk_q, blk_k)`` (seconds
    a step). Per-tile failures (e.g. VMEM overflow) are isolated into the
    record — this runs unattended in the auto-capture window and must never
    kill the surrounding bench."""
    sweep = {}
    for bq, bk in ((128, 512), (256, 256), (512, 512), (512, 1024),
                   (1024, 512), (1024, 1024)):
        if seq % bq or seq % bk:
            continue
        try:
            sweep[f"{bq}x{bk}"] = round(time_tiles(bq, bk) * 1000, 3)
        except Exception as e:
            sweep[f"{bq}x{bk}"] = f"error: {e}"[:100]
    out = {"tile_sweep_ms": sweep}
    timed = {k: v for k, v in sweep.items() if isinstance(v, float)}
    if timed:
        best = min(timed, key=timed.get)
        out["best_tiles"] = best
        out["best_tiles_ms"] = timed[best]
    return out


def _staging_phase_seconds() -> float:
    """Cumulative dl4j_fit_phase_seconds{phase="staging"} across fit loops.
    Under device prefetch the phase records only the consumer-visible wait
    for the already-staged batch, so the fit-bench A/B shows it collapse
    versus the synchronous path (the PR's acceptance signal; the full
    prefetch counters land in the --telemetry-out snapshot)."""
    from deeplearning4j_tpu.observability import global_registry
    fam = global_registry().snapshot().get("dl4j_fit_phase_seconds", {})
    return sum(s.get("sum", 0.0) for s in fam.get("series", [])
               if s.get("labels", {}).get("phase") == "staging")


def _fit_ab(net, data, warmup_data) -> dict:
    """Shared fit-API measurement: warm up, run the epoch once with
    synchronous staging (prefetch off), then once with the default
    double-buffered device prefetch — the headline number. Same net, same
    batches; params advance across both passes (throughput-only bench)."""
    net.fit_iterator(iter(warmup_data))  # compile + warm up
    float(net.score_value)  # hard sync (see module docstring)

    net.prefetch_depth = 0
    s0 = _staging_phase_seconds()
    t0 = time.perf_counter()
    net.fit_iterator(iter(data))
    float(net.score_value)
    dt_sync = time.perf_counter() - t0
    staging_sync = _staging_phase_seconds() - s0

    net.prefetch_depth = type(net).prefetch_depth  # the shipped default
    s0 = _staging_phase_seconds()
    t0 = time.perf_counter()
    net.fit_iterator(iter(data))
    float(net.score_value)  # waits on the whole param-dependency chain
    dt = time.perf_counter() - t0
    return {
        "dt": dt,
        "staging_s_sync": round(staging_sync, 4),
        "staging_s_prefetch": round(_staging_phase_seconds() - s0, 4),
        "sync_step_time_ms_total": round(dt_sync * 1000, 2),
        "prefetch_speedup": round(dt_sync / dt, 3) if dt else None,
    }


def _sharded_param_bytes(rule_set: str):
    """Per-device sharded-param-bytes gauge value for one rule set (set by
    the compile seam when the wrapper's step compiles)."""
    from deeplearning4j_tpu.observability import global_registry
    fam = global_registry().snapshot().get(
        "dl4j_sharded_param_bytes_per_device", {})
    for s in fam.get("series", []):
        if s.get("labels", {}).get("rule_set") == rule_set:
            return int(s["value"])
    return None


def _bench_sharded_fit(model: str, batch: int, iters: int, ksteps: int,
                       sharding: str, warmup: int = 1) -> dict:
    """--sharding axis: the same flagship geometry trained through the
    partition-rule engine's compile seam (ParallelWrapper.fit on a named
    mesh) instead of the single-device path. One record per rule set so
    bench_log.jsonl carries per-mode samples/s AND the per-device param
    footprint the rule set actually achieved (the zero3 acceptance signal:
    ~1/N of the replicated bytes)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    n_dev = len(jax.devices())
    if sharding == "dp_tp":
        if n_dev < 2 or n_dev % 2:
            raise ValueError(
                f"--sharding dp_tp needs an even device count, have {n_dev}")
        mesh = build_mesh({"data": n_dev // 2, "model": 2})
    else:
        mesh = build_mesh({"data": n_dev})

    rng = np.random.default_rng(0)
    if model == "fit_resnet50":
        from deeplearning4j_tpu.models.resnet import resnet50
        from deeplearning4j_tpu.nn.graph_network import ComputationGraph
        x = rng.normal(size=(batch, 224, 224, 3)).astype(np.float32)
        y = _onehot_batch(rng, batch, 1000)
        net = ComputationGraph(resnet50(n_classes=1000, image_size=224)).init()
    else:  # transformer
        from deeplearning4j_tpu.models.transformer import transformer_lm
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        ids = rng.integers(0, LM_VOCAB, (batch, LM_SEQ))
        x = y = np.eye(LM_VOCAB, dtype=np.float32)[ids]
        net = MultiLayerNetwork(transformer_lm(
            vocab_size=LM_VOCAB, width=256, n_layers=4, n_heads=4,
            max_len=LM_SEQ)).init()
    net.dispatch_ksteps = ksteps

    n_batches = iters * ksteps
    data = [DataSet(x, y) for _ in range(n_batches)]
    pw = (ParallelWrapper.builder(net).mesh(mesh).prefetch_buffer(2)
          .sharding(sharding).build())

    pw.fit(ListDataSetIterator(data[:max(1, warmup) * ksteps]))
    jax.block_until_ready(net.params_list)  # compile + warm up
    t0 = time.perf_counter()
    pw.fit(ListDataSetIterator(data))
    jax.block_until_ready(net.params_list)
    dt = time.perf_counter() - t0
    return {
        "samples_per_sec": batch * n_batches / dt,
        "step_time_ms": dt / n_batches * 1000,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "tflops_per_sec": 0.0, "mfu": 0.0,
        "api": "ParallelWrapper.fit",
        "sharding": sharding,
        "mesh": {k: int(v) for k, v in zip(mesh.axis_names,
                                           mesh.devices.shape)},
        "param_bytes_per_device": _sharded_param_bytes(sharding),
    }


def bench_fit_resnet50(batch: int, iters: int, ksteps: int,
                       warmup: int = 1, sharding: str = None) -> dict:
    """The PRODUCTION fit(DataSetIterator) path on ResNet-50 — not the raw
    multistep kernel. Measures what a user of the documented API gets:
    host-staged numpy batches, K-step grouping + stacking inside
    fit_iterator, lazy score sync (VERDICT round-2 item 2's acceptance bar:
    within ~15% of the raw multistep bench)."""
    import jax.numpy as jnp

    if sharding:
        return _bench_sharded_fit("fit_resnet50", batch, iters, ksteps,
                                  sharding, warmup)

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.graph_network import ComputationGraph

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 224, 224, 3)).astype(np.float32)
    y = _onehot_batch(rng, batch, 1000)
    conf = resnet50(n_classes=1000, image_size=224)
    net = ComputationGraph(conf).init()
    net.dispatch_ksteps = ksteps
    from deeplearning4j_tpu.common import get_policy
    if get_policy().compute_dtype == jnp.bfloat16:
        # compute casts to bf16 anyway; halve the host->device wire bytes
        net.stage_dtype = jnp.bfloat16
    n_batches = iters * ksteps
    data = [DataSet(x, y) for _ in range(n_batches)]

    ab = _fit_ab(net, data, data[:warmup * ksteps])
    dt = ab.pop("dt")
    return {
        "samples_per_sec": batch * n_batches / dt,
        "step_time_ms": dt / n_batches * 1000,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "tflops_per_sec": 0.0, "mfu": 0.0,  # same program as resnet50 bench
        "api": "ComputationGraph.fit_iterator",
        **ab,
    }


def bench_fit_lenet(batch: int, iters: int, ksteps: int,
                    warmup: int = 1) -> dict:
    """Production MultiLayerNetwork.fit_iterator throughput on LeNet."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.lenet import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 784)).astype(np.float32)
    y = _onehot_batch(rng, batch, 10)
    net = MultiLayerNetwork(lenet_mnist()).init()
    net.dispatch_ksteps = ksteps
    from deeplearning4j_tpu.common import get_policy
    if get_policy().compute_dtype == jnp.bfloat16:
        net.stage_dtype = jnp.bfloat16  # halve wire bytes (see resnet50 fit)
    n_batches = iters * ksteps
    data = [DataSet(x, y) for _ in range(n_batches)]

    ab = _fit_ab(net, data, data[:warmup * ksteps])
    dt = ab.pop("dt")
    return {
        "samples_per_sec": batch * n_batches / dt,
        "step_time_ms": dt / n_batches * 1000,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "tflops_per_sec": 0.0, "mfu": 0.0,
        "api": "MultiLayerNetwork.fit_iterator",
        **ab,
    }


def bench_serve(batch, iters, ksteps, serve_qps=None, serve_latency_ms=None,
                serve_batching=None, serve_quant=None,
                serve_replicas=None, serve_sharding=None,
                compile_cache=None, decode_kv=None, decode_page_size=None,
                decode_spec_draft=None, serve_tracing=None,
                serve_autoscale=None):
    """Micro-batching A/B on the serving engine (ISSUE 9 headline).

    Unlike the fit benches this is fully CPU-measurable: the win is
    dispatch amortization, not MXU width. The harness first calibrates the
    UNBATCHED saturation point (closed-loop peak through the real HTTP
    stack), then offers 1.5x that rate open-loop to both configurations —
    so "unbatched saturates" holds on any host without hand-tuned QPS —
    and reports the batched achieved throughput as the headline. The full
    A/B record (p50/p99, achieved QPS, batch occupancy, recompile count)
    is appended to scripts/serve_load.jsonl next to bench_log, and
    steady-state health is pinned by recompiles == bucket count.

    Round 11 adds the DECODE section: the token-streaming A/B
    (``run_decode_ab`` on a char-RNN) at one fixed offered sessions/sec
    for every phase — iteration-level continuous batching vs static
    request-level batching, and int8 weight-only decode vs dense. The
    ``serve_batching``/``serve_quant`` axes pick which phase supplies the
    row's decode_tokens_per_sec / decode_ttft_p99_ms numbers
    (config-distinct: a static or int8 capture must never stand in for
    the continuous dense row), and the cross-phase ratios ride along.

    Round 12 adds the REPLICA SCALING section: QPS-vs-replicas through the
    least-queue-depth router (``run_replica_ab``) at equal offered load,
    calibrated off the single-replica batched saturation point. The
    ``serve_replicas``/``serve_sharding`` axes are config-distinct; with
    ``serve_sharding="dp_tp"`` each replica pins its params sharded over
    its own mesh slice (the parent driver forces an 8-device CPU host
    platform for sharded rows, like ps_async). Per-replica steady-state
    health is pinned by recompiles == bucket count PER replica.

    Round 15 adds the TIME-TO-READY section: wall time of one full
    registration with parallel AOT warmup over every micro-batch bucket up
    to 16, cold (executable cache off — every bucket is an XLA compile)
    vs warm (every bucket deserialized from the compile cache). The warm
    number is what an elastic respawn or replica spawn actually pays; the
    ``compile_cache`` axis picks which one is the row's headline
    ``time_to_ready_s``.

    Round 17 adds the TRACING OVERHEAD section: the same warm MicroBatcher
    submit loop timed with the trace store disabled (every span a no-op
    singleton) vs enabled at 100% sampling, reported as
    ``trace_overhead_pct`` — the serve-path cost of always-on request
    tracing, budgeted at <= 2% by the tier-1 contract test. The
    ``serve_tracing`` axis is config-distinct (an untraced capture never
    stands in for the tracing-on default row).

    Round 18 adds the AUTOSCALE section (``serve_autoscale="on"``): the
    open-loop ramp A/B (``run_ramp_ab``) — a 10x offered-load swing
    against the SLO-driven autoscaled fleet vs a static fleet sized to
    the autoscaled run's time-weighted average replica count. The row
    carries ``ramp_slo_violation_seconds_auto/static`` (the acceptance
    floor), ``ramp_lost_requests`` (drain-without-loss scale-in) and
    ``ramp_scale_out_latency_s`` (warm-path decision-to-routable). Off
    by default: the ramp costs ~15s of wall clock.
    """
    import numpy as np

    from deeplearning4j_tpu.keras_server import (InferenceServer,
                                                 ModelRegistry)
    from deeplearning4j_tpu.keras_server.loadgen import (
        run_ab, run_closed_loop, run_closed_loop_proc)
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    # deliberately small: serving capacity on tiny per-request batches is
    # dispatch-overhead-bound, which is exactly what micro-batching
    # amortizes; a wide model just re-measures matmul FLOPs
    n_in, hidden, n_out = 16, 128, 8
    conf = (NeuralNetConfiguration.builder()
            .seed(7).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_in=hidden, n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_in=hidden, n_out=n_out, loss="mcxent",
                               activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()
    example = np.random.default_rng(0).normal(
        size=(1, n_in)).astype(np.float32)

    if serve_qps:
        qps = float(serve_qps)
        unbatched_peak = None
    else:
        # calibrate: unbatched closed-loop peak (client out-of-process,
        # like the measured phases) = the saturation point
        registry = ModelRegistry()
        registry.register("serve_mlp", net, version="cal")
        cal = InferenceServer(registry, max_batch=1, max_latency_s=0.0,
                              max_queue=512).start()
        try:
            run_closed_loop(cal.port, "serve_mlp", example, workers=1,
                            requests_per_worker=8)  # warm the compile
            peak = run_closed_loop_proc(cal.port, "serve_mlp",
                                        example.shape, workers=8,
                                        requests_per_worker=150)
        finally:
            cal.stop()
        unbatched_peak = peak["achieved_qps"]
        qps = max(50.0, round(1.5 * unbatched_peak, 1))

    record_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts",
        "serve_load.jsonl")
    rec = run_ab(net, model="serve_mlp", qps=qps,
                 duration_s=max(float(iters), 1.0), max_batch=batch,
                 max_latency_s=(serve_latency_ms or 4.0) / 1e3,
                 max_queue=2048, example=example, record_path=record_path)
    batched, unbatched = rec["batched"], rec["unbatched"]

    # decode section: continuous-vs-static + int8-vs-dense token streaming
    from deeplearning4j_tpu.keras_server.loadgen import run_decode_ab
    from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm
    dec_net = MultiLayerNetwork(char_rnn_lstm(32, hidden=64, layers=2)).init()
    drec = run_decode_ab(dec_net, model="bench_serve_decode", slots=8,
                         n_sessions=256, record_path=record_path)
    serve_batching = serve_batching or "continuous"
    serve_quant = serve_quant or "none"
    phase = (drec["int8"] if serve_quant == "int8"
             else drec[serve_batching])
    decode = {
        "serve_batching": serve_batching,
        "serve_quant": serve_quant,
        "decode_tokens_per_sec": phase["tokens_per_sec"],
        "decode_ttft_p99_ms": phase["ttft_p99_ms"],
        "decode_offered_sps": drec["offered_sps"],
        "decode_slot_occupancy": phase["mean_occupancy"],
        "decode_recompiles": phase["recompiles"],
        "decode_bucket_count": phase["bucket_count"],
        "decode_speedup": drec["tokens_per_sec_ratio"],
        "decode_ttft_p99_improvement": drec["ttft_p99_ratio"],
        "int8_prob_drift": drec["int8_vs_dense"]["mean_prob_drift"],
        "int8_top1_agreement": drec["int8_vs_dense"]["top1_agreement"],
        "int8_param_bytes_ratio": drec["int8_vs_dense"]["param_bytes_ratio"],
    }

    # paged KV memory plane + speculative decode section (ISSUE 16): the
    # dense-vs-paged A/B runs at EQUAL device state bytes (the pool is
    # sized to the dense engine's KV block, minus the trash page), so
    # sessions_ratio is the sessions-per-chip headline, and the spec A/B
    # measures the draft-verify speedup at whatever acceptance the tiny
    # draft earns — both streams pinned bitwise against the dense/greedy
    # oracle inside the harness itself
    from deeplearning4j_tpu.keras_server.loadgen import (run_paged_ab,
                                                         run_spec_ab)
    from deeplearning4j_tpu.models.transformer import transformer_lm
    decode_kv = decode_kv or "paged"
    page_size = int(decode_page_size or 16)
    spec_draft = decode_spec_draft or "tiny"
    tf_net = MultiLayerNetwork(transformer_lm(
        vocab_size=32, width=32, n_layers=2, n_heads=2, max_len=128,
        seed=5)).init()
    prec = run_paged_ab(tf_net, model="bench_serve_paged", dense_slots=4,
                        max_context=128, page_size=page_size,
                        n_sessions=24, max_new_tokens=16,
                        record_path=record_path)
    paged_sec = {
        "decode_kv": decode_kv,
        "decode_page_size": page_size,
        "decode_spec_draft": spec_draft,
        "paged_sessions_ratio": prec["sessions_ratio"],
        "paged_state_bytes": prec["paged"]["state_bytes"],
        "dense_state_bytes": prec["dense"]["state_bytes"],
        "paged_bitwise_equal": prec["bitwise_equal"],
        "paged_tokens_per_sec": prec[decode_kv]["tokens_per_sec"],
        "paged_prefix_share_ratio": prec["paged"]["prefix_share_ratio"],
        "spec_tokens_per_sec": None,
        "spec_speedup": None,
        "spec_acceptance": None,
        "spec_bitwise_equal": None,
    }
    if spec_draft != "none":
        draft_net = MultiLayerNetwork(transformer_lm(
            vocab_size=32, width=16, n_layers=1, n_heads=2, max_len=128,
            seed=9)).init()
        srec = run_spec_ab(tf_net, draft_net, model="bench_serve_spec",
                           slots=4, max_context=128, n_sessions=12,
                           max_new_tokens=16, record_path=record_path)
        paged_sec.update({
            "spec_tokens_per_sec": srec["spec"]["tokens_per_sec"],
            "spec_speedup": srec["tokens_per_sec_ratio"],
            "spec_acceptance": srec["acceptance"],
            "spec_bitwise_equal": srec["bitwise_equal"],
        })

    # replica scaling section: N pinned programs behind the least-queue
    # router. Wider than the dispatch-bound A/B model on purpose — replica
    # scale-out multiplies DEVICE capacity, so the scaled resource must be
    # device time; on the tiny MLP above both phases would sit on the same
    # host-dispatch ceiling and the ratio would measure nothing
    from deeplearning4j_tpu.keras_server.loadgen import run_replica_ab
    n_rep = int(serve_replicas or 2)
    shard = None if serve_sharding in (None, "none") else serve_sharding
    rn_in, rhidden, rn_out = 64, 256, 8
    rconf = (NeuralNetConfiguration.builder()
             .seed(11).learning_rate(0.1).updater("adam")
             .weight_init("xavier")
             .list()
             .layer(DenseLayer(n_in=rn_in, n_out=rhidden, activation="relu"))
             .layer(DenseLayer(n_in=rhidden, n_out=rhidden,
                               activation="relu"))
             .layer(DenseLayer(n_in=rhidden, n_out=rhidden,
                               activation="relu"))
             .layer(OutputLayer(n_in=rhidden, n_out=rn_out, loss="mcxent",
                                activation="softmax"))
             .build())
    rep_net = MultiLayerNetwork(rconf).init()
    rep_example = np.random.default_rng(1).normal(
        size=(1, rn_in)).astype(np.float32)
    # calibrate the single-replica BATCHED saturation point, then offer 2x
    # it to both phases: the baseline saturates, the scaled phase shows
    # its real headroom at the same offered load
    registry = ModelRegistry()
    registry.register("serve_rep", rep_net, version="cal")
    cal = InferenceServer(registry, max_batch=batch,
                          max_latency_s=(serve_latency_ms or 4.0) / 1e3,
                          max_queue=2048).start()
    try:
        run_closed_loop(cal.port, "serve_rep", rep_example, workers=2,
                        requests_per_worker=8)
        rpeak = run_closed_loop_proc(cal.port, "serve_rep",
                                     rep_example.shape, workers=8,
                                     requests_per_worker=100)
    finally:
        cal.stop()
    rep_qps = max(50.0, round(2.0 * rpeak["achieved_qps"], 1))
    rrec = run_replica_ab(
        rep_net, model="serve_rep", replicas=n_rep, sharding=shard,
        qps=rep_qps, duration_s=max(float(iters), 1.0), max_batch=batch,
        max_latency_s=(serve_latency_ms or 4.0) / 1e3, max_queue=4096,
        example=rep_example, record_path=record_path)
    replica_sec = {
        "serve_replicas": n_rep,
        "serve_sharding": serve_sharding or "none",
        "replica_offered_qps": rep_qps,
        "replica_qps_1": rrec["replicas_1"]["achieved_qps"],
        "replica_qps_n": rrec["replicas_n"]["achieved_qps"],
        "replica_speedup": rrec["replica_speedup"],
        "replica_recompiles_match_buckets":
            rrec["recompiles_match_buckets"],
    }

    # time-to-ready section: cold vs warm-start pin with full bucket
    # warmup. Three pins against a fresh store: cache off (baseline XLA
    # compiles), cache on (populates the store, untimed headline-wise),
    # cache on again (the measured warm pin — every bucket resolves via
    # deserialize_and_load, which is what a respawn/spawn pays).
    import tempfile

    ready_max_batch = 16
    compile_cache = compile_cache or "on"

    def _pin_once() -> float:
        reg = ModelRegistry(warmup_max_batch=ready_max_batch)
        fresh = MultiLayerNetwork(conf).init()
        t0 = time.perf_counter()
        reg.register("ready_mlp", fresh)
        return time.perf_counter() - t0

    def _with_cache(value, directory, fn):
        saved = {k: os.environ.get(k)
                 for k in ("DL4J_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR")}
        os.environ["DL4J_COMPILE_CACHE"] = value
        os.environ["JAX_COMPILATION_CACHE_DIR"] = directory
        try:
            return fn()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    with tempfile.TemporaryDirectory(prefix="dl4j-xc-bench-") as xcdir:
        cold_s = _with_cache("0", xcdir, _pin_once)
        _with_cache("1", xcdir, _pin_once)   # populate the store
        warm_s = _with_cache("1", xcdir, _pin_once)
    ready = {
        "compile_cache": compile_cache,
        "warmup_max_batch": ready_max_batch,
        "warmup_buckets": len(ModelRegistry.warmup_buckets(ready_max_batch)),
        "time_to_ready_cold_s": round(cold_s, 4),
        "time_to_ready_warm_s": round(warm_s, 4),
        "time_to_ready_s": round(
            cold_s if compile_cache == "off" else warm_s, 4),
        "time_to_ready_speedup": (round(cold_s / warm_s, 2)
                                  if warm_s > 0 else None),
    }
    # tracing overhead section: A/B the in-process submit path (registry +
    # MicroBatcher, no HTTP — socket jitter would swamp a 2% signal) with
    # the trace store disabled vs enabled at 100% sampling. Warm first so
    # neither phase pays the bucket compile.
    from deeplearning4j_tpu.observability.tracing import (
        TraceStore, global_trace_store, set_global_trace_store, trace_span)

    serve_tracing = serve_tracing or "on"
    tr_registry = ModelRegistry()
    tr_registry.register("trace_mlp", MultiLayerNetwork(conf).init())
    tr_example = np.random.default_rng(3).normal(
        size=(1, n_in)).astype(np.float32)
    from deeplearning4j_tpu.keras_server.batcher import MicroBatcher
    tr_batcher = MicroBatcher(tr_registry, max_batch=8,
                              max_latency_s=0.0005, max_queue=1024)
    tr_requests = 400

    def _trace_phase() -> float:
        # each submit runs under a per-request root span, mirroring the
        # HTTP handler's `http /v1/predict` root (admission + batch.queue
        # become children, not root traces of their own); with the store
        # disabled trace_span returns the no-op singleton so the off
        # phase pays nothing
        for f in [tr_batcher.submit("trace_mlp", tr_example)
                  for _ in range(32)]:
            f.result(timeout=30)  # warm: compile + settle the dispatcher
        t0 = time.perf_counter()
        for _ in range(tr_requests // 8):
            futs = []
            for _ in range(8):
                with trace_span("bench.request"):
                    futs.append(tr_batcher.submit("trace_mlp", tr_example))
            for f in futs:
                f.result(timeout=30)
        return time.perf_counter() - t0

    saved_store = global_trace_store()
    try:
        set_global_trace_store(TraceStore(enabled=False))
        trace_off_s = _trace_phase()
        set_global_trace_store(
            TraceStore(enabled=True, sample=1.0, capacity=256))
        trace_on_s = _trace_phase()
    finally:
        set_global_trace_store(saved_store)
        tr_batcher.close()
    # the in-process A/B isolates the absolute tracing cost per request
    # (HTTP jitter would swamp it); the pct expresses that cost against
    # the REAL serve-path request latency from the batched phase above
    trace_us = max(0.0, (trace_on_s - trace_off_s) / tr_requests * 1e6)
    tr_p50_us = batched["p50_ms"] * 1e3
    trace_sec = {
        "serve_tracing": serve_tracing,
        "trace_cost_us_per_request": round(trace_us, 1),
        "trace_overhead_pct": (round(trace_us / tr_p50_us * 100.0, 2)
                               if tr_p50_us > 0 else None),
    }

    # autoscale ramp section: only when armed — the three-segment ramp
    # plus the static control is the most expensive serve phase by far
    serve_autoscale = serve_autoscale or "off"
    autoscale_sec = {"serve_autoscale": serve_autoscale}
    if serve_autoscale == "on":
        from deeplearning4j_tpu.keras_server.loadgen import run_ramp_ab
        ramp_low = max(5.0, round(0.15 * unbatched_peak, 1))
        ramp = run_ramp_ab(
            net, model="ramp_mlp", qps_low=ramp_low,
            qps_high=10.0 * ramp_low, segment_s=2.0,
            slo_ms=float(os.environ.get("DL4J_SLO_P99_MS", "250")),
            min_replicas=1, max_replicas=4, cooldown_s=1.0,
            interval_s=0.2, max_batch=batch, max_queue=64,
            example=example, workers=16, record_path=record_path)
        autoscale_sec.update({
            "ramp_qps_low": ramp["qps_low"],
            "ramp_qps_high": ramp["qps_high"],
            "ramp_avg_replicas_auto": ramp["avg_replicas_auto"],
            "ramp_static_replicas": ramp["static_replicas"],
            "ramp_slo_violation_seconds_auto":
                ramp["slo_violation_seconds_auto"],
            "ramp_slo_violation_seconds_static":
                ramp["slo_violation_seconds_static"],
            "ramp_lost_requests": ramp["lost_requests"],
            "ramp_scale_out_latency_s": ramp["scale_out_latency_s"],
            "ramp_scale_events": ramp["scale_events"],
            "ramp_auto_beats_static": ramp["auto_beats_static"],
        })

    return {
        "samples_per_sec": batched["achieved_qps"],  # headline: batched QPS
        "offered_qps": qps,
        "calibrated_unbatched_peak_qps": unbatched_peak,
        "unbatched_qps": unbatched["achieved_qps"],
        "batched_speedup": rec["batched_speedup"],
        "p50_ms_unbatched": unbatched["p50_ms"],
        "p99_ms_unbatched": unbatched["p99_ms"],
        "p50_ms_batched": batched["p50_ms"],
        "p99_ms_batched": batched["p99_ms"],
        "p99_improvement": rec["p99_improvement"],
        "batch_occupancy": batched["batch_occupancy"],
        "bucket_count": batched["bucket_count"],
        "recompiles": batched["recompiles"],
        "max_batch": batch,
        "serve_record": record_path,
        **decode,
        **paged_sec,
        **replica_sec,
        **ready,
        **trace_sec,
        **autoscale_sec,
        "api": "keras_server.InferenceServer /v1/predict + /v1/generate",
    }


class _StragglerIterator:
    """Sync-DP straggler model: the barrier waits for the slowest worker
    every step, so one k×-slow worker stalls EVERY iteration by its extra
    step time. Injected as a per-batch sleep in front of the fused sync
    step (a fused DP step has no per-worker thread to slow down)."""

    def __init__(self, batches, stall_s: float):
        self._batches = batches
        self._stall = stall_s

    def reset(self):
        pass

    def __iter__(self):
        for ds in self._batches:
            time.sleep(self._stall)
            yield ds


def _transport_push_ab(base_params, workers: int, rounds: int = 60) -> dict:
    """Push-window throughput twin for the host data plane (ISSUE 14): W
    concurrent workers hammering pull+push rounds of the flat LeNet param
    vector through a real TCP frontend, once over plain TCP frames and once
    over the shared-memory rings. Same server code, same arithmetic — the
    ratio is pure byte-plane cost. Staleness cap is effectively off so
    every push applies (throughput, not convergence, is under test)."""
    import threading

    from deeplearning4j_tpu.parallel import ps_transport as pst
    from deeplearning4j_tpu.parallel.param_server import (ParameterServer,
                                                          flatten_tree)

    flat, _ = flatten_tree(base_params)
    delta = np.zeros_like(flat)

    def run(kind: str):
        srv = ParameterServer([flat.copy()], staleness_cap=1 << 40)
        fe = pst.ParameterServerTcpFrontend(srv).start()
        cls = pst.ShmTransport if kind == "shm" else pst.TcpTransport
        transports = [cls(("127.0.0.1", fe.port)) for _ in range(workers)]
        try:
            for t in transports:
                t.pull()  # connect (and for shm: negotiate) untimed
            if kind == "shm" and not all(
                    t.shm_active for t in transports):
                return None  # negotiation refused (no /dev/shm): no number
            barrier = threading.Barrier(workers + 1)

            def work(t):
                v, _ = t.pull()
                barrier.wait()
                for _ in range(rounds):
                    v = t.push(delta, v).version
                barrier.wait()

            threads = [threading.Thread(target=work, args=(t,), daemon=True)
                       for t in transports]
            for th in threads:
                th.start()
            barrier.wait()
            t0 = time.perf_counter()
            barrier.wait()
            dt = time.perf_counter() - t0
            for th in threads:
                th.join(timeout=10.0)
            return workers * rounds / dt
        finally:
            for t in transports:
                t.close()
            fe.stop()

    tcp = run("tcp")
    shm = run("shm")
    return {
        "push_ab_workers": workers,
        "push_ab_rounds": rounds,
        "push_ab_param_bytes": int(flat.nbytes),
        "tcp_push_windows_per_sec": round(tcp, 1) if tcp else None,
        "shm_push_windows_per_sec": round(shm, 1) if shm else None,
        "shm_push_speedup": (round(shm / tcp, 3) if (tcp and shm) else None),
    }


def bench_ps_async(batch, iters, ksteps, ps_workers=None, ps_straggler=None,
                   ps_transport=None):
    """Straggler A/B: async parameter server vs the sync-DP barrier
    (ISSUE 10 headline). CPU-measured by design, like serve: the win is
    host-side orchestration (no per-step barrier), not MXU width — the
    parent driver forces JAX_PLATFORMS=cpu + an 8-device host platform so
    the sync phase gets a real data mesh on any box.

    Phase A (throughput + time-to-loss): one worker of W sleeps k× the
    median per-step delay. Sync = ParallelWrapper over a data mesh at equal
    worker count, stalled every step by the straggler's extra time (the
    barrier semantic); async = ParameterServerParallelWrapper with the same
    sleeps injected per worker thread — the straggler only slows its own
    pushes. Phase B (loss parity at equal samples): 2 separate-process TCP
    workers with bf16 delta compression vs a single-process sync-DP fit of
    the same LeNet on the same batches — 2 epochs each, so parity is
    measured at the label-noise plateau both paths converge to (comparing
    mid-descent would measure descent speed, not fidelity).

    ISSUE 14 adds the host-data-plane section: ``ps_transport`` picks the
    wire the phase-B workers ride ("tcp" frames or the "shm" rings), and
    every record carries the W-worker push-window throughput twin
    (``tcp_push_windows_per_sec`` / ``shm_push_windows_per_sec`` /
    ``shm_push_speedup``) so one row proves what the shared-memory plane
    buys at this worker count.
    """
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.lenet import lenet_mnist
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.param_server import (
        ParameterServerParallelWrapper)
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    W = int(ps_workers or 4)
    k = float(ps_straggler or 4.0)
    transport = ps_transport or "tcp"
    delay_s = 0.02  # median per-step worker delay; straggler sleeps k*this
    push_frequency, staleness_cap = 4, 8
    n_batches = iters * ksteps

    # learnable 10-class cluster data on the LeNet input shape, so the
    # time-to-loss and parity numbers track real convergence; 25% label
    # noise gives the loss an irreducible floor (~1.0 nats) both paths
    # plateau at — a relative parity gap near zero loss is meaningless
    rng = np.random.default_rng(0)
    means = rng.normal(0.0, 1.0, (10, 784)).astype(np.float32)
    data = []
    for _ in range(n_batches):
        lab = rng.integers(0, 10, batch)
        x = (means[lab] + rng.normal(0, 0.5, (batch, 784))).astype(np.float32)
        noisy = np.where(rng.random(batch) < 0.25,
                         rng.integers(0, 10, batch), lab)
        data.append(DataSet(x, np.eye(10, dtype=np.float32)[noisy]))
    gx = np.concatenate([d.features for d in data])
    gy = np.concatenate([d.labels for d in data])

    base = MultiLayerNetwork(lenet_mnist()).init()

    # --- phase A sync: the barrier pays the straggler's extra time per step
    sync_net = base.clone()
    mesh = build_mesh({"data": min(W, len(jax.devices()))})
    pw = ParallelWrapper(sync_net, prefetch=0, mesh=mesh)
    pw.fit(ListDataSetIterator(data[:2]))  # compile outside the timed loop
    t0 = time.perf_counter()
    pw.fit(_StragglerIterator(data, k * delay_s))  # barrier = slowest worker
    sync_dt = time.perf_counter() - t0
    sync_loss = float(sync_net.score(gx, gy))

    # --- phase A async: same sleeps per worker thread, no barrier
    async_net = base.clone()
    delays = [k * delay_s] + [delay_s] * (W - 1)
    ps = (ParameterServerParallelWrapper.builder(async_net)
          .workers(W).push_frequency(push_frequency)
          .staleness(staleness_cap).transport("inproc")
          .worker_delays(*delays).build())
    ps.fit(ListDataSetIterator(data[:2]))  # compile outside the timed loop
    t0 = time.perf_counter()
    ps.fit(ListDataSetIterator(data))
    async_dt = time.perf_counter() - t0
    async_loss = float(async_net.score(gx, gy))

    # --- phase B: 2-process TCP async vs single-process sync-DP, equal
    # samples from the same init (loss-parity proof; bf16 deltas on the wire)
    tcp_net = base.clone()
    # push_frequency 2 here: shorter windows keep wire staleness ~0-1 and
    # let the background puller rebase mid-window, which is what holds the
    # parity gap down (measured: 2.8% at pf=2 vs 4.6% at pf=4)
    tcp = (ParameterServerParallelWrapper.builder(tcp_net)
           .workers(2).push_frequency(2)
           .staleness(staleness_cap).transport(transport)
           .compression("bf16").build())
    t0 = time.perf_counter()
    tcp.fit(ListDataSetIterator(data), epochs=2)
    tcp_dt = time.perf_counter() - t0
    oracle = base.clone()
    oracle.fit_iterator(ListDataSetIterator(data), epochs=2)
    tcp_loss = float(tcp_net.score(gx, gy))
    sync_dp_loss = float(oracle.score(gx, gy))

    r = {
        "samples_per_sec": batch * n_batches / async_dt,
        "sync_samples_per_sec": batch * n_batches / sync_dt,
        "async_speedup": (batch * n_batches / async_dt)
        / (batch * n_batches / sync_dt),
        "async_time_s": async_dt, "sync_time_s": sync_dt,
        "async_loss": async_loss, "sync_loss": sync_loss,
        "workers": W, "straggler_factor": k,
        "straggler_base_delay_ms": delay_s * 1e3,
        "push_frequency": push_frequency, "staleness_cap": staleness_cap,
        "applied_pushes": ps.server.pushes,
        "rejected_pushes": ps.server.rejected,
        "tcp_workers": 2, "tcp_epochs": 2, "tcp_time_s": tcp_dt,
        "tcp_async_loss": tcp_loss, "sync_dp_loss": sync_dp_loss,
        "tcp_loss_gap": abs(tcp_loss / sync_dp_loss - 1.0),
        "tcp_worker_stats": tcp.worker_stats,
        "ps_transport": transport,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "api": "parallel.ParameterServerParallelWrapper",
        **_transport_push_ab(base.params_list, W),
    }
    _append_ps_ab("ps_async", r)
    return r


def _append_ps_ab(model: str, record: dict) -> None:
    """Append one PS A/B row to scripts/ps_ab.jsonl: the straggler record
    (ps_async, ISSUE 10) and the worker-kill record (elastic) accrete side
    by side so the fleet-health story is one file. Measurement log only —
    never read back for bench_log config matching."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scripts", "ps_ab.jsonl")
    row = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "model": model, "record": record}
    try:
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
    except OSError:  # lint: swallowed-exception-ok (read-only checkout must not fail the bench)
        pass


def _bench_elastic_once(batch, iters, ksteps, elastic_workers=None,
                        elastic_kill=None, ps_transport=None,
                        compile_cache_label=None):
    """Worker-kill A/B on the elastic trainer (ISSUE 13 headline):
    SIGKILL one of W separate-process workers mid-fit and measure the
    throughput dip plus the recovery time back to 90% of the pre-kill
    rate (lease expiry -> shard handoff -> replacement registers,
    restores from the PS, and resumes the shard at the committed broker
    offset). CPU-measured by design like ps_async: the number under test
    is host-side membership/handoff orchestration, not MXU width.

    Throughput proxy: the PS version counter advances once per applied
    push window (push_frequency steps x batch samples), sampled on a
    timeline thread; rates are versions/sec over a sliding window scaled
    to samples/sec. The kill fires when the fleet reaches
    ``elastic_kill`` of the expected total push windows.
    """
    import threading

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.elastic import ElasticTrainer

    W = int(elastic_workers or 4)
    kill_frac = float(elastic_kill if elastic_kill is not None else 0.5)
    transport = ps_transport or "tcp"
    push_frequency, delay_s = 4, 0.2
    n_batches = iters * ksteps

    # learnable 10-class cluster data so the loss trend stays meaningful
    rng = np.random.default_rng(0)
    means = rng.normal(0.0, 1.0, (10, 64)).astype(np.float32)
    data = []
    for _ in range(n_batches):
        lab = rng.integers(0, 10, batch)
        x = (means[lab] + rng.normal(0, 0.5, (batch, 64))).astype(np.float32)
        data.append(DataSet(x, np.eye(10, dtype=np.float32)[lab]))

    # the worker net is deliberately DEEP (46 dense layers, ~7ms/step):
    # a respawned replacement's recovery is dominated by its cold XLA
    # compile of the adam train step (~3s here, minutes for real models)
    # — exactly the tax the round-15 executable cache removes, so the
    # cold-vs-warm recovery A/B measures the mechanism and not the noise
    # floor of a sub-300ms toy compile. He init + adam keep a stack this
    # deep actually learning; no conv so process start itself stays fast
    # (it is part of the measured recovery)
    lb = (NeuralNetConfiguration.builder()
          .seed(12345).learning_rate(0.001).updater("adam")
          .weight_init("relu")
          .list()
          .layer(DenseLayer(n_in=64, n_out=128, activation="relu")))
    for _ in range(44):
        lb = lb.layer(DenseLayer(n_in=128, n_out=128, activation="relu"))
    conf = (lb.layer(OutputLayer(n_in=128, n_out=10, loss="mcxent",
                                 activation="softmax"))
            .build())
    net = MultiLayerNetwork(conf).init()

    trainer = (ElasticTrainer.builder(net)
               .workers(W).push_frequency(push_frequency)
               .staleness(8).lease_timeout(10.0)
               .respawn(True)
               .transport(transport)
               .worker_delays(*([delay_s] * W))
               .fit_timeout(180.0).build())

    # expected applied windows over the whole run; the kill fires at
    # kill_frac of that — "halfway" by work done, not wall time
    expected_versions = max(1, n_batches // push_frequency)
    kill_at = max(1, int(expected_versions * kill_frac))

    timeline = []  # (t, version) samples
    killed_at = [None]  # wall-clock instant of the SIGKILL

    def _observe() -> None:
        while trainer.server is None and not fit_done.is_set():
            time.sleep(0.01)
        while not fit_done.is_set():
            v = trainer.server.version
            timeline.append((time.perf_counter(), v))
            if (kill_frac > 0 and killed_at[0] is None and v >= kill_at
                    and trainer.chaos_kill(0)):
                killed_at[0] = time.perf_counter()
            time.sleep(0.25)

    fit_done = threading.Event()
    obs = threading.Thread(target=_observe, daemon=True,
                           name="elastic-bench-observer")
    obs.start()
    t0 = time.perf_counter()
    try:
        trainer.fit(ListDataSetIterator(data))
    finally:
        fit_done.set()
    fit_dt = time.perf_counter() - t0
    obs.join(timeout=2.0)

    # sliding-window rates (versions/sec over the trailing second),
    # scaled to samples/sec via window size x batch
    scale = push_frequency * batch

    def _rates(points):
        out = []
        for i in range(1, len(points)):
            j = i
            while j > 0 and points[i][0] - points[j - 1][0] < 1.0:
                j -= 1
            dt = points[i][0] - points[j][0]
            if dt > 0:
                out.append((points[i][0],
                            (points[i][1] - points[j][1]) / dt * scale))
        return out

    rates = _rates(timeline)
    dip_pct = recovery_s = None
    pre_rate = post_min = None
    if killed_at[0] is not None and rates:
        pre = [r for t, r in rates if t <= killed_at[0] and r > 0]
        post = [(t, r) for t, r in rates if t > killed_at[0]]
        if pre and post:
            pre_rate = float(np.median(pre))
            # recovery = first instant the rate is back at >=90% of the
            # pre-kill median AND stays there for a full second (push
            # windows are bursty; a single sample above the bar is noise,
            # not a respawned worker)
            recovery_s = fit_dt - (killed_at[0] - t0)  # worst case: never
            recovered_t = None
            for i, (t, r) in enumerate(post):
                if r < 0.9 * pre_rate:
                    continue
                hold = [q for u, q in post[i:] if u - t <= 1.0]
                if all(q >= 0.9 * pre_rate for q in hold):
                    recovery_s = t - killed_at[0]
                    recovered_t = t
                    break
            # the dip is what the fleet lost BETWEEN kill and recovery —
            # the end-of-run drain taper (shards finishing) must not
            # masquerade as preemption damage
            dip_end = recovered_t if recovered_t is not None \
                else killed_at[0] + 10.0
            dipped = [r for t, r in post if t <= dip_end]
            if dipped:
                post_min = min(dipped)
                dip_pct = max(0.0, (1.0 - post_min / pre_rate) * 100.0)

    st = trainer.stats
    r = {
        "samples_per_sec": batch * n_batches / fit_dt,
        "fit_time_s": fit_dt,
        "worker_loss_dip_pct": dip_pct,
        "recovery_seconds": recovery_s,
        "pre_kill_samples_per_sec": pre_rate,
        "post_kill_min_samples_per_sec": post_min,
        "workers": W, "kill_fraction": kill_frac, "killed_shard": 0,
        "kill_at_version": kill_at,
        "worker_step_delay_ms": delay_s * 1e3,
        "push_frequency": push_frequency,
        "published_batches": st["published"],
        "worker_steps": st["steps"],
        "handoffs": st["handoffs"], "fenced": st["fenced"],
        "lease_expiries": st["lease_expiries"], "joins": st["joins"],
        "final_loss": float(net.score(
            np.concatenate([d.features for d in data]),
            np.concatenate([d.labels for d in data]))),
        "ps_transport": transport,
        "compile_cache": compile_cache_label,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "api": "parallel.ElasticTrainer",
    }
    _append_ps_ab("elastic", r)
    return r


def bench_elastic(batch, iters, ksteps, elastic_workers=None,
                  elastic_kill=None, ps_transport=None, compile_cache=None):
    """Elastic worker-kill A/B, compile-cache-aware (round 15).

    The measured recovery window is compile-bound: the respawned worker
    process pays a cold XLA compile of the train step before its first
    push. With the executable cache on (the default), gen-0 workers
    persist their step executables and the respawn warm-loads from disk
    — so the run itself exercises the warm path. ``--compile-cache off``
    measures only the cold world; the default runs BOTH (cold first, in
    the same fresh store with the cache disabled) and reports the warm
    run's numbers as the headline with ``recovery_seconds_cold`` riding
    along for the A/B.
    """
    import tempfile

    mode = compile_cache or "on"

    def once(cache_on: str, directory: str, label: str):
        saved = {k: os.environ.get(k)
                 for k in ("DL4J_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR")}
        os.environ["DL4J_COMPILE_CACHE"] = cache_on
        os.environ["JAX_COMPILATION_CACHE_DIR"] = directory
        try:
            return _bench_elastic_once(
                batch, iters, ksteps, elastic_workers=elastic_workers,
                elastic_kill=elastic_kill, ps_transport=ps_transport,
                compile_cache_label=label)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    if mode == "off":
        with tempfile.TemporaryDirectory(prefix="dl4j-xc-bench-") as d:
            return once("0", d, "off")
    with tempfile.TemporaryDirectory(prefix="dl4j-xc-bench-") as d:
        cold = once("0", d, "off")
        warm = once("1", d, "on")
    r = dict(warm)
    r["compile_cache"] = "on"
    r["recovery_seconds_cold"] = cold["recovery_seconds"]
    r["samples_per_sec_cold"] = cold["samples_per_sec"]
    if warm.get("recovery_seconds") and cold.get("recovery_seconds"):
        r["recovery_improvement"] = round(
            1.0 - warm["recovery_seconds"] / cold["recovery_seconds"], 3)
    return r


def bench_ingest(batch, iters, ksteps, ingest_codec=None):
    """Native vs python ingest-decode A/B (ISSUE 14): MB/s turning broker
    frame payloads of raw record bytes into float32. ``batch`` is the
    record size in KB (default 4 — sample-sized: a CIFAR image is 3 KB),
    ``iters`` the timing repetitions (best-of wins: the number under test
    is decoder bandwidth, not scheduler noise on a shared host); records
    ride ~512 KB frames, ~128 MB total per rep.

    This is the consumer-side seam the ISSUE names: the python path is
    the per-record frombuffer/astype fallback — one GIL-bound numpy
    round-trip per record, fixed cost dominating at sample-sized
    records — while the native path decodes each frame's payload in ONE
    fused off-GIL pass (the batched decoder) and splits records as
    views. CPU-measured by design: host-side ingest, not MXU width.
    """
    from deeplearning4j_tpu import nativert

    codec = ingest_codec or "u8"
    record_kb = int(batch)
    rec_bytes = record_kb * 1024
    per_frame = max(1, (512 << 10) // rec_bytes)
    frame_bytes = per_frame * rec_bytes
    n_frames = max(1, (128 << 20) // frame_bytes)
    total_mb = n_frames * frame_bytes / (1 << 20)

    rng = np.random.default_rng(0)
    if codec == "u8":
        frames = [rng.integers(0, 256, frame_bytes,
                               dtype=np.uint8).tobytes()
                  for _ in range(n_frames)]
    else:
        width = nativert._INGEST_WIDTH[nativert.INGEST_CODECS[codec]]
        n = frame_bytes // width
        if codec == "bf16":
            import ml_dtypes
            payload = rng.standard_normal(n, dtype=np.float32).astype(
                ml_dtypes.bfloat16).tobytes()
        else:
            payload = rng.standard_normal(n, dtype=np.float32).tobytes()
        frames = [payload for _ in range(n_frames)]

    def _py_run():
        t0 = time.perf_counter()
        for frame in frames:
            v = memoryview(frame)
            for i in range(per_frame):
                nativert.decode_records_py(
                    v[i * rec_bytes:(i + 1) * rec_bytes], codec)
        return total_mb / (time.perf_counter() - t0)

    def _native_run():
        t0 = time.perf_counter()
        for frame in frames:
            out = nativert.decode_records(frame, codec)
            np.split(out, per_frame)  # per-record views, no copy
        return total_mb / (time.perf_counter() - t0)

    native_ok = nativert.native_available()
    py_mb = max(_py_run() for _ in range(iters))
    native_mb = max(_native_run() for _ in range(iters)) if native_ok else None

    r = {
        "samples_per_sec": native_mb if native_mb is not None else py_mb,
        "path": "native" if native_mb is not None else "python",
        "record_kb": record_kb,
        "records_per_frame": per_frame,
        "frames": n_frames,
        "total_mb": round(total_mb, 1),
        "ingest_codec": codec,
        "python_mb_per_sec": round(py_mb, 1),
        "native_mb_per_sec": (round(native_mb, 1)
                              if native_mb is not None else None),
        "ingest_speedup": (round(native_mb / py_mb, 3)
                           if native_mb is not None else None),
        "native_available": native_ok,
        "batch": batch, "iters": iters, "ksteps": ksteps,
        "api": "nativert.decode_records",
    }
    _append_ps_ab("ingest", r)
    return r


_METRICS = {
    "lenet": "lenet_mnist_samples_per_sec",
    "fit_lenet": "lenet_fit_api_samples_per_sec",
    "fit_resnet50": "resnet50_fit_api_samples_per_sec",
    "char_rnn": "char_rnn_samples_per_sec",
    "transformer": "transformer_lm_samples_per_sec",
    "moe": "moe_transformer_samples_per_sec",
    "resnet50": "resnet50_samples_per_sec_per_chip",
    "vgg16": "vgg16_samples_per_sec_per_chip",
    "word2vec": "word2vec_pairs_per_sec",
    "attention": "flash_attention_tokens_per_sec",
    "serve": "serve_batched_requests_per_sec",
    "ps_async": "ps_async_samples_per_sec",
    "elastic": "elastic_ps_samples_per_sec",
    "ingest": "native_ingest_decode_mb_per_sec",
}

#: models whose headline is not a training samples/sec number
_UNITS = {"serve": "requests/sec", "ingest": "MB/sec"}

_DEFAULT_MODEL = "resnet50"  # the flagship; bare bench.py runs it

_DEFAULTS = {  # model -> (batch, iters, ksteps)
    "lenet": (128, 20, 16),
    "fit_lenet": (128, 20, 16),
    "resnet50": (128, 5, 16),  # K=16 measured +1.5% over K=8 (r5)
    "vgg16": (64, 4, 8),  # ~4x ResNet-50 flops/sample: half the batch
    "fit_resnet50": (64, 4, 8),
    "char_rnn": (32, 5, 8),
    "transformer": (16, 5, 8),
    "moe": (8, 5, 4),
    "word2vec": (1024, 10, 32),
    "attention": (4, 5, 4),
    "serve": (32, 3, 1),  # batch = serving max_batch, iters = seconds/phase
    "ps_async": (32, 48, 1),  # iters = total minibatches through each path
    "elastic": (32, 192, 1),  # iters = total minibatches across the fleet
    "ingest": (4, 4, 1),  # batch = record KB, iters = timing reps
}


def _bench_fns():
    return {"lenet": bench_lenet, "resnet50": bench_resnet50,
            "vgg16": bench_vgg16,
            "fit_lenet": bench_fit_lenet, "fit_resnet50": bench_fit_resnet50,
            "char_rnn": bench_char_rnn, "transformer": bench_transformer,
            "moe": bench_moe,
            "word2vec": bench_word2vec, "attention": bench_attention,
            "serve": bench_serve, "ps_async": bench_ps_async,
            "elastic": bench_elastic, "ingest": bench_ingest}


#: per-model default dtype policy = the measured-best config on chip
#: (BASELINE.md round-5): bf16 activations win big on the flagships
#: (+22% ResNet-50, +52% transformer) but LOSE on tiny models where the
#: convert ops dominate (LeNet: 240k vs 374k samples/s). A bare
#: `python bench.py --model X` therefore reports each model's production
#: configuration; --f32/--bf16-matmul/--bf16-act force a specific one.
_DTYPE_DEFAULT = {"lenet": "bf16", "fit_lenet": "bf16",
                  "word2vec": "bf16", "attention": "bf16",
                  # serving measures f32 end-to-end request latency; bf16
                  # convert ops on tiny batches would dominate like LeNet
                  "serve": "f32",
                  # PS A/B measures host-side orchestration (barrier vs
                  # async push/pull), not MXU width: f32 like serve
                  "ps_async": "f32",
                  # elastic measures membership/handoff orchestration on
                  # subprocess CPU workers: same reasoning as ps_async
                  "elastic": "f32",
                  # ingest decodes record bytes on the host: no matmuls
                  "ingest": "f32"}


def _dtype_mode(model: str, *, bf16_act: bool, bf16_matmul: bool,
                f32: bool) -> str:
    if f32:
        return "f32"
    if bf16_matmul:
        return "bf16"
    if bf16_act:
        return "bf16_act"
    return _DTYPE_DEFAULT.get(model, "bf16_act")


def _reduction_mode(dtype_mode: str, reduction_dtype: str | None) -> str:
    """Resolved reduction policy: explicit --reduction-dtype wins; the
    bf16-act flagship path defaults to bf16 single-pass statistics (the
    round-6 reduction-precision subsystem — see BASELINE.md), every other
    mode defaults to classic at-least-f32 statistics."""
    if reduction_dtype:
        return reduction_dtype
    return "bf16" if dtype_mode == "bf16_act" else "f32"


def _child_main(args) -> None:
    """Run one benchmark in-process and print its JSON record."""
    global _PROFILE_SPEC
    mode = _dtype_mode(args.model, bf16_act=args.bf16_act,
                       bf16_matmul=args.bf16_matmul, f32=args.f32)
    rmode = _reduction_mode(mode, args.reduction_dtype)
    if mode == "bf16":
        from deeplearning4j_tpu.common import bf16_matmul_policy
        bf16_matmul_policy()
    elif mode == "bf16_act":
        if rmode == "bf16":
            # the measured flagship recipe: bf16 single-pass norm statistics
            # + f32-pinned weight-grad accumulation
            from deeplearning4j_tpu.common import flagship_bf16_policy
            flagship_bf16_policy()
        else:
            from deeplearning4j_tpu.common import full_bf16_policy
            full_bf16_policy()
    if mode != "bf16_act" and rmode == "bf16":
        # explicit opt-in on a non-flagship mode: bf16 stats + f32 grad accum
        # on top of whatever base policy is installed
        import jax.numpy as jnp
        from deeplearning4j_tpu.common import set_policy
        set_policy(reduction_dtype=jnp.bfloat16, grad_accum_dtype=jnp.float32)

    if args.seq:
        os.environ["DL4J_ATTN_SEQ"] = str(args.seq)
    if args.vocab:
        os.environ["DL4J_W2V_VOCAB"] = str(args.vocab)
    db, di, dk = _DEFAULTS[args.model]
    kwargs = {}
    if args.hidden and args.model == "char_rnn":
        kwargs["hidden"] = args.hidden
    if args.lstm_impl and args.model == "char_rnn":
        kwargs["lstm_impl"] = args.lstm_impl
    if args.model == "serve":
        if args.serve_qps:
            kwargs["serve_qps"] = args.serve_qps
        if args.serve_latency_ms:
            kwargs["serve_latency_ms"] = args.serve_latency_ms
        if args.serve_batching:
            kwargs["serve_batching"] = args.serve_batching
        if args.serve_quant:
            kwargs["serve_quant"] = args.serve_quant
        if args.serve_replicas:
            kwargs["serve_replicas"] = args.serve_replicas
        if args.serve_sharding:
            kwargs["serve_sharding"] = args.serve_sharding
        if args.compile_cache:
            kwargs["compile_cache"] = args.compile_cache
        if args.decode_kv:
            kwargs["decode_kv"] = args.decode_kv
        if args.decode_page_size:
            kwargs["decode_page_size"] = args.decode_page_size
        if args.decode_spec_draft:
            kwargs["decode_spec_draft"] = args.decode_spec_draft
        if args.serve_tracing:
            kwargs["serve_tracing"] = args.serve_tracing
        if args.serve_autoscale:
            kwargs["serve_autoscale"] = args.serve_autoscale
    if args.model == "ps_async":
        if args.ps_workers:
            kwargs["ps_workers"] = args.ps_workers
        if args.ps_straggler:
            kwargs["ps_straggler"] = args.ps_straggler
    if args.model == "elastic":
        if args.elastic_workers:
            kwargs["elastic_workers"] = args.elastic_workers
        if args.elastic_kill is not None:
            kwargs["elastic_kill"] = args.elastic_kill
        if args.compile_cache:
            kwargs["compile_cache"] = args.compile_cache
    if args.model in ("ps_async", "elastic") and args.ps_transport:
        kwargs["ps_transport"] = args.ps_transport
    if args.model == "ingest" and args.ingest_codec:
        kwargs["ingest_codec"] = args.ingest_codec
    if getattr(args, "sharding", None):
        if args.model not in _SHARDING_CAPABLE:
            raise SystemExit(
                f"--sharding supports {sorted(_SHARDING_CAPABLE)}, "
                f"not '{args.model}'")
        kwargs["sharding"] = args.sharding

    # arm the attribution capture on an explicit --xplane-attribution
    profile_trigger = ("bench" if getattr(args, "xplane_attribution", False)
                       else None)
    if profile_trigger and args.model in _PROFILE_CAPABLE:
        _PROFILE_SPEC = {"trigger": profile_trigger}

    r = _bench_fns()[args.model](args.batch or db, args.iters or di,
                                 args.ksteps or dk, **kwargs)

    if profile_trigger and args.model not in _PROFILE_CAPABLE:
        r["profile_error"] = (
            f"model '{args.model}' does not run through the multistep "
            "harness; xplane attribution unsupported")

    base = BASELINE_SAMPLES_PER_SEC.get(args.model)
    vs = round(r["samples_per_sec"] / base, 3) if base else None
    import jax
    r["backend"] = jax.default_backend()
    r["dtype"] = mode
    r["reduction_dtype"] = rmode
    if args.telemetry_out:
        # registry snapshot goes to a FILE beside the headline JSON — stdout
        # carries exactly one JSON line (the parent's parse contract)
        from deeplearning4j_tpu.observability import (global_registry,
                                                      global_tracker)
        global_registry().write_jsonl(
            args.telemetry_out, source="bench",
            model=args.model, dtype=mode, reduction_dtype=rmode,
            compile_events=global_tracker().snapshot_events())
    print(json.dumps({
        "metric": _METRICS[args.model],
        "value": round(r["samples_per_sec"], 2),
        "unit": _UNITS.get(args.model, "samples/sec"),
        "vs_baseline": vs,
        "detail": r,
    }), flush=True)


def main() -> None:
    """Parent driver: run the benchmark in a killable subprocess.

    One process for each chip: this parent never touches JAX (importing the
    package initialises no backend), so the child it starts is the only
    process that holds the device. Each attempt has a hard timeout; when
    the retry budget is spent the parent prints ONE machine-readable error
    record (never a stack trace) and exits non-zero — a run that found no
    chip, crashed or timed out is a failed run, and carries no number.
    """
    import subprocess
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=_DEFAULT_MODEL,
                    choices=sorted(_METRICS))
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None,
                    help="attention bench sequence length (config-distinct "
                         "in bench_log matching, unlike the env override)")
    ap.add_argument("--vocab", type=int, default=None,
                    help="word2vec bench vocab size (config-distinct)")
    ap.add_argument("--hidden", type=int, default=None,
                    help="char_rnn LSTM hidden width (config-distinct); "
                         ">=1024 is the MFU-floor grid row")
    ap.add_argument("--ksteps", type=int, default=None,
                    help="train steps fused per host dispatch")
    ap.add_argument("--lstm-impl", default=None,
                    choices=("auto", "scan", "fused", "pallas"),
                    help="char_rnn recurrent-engine headline variant "
                         "(config-distinct). Every record also carries the "
                         "three-way A/B fields (scan/fused/pallas "
                         "samples_per_sec + *_speedup); this picks which "
                         "one is the headline. Default: auto (the "
                         "production DL4J_LSTM_IMPL gate)")
    dt = ap.add_mutually_exclusive_group()
    dt.add_argument("--f32", action="store_true",
                    help="float32 compute")
    dt.add_argument("--bf16-matmul", action="store_true",
                    help="bfloat16 matmuls/convs with f32 activations (the "
                         "pre-round-5 default)")
    dt.add_argument("--bf16-act", action="store_true",
                    help="full_bf16_policy: bfloat16 activations too (halves "
                         "activation HBM traffic; norm stats/losses stay "
                         "f32). THE DEFAULT since round 5: on-chip it is "
                         "+22%% on ResNet-50 and +52%% on the transformer "
                         "with loss curves matching (BASELINE.md round-5)")
    ap.add_argument("--reduction-dtype", choices=("f32", "bf16"), default=None,
                    help="normalization-statistics reduction dtype. Default: "
                         "bf16 under --bf16-act (the flagship single-pass "
                         "recipe — kills the standalone f32 upcast-reduce "
                         "fusions, ~23%% of r5 ResNet-50 bf16 device time; "
                         "weight-grad accumulation stays f32-pinned via "
                         "preferred_element_type), f32 everywhere else. "
                         "'f32' restores the classic at-least-f32 statistics "
                         "on the bf16-act path")
    ap.add_argument("--sharding", default=None,
                    choices=("dp", "dp_tp", "zero3"),
                    help="train through the partition-rule sharding engine "
                         "(ParallelWrapper.fit on a named mesh) instead of "
                         "the single-device path; fit_resnet50/transformer "
                         "only (config-distinct). The record carries the "
                         "achieved param_bytes_per_device from "
                         "dl4j_sharded_param_bytes_per_device")
    ap.add_argument("--serve-qps", type=float, default=None,
                    help="serve bench offered open-loop request rate "
                         "(config-distinct). Default: auto-calibrate — "
                         "measure the unbatched closed-loop saturation "
                         "point through the real HTTP stack, then offer "
                         "1.5x that rate to both A/B phases")
    ap.add_argument("--serve-latency-ms", type=float, default=None,
                    help="serve bench micro-batcher max coalescing wait "
                         "(config-distinct); default 4ms")
    ap.add_argument("--serve-batching", default=None,
                    choices=("continuous", "static"),
                    help="serve bench decode scheduling for the row's "
                         "decode_tokens_per_sec / decode_ttft_p99_ms "
                         "(config-distinct); default continuous — "
                         "iteration-level slot admission/eviction vs "
                         "request-level full-batch drain")
    ap.add_argument("--serve-quant", default=None, choices=("int8", "none"),
                    help="serve bench decode weight quantization for the "
                         "row's decode numbers (config-distinct); default "
                         "none (policy-dtype dense weights)")
    ap.add_argument("--serve-replicas", type=int, default=None,
                    help="serve bench replica count for the QPS-vs-replicas "
                         "scaling section (config-distinct); default 2 — N "
                         "independent pinned programs behind the least-"
                         "queue-depth router vs a single replica at equal "
                         "offered load")
    ap.add_argument("--serve-sharding", default=None,
                    choices=("dp_tp", "none"),
                    help="serve bench replica pin placement "
                         "(config-distinct); default none (one device per "
                         "replica). dp_tp shards each replica's pinned "
                         "params over its own mesh slice via the partition-"
                         "rule engine — bitwise-equal gather-at-use "
                         "serving, forced onto an 8-device CPU host "
                         "platform (NOT the fit path's --sharding axis: "
                         "serve rows never take --sharding)")
    ap.add_argument("--decode-kv", default=None, choices=("paged", "dense"),
                    help="serve bench decode KV layout for the row's "
                         "paged_tokens_per_sec (config-distinct); default "
                         "paged — page-table pool + CoW prefix sharing vs "
                         "dense per-slot [cap, max_context] blocks; both "
                         "phases always run (the A/B pins bitwise "
                         "equality), the axis picks the headline phase")
    ap.add_argument("--decode-page-size", type=int, default=None,
                    help="serve bench paged-decode physical page size in "
                         "tokens (config-distinct); default 16")
    ap.add_argument("--decode-spec-draft", default=None,
                    choices=("tiny", "none"),
                    help="serve bench speculative-decode draft model "
                         "(config-distinct); default tiny (a 1-layer "
                         "width-16 transformer proposing 3 tokens/round); "
                         "'none' skips the spec section (its fields "
                         "report null)")
    ap.add_argument("--serve-tracing", default=None, choices=("on", "off"),
                    help="serve bench request-tracing axis (config-"
                         "distinct); default on — the overhead A/B always "
                         "runs both phases and trace_overhead_pct reports "
                         "the serve-path cost of 100%%-sampled tracing "
                         "(budget <= 2%%, pinned by test_bench_contract)")
    ap.add_argument("--serve-autoscale", default=None,
                    choices=("on", "off"),
                    help="serve bench autoscaling ramp axis (config-"
                         "distinct); default off. 'on' runs the open-loop "
                         "ramp A/B: SLO-driven autoscaled fleet vs a "
                         "static fleet at the same average replica count "
                         "(ramp_slo_violation_seconds_auto/static, "
                         "ramp_lost_requests, ramp_scale_out_latency_s)")
    ap.add_argument("--ps-workers", type=int, default=None,
                    help="ps_async bench worker count for the straggler A/B "
                         "(config-distinct); default 4")
    ap.add_argument("--ps-straggler", type=float, default=None,
                    help="ps_async bench straggler factor: one worker of "
                         "--ps-workers sleeps this multiple of the median "
                         "per-step delay (config-distinct); default 4")
    ap.add_argument("--elastic-workers", type=int, default=None,
                    help="elastic bench fleet size: separate-process "
                         "workers behind the membership oracle "
                         "(config-distinct); default 4")
    ap.add_argument("--elastic-kill", type=float, default=None,
                    help="elastic bench kill point: SIGKILL shard 0's "
                         "worker when this fraction of the expected push "
                         "windows has landed (config-distinct); default "
                         "0.5, 0 disables the kill")
    ap.add_argument("--compile-cache", choices=("on", "off"), default=None,
                    help="serve/elastic: executable-cache mode for the "
                         "warm-start sections. 'off' measures only the "
                         "cold world (time_to_ready_s / recovery_seconds "
                         "are cold numbers); the default 'on' reports the "
                         "warm numbers with the cold A/B riding along")
    ap.add_argument("--ps-transport", choices=("tcp", "shm"), default=None,
                    help="ps_async/elastic bench PS byte plane: 'tcp' "
                         "loopback frames or 'shm' shared-memory segments "
                         "negotiated over the same socket (config-distinct); "
                         "default tcp")
    ap.add_argument("--ingest-codec", choices=("u8", "bf16", "f32"),
                    default=None,
                    help="ingest bench record codec for the native-vs-"
                         "python decode A/B (config-distinct); default u8")
    ap.add_argument("--telemetry-out", default=None,
                    help="append a metrics-registry snapshot (JSONL) to this "
                         "file beside the headline JSON; measurement-only — "
                         "ignored for bench_log config matching")
    ap.add_argument("--xplane-attribution", action="store_true",
                    help="after the timed loop, re-dispatch the compiled "
                         "program under a TraceSession capture and attach "
                         "the per-op category split (xplane_attribution) to "
                         "the record — or a profile_error field when capture/"
                         "parsing fails; measurement-only, ignored for "
                         "bench_log config matching")
    ap.add_argument("--flight-recorder-dir", default=None, metavar="DIR",
                    help="arm the flight recorder: bundles (crash, signal, "
                         "device-unreachable) are written under DIR instead "
                         "of next to scripts/bench_log.jsonl")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    # worst case must finish inside the harness's own command timeout
    # (round-1 artifacts show it kills at ~600s): 2 x 240s + 5s backoff < 500s
    ap.add_argument("--attempts", type=int, default=2)
    ap.add_argument("--attempt-timeout", type=float, default=240.0)
    args = ap.parse_args()

    if args.flight_recorder_dir:
        from deeplearning4j_tpu.observability import (
            global_recorder, install_signal_handlers,
        )
        global_recorder().set_dump_dir(args.flight_recorder_dir)
        if args.child:
            install_signal_handlers()

    if args.child:
        _child_main(args)
        return

    # forward our full argv so new flags can never silently drop from the
    # child (--child's parser ignores --attempts/--attempt-timeout)
    cmd = [sys.executable, os.path.abspath(__file__), "--child"] + sys.argv[1:]

    # ps_async and elastic measure host-side orchestration and are
    # CPU-measured by design (the straggler A/B needs a data mesh at
    # worker count on any box; the elastic coordinator's subprocess workers
    # train on the CPU and a chip belongs to one process); a
    # sharded-replica serve row likewise needs an 8-device host platform
    # so each replica gets a real mesh slice; every other model inherits
    # the env untouched. Their records say backend "cpu": counts, never
    # device rates.
    child_env = None
    if args.model in ("ps_async", "elastic", "ingest") or (
            args.model == "serve"
            and getattr(args, "serve_sharding", None) == "dp_tp"):
        child_env = os.environ.copy()
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

    def _scan_json(stdout) -> dict | None:
        if isinstance(stdout, bytes):
            stdout = stdout.decode("utf-8", errors="replace")
        for line in reversed((stdout or "").strip().splitlines()):
            try:
                rec = json.loads(line)
            except (json.JSONDecodeError, ValueError):
                continue
            if isinstance(rec, dict) and "metric" in rec:
                return rec
        return None

    def _tail(s) -> str:
        if isinstance(s, bytes):
            s = s.decode("utf-8", errors="replace")
        return (s or "")[-600:]

    from deeplearning4j_tpu.observability import global_recorder

    last_err = ""
    last_was_timeout = False
    retry_timeline = []
    for attempt in range(args.attempts):
        t_attempt = time.time()
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=args.attempt_timeout,
                                  env=child_env)
            rec = _scan_json(proc.stdout)
            if rec is None:
                last_was_timeout = False
                last_err = (f"attempt {attempt + 1}: rc={proc.returncode}; "
                            + _tail(proc.stderr or proc.stdout))
        except subprocess.TimeoutExpired as e:
            # the child may have printed its record and then hung in
            # teardown — a timeout after a valid JSON line is still a success
            rec = _scan_json(e.stdout)
            if rec is None:
                last_was_timeout = True
                last_err = (f"attempt {attempt + 1}: timed out after "
                            f"{args.attempt_timeout}s; stderr tail: "
                            + _tail(e.stderr))
        retry_timeline.append({
            "attempt": attempt + 1, "started": t_attempt,
            "elapsed_s": time.time() - t_attempt,
            "outcome": ("ok" if rec is not None
                        else "timeout" if last_was_timeout else "crash"),
            "error": None if rec is not None else last_err,
        })
        global_recorder().record("bench_attempt", **retry_timeline[-1])
        if rec is not None:
            rec["detail"] = dict(rec.get("detail", {}), attempt=attempt + 1)
            print(json.dumps(rec), flush=True)
            return
        if attempt + 1 < args.attempts:
            time.sleep(5 * (attempt + 1))

    # Retry budget exhausted: emit a machine-readable error record and fail.
    kind = ("every attempt timed out"
            if last_was_timeout else "benchmark child crashed on every attempt")
    rec = {
        "metric": _METRICS[args.model],
        "value": 0.0,
        "unit": _UNITS.get(args.model, "samples/sec"),
        "vs_baseline": 0.0,
        "error": kind + ": " + last_err.replace("\n", " | "),
    }
    if args.flight_recorder_dir:
        # self-diagnosing failure artifact: env, retry timeline, the record
        bundle = global_recorder().dump(
            dir=args.flight_recorder_dir, reason="bench-failed",
            extra={"retry_timeline": retry_timeline, "record": rec})
        if bundle:
            rec["flight_bundle"] = bundle
    print(json.dumps(rec), flush=True)
    sys.exit(1)


#: when the per-model dtype defaults landed (round 5) — bare rows logged
#: before this instant were measured under the old global bf16-matmul default
_DTYPE_DEFAULT_CHANGE_TS = "2026-07-31T04:35:00Z"

#: when bf16 reductions became the bf16-act default (round 6) — bf16-act rows
#: logged before this instant ran classic at-least-f32 statistics
_RDTYPE_DEFAULT_CHANGE_TS = "2026-08-05T00:00:00Z"

#: when the recurrent engine landed (round 6) — bare char_rnn rows logged
#: before this instant measured the old scan path, not today's fused default
_LSTM_IMPL_DEFAULT_CHANGE_TS = "2026-08-05T12:00:00Z"

#: when bench rows grew xplane attribution (round 7) — rows logged before
#: this instant can never carry the fields below. --xplane-attribution is
#: measurement-only (like --telemetry-out): it must NOT make a config
#: distinct in bench_log matching, so a prior healthy row without the
#: fields still stands in for an attribution-armed request during an outage
_XPLANE_ATTRIBUTION_LANDED_TS = "2026-08-05T16:00:00Z"

#: the exact attribution field names a bench row may carry (the bench-row
#: contract; pinned by tests/test_bench_contract.py)
XPLANE_ATTRIBUTION_FIELDS = ("xplane_attribution", "profile_trace",
                             "profile_error", "profile_variant")

#: when the --sharding grid axis landed (round 8) — rows logged before this
#: instant all measured the single-device fit path, so during an outage they
#: may stand in only for an UNSHARDED request, never for a --sharding row
_SHARDING_AXIS_LANDED_TS = "2026-08-05T20:00:00Z"

#: when the serving-engine grid axes landed (round 9) — no bench_log row
#: before this instant can be a '--model serve' row at all, and rows logged
#: since carry the offered-QPS / coalescing-latency knobs as config axes so
#: an outage can never serve a number measured under a different load shape
_SERVE_AXIS_LANDED_TS = "2026-08-05T22:00:00Z"

#: when the async parameter-server engine landed (round 10) — no bench_log
#: row before this instant can be a '--model ps_async' row at all, and rows
#: logged since carry the worker-count / straggler-factor knobs as config
#: axes so an outage can never serve a number measured under a different
#: straggler shape
_PS_AXIS_LANDED_TS = "2026-08-05T22:00:30Z"

#: when the continuous-batching decode section landed on the serve bench
#: (round 11) — serve rows logged before this instant carry no decode
#: numbers (their axes normalize to None, never equal to a live request's
#: resolved "continuous"/"none"), so an outage can never serve a
#: decode-less row for a request whose headline now includes
#: decode_tokens_per_sec; rows since carry the scheduling-mode /
#: weight-quantization knobs as config axes so a static or int8 capture
#: can never stand in for the continuous dense row
_SERVE_DECODE_AXIS_LANDED_TS = "2026-08-05T23:30:00Z"

#: when the sharded multi-replica serving section landed (round 12) —
#: serve rows logged before this instant predate the ReplicaSet and carry
#: no replica-scaling numbers (their axes normalize to None), so an outage
#: can never serve a replica-less row for a request whose headline now
#: includes replica_speedup; rows since carry the replica-count / pin-
#: placement knobs as config axes so a 4-replica or dp_tp-sharded capture
#: can never stand in for the standard 2-replica single-device row
_SERVE_REPLICA_AXIS_LANDED_TS = "2026-08-06T00:00:00Z"

#: when the elastic trainer landed (round 13) — no bench_log row before
#: this instant can be a '--model elastic' row at all, and rows logged
#: since carry the fleet-size / kill-point knobs as config axes so an
#: outage can never serve a no-kill or 8-worker capture for the standard
#: 4-worker kill-at-50% recovery row
_ELASTIC_AXIS_LANDED_TS = "2026-08-06T02:00:00Z"

#: when the host data plane landed (ISSUE 14): rows before this predate
#: --ps-transport (all PS traffic rode tcp frames) and the ingest model;
#: a pre-plane tcp row must not stand in for today's shm capture
_DATAPLANE_AXIS_LANDED_TS = "2026-08-06T06:00:00Z"

#: when the warm-start compile plane landed (ISSUE 15): rows before this
#: predate --compile-cache and the time_to_ready / warm-recovery sections;
#: an all-cold row must not stand in for today's warm-headline capture
_COMPILE_CACHE_AXIS_LANDED_TS = "2026-08-06T10:00:00Z"

#: when the paged decode memory plane landed (ISSUE 16): serve rows before
#: this predate --decode-kv / --decode-page-size / --decode-spec-draft
#: (all decode traffic ran dense KV, no draft model existed), so an old
#: dense capture must never stand in for today's paged-headline row, and a
#: no-draft capture must never stand in for the spec-decode speedup row
_PAGED_DECODE_AXIS_LANDED_TS = "2026-08-07T08:00:00Z"

#: when the request-tracing plane landed (ISSUE 17): serve rows before
#: this predate --serve-tracing and the trace_overhead_pct field (requests
#: ran untraced), so an untraced capture must never stand in for today's
#: tracing-on default row whose headline carries the overhead budget
_SERVE_TRACING_AXIS_LANDED_TS = "2026-08-07T12:00:00Z"

#: when the autoscaling serving fleet landed (ISSUE 18): serve rows before
#: this predate --serve-autoscale and the ramp A/B section (fleets were a
#: fixed --serve-replicas guess), so a static-fleet capture must never
#: stand in for the autoscaled ramp row and vice versa
_SERVE_AUTOSCALE_AXIS_LANDED_TS = "2026-08-07T16:00:00Z"


def _config_key(args_str: str, ts: str = None) -> dict:
    """The fields that make two bench invocations the SAME config: model,
    dtype mode, explicit batch/ksteps. Unrecognized flags are ignored."""
    toks = args_str.split()

    def val(flag):
        return toks[toks.index(flag) + 1] if (flag in toks
                                              and toks.index(flag) + 1
                                              < len(toks)) else None

    # normalize argparse defaults so a BARE invocation (the driver's
    # end-of-round run) is the SAME config as explicit '--model resnet50
    # --bf16-act' capture rows; dtype resolution mirrors _dtype_mode
    model = val("--model") or _DEFAULT_MODEL
    mode = _dtype_mode(model,
                       bf16_act="--bf16-act" in toks,
                       bf16_matmul="--bf16-matmul" in toks,
                       f32="--f32" in toks)
    if ts is not None and ts < _DTYPE_DEFAULT_CHANGE_TS \
            and not any(f in toks for f in ("--bf16-act", "--bf16-matmul",
                                            "--f32")):
        # rows logged before round 5's per-model defaults ran bare under the
        # old bf16-matmul default; reinterpreting them as bf16_act would let
        # an outage serve a wrong-dtype number (+22-52%% apart on flagships)
        mode = "bf16"
    rdtype = val("--reduction-dtype") or _reduction_mode(mode, None)
    if ts is not None and ts < _RDTYPE_DEFAULT_CHANGE_TS \
            and "--reduction-dtype" not in toks:
        # pre-round-6 rows predate the reduction-precision subsystem: they
        # all ran at-least-f32 statistics regardless of dtype mode
        rdtype = "f32"
    lstm_impl = None
    if model == "char_rnn":
        lstm_impl = val("--lstm-impl") or "auto"
        if ts is not None and ts < _LSTM_IMPL_DEFAULT_CHANGE_TS \
                and "--lstm-impl" not in toks:
            # pre-engine rows measured the reference scan path; an outage
            # must not serve an old scan number for today's fused/auto row
            lstm_impl = "scan"
    sharding = None
    if model in _SHARDING_CAPABLE:
        sharding = val("--sharding")
        if ts is not None and ts < _SHARDING_AXIS_LANDED_TS:
            # pre-round-8 rows predate the sharding engine: they all measured
            # the single-device fit path, whatever flags a later reader asks
            sharding = None
    serve_qps = serve_latency_ms = None
    if model == "serve" and not (ts is not None
                                 and ts < _SERVE_AXIS_LANDED_TS):
        # 'auto' (the calibrated default) is its own config: a row captured
        # at an explicit --serve-qps must not stand in for a calibrated run
        serve_qps = val("--serve-qps") or "auto"
        serve_latency_ms = val("--serve-latency-ms") or "4"
    serve_batching = serve_quant = None
    if model == "serve" and not (ts is not None
                                 and ts < _SERVE_DECODE_AXIS_LANDED_TS):
        # defaults are their own config: a static-batching or int8 capture
        # must never stand in for the continuous dense decode row
        serve_batching = val("--serve-batching") or "continuous"
        serve_quant = val("--serve-quant") or "none"
    serve_replicas = serve_sharding = None
    if model == "serve" and not (ts is not None
                                 and ts < _SERVE_REPLICA_AXIS_LANDED_TS):
        # defaults are their own config: a 4-replica or dp_tp-sharded
        # capture must never stand in for the 2-replica single-device row
        serve_replicas = val("--serve-replicas") or "2"
        serve_sharding = val("--serve-sharding") or "none"
    ps_workers = ps_straggler = None
    if model == "ps_async" and not (ts is not None
                                    and ts < _PS_AXIS_LANDED_TS):
        # defaults are their own config: a 2-worker or 8x-straggler capture
        # must never stand in for the standard 4-worker/4x A/B
        ps_workers = val("--ps-workers") or "4"
        ps_straggler = val("--ps-straggler") or "4"
    elastic_workers = elastic_kill = None
    if model == "elastic" and not (ts is not None
                                   and ts < _ELASTIC_AXIS_LANDED_TS):
        # defaults are their own config: a no-kill or 8-worker capture
        # must never stand in for the 4-worker kill-at-50% recovery row
        elastic_workers = val("--elastic-workers") or "4"
        elastic_kill = val("--elastic-kill") or "0.5"
    ps_transport = ingest_codec = None
    if model in ("ps_async", "elastic") and not (
            ts is not None and ts < _DATAPLANE_AXIS_LANDED_TS):
        # defaults are their own config: an shm capture must never stand
        # in for the tcp baseline row (the A/B the headline compares)
        ps_transport = val("--ps-transport") or "tcp"
    if model == "ingest" and not (ts is not None
                                  and ts < _DATAPLANE_AXIS_LANDED_TS):
        ingest_codec = val("--ingest-codec") or "u8"
    compile_cache = None
    if model in ("serve", "elastic") and not (
            ts is not None and ts < _COMPILE_CACHE_AXIS_LANDED_TS):
        # defaults are their own config: a cold-only --compile-cache off
        # capture must never stand in for the warm-headline default row
        compile_cache = val("--compile-cache") or "on"
    decode_kv = decode_page_size = decode_spec_draft = None
    if model == "serve" and not (
            ts is not None and ts < _PAGED_DECODE_AXIS_LANDED_TS):
        # defaults are their own config: a dense-KV or no-draft capture
        # must never stand in for the paged + spec-decode headline row
        decode_kv = val("--decode-kv") or "paged"
        decode_page_size = val("--decode-page-size") or "16"
        decode_spec_draft = val("--decode-spec-draft") or "tiny"
    serve_tracing = None
    if model == "serve" and not (
            ts is not None and ts < _SERVE_TRACING_AXIS_LANDED_TS):
        # default-on is its own config: an untraced capture must never
        # stand in for the tracing-on row (and vice versa)
        serve_tracing = val("--serve-tracing") or "on"
    serve_autoscale = None
    if model == "serve" and not (
            ts is not None and ts < _SERVE_AUTOSCALE_AXIS_LANDED_TS):
        # default-off is its own config: a row without the ramp A/B must
        # never stand in for the autoscaled capture (and vice versa)
        serve_autoscale = val("--serve-autoscale") or "off"
    return {"model": model, "batch": val("--batch"),
            "ksteps": val("--ksteps"), "dtype": mode, "rdtype": rdtype,
            "seq": val("--seq"), "vocab": val("--vocab"),
            "hidden": val("--hidden"), "lstm_impl": lstm_impl,
            "sharding": sharding, "serve_qps": serve_qps,
            "serve_latency_ms": serve_latency_ms,
            "serve_batching": serve_batching, "serve_quant": serve_quant,
            "serve_replicas": serve_replicas,
            "serve_sharding": serve_sharding,
            "ps_workers": ps_workers, "ps_straggler": ps_straggler,
            "elastic_workers": elastic_workers,
            "elastic_kill": elastic_kill,
            "ps_transport": ps_transport, "ingest_codec": ingest_codec,
            "compile_cache": compile_cache, "decode_kv": decode_kv,
            "decode_page_size": decode_page_size,
            "decode_spec_draft": decode_spec_draft,
            "serve_tracing": serve_tracing,
            "serve_autoscale": serve_autoscale}


if __name__ == "__main__":
    main()
