#!/usr/bin/env python3
"""Does the system still start on the chip? The quickest proof.

One process drives the main path once, through the entry points a user calls,
at the full width of ResNet-50 (README headline: batch 128, ``bfloat16_full``,
``dispatch_ksteps`` 8), then the paths whose kernels exist only on a TPU, each
at a shape where the kernel engages by the program's own gate:

    native            the C++ host runtime builds from native/src and loads
    resnet50_train    ComputationGraph.fit_iterator, K-step lax.scan dispatch
    resnet50_serve    InferenceServer, POST /v1/predict == net.output
    xent              fused softmax-xent kernel vs XLA math
    flash             flash forward + tiled backward vs XLA math, T = 4096
    transformer_train transformer_lm.fit_iterator at T = 4096
    lstm              Pallas LSTM cell vs the scan oracle, hidden 512
    char_rnn_train    char_rnn_lstm at hidden 512 under the engine's `auto`
    decode            DecodeEngine(kv="paged", quant="int8") vs the dense engine

Every phase prints one JSON line (seconds, compile seconds, kernels engaged per
``dl4j_pallas_dispatch_total``, max abs difference) and FAILS if the kernel it
was there to exercise did not engage; a kernel its gate refuses from the shape
prints ``gated: <reason>``. A phase that raises fails the run; the others still
run, so one chip call reports everything.

``--chips 4`` runs instead, and only: ParallelWrapper sync data parallelism on a
4-device ``data`` mesh against the same global batches on one device, and a
ReplicaSet of 4 unsharded replicas, one per chip.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
``ok`` is false, and the exit code 1, when the device is not a TPU — a CPU run
is never a pass. ``--tiny`` exists only so the control flow can be rehearsed on
a CPU (small shapes, kernels in interpret mode where a test hook exists). The
script never sets JAX_PLATFORMS. Compile caches: JAX_COMPILATION_CACHE_DIR where
set, else ``.jax_cache`` in the checkout (nn/compile_cache.cache_root).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback
import urllib.request

import numpy as np

#: relative tolerances: max abs difference over the reference's max abs value.
#: Through a matmul the dtype of the operands does not matter on this chip: at
#: default precision the MXU rounds float32 operands to bfloat16 (one pass), in
#: the kernels and in XLA's own math alike, so float32 and bfloat16 paths carry
#: the same 2^-8 operand rounding, a few times over. (The first chip run held
#: float32 to 2e-3 and measured 7e-3 on the flash kernel against a reference
#: at "highest" precision; bfloat16 measured the same.) Without a matmul a
#: float32 kernel is held to float32 rounding.
TOL_MATMUL = 3e-2
TOL_ELEMENTWISE = {"float32": 1e-4, "bfloat16": 1e-2}


# ------------------------------------------------------------------ reporting
class Report:
    """Per-phase lines on stdout, and what the last line needs."""

    def __init__(self):
        import jax
        from jax import monitoring

        self.ok = True
        self._compile_s = 0.0
        self._jax_cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}

    def _on_duration(self, event, duration, **_):
        if event.endswith("backend_compile_duration"):
            self._compile_s += duration

    def _on_event(self, event, **_):
        if event.endswith("/compilation_cache/cache_hits"):
            self._jax_cache_hits += 1

    @staticmethod
    def _counter(name):
        from deeplearning4j_tpu.observability.metrics import global_registry
        series = global_registry().snapshot().get(name, {}).get("series", [])
        return {tuple(sorted(s["labels"].items())): s["value"] for s in series}

    def dispatch(self):
        """{kernel: [engaged, not engaged]} so far, per trace."""
        from deeplearning4j_tpu.observability.names import (
            PALLAS_DISPATCH_TOTAL)
        out = {}
        for labels, v in self._counter(PALLAS_DISPATCH_TOTAL).items():
            d = dict(labels)
            out.setdefault(d["kernel"], [0, 0])[d["engaged"] != "true"] += int(v)
        return out

    def store_hits(self):
        from deeplearning4j_tpu.observability.names import (
            COMPILE_CACHE_HITS_TOTAL)
        return int(sum(self._counter(COMPILE_CACHE_HITS_TOTAL).values()))

    def phase(self, name, fn, must_engage=()):
        """Run one phase; -> its result dict (None if it raised). ``fn``
        returns a dict; ``must_engage`` names the kernels whose engaged count
        has to rise during it, unless the dict carries ``gated``."""
        before, c0, t0 = self.dispatch(), self._compile_s, time.perf_counter()
        line = {"phase": name, "ok": True}
        result = None
        try:
            result = fn() or {}
            line.update(result)
        except Exception as e:  # a phase that raises fails the run, not the others
            traceback.print_exc()
            line.update(ok=False, error=f"{type(e).__name__}: {e}"[:500])
        after = self.dispatch()
        kernels = {k: [a - b for a, b in zip(v, before.get(k, [0, 0]))]
                   for k, v in after.items()}
        line["kernels"] = {k: {"engaged": v[0], "xla": v[1]}
                           for k, v in kernels.items() if any(v)}
        for k in must_engage:
            if kernels.get(k, [0, 0])[0] < 1 and k not in line.get("gated", {}):
                line["ok"] = False
                line.setdefault("not_engaged", []).append(k)
        line["seconds"] = round(time.perf_counter() - t0, 3)
        line["compile_seconds"] = round(self._compile_s - c0, 3)
        self.ok &= bool(line["ok"])
        print(json.dumps(line), flush=True)
        return result if line["ok"] else None

    def finish(self):
        from deeplearning4j_tpu.nn import compile_cache
        root = compile_cache.cache_root()
        store = compile_cache.cache_dir()
        size = {True: 0, False: 0}      # bytes: in the store / JAX's own
        for d, _, files in os.walk(root):
            for f in files:
                size[d.startswith(store)] += os.path.getsize(
                    os.path.join(d, f))
        print(json.dumps({"phase": "caches", "root": root,
                          "jax_persistent_hits": self._jax_cache_hits,
                          "executable_store_hits": self.store_hits(),
                          "jax_persistent_mib": round(size[False] / 2**20, 1),
                          "executable_store_mib": round(size[True] / 2**20, 1)}),
              flush=True)
        ok = self.ok and self.device["platform"] == "tpu"
        print(json.dumps({"ok": ok, "device": self.device}), flush=True)
        return 0 if ok else 1


def _check(line, name, got, want, tol):
    """Record max |got - want| under ``name``; fail the phase's line when it
    exceeds ``tol`` times |want|'s max (or ``got`` is not finite)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    diff = (float(np.max(np.abs(got - want)))
            if np.all(np.isfinite(got)) else float("nan"))
    scale = max(float(np.max(np.abs(want))), 1e-6)
    line.setdefault("max_abs_diff", {})[name] = diff
    line.setdefault("max_rel_diff", {})[name] = diff / scale
    if not diff <= tol * scale:
        line["ok"] = False
        line.setdefault("out_of_tolerance", []).append(name)


def _flat(tree):
    import jax
    return np.concatenate([np.asarray(a, np.float32).ravel()
                           for a in jax.tree_util.tree_leaves(tree)])


def _onehot(rng, shape, n):
    return np.eye(n, dtype=np.float32)[rng.integers(0, n, shape)]


def _exact_math():
    """XLA reference math at full f32 matmul precision (the TPU's default
    rounds f32 operands to bf16, which would make the reference the noisy
    side of the comparison)."""
    import jax
    return jax.default_matmul_precision("highest")


# -------------------------------------------------------------------- phases
def phase_native():
    from deeplearning4j_tpu import nativert

    if shutil.which("g++") is None:
        return {"gated": {"native": "no C++ compiler on this machine; the "
                          "pure-Python decoders are what runs here"}}
    if nativert.get_runtime() is None:
        raise RuntimeError(f"native runtime did not load: "
                           f"{nativert.load_error()}")
    raw = np.random.default_rng(0).integers(0, 256, 1 << 16, np.uint8).tobytes()
    native = nativert.decode_records(raw, "u8")
    if native is None or not np.array_equal(
            native, nativert.decode_records_py(raw, "u8")):
        raise RuntimeError("native u8 decode differs from the Python decoder")
    return {"runtime_version": int(nativert.get_runtime()
                                   .dl4j_runtime_version())}


def make_resnet(a):
    from deeplearning4j_tpu.models.resnet import resnet50
    from deeplearning4j_tpu.nn.graph_network import ComputationGraph

    if a.tiny:
        conf = resnet50(n_classes=10, image_size=32, stage_blocks=(1, 1, 1, 1),
                        seed=a.seed)
    else:
        conf = resnet50(n_classes=1000, image_size=224, seed=a.seed)
    conf.global_conf.dtype = "bfloat16_full"
    net = ComputationGraph(conf).init()
    net.dispatch_ksteps = 2 if a.tiny else 8
    return net


def phase_resnet_train(a, net):
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets.dataset import DataSet

    rng = np.random.default_rng(a.seed)
    batch, size, classes = (8, 32, 10) if a.tiny else (128, 224, 1000)
    dispatches = 3
    # a few distinct seeded batches, cycled: host RNG for 24 full batches
    # would take longer than the training they feed
    pool = [DataSet(rng.standard_normal((batch, size, size, 3), np.float32),
                    _onehot(rng, batch, classes)) for _ in range(4)]
    n = dispatches * net.dispatch_ksteps
    net.stage_dtype = jnp.bfloat16   # compute casts to bf16 anyway
    loss, moved = _fit(net, [pool[i % 4] for i in range(n)])
    return {"ok": bool(np.isfinite(loss) and moved > 0), "loss": loss,
            "param_max_abs_change": moved, "steps": n, "batch": batch}


def phase_resnet_serve(a, net):
    from deeplearning4j_tpu.keras_server import ModelRegistry
    from deeplearning4j_tpu.keras_server.serving import InferenceServer

    rng = np.random.default_rng(a.seed + 1)
    size = 32 if a.tiny else 224
    rows = 4
    srv = InferenceServer(ModelRegistry(), max_batch=rows,
                          max_latency_s=0.001, request_timeout_s=600.0)
    line = {"ok": True, "requests": 0}
    try:
        srv.registry.register("resnet50", net)
        srv.start()
        for _ in range(3):
            x = rng.standard_normal((rows, size, size, 3), np.float32)
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/predict",
                data=json.dumps({"model": "resnet50",
                                 "inputs": x.tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=600) as resp:
                body = json.loads(resp.read())
            got = np.asarray(body["predictions"], np.float32)
            want = np.asarray(net.output(x)[0], np.float32)
            if got.shape != want.shape:
                raise RuntimeError(f"served {got.shape}, net.output "
                                   f"{want.shape}")
            _check(line, f"request{line['requests']}", got, want, TOL_MATMUL)
            line["requests"] += 1
        line["bitwise"] = all(v == 0.0 for v in line["max_abs_diff"].values())
    finally:
        srv.stop()
    return line


def phase_xent(a):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    line = {"ok": True}
    rng = np.random.default_rng(a.seed)
    for n, c, dt in ((64, 10, jnp.float32), (256, 100, jnp.bfloat16)) \
            if a.tiny else ((4096, 1000, jnp.float32),
                            (4096, 1000, jnp.bfloat16),
                            (4096, 32000, jnp.bfloat16)):
        logits = jnp.asarray(rng.standard_normal((n, c), np.float32) * 3, dt)
        labels = jnp.asarray(_onehot(rng, n, c), dt)
        loss, grad = jax.jit(
            lambda lg, lb: pk.softmax_cross_entropy(lg, lb, interpret=a.interpret)
        )(logits, labels)
        with _exact_math():
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            want_loss = -jnp.sum(labels.astype(jnp.float32) * lp, axis=-1)
            want_grad = jnp.exp(lp) - labels.astype(jnp.float32)
        tag = f"C{c}-{jnp.dtype(dt).name}"
        _check(line, f"loss-{tag}", loss, want_loss,
               TOL_ELEMENTWISE["float32"])
        _check(line, f"grad-{tag}", grad, want_grad,
               TOL_ELEMENTWISE[jnp.dtype(dt).name])
    return line


def phase_flash(a):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    line = {"ok": True}
    rng = np.random.default_rng(a.seed)
    T = 256 if a.tiny else 4096
    for dt in (jnp.bfloat16, jnp.float32):
        q, k, v, g = (jnp.asarray(rng.standard_normal((2, T, 4, 64),
                                                      np.float32), dt)
                      for _ in range(4))

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * g.astype(jnp.float32))

        def kernel(q, k, v):
            # the gate decides (T >= _MIN_SEQ, _PBWD_MIN_SEQ); on a CPU
            # rehearsal interpret mode stands in for the chip
            return pk.flash_attention(q, k, v, True, a.interpret, a.interpret)

        got = jax.jit(jax.value_and_grad(
            lambda *x: loss(kernel, *x), argnums=(0, 1, 2)))(q, k, v)
        out = jax.jit(kernel)(q, k, v)
        with _exact_math():
            ref = lambda q, k, v: pk._attention_xla(  # noqa: E731
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), True)
            want = jax.jit(jax.value_and_grad(
                lambda *x: loss(ref, *x), argnums=(0, 1, 2)))(q, k, v)
            want_out = jax.jit(ref)(q, k, v)
        tag = jnp.dtype(dt).name
        _check(line, f"out-{tag}", out, want_out, TOL_MATMUL)
        for name, gk, gw in zip(("dq", "dk", "dv"), got[1], want[1]):
            _check(line, f"{name}-{tag}", gk, gw, TOL_MATMUL)
    line["T"] = T
    return line


def _lm(a, T):
    """The repo's LM at its full width (256; depth cut to 2 blocks)."""
    from deeplearning4j_tpu.models.transformer import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = transformer_lm(vocab_size=32 if a.tiny else 256,
                          width=32 if a.tiny else 256, n_layers=2, n_heads=4,
                          max_len=T, seed=a.seed)
    return MultiLayerNetwork(conf).init()


def _next_token_batches(rng, n, B, T, V):
    """``n`` seeded [B, T, V] one-hot batches with next-token labels."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    eye = np.eye(V, dtype=np.float32)
    ids = rng.integers(0, V, (n, B, T + 1))
    return [DataSet(eye[i[:, :-1]], eye[i[:, 1:]]) for i in ids]


def _fit(net, data):
    """fit_iterator over ``data`` -> (final loss, max abs parameter change)."""
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator

    before = _flat(net.params_list)
    net.fit_iterator(ListDataSetIterator(data))
    loss = float(net.score_value)    # host read: the donated chain is done
    return loss, float(np.max(np.abs(_flat(net.params_list) - before)))


def phase_transformer_train(a):
    V, T, B = (32, 64, 2) if a.tiny else (256, 4096, 2)
    net = _lm(a, T)
    net.conf.global_conf.dtype = "bfloat16"      # read when the step is traced
    net.dispatch_ksteps = 2
    data = _next_token_batches(np.random.default_rng(a.seed), 4, B, T, V)
    loss, moved = _fit(net, data)
    return {"ok": bool(np.isfinite(loss) and moved > 0), "loss": loss,
            "param_max_abs_change": moved, "T": T, "steps": len(data)}


def _lstm_shapes(a):
    # char-RNN layer 0: TBPTT chunk 50, one-hot vocab 64, hidden 512
    return (4, 10, 8, 16) if a.tiny else (32, 50, 64, 512)


def phase_lstm(a):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import common
    from deeplearning4j_tpu.ops import lstm as eng

    B, T, F, H = _lstm_shapes(a)
    rng = np.random.default_rng(a.seed)
    params = {k: jnp.asarray(rng.standard_normal(s, np.float32) * 0.1)
              for k, s in (("W", (F, 4 * H)), ("RW", (H, 4 * H)),
                           ("b", (4 * H,)), ("pI", (H,)), ("pF", (H,)),
                           ("pO", (H,)))}
    x = jnp.asarray(_onehot(rng, (B, T), F))
    h0 = jnp.zeros((B, H), jnp.float32)
    line = {"ok": True, "gated": {}}

    def run(impl, p, x):
        def loss(p, x):
            ys, (h, c) = eng.lstm_sequence(
                p, x, jnp.tanh, jax.nn.sigmoid, h0, h0, True, None, impl=impl,
                interpret=a.interpret)
            return jnp.sum(jnp.cos(ys.astype(jnp.float32))), ys
        (_, ys), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                            has_aux=True)(p, x)
        return ys, grads

    for policy in ("bfloat16", "float32"):
        fn = common.wrap_with_policy(run, policy)
        cd = jnp.bfloat16 if policy == "bfloat16" else jnp.float32
        why = eng.pallas_refusal(H, T, B, F, dtype=cd, interpret=a.interpret)
        if a.tiny and not why:
            # the rehearsal sits under the auto thresholds; ask by name
            impl = "pallas"
        else:
            impl = "auto"
        if why:
            line["gated"][f"lstm_cell-{policy}"] = why
        ys, (dp, dx) = jax.jit(fn, static_argnums=0)(impl, params, x)
        with _exact_math():
            ys0, (dp0, dx0) = jax.jit(fn, static_argnums=0)("scan", params, x)
        _check(line, f"ys-{policy}", ys, ys0, TOL_MATMUL)
        _check(line, f"dx-{policy}", dx, dx0, TOL_MATMUL)
        _check(line, f"dW-{policy}", _flat(dp), _flat(dp0), TOL_MATMUL)
    if "lstm_cell-bfloat16" in line["gated"]:
        line["gated"]["lstm_cell"] = line["gated"]["lstm_cell-bfloat16"]
    return line


def phase_char_rnn_train(a):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops import lstm as eng

    B, T, V, H = _lstm_shapes(a)
    conf = char_rnn_lstm(vocab_size=V, hidden=H, layers=2, tbptt_length=T,
                         seed=a.seed)
    conf.global_conf.dtype = "bfloat16"
    net = MultiLayerNetwork(conf).init()
    # two TBPTT chunks to a batch
    data = _next_token_batches(np.random.default_rng(a.seed), 3, B, 2 * T, V)
    loss, moved = _fit(net, data)
    # the engine's own verdict per layer, from the shapes it saw
    layers = {}
    for i, n_in in enumerate((V, H)):
        why = eng.pallas_refusal(H, T, B, n_in, dtype=jnp.bfloat16)
        layers[f"layer{i}"] = why or "pallas"
    line = {"ok": bool(np.isfinite(loss) and moved > 0), "loss": loss,
            "param_max_abs_change": moved, "hidden": H}
    line["gated"] = {f"lstm_cell-{k}": v for k, v in layers.items()
                     if v != "pallas"}
    if len(line["gated"]) == len(layers):    # no layer could take the kernel
        line["gated"]["lstm_cell"] = layers["layer0"]
    return line


def phase_decode(a):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.keras_server.decode import DecodeEngine
    from deeplearning4j_tpu.models.transformer import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops import paged_attention, quant

    V, W, ctx = (128, 128, 64) if a.tiny else (512, 512, 256)
    net = MultiLayerNetwork(transformer_lm(
        vocab_size=V, width=W, n_layers=2, n_heads=W // 64, max_len=ctx,
        seed=a.seed)).init()
    rng = np.random.default_rng(a.seed)
    prompts = [rng.integers(0, V, 5).tolist() for _ in range(4)]
    line = {"ok": True}

    def generate(kv):
        eng = DecodeEngine(net, kv=kv, quant="int8", max_context=ctx,
                           min_slots=4, max_slots=4, page_size=16)
        try:
            sessions = [eng.submit(p, max_new_tokens=8) for p in prompts]
            return [s.result(timeout=600) for s in sessions]
        finally:
            eng.close()

    paged, dense = generate("paged"), generate("dense")
    line["tokens"] = sum(len(t) for t in paged)
    line["tokens_equal_dense_engine"] = paged == dense
    line["ok"] &= paged == dense and line["tokens"] == 32

    # the two kernels against XLA math at the engine's own shapes
    H, D, ps = W // 64, 64, 16
    pool = jnp.asarray(rng.standard_normal((4 * ctx // ps + 1, ps, H, D),
                                           np.float32), jnp.bfloat16)
    table = jnp.asarray(rng.integers(0, pool.shape[0], (4, ctx // ps)),
                        jnp.int32)
    got = jax.jit(lambda p, t: paged_attention.paged_gather(p, t))(pool, table)
    want = jax.jit(lambda p, t: paged_attention.paged_gather(
        p, t, impl="xla"))(pool, table)
    line["paged_gather_bitwise"] = bool(jnp.array_equal(got, want))
    line["ok"] &= line["paged_gather_bitwise"]

    leaf = quant.quantize_per_channel(
        jnp.asarray(rng.standard_normal((W, 4 * W), np.float32)))
    x = jnp.asarray(rng.standard_normal((4, W), np.float32))
    got = jax.jit(lambda x: quant.quantized_matmul(x, leaf))(x)
    with _exact_math():
        want = jax.jit(lambda x: jnp.matmul(
            x, leaf.q.astype(jnp.float32)) * leaf.scale)(x)
    _check(line, "int8_matmul", got, want, TOL_MATMUL)
    return line


# ------------------------------------------------------------ four-chip path
def phase_dp_train(a):
    """ParallelWrapper sync DP on a 4-device data mesh == the same global
    batches on one device; the batch really is split four ways and the
    gradient all-reduce is in the program."""
    import jax

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.observability.compile_tracker import global_tracker
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    T = 64 if a.tiny else 1024       # >= _MIN_SEQ: on one device flash engages
    V = 32 if a.tiny else 256
    B = 8                            # global batch, 2 per chip
    data = _next_token_batches(np.random.default_rng(a.seed), 4, B, T, V)
    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])
    line = {"ok": True, "T": T, "global_batch": B}

    def compare(tag, max_tol, l2_tol):
        """Train a fresh pair, one device against four, on the same batches;
        hold the parameter UPDATE's difference to ``max_tol`` (max abs) and
        ``l2_tol`` (L2), each relative to the one-device update."""
        single, multi = _lm(a, T), _lm(a, T)
        single.dispatch_ksteps = multi.dispatch_ksteps = 2
        before = _flat(single.params_list)
        # the reference runs the math the four-chip program runs: in a jit
        # that GSPMD partitions the kernels take their XLA path (see "gated"
        # below), so the one-device side is held to it too — what is
        # compared is one device against four, not a kernel against XLA
        # (the one-chip run does that). DL4J_* switches are part of an
        # executable's fingerprint.
        os.environ["DL4J_TPU_DISABLE_PALLAS"] = "1"
        try:
            single.fit_iterator(ListDataSetIterator(data))
        finally:
            del os.environ["DL4J_TPU_DISABLE_PALLAS"]
        ParallelWrapper(multi, mesh=mesh).fit(ListDataSetIterator(data))
        step = _flat(single.params_list) - before
        diff = _flat(multi.params_list) - before - step
        loss1, loss4 = float(single.score_value), float(multi.score_value)
        res = {"loss_single": loss1, "loss_dp": loss4,
               "update_max_abs": float(np.max(np.abs(step))),
               "diff_max_abs": float(np.max(np.abs(diff))),
               "diff_rel_l2": float(np.linalg.norm(diff)
                                    / max(np.linalg.norm(step), 1e-30)),
               "tolerance": {"max_abs_rel": max_tol, "rel_l2": l2_tol}}
        res["ok"] = bool(
            np.isfinite(loss4) and res["update_max_abs"] > 0
            and abs(loss4 - loss1) <= 1e-3 * abs(loss1)
            and res["diff_max_abs"] <= max_tol * res["update_max_abs"]
            and res["diff_rel_l2"] <= l2_tol)
        line[tag] = res
        line["ok"] &= res["ok"]

    # Four devices sum the gradient in another order than one. At float32
    # matmul precision that is float32 rounding, and the update must agree
    # tightly. At the TPU's default precision (float32 operands rounded to
    # bfloat16) a different per-device batch also changes how XLA tiles each
    # matmul; gradient elements that are small sums of cancelling terms then
    # differ by a large fraction of themselves, and Adam turns every element's
    # gradient into a step of about the learning rate whatever its size — so
    # single elements may differ by a good part of a step (the first
    # four-chip run measured 16% of the largest update) while the update as a
    # whole agrees: held on its L2 norm, the largest element only reported
    # against a bound of a whole step.
    with jax.default_matmul_precision("highest"):
        compare("float32_precision", max_tol=1e-2, l2_tol=1e-2)
    compare("default_precision", max_tol=1.0, l2_tol=1e-1)

    exe = global_tracker().executable("ParallelWrapper.sync_multistep") \
        or global_tracker().executable("ParallelWrapper.sync_step")
    if exe is None:
        raise RuntimeError("no sync-DP executable was noted by the tracker")
    hlo = exe.as_text()
    line["all_reduce_ops"] = hlo.count(" all-reduce(") \
        + hlo.count(" all-reduce-start(")
    # what became of the kernels: GSPMD cannot partition a Mosaic call (the
    # TPU lowering refuses the program), so the gates leave a jit over four
    # devices to XLA math and say why
    line["pallas_custom_calls"] = hlo.count('"tpu_custom_call"')
    with pk.partitioned_trace(mesh.size):
        line["gated"] = {"flash_attention": pk.pallas_unavailable()}
    batch_in = [s for s in jax.tree_util.tree_leaves(exe.input_shardings)
                if getattr(s, "spec", None) and "data" in str(s.spec)]
    line["batch_inputs_split_over_data"] = len(batch_in)
    shard = batch_in[0].shard_shape((2, B, T, V)) if batch_in else None
    line["per_device_batch"] = None if shard is None else int(shard[1])
    line["ok"] &= (line["all_reduce_ops"] > 0 and len(batch_in) >= 2
                   and line["per_device_batch"] == B // 4)
    line["devices_in_program"] = len(exe.runtime_executable().local_devices())
    return line


def phase_replica_set(a):
    import jax

    from deeplearning4j_tpu.keras_server import ReplicaSet
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    width = 32 if a.tiny else 1024
    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(a.seed).list()
        .layer(DenseLayer(n_in=width, n_out=width, activation="relu"))
        .layer(OutputLayer(n_in=width, n_out=16, loss="mcxent",
                           activation="softmax")).build()).init()
    rs = ReplicaSet(4, max_batch=8, max_latency_s=0.001,
                    devices=jax.devices()[:4])
    line = {"ok": True}
    try:
        rs.register("mlp", net, version="v1")
        x = np.random.default_rng(a.seed).standard_normal(
            (4, width)).astype(np.float32)
        want = np.asarray(net.output(x))
        placed, served = [], []
        for r in rs.replicas:
            pf = r.registry.active("mlp").predict_fn
            devs = {d for leaf in jax.tree_util.tree_leaves(
                pf.params_snapshot()) for d in leaf.devices()}
            placed.append(sorted(d.id for d in devs))
            out = r.batcher.submit("mlp", x).result(timeout=300)
            got = np.asarray(out["predictions"])
            served.append(sorted(d.id for d in pf(x).devices()))
            _check(line, f"replica{r.index}", got, want, TOL_MATMUL)
        line["param_devices"] = placed
        line["output_devices"] = served
        distinct = (all(len(p) == 1 for p in placed)
                    and len({p[0] for p in placed}) == 4 and placed == served)
        line["four_distinct_devices"] = distinct
        line["ok"] &= distinct
    finally:
        rs.close()
    return line


# ----------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip phases (DP training, "
                         "ReplicaSet placement)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse the control flow at toy sizes on a CPU; "
                         "the result is ok=false there by construction")
    a = ap.parse_args(argv)

    import jax

    from deeplearning4j_tpu.nn import compile_cache

    platform = jax.devices()[0].platform
    #: interpret mode is something the rehearsal asks for, never the chip
    a.interpret = a.tiny and platform != "tpu"
    if a.interpret:
        for hook in ("DL4J_XENT_INTERPRET", "DL4J_LSTM_INTERPRET",
                     "DL4J_PAGED_GATHER_INTERPRET", "DL4J_INT8_INTERPRET"):
            os.environ[hook] = "1"
    rep = Report()
    print(json.dumps({"phase": "start", "device": rep.device,
                      "jax": jax.__version__, "chips": a.chips,
                      "tiny": a.tiny,
                      "cache_root": compile_cache.cache_root()}), flush=True)
    if platform != "tpu" and not a.tiny:
        print("chip_smoke: no TPU here (JAX reports "
              f"{platform!r}); nothing was run", file=sys.stderr)
        rep.ok = False
        return rep.finish()
    if len(jax.devices()) < a.chips:
        print(f"chip_smoke: --chips {a.chips} needs {a.chips} devices, JAX "
              f"reports {len(jax.devices())}", file=sys.stderr)
        rep.ok = False
        return rep.finish()

    if a.chips == 4:
        rep.phase("dp_train", lambda: phase_dp_train(a))
        rep.phase("replica_set", lambda: phase_replica_set(a))
        return rep.finish()

    rep.phase("native", phase_native)
    net = make_resnet(a)
    trained = rep.phase("resnet50_train", lambda: phase_resnet_train(a, net),
                        must_engage=("softmax_cross_entropy",))
    if trained is not None:
        rep.phase("resnet50_serve", lambda: phase_resnet_serve(a, net))
    else:
        rep.phase("resnet50_serve", lambda: {
            "ok": False, "error": "skipped: the training phase failed"})
    del net
    rep.phase("xent", lambda: phase_xent(a),
              must_engage=("softmax_cross_entropy",))
    rep.phase("flash", lambda: phase_flash(a),
              must_engage=("flash_attention", "flash_attention_bwd"))
    rep.phase("transformer_train", lambda: phase_transformer_train(a),
              must_engage=("flash_attention", "flash_attention_bwd",
                           "softmax_cross_entropy"))
    rep.phase("lstm", lambda: phase_lstm(a), must_engage=("lstm_cell",))
    rep.phase("char_rnn_train", lambda: phase_char_rnn_train(a),
              must_engage=("lstm_cell",))
    rep.phase("decode", lambda: phase_decode(a),
              must_engage=("paged_gather", "int8_matmul"))
    return rep.finish()


if __name__ == "__main__":
    try:
        code = main()
    except Exception as e:  # no JAX, no package, no device: still one last line
        traceback.print_exc()
        print(json.dumps({"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}),
              flush=True)
        code = 1
    sys.exit(code)
