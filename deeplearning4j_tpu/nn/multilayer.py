"""MultiLayerNetwork: sequential network with fit/output/score/evaluate.

Reference: nn/multilayer/MultiLayerNetwork.java (2486 LoC) — init:386,
fit(DataSetIterator):978, backprop:1049, computeGradientAndScore:1807, feedForward:657,
rnnTimeStep:2196, doTruncatedBPTT:1140.

TPU-native redesign: the whole optimizer step — forward, loss (+l1/l2), autodiff
backward, gradient normalization, updater math, parameter update — is ONE jit-compiled
pure function over the parameter pytree, donated so XLA updates in place. The reference's
Solver/StochasticGradientDescent loop (optimize/solvers/StochasticGradientDescent.java:51)
collapses into that fused step; listeners observe from the host side.

Mutable-object API (net.fit(...), net.output(...)) is preserved as a thin stateful shell
over the pure functions so reference users feel at home; the pure train_step itself is
exposed for ParallelWrapper/pjit composition (see deeplearning4j_tpu.parallel).

This module is also the home of the fit loop. ``LazyScore``, the base of
MultiLayerNetwork and ComputationGraph, holds the one copy of ``fit_iterator``, the
staged K-step epoch, the per-batch step and the record of a dispatched step;
ParallelWrapper's synchronous loop runs the same code as a ``LoopOwner``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import time
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import common
from deeplearning4j_tpu.datasets.prefetch import (
    DevicePrefetcher, HostGroupRing, begin_group, current_group, end_group,
)
from deeplearning4j_tpu.observability.compile_tracker import (
    global_tracker as _compile_tracker,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    dump_on_unhandled as _dump_on_unhandled,
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.names import (
    FIT_PHASE_SECONDS, MOE_COMPUTED_ROWS_TOTAL, MOE_EXPERT_ROWS_MAX,
    MOE_EXPERT_ROWS_MAX_TOTAL, MOE_ROUTED_ROWS_TOTAL, MOE_TOKENS_TOTAL,
    REMAT_KEPT_BYTES_TOTAL,
)
from deeplearning4j_tpu.observability.metrics import (
    global_registry as _obs_registry,
)
from deeplearning4j_tpu.observability.profiler import (
    note_dispatch as _profile_note_dispatch,
)
from deeplearning4j_tpu.observability.startup import (
    log_time_to_first_step as _log_time_to_first_step,
)
from deeplearning4j_tpu.observability.watchdog import beat as _wd_beat
from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.layers.base import PretrainLayer
from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM
from deeplearning4j_tpu.nn.updaters import (
    UpdaterSpec, effective_lr, grads_to_param_dtype, normalize_gradients,
    updater_init, updater_step, updater_step_with_param,
)
from deeplearning4j_tpu.ops.remat import checkpoint_layer
from deeplearning4j_tpu.utils.pytree import flatten_params, num_params, unflatten_params

Array = jax.Array

# step-time attribution series (resolved once — per-step cost is two
# perf_counter reads and one locked float add per phase; budget pinned by
# tests/test_bench_contract.py::test_telemetry_overhead_budget)
_phase_hist = _obs_registry().histogram(
    FIT_PHASE_SECONDS,
    "host wall seconds per fit-loop phase (staging: host cast+transfer "
    "submit, or with device prefetch the visible wait for the staged batch; "
    "device: the wait for the step of the staged group two back before the "
    "next is dispatched; dispatch: jitted-call submit; listeners: callback "
    "overhead)")
_t_staging = _phase_hist.labels(phase="staging")
_t_device = _phase_hist.labels(phase="device")
_t_dispatch = _phase_hist.labels(phase="dispatch")
_t_listeners = _phase_hist.labels(phase="listeners")


_moe_tokens = _obs_registry().counter(
    MOE_TOKENS_TOTAL, "tokens that went through the expert layers of "
    "dispatched K-step groups (each counted once, not once per layer)")
_moe_routed = _obs_registry().counter(
    MOE_ROUTED_ROWS_TOTAL, "(token, choice) pairs routed to an expert this "
    "chip holds, by expert layer")
_moe_computed = _obs_registry().counter(
    MOE_COMPUTED_ROWS_TOTAL, "rows the grouped expert products ran over, "
    "padding to whole row tiles included, by expert layer")
_moe_rows_max = _obs_registry().gauge(
    MOE_EXPERT_ROWS_MAX, "rows of the busiest held expert in one step, the "
    "largest of the last group read, by expert layer")
_moe_rows_max_total = _obs_registry().counter(
    MOE_EXPERT_ROWS_MAX_TOTAL, "rows of the busiest held expert, summed over "
    "the steps (over the routed rows / experts held of the same steps: how "
    "uneven the routing was), by expert layer")


_remat_kept = _obs_registry().counter(
    REMAT_KEPT_BYTES_TOTAL, "bytes the checkpointed decoder blocks of "
    "dispatched steps kept from forward to backward besides their inputs "
    "(ops/remat.py), summed over the blocks, by the kept value's name")


def _updater_spec(layer) -> UpdaterSpec:
    return UpdaterSpec(
        name=layer.updater or "sgd",
        momentum=layer.momentum if layer.momentum is not None else 0.9,
        momentum_schedule=getattr(layer, "momentum_schedule", None),
        rho=layer.rho if layer.rho is not None else 0.95,
        rms_decay=layer.rms_decay if layer.rms_decay is not None else 0.95,
        adam_mean_decay=layer.adam_mean_decay if layer.adam_mean_decay is not None else 0.9,
        adam_var_decay=layer.adam_var_decay if layer.adam_var_decay is not None else 0.999,
        epsilon=layer.epsilon if layer.epsilon is not None else 1e-8,
    )


def _regularization(conf: MultiLayerConfiguration, params_list) -> Array:
    """l1 * |W|_1 + 0.5 * l2 * ||W||^2 over regularizable params (reference
    BaseLayer.calcL1/calcL2; gated on use_regularization like the builder's
    .regularization(true))."""
    if not conf.global_conf.use_regularization:
        return jnp.float32(0.0)
    total = jnp.float32(0.0)
    for layer, params in zip(conf.layers, params_list):
        for name in layer.regularizable_params():
            if name not in params:
                continue
            w = params[name]
            if layer.l1:
                total = total + layer.l1 * jnp.sum(jnp.abs(w))
            if layer.l2:
                total = total + 0.5 * layer.l2 * jnp.sum(w * w)
    return total


def _layer_scope(index: int, layer) -> str:
    """The ``jax.named_scope`` of one layer's work: it is in the ``op_name``
    of every operation the layer traces, forward and (inside JAX's
    ``transpose(jvp(...))``) backward, so device time can be read by layer."""
    return f"layer/{index}_{type(layer).__name__}"


def forward_fn(conf: MultiLayerConfiguration, params_list, state_list, x, *,
               train: bool, rng: Optional[jax.Array], mask: Optional[Array] = None,
               collect: bool = False):
    """Pure feed-forward through all layers (reference feedForwardToLayer:680).
    Returns (output, new_state_list, activations_list_or_None)."""
    h = x
    new_states = []
    acts = [] if collect else None
    rngs = (jax.random.split(rng, len(conf.layers))
            if rng is not None else [None] * len(conf.layers))
    for i, layer in enumerate(conf.layers):
        pp = conf.preprocessor(i)
        with jax.named_scope(_layer_scope(i, layer)):
            if pp is not None:
                h = pp.pre_process(h, mask)
            h, ns = layer.apply(params_list[i], state_list[i], h,
                                train=train, rng=rngs[i], mask=mask)
        new_states.append(ns)
        if collect:
            acts.append(h)
    return h, new_states, acts


def loss_fn(conf: MultiLayerConfiguration, params_list, state_list, x, y, rng,
            fmask=None, lmask=None):
    """Training loss: forward to the last (loss) layer + regularization.
    Returns (loss, new_state_list).

    With ``gradient_checkpointing`` set, each layer application is wrapped in
    ``ops/remat.py::checkpoint_layer``: backward recomputes the layer's
    forward instead of holding its activations in HBM, at ~1.3x FLOPs. A
    layer keeps its input and, by name, its attention kernels' outputs, which
    the recomputed forward then does not make again: per decoder block
    tokens x heads x value width x 2 B of the flash core's output (float32
    log-sum-exps beside it), and B x T x T bytes of int8 selection under an
    indexer (``DecoderBlock.remat_kept_bytes``; counter
    ``dl4j_remat_kept_bytes_total``). Every other activation lives for O(1)
    layers; the kept bytes grow with the depth."""
    layers = conf.layers
    last = layers[-1]
    if not last.has_loss():
        raise ValueError("Last layer has no loss function; cannot compute supervised loss")
    remat = conf.global_conf.gradient_checkpointing
    h = x
    new_states = []
    rngs = (jax.random.split(rng, len(layers))
            if rng is not None else [None] * len(layers))
    for i, layer in enumerate(layers[:-1]):
        pp = conf.preprocessor(i)
        with jax.named_scope(_layer_scope(i, layer)):
            if pp is not None:
                h = pp.pre_process(h, fmask)
            if remat:
                def f(p, hh, _layer=layer, _s=state_list[i], _r=rngs[i]):
                    return _layer.apply(p, _s, hh, train=True, rng=_r,
                                        mask=fmask)
                h, ns = checkpoint_layer(f)(params_list[i], h)
            else:
                h, ns = layer.apply(params_list[i], state_list[i], h,
                                    train=True, rng=rngs[i], mask=fmask)
        new_states.append(ns)
    pp = conf.preprocessor(len(layers) - 1)
    with jax.named_scope("loss"):
        if pp is not None:
            h = pp.pre_process(h, fmask)
        h = last.apply_dropout(h, rngs[-1], True)
        loss = last.compute_loss(params_list[-1], h, y, lmask)
        new_states.append(state_list[-1])
        loss = loss + _aux_losses(layers, new_states)
        loss = loss + _regularization(conf, params_list)
    return loss, new_states


#: auxiliary objectives a layer may publish as scalars in its state, each
#: with the layer's field that weighs it
_AUX_TERMS = (("aux_loss", "aux_loss_weight"),
              ("index_loss", "index_loss_weight"))


def _aux_losses(layers, new_states):
    """Sum layer-declared auxiliary objectives: a layer publishes one by
    returning a scalar in its state under a name of ``_AUX_TERMS``:
    "aux_loss" (e.g. MoELayer's Switch load-balance term, a DecoderBlock's
    sequence-wise one) weighted by its ``aux_loss_weight``, and "index_loss"
    (a DecoderBlock's indexer: the divergence of its scores from the core's
    probabilities, whose gradient reaches the indexer's leaves alone)
    weighted by its ``index_loss_weight``. A layer may publish both."""
    total = jnp.float32(0.0)
    for layer, ns in zip(layers, new_states):
        if not isinstance(ns, dict):
            continue
        for term, weight in _AUX_TERMS:
            if term in ns:
                total = total + getattr(layer, weight, 1.0) * ns[term]
    return total


def _apply_updates(conf, params_list, grads, upd_state, iteration):
    """Per-layer gradient normalization + updater math of one train step."""
    g = conf.global_conf
    new_params = []
    new_upd = []
    for i, layer in enumerate(conf.layers):
        g_i = grads[i]
        if not g_i:
            new_params.append(params_list[i])
            new_upd.append(upd_state[i])
            continue
        g_i = normalize_gradients(g_i, layer.gradient_normalization,
                                  layer.gradient_normalization_threshold or 1.0)
        spec = _updater_spec(layer)
        lr = effective_lr(layer.learning_rate, g.lr_policy, iteration,
                          g.lr_policy_decay_rate, g.lr_policy_power,
                          g.lr_policy_steps, g.lr_schedule, g.max_num_iterations)
        lr_bias = (jnp.float32(layer.bias_learning_rate)
                   if layer.bias_learning_rate is not None else lr)
        p_new = {}
        u_new = {}
        for name, grad in g_i.items():
            this_lr = lr_bias if name in ("b", "vb", "beta") else lr
            step, ustate = updater_step_with_param(
                spec, grad, params_list[i][name], upd_state[i][name],
                this_lr, iteration)
            p_new[name] = params_list[i][name] - step
            u_new[name] = ustate
        new_params.append(p_new)
        new_upd.append(u_new)
    return new_params, new_upd


def make_train_step(conf: MultiLayerConfiguration, loss=None, *,
                    health: bool = False):
    """Build the fused train step: grads via autodiff, per-layer normalization + updater.
    Pure: (params, states, upd_states, x, y, rng, iteration, fmask, lmask) ->
    (params', states', upd_states', loss).

    ``loss`` optionally replaces the standard ``loss_fn`` with a callable of
    the same signature (params_list, state_list, x, y, rng, fmask, lmask) ->
    (loss, new_state_list) — e.g. PipelineTrainer's pipelined forward — while
    keeping the updater/clipping/schedule semantics identical.

    ``health=True`` fuses the health monitor's summary (grad/update norms,
    non-finite count, loss — see ``observability.health.health_terms``) into
    the step and appends its packed vector to the return tuple. Computed
    where grads, old params, and new params all coexist as program values,
    so it stays donation-safe; off-cadence fit dispatches use the plain
    variant and are byte-identical to unmonitored training."""
    g = conf.global_conf
    if loss is None:
        loss = functools.partial(loss_fn, conf)

    def train_step(params_list, state_list, upd_state, x, y, rng, iteration,
                   fmask=None, lmask=None):
        (loss_val, new_states), grads = jax.value_and_grad(
            lambda p: loss(p, state_list, x, y, rng, fmask, lmask),
            has_aux=True)(params_list)
        with jax.named_scope("update"):
            grads = grads_to_param_dtype(grads, params_list)
            new_params, new_upd = _apply_updates(conf, params_list, grads,
                                                 upd_state, iteration)
        if health:
            from deeplearning4j_tpu.observability.health import health_terms

            haux = health_terms(grads, params_list, new_params, loss_val)
            return new_params, new_states, new_upd, loss_val, haux
        return new_params, new_states, new_upd, loss_val

    # a config-declared dtype policy is baked in at trace time (GlobalConf.dtype)
    return common.wrap_with_policy(train_step, g.dtype)


def make_multistep_train_step(conf: MultiLayerConfiguration, *,
                              health: bool = False):
    """K fused train steps per host dispatch via `lax.scan`.

    Takes a device-resident stack of K minibatches ``xs, ys`` of shape
    ``(K, B, ...)`` and applies the full train step K times inside one XLA
    program. On TPU this amortizes host->device dispatch latency (cf. the
    reference's per-minibatch `MultiLayerNetwork.fit` loop at MultiLayerNetwork.java:1540 which pays a
    host round-trip every step) across K steps; inputs stay in HBM the whole
    time. Returns the per-step losses as a (K,) array — listeners that only
    fire every N iterations can then read just the scores they need without
    forcing a host sync per step.

    ``health=True`` threads the per-step health vector through the scan and
    returns it stacked as ``(K, 4)`` after the losses; the dispatcher picks
    the row for the cadence-due iteration (a lazy device gather, no sync).

    Where expert layers publish ``moe_rows`` in their state (DecoderBlock),
    every step's rows come back last, stacked ``(K, layers, 3)`` int32: the
    fit loop reads them on the host once they are ready, beside the score
    (``LazyScore._note_moe_rows``).
    """
    step = make_train_step(conf, health=health)

    def dl4j_train_ksteps(params_list, state_list, upd_state, xs, ys, rng,
                          iteration0):
        def body(carry, batch):
            p, s, u, it = carry
            x, y = batch
            key = jax.random.fold_in(rng, it)
            p, s, u, *out = step(p, s, u, x, y, key, it)
            rows = [st["moe_rows"] for st in s
                    if isinstance(st, dict) and "moe_rows" in st]
            if rows:
                out.append(jnp.stack(rows))
            return (p, s, u, it + 1), tuple(out)

        (p, s, u, _), out = jax.lax.scan(
            body, (params_list, state_list, upd_state, iteration0), (xs, ys))
        return (p, s, u) + out

    # the function's name is the compiled module's (``jit_dl4j_train_ksteps``):
    # a profile's reader finds the step program by it
    return dl4j_train_ksteps


def _stage_host(x, dtype, out=None):
    """Cast features to the staging dtype ON THE HOST, before the device
    transfer, so ``stage_dtype`` halves host->device wire bytes on every fit
    path (its documented contract). Device-resident jax Arrays are cast on
    device instead — pulling them back to host would defeat the point.

    With ``out`` (one batch's place in a host group buffer of the staged
    dtype) the cast is fused into the copy there: one pass over the bytes,
    the same round-to-nearest-even cast as ``astype``, bit for bit."""
    if out is not None:
        np.copyto(out, x, casting="unsafe")
        return out
    if dtype is None:
        return x
    on_device = isinstance(x, jax.Array)
    if not on_device:
        x = np.asarray(x)
    if not _castable(x.dtype, dtype):
        return x        # an integer leaf (token ids) is left as it is
    return x.astype(dtype) if on_device else x.astype(dtype, copy=False)


def _castable(leaf_dtype, stage_dtype) -> bool:
    """Whether staging may cast a feature leaf of ``leaf_dtype`` to
    ``stage_dtype``: never an integer leaf (token ids, class ids) to a float
    type, which cannot hold them (bfloat16 is exact only up to 256)."""
    return not (np.issubdtype(np.dtype(leaf_dtype), np.integer)
                and not np.issubdtype(np.dtype(stage_dtype), np.integer))


def stage_group(batches: list, dtype, ring: HostGroupRing, sharding=None):
    """Stage one K-step group of host batches ``[(features, labels), ...]``
    (arrays, or for a graph one list of arrays per stream) as ``(K, B, ...)``
    device arrays, the features cast to ``dtype`` on the host (None keeps
    their own, and an integer leaf always keeps its own: ``_castable``).
    Returns ``(xs, ys)``.

    One pass over the bytes: every batch is written straight into its place
    in a slot of ``ring`` (``datasets.prefetch.HostGroupRing``: reused host
    group buffers of the staged dtype), the cast fused into the copy, and the
    slot, cut to the group's length, is handed to the device with a
    ``jax.device_put`` that does not wait for the copy: a fresh device buffer
    every group, from host pages that are warm. The ring rewrites a slot only
    when the transfer last made from it has finished.

    ``sharding`` lays the group out over a mesh (``ParallelWrapper``): called
    with a leaf of one batch, it returns the ``Sharding`` of that leaf's
    stacked ``(K, B, ...)`` array, and every device is sent its shard
    straight from the slot. Left out, the default device.

    Writes the group's three stage spans under the calling thread's
    ``current_group()``: ``input.stack`` (taking the slot, which includes any
    wait for its last transfer, and writing the labels), ``input.cast`` (the
    pass over the features) and ``input.h2d`` (the submission, with the
    ``bytes`` submitted). They follow one another without a gap, so with
    ``input.pull`` they add up to the staging counter."""
    t0 = t1 = t2 = time.time_ns()
    tree = jax.tree_util
    columns = list(zip(*(tree.tree_leaves(b) for b in batches)))
    treedef = tree.tree_structure(batches[0])
    n_features = treedef.children()[0].num_leaves
    spec = tuple(
        (col[0].shape,
         np.dtype(dtype) if dtype is not None and i < n_features
         and _castable(col[0].dtype, dtype)
         else np.result_type(*(a.dtype for a in col)))
        for i, col in enumerate(columns))

    def fill(staged: list):
        nonlocal t1, t2

        def write(leaves: slice):
            for out, col in zip(staged[leaves], columns[leaves]):
                for place, a in zip(out, col):
                    _stage_host(a, out.dtype, out=place)

        write(slice(n_features, None))      # labels
        t1 = time.time_ns()
        write(slice(n_features))            # features
        t2 = time.time_ns()

    device = ring.stage(
        spec, len(batches), fill,
        None if sharding is None else [sharding(col[0]) for col in columns])
    t3 = time.time_ns()
    rec, group = _flight_recorder(), current_group()
    rec.record_span("input.stack", t0, t1, group=group, cause="input.pull")
    rec.record_span("input.cast", t1, t2, group=group, cause="input.pull")
    rec.record_span("input.h2d", t2, t3, group=group, cause="input.pull",
                    bytes=sum(d.nbytes for d in device))
    return tree.tree_unflatten(treedef, device)


def wait_for_step(losses) -> None:
    """Flow control's one wait a staged group (``LazyScore._dispatch_staged``,
    ``ParallelWrapper``'s synchronous loop): block until ``losses``, the loss
    stack of the group dispatched two before, is ready; None (the first two
    groups) waits for nothing. The ``device`` phase and the calling thread's
    group's ``fit.step_wait`` span."""
    if losses is None:
        return
    w0_ns = time.time_ns()
    with _t_device.time():
        # lint: host-sync-in-hot-loop-ok (flow control: the one wait per K-step group that bounds staged groups in HBM)
        losses.block_until_ready()
    _flight_recorder().record_span(
        "fit.step_wait", w0_ns, time.time_ns(),
        group=current_group(), cause="fit.dispatch")


def book_fit_call(path: str, k: int, epochs: int, t0_ns: int) -> None:
    """The span ``fit.call`` of one ``fit_iterator`` / ``ParallelWrapper.fit``
    call that began at ``t0_ns`` and returns now (or raises): the root of
    its groups' spans. The calling thread's group ends with it, so that what
    the thread resolves afterwards is not booked to the call's last
    dispatch; the process's first call logs the time to the first step
    (``observability/startup.py``)."""
    _flight_recorder().record_span("fit.call", t0_ns, time.time_ns(),
                                   path=path, k=k, epochs=epochs)
    end_group()
    _log_time_to_first_step()


def _batch_size(x, axis: int = 0) -> int:
    """Examples in a batch (arrays, or a list of arrays per stream), read off
    its first leaf; ``axis`` 1 for a stacked (K, B, ...) group."""
    leaves = jax.tree_util.tree_leaves(x)
    return int(leaves[0].shape[axis]) if leaves and leaves[0].ndim > axis else 0


@dataclasses.dataclass(frozen=True)
class LoopOwner:
    """What the staged fit loop (``LazyScore._fit_epoch``) takes from a caller
    that drives a network through programs of its own: ``ParallelWrapper``'s
    synchronous loop, compiled over its mesh. A network fitting itself passes
    none: its own programs, the default device, its ``prefetch_depth`` and a
    ring it drops when ``fit_iterator`` returns."""

    #: ``path`` label of the loop's ``dl4j_prefetch_*`` series
    path: str
    #: groups staged ahead of the dispatch loop
    depth: int
    #: host slots of the staged groups, the owner's to keep and to drain
    ring: HostGroupRing
    #: host leaf of a lone batch -> its device array
    put: Callable
    #: leaf of one batch -> the ``Sharding`` of its stacked (K, B, ...) group
    group_sharding: Callable
    #: ``{"train_step" | "multistep": (compiled program, its name in the
    #: compile tracker)}``, called as the networks' own are, without masks
    programs: dict
    #: called with the steps a dispatch ran (the owner's own accounting);
    #: returns further fields of the dispatch's step record
    note_steps: Callable


class LazyScore:
    """The networks' shared base: the lazily read score, the per-network
    program cache (``_jit``) and the fit loop (below, "the fit loop").

    `score_value` syncs device->host only when actually read. The
    reference's fit loop computes `score` eagerly every iteration
    (MultiLayerNetwork.java:1807 computeGradientAndScore) because its
    listeners observe synchronously. On TPU `float(loss)` is a full host
    round-trip that drains the dispatch queue, so the training loops
    here store the device-resident loss (or a thunk indexing into a K-step
    loss stack) and materialize it lazily: a ScoreIterationListener printing
    every N iterations costs N times fewer syncs, and a listener-free fit
    costs none at all. Reads are cached, so repeated access is one sync.
    """

    _score_raw = float("nan")

    #: batch size of the most recently fitted minibatch — set by every fit
    #: path on both network types; PerformanceListener reads it to compute
    #: samples/sec (the reference tracks it on the DataSet instead)
    last_batch_size: int = 0

    #: attached ``observability.health.HealthMonitor`` (or None). When set,
    #: the fit loops dispatch the health variant of the train step whenever
    #: the monitor's cadence is due; off-cadence dispatches are untouched.
    health_monitor = None

    @property
    def score_value(self) -> float:
        raw = self._score_raw
        if callable(raw):
            raw = float(raw())
            self._score_raw = raw
            self._note_moe_rows(finished=True)
        elif not isinstance(raw, float):
            raw = float(raw)
            self._score_raw = raw
        return raw

    def _note_step_counts(self, steps: int, x) -> None:
        """Book ``steps`` dispatched steps of what each layer with a
        ``step_counts`` reports a step counts, each counter labelled by the
        layer's index, and under ``gradient_checkpointing`` the bytes the
        layers with a ``remat_kept_bytes`` kept for their backward
        (``dl4j_remat_kept_bytes_total``, summed over them): a function of
        the batch's shape and each layer's fields alone, so nothing is read
        from the device."""
        layers = getattr(getattr(self, "conf", None), "layers", None) or ()
        if not any(hasattr(l, "step_counts") for l in layers):
            return
        shape = jax.tree_util.tree_leaves(x)[0].shape
        key = (steps, shape)
        if getattr(self, "_step_counts_key", None) != key:
            batch, seq = shape[-2:]
            name = self.conf.global_conf.dtype
            dtype = (common.resolve_policy(name) if name
                     else common.get_policy()).output_dtype
            self._step_counts_key = key
            self._step_counts = [
                (counter, str(i), n)
                for i, l in enumerate(layers) if hasattr(l, "step_counts")
                for counter, n in l.step_counts(batch, seq, dtype).items()]
            kept = collections.Counter()
            if self.conf.global_conf.gradient_checkpointing:
                for l in layers:
                    if hasattr(l, "remat_kept_bytes"):
                        kept.update(l.remat_kept_bytes(batch, seq, dtype))
            self._kept_bytes = sorted(kept.items())
        for counter, layer, n in self._step_counts:
            _obs_registry().counter(counter).labels(layer=layer).inc(steps * n)
        for name, nbytes in self._kept_bytes:
            _remat_kept.labels(name=name).inc(steps * nbytes)

    #: ``(rows (K, layers, 3) on the device, tokens)`` of the dispatched
    #: groups whose expert-layer rows the host has not read yet
    _pending_moe_rows: tuple = ()

    def _note_moe_rows(self, finished: bool = False) -> None:
        """Book the expert layers' rows of every dispatched group whose step
        has finished (``dl4j_moe_*``), oldest first, and never wait for one:
        called after each dispatch, where flow control has just waited for
        the group two back, and when a score has been read. ``finished``:
        the newest group's score has just been read, so every pending group
        has finished, whatever ``is_ready`` says of its rows in that
        instant (they are outputs of the program that wrote the score)."""
        pending = self._pending_moe_rows
        if not pending:
            return
        layers = [str(i) for i, st in enumerate(self.state_list)
                  if isinstance(st, dict) and "moe_rows" in st]
        while pending and (finished or pending[0][0].is_ready()):
            (rows, tokens), pending = pending[0], pending[1:]
            self._pending_moe_rows = pending
            rows = np.asarray(rows)           # (K, layers, 3), finished
            _moe_tokens.inc(tokens)
            for j, layer in enumerate(layers):
                _moe_routed.labels(layer=layer).inc(int(rows[:, j, 0].sum()))
                _moe_computed.labels(layer=layer).inc(int(rows[:, j, 1].sum()))
                _moe_rows_max.labels(layer=layer).set(int(rows[:, j, 2].max()))
                _moe_rows_max_total.labels(layer=layer).inc(
                    int(rows[:, j, 2].sum()))

    @score_value.setter
    def score_value(self, value) -> None:
        self._score_raw = value

    #: one copy of the user-facing message (raised from several entry points
    #: on both network types)
    NOT_INITIALIZED_MSG = (
        "Network not initialized — call net.init() before fit/output "
        "(reference MultiLayerNetwork.init:386 / ComputationGraph.init:266)")

    def _book_init(self, t0_ns: int) -> None:
        """The span ``startup.init`` of an ``init()`` that began at ``t0_ns``
        and returns now (under a caller's ``jax.jit`` it times the trace):
        the class and the parameters' count, from their shapes."""
        _flight_recorder().record_span(
            "startup.init", t0_ns, time.time_ns(), cause="startup.import",
            cls=type(self).__name__, params=num_params(self.params_list))

    def _require_init(self) -> None:
        """Raise the reference's actionable not-initialized error instead of a
        NoneType crash (both network types share this via LazyScore)."""
        if getattr(self, "params_list", None) is None:
            raise RuntimeError(self.NOT_INITIALIZED_MSG)

    def _jit(self, name, fn, donate=None, fingerprint=None, extra=()):
        """Per-network compiled-program cache, keyed on the program name AND
        the active dtype policy: the policy is read at trace time, so a
        name-only key would silently pin the policy active at first call.
        A config-declared ``dtype`` (GlobalConf.dtype) overrides the global
        policy for this network's programs.

        ``fingerprint`` overrides the identity used by the persistent
        executable cache when ``name`` carries per-instance decoration
        (serving versions ``@v2``, replica ranks ``~r1``) that must still
        share warm entries. ``extra`` is a flat tuple of additional
        program-geometry axes (e.g. the decode plane's page_size / pool
        size) folded into both this cache's key and the persistent
        executable fingerprint — same name, different geometry must never
        resolve to the same traced program."""
        if not hasattr(self, "_jit_cache"):
            self._jit_cache = {}
        conf_dtype = getattr(getattr(getattr(self, "conf", None),
                                     "global_conf", None), "dtype", None)
        fn = common.wrap_with_policy(fn, conf_dtype)
        pol = common.effective_policy_key(conf_dtype)
        key = (name, tuple(extra)) + pol
        if key not in self._jit_cache:
            # evict programs traced under a different policy — repeatedly
            # switching the global dtype policy must not grow the cache
            # without bound (each entry pins a compiled XLA program)
            for stale in [k for k in self._jit_cache if k[2:] != pol]:
                del self._jit_cache[stale]
            jitted = (jax.jit(fn, donate_argnums=donate)
                      if donate else jax.jit(fn))
            # every cache miss is a (future) compile: build_program wraps
            # the fresh jit so its first call per abstract signature is
            # timed and recorded (and, cache enabled, resolved through the
            # persistent executable store). A dtype-policy flip re-keys
            # this cache, lands here again, and thus counts as a new
            # compile of the same name — which is what the recompile-storm
            # detector watches.
            from deeplearning4j_tpu.nn import compile_cache as _cc

            cls = type(self).__name__
            self._jit_cache[key] = _cc.build_program(
                f"{cls}.{name}", jitted, cache_key=key,
                fingerprint=f"{cls}.{fingerprint or name}",
                conf=getattr(self, "conf", None),
                extra=("donate", donate) + tuple(extra) + tuple(pol))
        return self._jit_cache[key]

    # ------------------------------------------------------------ the fit loop
    # One copy of the staged K-step loop and of the per-batch step, for
    # MultiLayerNetwork, ComputationGraph and (through a ``LoopOwner``)
    # ParallelWrapper's synchronous loop. A batch is arrays for the one and a
    # list of arrays per stream for the other: everything below maps over
    # leaves, and the hooks name what a network type brings.

    #: hooks: the module-level builders of this network type's one-step and
    #: K-step programs (make_train_step / make_graph_train_step and their
    #: ``multistep`` twins); both take ``health=`` for the monitored variant
    _step_builder = None
    _multistep_builder = None

    #: hook: this network type's ``path`` label on the ``dl4j_prefetch_*``
    #: series ("multilayer" / "graph")
    _fit_path = None

    def _batch_of(self, ds) -> tuple:
        """Hook: ``(features, labels, feature masks, label masks)`` of a
        dataset in this network type's tree shape; absent masks are None."""
        raise NotImplementedError

    def _tbptt_active(self) -> bool:
        """Hook: whether a batch goes through the type's own ``_fit_tbptt``
        (truncated BPTT over layers that carry a streaming state)."""
        raise NotImplementedError

    #: train steps fused per host dispatch in fit_iterator (lax.scan); 1
    #: disables the K-step path. Calibrated 2026-07-31 on a v5e, record not
    #: kept; re-derive in a cell.
    dispatch_ksteps: int = 8

    #: optional dtype (e.g. jnp.bfloat16) features are cast to on the host
    #: BEFORE the device transfer in the fused fit path. Halves host->device
    #: bytes (BASELINE.md round-3 fit-API analysis). Labels stay untouched.
    #: None keeps exact f32 staging. Either way a K-step group is written
    #: once, batch by batch, into a reused host slot of the staged dtype
    #: (``stage_group``), never stacked in float32 first.
    stage_dtype = None

    #: K-step groups staged + transferred ahead of the dispatch loop on a
    #: background thread (datasets.prefetch.DevicePrefetcher): 2 = double
    #: buffering (batch n+1 in flight to HBM while step n executes), 0 =
    #: synchronous staging (the pre-prefetch behavior; bit-identical params
    #: either way — tests/test_prefetch.py).
    prefetch_depth: int = 2

    #: Solver facade instance when optimization_algo != SGD (built lazily)
    _solver = None

    #: the host slots for staged groups (``HostGroupRing``), made on the
    #: first staged group; ``fit_iterator`` drops them when it returns
    _host_ring = None

    #: loss stacks of the two staged groups dispatched last, older first
    _staged_losses = (None, None)

    def _next_rng(self):
        self._require_init()
        if self._rng is None:
            raise RuntimeError(self.NOT_INITIALIZED_MSG)
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _uses_sgd(self) -> bool:
        algo = self.conf.global_conf.optimization_algo
        return algo in (None, "stochastic_gradient_descent")

    def _fused_ok(self) -> bool:
        """Whether the fused step programs (one step, or K in a scan) train
        this configuration: SGD, one iteration a batch, no streaming state to
        thread through truncated BPTT. The one predicate behind ``fit``,
        ``fit_iterator`` and ``ParallelWrapper``'s synchronous loop; what it
        declines goes batch by batch through ``_fit_batch``'s routes."""
        return (self._uses_sgd()
                and self.conf.global_conf.iterations <= 1
                and not self._tbptt_active())

    def _fit_arrays(self, x, y, fmask, lmask, epochs: int) -> None:
        """``epochs`` steps on one batch (``fit`` on arrays or a dataset):
        unmasked and ``_fused_ok``, K per dispatch from a batch staged once
        (``_fit_repeated``), else one ``_fit_batch`` each."""
        if (epochs > 1 and fmask is None and lmask is None
                and self.dispatch_ksteps > 1 and self._fused_ok()):
            self._fit_repeated(x, y, epochs)
            return
        for _ in range(epochs):
            begin_group()
            self._fit_batch(x, y, fmask, lmask)

    def _fit_repeated(self, x, y, epochs: int) -> None:
        """``epochs`` repeated steps on one device-resident batch, K per
        dispatch via the scanned train step (broadcast along the scan axis —
        XLA reads the same HBM buffer each step, no K-fold staging)."""
        tree_map = jax.tree_util.tree_map
        with _t_staging.time():
            xd = tree_map(
                lambda a: jnp.asarray(_stage_host(a, self.stage_dtype)), x)
            yd = tree_map(jnp.asarray, y)
        self.last_batch_size = _batch_size(xd)
        remaining = epochs
        while remaining > 0:
            k = min(self.dispatch_ksteps, remaining)
            xs, ys = tree_map(
                lambda a: jnp.broadcast_to(a[None], (k,) + a.shape), (xd, yd))
            begin_group()
            self._run_steps("multistep", k, xs, ys)
            remaining -= k

    @_dump_on_unhandled("{cls}.fit_iterator")
    def fit_iterator(self, iterator: Iterable, epochs: int = 1,
                     ksteps: Optional[int] = None) -> None:
        """Fit from a DataSetIterator (reference MultiLayerNetwork
        fit(DataSetIterator):978, ComputationGraph fit:747).

        TPU fast path: accumulates up to ``ksteps`` host-staged minibatches,
        stages them as one (K, B, ...) device transfer, and runs all K
        train steps inside ONE XLA dispatch (the type's ``multistep``
        program) — the per-minibatch host round-trip of the reference's fit
        loop is paid once per K steps. Listeners still observe every
        iteration; reading `score_value` lazily indexes the on-device loss
        stack (LazyScore), so a listener firing every N iterations costs
        ~K*N fewer syncs. Falls back to per-batch dispatch for TBPTT, masked
        batches, iterations>1 configs, or ragged batch shapes.
        """
        k = self.dispatch_ksteps if ksteps is None else max(1, ksteps)
        t0_ns = time.time_ns()
        try:
            for _ in range(epochs):
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_start"):
                        listener.on_epoch_start(self)
                if hasattr(iterator, "reset"):
                    iterator.reset()
                if self.conf.pretrain:
                    self.pretrain(iterator)
                    if hasattr(iterator, "reset"):
                        iterator.reset()
                self._fit_epoch(iterator, k)
                for listener in self.listeners:
                    if hasattr(listener, "on_epoch_end"):
                        listener.on_epoch_end(self)
                self.epoch += 1
        finally:
            self._release_staging()
            book_fit_call(self._fit_path, k, epochs, t0_ns)

    def _fit_epoch(self, iterator, k: int, owner=None) -> None:
        """One pass over ``iterator``: through the staged loop where the
        fused programs train this configuration and there is something to
        stage for (K > 1, or an owner's mesh), else batch by batch."""
        if self._fused_ok() and (k > 1 or owner is not None):
            self._fit_epoch_staged(iterator, k, owner)
            return
        for ds in iterator:
            begin_group()
            self._fit_batch(*self._batch_of(ds))

    def _fit_epoch_staged(self, iterator, k: int, owner=None) -> None:
        """The staged K-step loop: pull up to ``k`` unmasked batches of one
        shape, stage the group on the producer thread, dispatch it once the
        step two groups back has finished, run the listeners. Masked batches
        and lone batches (a ragged tail, K = 1) take the per-batch step; only
        an unmasked lone batch takes the owner's."""
        from deeplearning4j_tpu.utils.batching import k_step_groups

        tree_map = jax.tree_util.tree_map
        path, depth, put = (
            (self._fit_path, self.prefetch_depth, jnp.asarray)
            if owner is None else (owner.path, owner.depth, owner.put))

        def to_batch(ds):
            x, y, fmask, lmask = self._batch_of(ds)
            if fmask is not None or lmask is not None:
                return None  # masked -> per-batch fallback
            return tree_map(np.asarray, (x, y))   # host staging, no device sync

        def stage(kind_item):
            # producer thread: cast into a host slot + NON-BLOCKING put — the
            # (K, B, ...) group is in flight to HBM while the previous
            # dispatch executes; a lone batch is put as it is. Singles pass
            # through to the host fallback path unchanged.
            kind, item = kind_item
            if kind != "group":
                return kind_item
            if len(item) == 1:
                return kind, [tree_map(put, item[0])]
            xs, ys = self._stage_group(item, owner)
            return "staged", (xs, ys, len(item))

        # closed on the way out, so that no producer is staging into a ring
        # when its owner drains it
        with DevicePrefetcher(k_step_groups(iterator, k, to_batch), stage,
                              depth=depth, path=path,
                              wait_series=_t_staging) as pf:
            for kind, item in pf:
                if kind == "single":
                    self._fit_batch(*self._batch_of(item))
                elif kind == "group":
                    self._fit_batch(*item[0], owner=owner)
                else:
                    self._dispatch_staged(*item, owner=owner)

    def _stage_group(self, batches: list, owner=None):
        """``stage_group`` into the loop's ring. A network's own has
        ``prefetch_depth + 2`` slots: as many groups as are alive at once
        (one being staged, ``prefetch_depth`` queued, one dispatched) and one
        more, so that the transfer out of a slot has long finished when its
        turn comes again. An owner brings its ring and its layout."""
        if owner is not None:
            return stage_group(batches, self.stage_dtype, owner.ring,
                               owner.group_sharding)
        size = max(0, self.prefetch_depth) + 2
        ring = self._host_ring
        if ring is None or ring.size != size:
            ring = self._host_ring = HostGroupRing(size, self._fit_path)
        return stage_group(batches, self.stage_dtype, ring)

    def _release_staging(self) -> None:
        """Let go of what the staged fit loop keeps between groups: the host
        slots (``prefetch_depth + 2`` groups of the staged dtype) and the
        last groups' losses."""
        self._host_ring = None
        self._staged_losses = (None, None)

    def _dispatch_staged(self, xs, ys, n: int, owner=None) -> None:
        """Run a K-step group whose (K, B, ...) stacks are already device-
        resident (or in flight — dispatch never blocks on the transfer).

        Donation hand-off: a network's own K-step program DONATES params/
        states/updater buffers — XLA updates them in place (no 2x param HBM
        during the step) and the previous arrays are consumed; anyone holding
        stale references gets a loud "deleted buffer" error, never silent
        corruption (clone() deep-copies for this reason; donation is a no-op
        on CPU). The staged xs/ys are NOT in the donated argnums and were
        freshly created by device_put on the prefetch thread, so a prefetched
        group can never alias a buffer the in-flight step is consuming.

        Flow control: the group is dispatched once the step of the group two
        before it has finished, so one group is queued behind the running
        step and the dispatch call is hidden. Without a bound the producer,
        where it is the faster side, piles staged groups up in HBM: each
        dispatched group holds its inputs there until its step has run, and
        the runtime lets a host run some thirty dispatches ahead."""
        self.last_batch_size = _batch_size(xs, axis=1)
        two_back, one_back = self._staged_losses
        losses = self._run_steps("multistep", n, xs, ys, after=two_back,
                                 owner=owner)
        self._staged_losses = (one_back, losses)

    def _fit_batch(self, x, y, fmask=None, lmask=None, owner=None) -> None:
        """One batch through the per-batch step: the solver for a
        configuration that asks for one, the type's ``_fit_tbptt`` under
        truncated BPTT, else ``iterations`` dispatches of the one-step
        program (an owner's takes no masks: masked batches are not its)."""
        if not self._uses_sgd():
            # honor optimization_algo: LBFGS/CG/line-GD configs route through
            # the Solver facade (reference Solver.java:55 getOptimizer
            # dispatch) instead of silently training with SGD
            from deeplearning4j_tpu.optimize.solvers import Solver

            if self._solver is None:
                self._solver = Solver(self)
            self._solver.optimize(x, y)
            return
        if self._tbptt_active():
            self._fit_tbptt(x, y, fmask, lmask)
            return
        with _t_staging.time():
            x, y, fmask, lmask = jax.tree_util.tree_map(
                jnp.asarray, (x, y, fmask, lmask))
        self.last_batch_size = _batch_size(x)
        masks = (fmask, lmask) if owner is None else ()
        for _ in range(max(1, self.conf.global_conf.iterations)):
            self._run_steps("train_step", 1, x, y, *masks, owner=owner)

    def _program(self, kind: str, health: bool, owner):
        """The step program of ``kind`` ("train_step" / "multistep") and its
        name in the compile tracker: the owner's, else this network's own,
        jitted on first use (the K-step program donates the state)."""
        if owner is not None:
            return owner.programs[kind]
        name = kind + "_health" if health else kind
        multi = kind == "multistep"
        build = (type(self)._multistep_builder if multi
                 else type(self)._step_builder)
        program = self._jit(name, build(self.conf, health=health),
                            donate=(0, 1, 2) if multi else None)
        return program, f"{type(self).__name__}.{name}"

    def _run_steps(self, kind: str, n: int, x, y, *masks, after=None,
                   owner=None):
        """Dispatch one call of a step program and book its ``n`` steps:
        ``kind`` "train_step" (one batch, ``n`` 1, returns the loss) or
        "multistep" ((K, B, ...) stacks, returns the (K,) loss stack). Picks
        the health variant when the attached monitor's cadence falls inside
        the call's steps (an owner's programs have none); params/states/
        updater are replaced by the program's outputs. ``after``: a device
        array to wait for first (``_dispatch_staged``; the ``device`` phase
        and the group's ``fit.step_wait`` span), once everything but the call
        is done."""
        multi = kind == "multistep"
        hm = self.health_monitor if owner is None else None
        due_i = hm.due_index(self.iteration, n) if hm is not None else None
        program, name = self._program(kind, due_i is not None, owner)
        wait_for_step(after)
        t0, t0_ns = time.perf_counter(), time.time_ns()
        out = program(self.params_list, self.state_list, self.updater_state,
                      x, y, self._next_rng(), jnp.int32(self.iteration),
                      *masks)
        dt, t1_ns = time.perf_counter() - t0, time.time_ns()
        (self.params_list, self.state_list, self.updater_state,
         losses, *rest) = out
        if due_i is not None:
            # the due step's packed health vector (out of a group's (K, 4) a
            # lazy device gather) — the monitor parks it; the host sync
            # happens at poll() time
            haux = rest.pop(0)
            hm.offer(haux[due_i] if multi else haux, self.iteration + due_i)
        if rest:
            tokens = math.prod(jax.tree_util.tree_leaves(x)[0].shape[:3])
            self._pending_moe_rows = (*self._pending_moe_rows,
                                      (rest[0], tokens))
            self._note_moe_rows()
        self._note_step_counts(n, x)
        fields = {} if owner is None else owner.note_steps(n)
        self._book_steps(name, n, losses if multi else [losses], t0_ns,
                         t1_ns, dt, **fields)
        return losses

    def _book_steps(self, name: str, n: int, scores, t0_ns: int, t1_ns: int,
                    dt: float, **fields) -> None:
        """The one record of "``n`` steps were dispatched": by the program
        ``name``, in a call from ``t0_ns`` to ``t1_ns`` that took ``dt``
        seconds; ``scores[i]`` is step i's loss on the device. The
        ``dispatch`` phase, the profiler's and the step clock's note, the
        calling thread's group's ``fit.dispatch`` span (the ``step`` event,
        with ``fields``), then per step the iteration count, its lazy score
        and the listeners (the ``listeners`` phase, ``fit.listeners``), and
        the watchdog's beat."""
        _t_dispatch.observe(dt)
        _profile_note_dispatch(dt)
        _compile_tracker().note_step(n, fn=name)
        rec, group = _flight_recorder(), current_group()
        rec.record_span(
            "fit.dispatch", t0_ns, t1_ns, kind="step", group=group,
            cause="fit.wait", path=name, it=self.iteration, k=n,
            batch=self.last_batch_size, dispatch_s=dt, **fields)
        l0_ns = time.time_ns()
        with _t_listeners.time():
            for i in range(n):
                self.iteration += 1
                self.score_value = (lambda ls=scores, j=i: ls[j])
                for listener in self.listeners:
                    listener.iteration_done(self, self.iteration)
        rec.record_span("fit.listeners", l0_ns, time.time_ns(), group=group,
                        cause="fit.dispatch")
        _wd_beat(self.iteration)


class MultiLayerNetwork(LazyScore):
    """Stateful convenience shell over the pure functions above."""

    _step_builder = staticmethod(make_train_step)
    _multistep_builder = staticmethod(make_multistep_train_step)
    _fit_path = "multilayer"

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.params_list: Optional[list] = None
        self.state_list: Optional[list] = None
        self.updater_state: Optional[list] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: list = []
        self.score_value = float("nan")
        self._rng = None
        self._jit_cache: dict = {}
        self._rnn_state: Optional[list] = None  # streaming rnnTimeStep state

    # ------------------------------------------------------------------ lifecycle
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        t0_ns = time.time_ns()
        g = self.conf.global_conf
        key = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng = jax.random.fold_in(key, 0xD14)
        n = len(self.conf.layers)
        keys = jax.random.split(key, n)
        itype = self.conf.input_type
        self.params_list = []
        self.state_list = []
        cur = itype
        for i, layer in enumerate(self.conf.layers):
            if cur is not None:
                pp = self.conf.preprocessor(i)
                if pp is not None:
                    cur = pp.output_type(cur)
            self.params_list.append(layer.init_params(keys[i], cur))
            self.state_list.append(layer.init_state(cur))
            if cur is not None:
                cur = layer.output_type(cur)
        self.updater_state = [
            {name: updater_init(_updater_spec(layer), p)
             for name, p in params.items()}
            for layer, params in zip(self.conf.layers, self.params_list)
        ]
        self._book_init(t0_ns)
        return self

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    # ------------------------------------------------------------------ params API
    def params(self) -> Array:
        """Flat 1-D parameter view (reference MultiLayerNetwork.params())."""
        return flatten_params(self.params_list)

    def set_params(self, flat: Array) -> None:
        self.params_list = unflatten_params(self.params_list, flat)

    def num_params(self) -> int:
        return num_params(self.params_list)

    # ------------------------------------------------------------------ inference
    def output(self, x, train: bool = False) -> Array:
        """Forward pass returning final activations (reference output:2061).
        ``train=True`` runs training-mode layer behavior (batch statistics);
        dropout needs an rng and is not applied on this inference path."""
        self._require_init()
        x = jnp.asarray(x)

        fn = self._jit(f"output_train{train}",
                       functools.partial(self._output_pure, train=train))
        out, _ = fn(self.params_list, self.state_list, x)
        return out

    def _output_pure(self, params_list, state_list, x, *, train):
        out, ns, _ = forward_fn(self.conf, params_list, state_list, x,
                                train=train, rng=None)
        return out, ns

    def feed_forward(self, x, train: bool = False) -> list:
        """Per-layer activations (reference feedForward:657)."""
        self._require_init()
        out, _, acts = forward_fn(self.conf, self.params_list, self.state_list,
                                  jnp.asarray(x), train=train, rng=None, collect=True)
        return acts

    def predict(self, x) -> np.ndarray:
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def score(self, x=None, y=None, dataset=None) -> float:
        """Loss (incl. regularization) on a dataset, no dropout; a DataSet's
        feature/label masks are honored like fit()'s (reference score:1704
        via setLayerMaskArrays)."""
        self._require_init()
        fmask = lmask = None
        if dataset is not None:
            x, y = dataset.features, dataset.labels
            fmask = (jnp.asarray(dataset.features_mask)
                     if dataset.features_mask is not None else None)
            lmask = (jnp.asarray(dataset.labels_mask)
                     if dataset.labels_mask is not None else None)
        x, y = jnp.asarray(x), jnp.asarray(y)
        fn = self._jit("score", self._score_pure)
        return float(fn(self.params_list, self.state_list, x, y, fmask,
                        lmask))

    def _eval_trunk(self, params_list, state_list, x, fmask=None):
        """Eval-mode forward to the last layer's input with feature-mask
        threading — the ONE trunk behind score() and score_examples() (same
        walk as loss_fn's, without training state)."""
        layers = self.conf.layers
        h = x
        for i, layer in enumerate(layers[:-1]):
            pp = self.conf.preprocessor(i)
            if pp is not None:
                h = pp.pre_process(h, fmask)
            h, _ = layer.apply(params_list[i], state_list[i], h, train=False,
                               rng=None, mask=fmask)
        pp = self.conf.preprocessor(len(layers) - 1)
        if pp is not None:
            h = pp.pre_process(h, fmask)
        return h

    def _score_pure(self, params_list, state_list, x, y, fmask=None,
                    lmask=None):
        h = self._eval_trunk(params_list, state_list, x, fmask)
        loss = self.conf.layers[-1].compute_loss(params_list[-1], h, y, lmask)
        return loss + _regularization(self.conf, params_list)

    def score_examples(self, x, y=None, add_regularization: bool = False):
        """Per-example loss scores, un-reduced (reference scoreExamples:1742/1759)
        — the anomaly-detection / example-weighting API. ``x`` may be a
        DataSet, whose labels mask weights each example's own loss (padded
        timesteps don't count, as in fit()). With ``add_regularization`` the
        network's l1/l2 term is added to every example's score."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        self._require_init()
        fmask = lmask = None
        if y is None and isinstance(x, DataSet):
            fmask = (jnp.asarray(x.features_mask)
                     if x.features_mask is not None else None)
            lmask = (jnp.asarray(x.labels_mask)
                     if x.labels_mask is not None else None)
            x, y = x.features, x.labels
        fn = self._jit("score_examples", self._score_examples_pure)
        per = fn(self.params_list, self.state_list, jnp.asarray(x),
                 jnp.asarray(y), fmask, lmask)
        if add_regularization:
            per = per + _regularization(self.conf, self.params_list)
        return np.asarray(per)

    def _score_examples_pure(self, params_list, state_list, x, y, fmask,
                             lmask):
        h = self._eval_trunk(params_list, state_list, x, fmask)
        last = self.conf.layers[-1]

        # per-example: the scalar loss of a single-example batch IS that
        # example's score (keeps every loss function's own reduction rules)
        def one(hi, yi, mi=None):
            return last.compute_loss(params_list[-1], hi[None], yi[None],
                                     mi[None] if mi is not None else None)

        if lmask is not None:
            return jax.vmap(one)(h, y, lmask)
        return jax.vmap(one)(h, y)

    def f1_score(self, x, y=None) -> float:
        """F1 on a dataset or (x, y) arrays (reference f1Score:931/1683)."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        if y is None and isinstance(x, DataSet):
            x, y = x.features, x.labels
        return self.evaluate(x, y).f1()

    # ------------------------------------------------------------------ training
    @_dump_on_unhandled("MultiLayerNetwork.fit")
    def fit(self, x, y=None, *, epochs: int = 1, fmask=None, lmask=None) -> None:
        """Fit on arrays, a DataSet, or a DataSetIterator (reference fit:978).

        Array/DataSet fits with ``epochs > 1`` take the K-step fused path
        when eligible: the batch is staged on device ONCE and broadcast
        across the scan axis, so repeated epochs cost one host transfer and
        ``ceil(epochs/K)`` dispatches instead of ``epochs`` round-trips."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        if y is None and isinstance(x, DataSet):
            self._fit_arrays(*self._batch_of(x), epochs)
        elif y is None and hasattr(x, "__iter__") and not isinstance(x, (jnp.ndarray, np.ndarray)):
            self.fit_iterator(x, epochs=epochs)
        else:
            # the loop maps over leaves: a nested list is one array here
            x, y = (a if hasattr(a, "shape") else jnp.asarray(a)
                    for a in (x, y))
            self._fit_arrays(x, y, fmask, lmask, epochs)

    def _batch_of(self, ds) -> tuple:
        return ds.features, ds.labels, ds.features_mask, ds.labels_mask

    def _tbptt_active(self) -> bool:
        return (self.conf.backprop_type == "TruncatedBPTT"
                and any(isinstance(l, LSTM) for l in self.conf.layers))

    # ------------------------------------------------------------------ TBPTT
    def _fit_tbptt(self, x, y, fmask=None, lmask=None) -> None:
        """Truncated BPTT (reference doTruncatedBPTT:1140): slice the time axis into
        tbptt_fwd_length chunks; RNN state carries across chunks via lax.stop_gradient
        (the truncation). Time axis = 1 ([B,T,F] layout)."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        self.last_batch_size = int(x.shape[0]) if x.ndim else 0
        T = x.shape[1]
        L = self.conf.tbptt_fwd_length
        n_chunks = max(1, math.ceil(T / L))
        step = self._jit("tbptt_step", make_tbptt_step(self.conf))
        rnn_state = _init_rnn_states(self.conf, x.shape[0], x.dtype)
        for c in range(n_chunks):
            sl = slice(c * L, min((c + 1) * L, T))
            xc, yc = x[:, sl], y[:, sl]
            fm = fmask[:, sl] if fmask is not None else None
            lm = lmask[:, sl] if lmask is not None else None
            (self.params_list, self.state_list, self.updater_state, rnn_state,
             loss) = step(self.params_list, self.state_list, self.updater_state,
                          rnn_state, xc, yc, self._next_rng(),
                          jnp.int32(self.iteration), fm, lm)
            _compile_tracker().note_step(fn=f"{type(self).__name__}.tbptt_step")
            _flight_recorder().record(
                "step", path=f"{type(self).__name__}.tbptt_step",
                it=self.iteration, batch=self.last_batch_size)
            self.score_value = loss  # device scalar; synced lazily (LazyScore)
            self.iteration += 1
            for listener in self.listeners:
                listener.iteration_done(self, self.iteration)
            _wd_beat(self.iteration)

    # ------------------------------------------------------------------ pretrain
    def pretrain(self, iterator) -> None:
        """Greedy layerwise unsupervised pretraining (reference pretrain:152):
        for each pretrain layer, feed inputs forward to it and minimize its
        unsupervised objective."""
        for idx, layer in enumerate(self.conf.layers):
            if isinstance(layer, PretrainLayer):
                self.pretrain_layer(idx, iterator)

    def pretrain_layer(self, layer_idx: int, iterator) -> None:
        """Pretrain ONE layer unsupervised (reference pretrainLayer:183);
        earlier layers run in eval mode to produce its input."""
        self._require_init()
        if not 0 <= layer_idx < len(self.conf.layers):
            raise ValueError(
                f"layer_idx {layer_idx} out of range for "
                f"{len(self.conf.layers)} layers")
        if not isinstance(self.conf.layers[layer_idx], PretrainLayer):
            raise ValueError(
                f"Layer {layer_idx} "
                f"({type(self.conf.layers[layer_idx]).__name__}) is not "
                "pretrainable — layerwise pretraining needs an unsupervised "
                "layer (VAE, RBM, AutoEncoder)")
        step = self._jit(f"pretrain:{layer_idx}",
                         make_pretrain_step(self.conf, layer_idx))
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            x = jnp.asarray(ds.features)
            (self.params_list[layer_idx], self.updater_state[layer_idx],
             loss) = step(self.params_list, self.state_list,
                          self.updater_state[layer_idx], x,
                          self._next_rng(), jnp.int32(self.iteration))
            self.score_value = loss  # synced lazily (LazyScore)

    # ------------------------------------------------------------------ evaluation
    def evaluate(self, iterator_or_x, y=None, labels_list=None, top_n: int = 1):
        """Evaluate classification accuracy over an iterator or an (x, y) pair.

        ``labels_list`` attaches class-label names to the returned Evaluation's
        stats; ``top_n`` tracks top-N accuracy alongside top-1 (reference
        MultiLayerNetwork.evaluate(DataSetIterator, List<String>, int)).
        """
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(labels=labels_list, top_n=top_n)
        if y is not None:
            ev.eval(np.asarray(y), np.asarray(self.output(iterator_or_x)))
            return ev
        it = iterator_or_x
        if hasattr(it, "reset"):
            it.reset()
        for ds in it:
            out = self.output(ds.features)
            ev.eval(np.asarray(ds.labels), np.asarray(out),
                    mask=np.asarray(ds.labels_mask) if ds.labels_mask is not None else None)
        return ev

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval.regression import RegressionEvaluation

        ev = RegressionEvaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            ev.eval(np.asarray(ds.labels), np.asarray(self.output(ds.features)))
        return ev

    def _evaluate_roc_impl(self, roc, iterator):
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            roc.eval(np.asarray(ds.labels),
                     np.asarray(self.output(ds.features)))
        return roc

    def evaluate_roc(self, iterator, threshold_steps: int = 30):
        from deeplearning4j_tpu.eval.roc import ROC

        return self._evaluate_roc_impl(ROC(threshold_steps), iterator)

    def evaluate_roc_multiclass(self, iterator, threshold_steps: int = 30):
        """One-vs-all ROC per class (reference evaluateROCMultiClass:2401)."""
        from deeplearning4j_tpu.eval.roc import ROCMultiClass

        return self._evaluate_roc_impl(ROCMultiClass(threshold_steps),
                                       iterator)

    # ------------------------------------------------------------------ rnn API
    def rnn_time_step(self, x) -> Array:
        """Streaming inference carrying hidden state across calls (reference
        rnnTimeStep:2196). x: [B,T,F] (T may be 1)."""
        self._require_init()
        x = jnp.asarray(x)
        if self._rnn_state is None:
            self._rnn_state = _init_rnn_states(self.conf, x.shape[0], x.dtype)
        fn = self._jit("rnn_time_step", functools.partial(_rnn_forward, self.conf))
        out, self._rnn_state = fn(self.params_list, self.state_list,
                                  self._rnn_state, x)
        return out

    def rnn_get_previous_state(self):
        """Per-layer streaming LSTM state (reference rnnGetPreviousState:2225);
        None until rnn_time_step has run."""
        return self._rnn_state

    def rnn_set_previous_state(self, state) -> None:
        """Install streaming state captured by rnn_get_previous_state
        (reference rnnSetPreviousState:2235) — serving handoff/restore."""
        self._rnn_state = (jax.tree_util.tree_map(jnp.asarray, state)
                           if state is not None else None)

    def rnn_clear_previous_state(self) -> None:
        self._rnn_state = None

    # ------------------------------------------------------------------ grads (for checks)
    def gradient_and_score(self, x, y, fmask=None, lmask=None):
        """(grads pytree, score) without updating params (reference
        computeGradientAndScore:1807). Deterministic: no dropout rng."""
        self._require_init()
        x, y = jnp.asarray(x), jnp.asarray(y)

        def lf(p):
            loss, _ = loss_fn(self.conf, p, self.state_list, x, y, None, fmask, lmask)
            return loss

        loss, grads = jax.value_and_grad(lf)(self.params_list)
        return grads, float(loss)

    def clone(self) -> "MultiLayerNetwork":
        import copy

        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        # REAL buffer copies, not aliases: the fused fit path donates param
        # buffers to XLA, so a clone sharing arrays with the original would
        # see its arrays deleted when either of them trains
        cp = lambda a: jnp.array(a)
        net.params_list = jax.tree_util.tree_map(cp, self.params_list)
        net.state_list = jax.tree_util.tree_map(cp, self.state_list)
        net.updater_state = jax.tree_util.tree_map(cp, self.updater_state)
        net.iteration = self.iteration
        net.epoch = self.epoch
        net._rng = self._rng
        if self._rnn_state is not None:  # mid-stream serving handoff
            net._rnn_state = jax.tree_util.tree_map(cp, self._rnn_state)
        return net


# ---------------------------------------------------------------------- rnn helpers
def _init_rnn_states(conf, batch, dtype):
    states = []
    for layer in conf.layers:
        if isinstance(layer, LSTM):
            states.append({"h": jnp.zeros((batch, layer.n_out), dtype),
                           "c": jnp.zeros((batch, layer.n_out), dtype)})
        else:
            states.append({})
    return states


def _rnn_forward(conf, params_list, state_list, rnn_states, x):
    """Forward pass threading LSTM streaming state (pure)."""
    h = x
    new_rnn = []
    for i, layer in enumerate(conf.layers):
        pp = conf.preprocessor(i)
        if pp is not None:
            h = pp.pre_process(h)
        if isinstance(layer, LSTM) and not type(layer).__name__.startswith("GravesBidirectional"):
            h, rs = layer.apply_streaming(params_list[i], rnn_states[i], h)
            new_rnn.append(rs)
        else:
            h, _ = layer.apply(params_list[i], state_list[i], h, train=False, rng=None)
            new_rnn.append(rnn_states[i])
    return h, new_rnn


def make_tbptt_step(conf: MultiLayerConfiguration):
    """TBPTT train step: like make_train_step but threads LSTM state across chunks,
    truncating gradients at chunk boundaries with stop_gradient."""
    g = conf.global_conf

    def tbptt_step(params_list, state_list, upd_state, rnn_states, x, y, rng,
                   iteration, fmask=None, lmask=None):
        def lf(p):
            h = x
            new_rnn = []
            chunk_states = []
            rngs = jax.random.split(rng, len(conf.layers)) if rng is not None else None
            for i, layer in enumerate(conf.layers[:-1]):
                pp = conf.preprocessor(i)
                if pp is not None:
                    h = pp.pre_process(h, fmask)
                if isinstance(layer, LSTM) and not type(layer).__name__.startswith("GravesBidirectional"):
                    h, rs = layer.apply_streaming(p[i], rnn_states[i], h, mask=fmask)
                    new_rnn.append(jax.tree_util.tree_map(jax.lax.stop_gradient, rs))
                    chunk_states.append(state_list[i])
                else:
                    h, ns = layer.apply(p[i], state_list[i], h, train=True,
                                        rng=rngs[i], mask=fmask)
                    new_rnn.append(rnn_states[i])
                    chunk_states.append(ns)
            last = conf.layers[-1]
            h = last.apply_dropout(h, rngs[-1], True)
            loss = last.compute_loss(p[-1], h, y, lmask)
            new_rnn.append(rnn_states[-1])
            # layer-declared aux objectives (MoE load balance) apply per
            # TBPTT chunk exactly as in the standard loss_fn
            loss = loss + _aux_losses(conf.layers, chunk_states)
            return loss + _regularization(conf, p), new_rnn

        (loss, new_rnn), grads = jax.value_and_grad(lf, has_aux=True)(params_list)
        grads = grads_to_param_dtype(grads, params_list)
        new_params = []
        new_upd = []
        for i, layer in enumerate(conf.layers):
            g_i = grads[i]
            if not g_i:
                new_params.append(params_list[i])
                new_upd.append(upd_state[i])
                continue
            g_i = normalize_gradients(g_i, layer.gradient_normalization,
                                      layer.gradient_normalization_threshold or 1.0)
            spec = _updater_spec(layer)
            lr = effective_lr(layer.learning_rate, g.lr_policy, iteration,
                              g.lr_policy_decay_rate, g.lr_policy_power,
                              g.lr_policy_steps, g.lr_schedule, g.max_num_iterations)
            p_new, u_new = {}, {}
            for name, grad in g_i.items():
                step, ustate = updater_step_with_param(
                    spec, grad, params_list[i][name], upd_state[i][name], lr,
                    iteration)
                p_new[name] = params_list[i][name] - step
                u_new[name] = ustate
            new_params.append(p_new)
            new_upd.append(u_new)
        return new_params, state_list, new_upd, new_rnn, loss

    return common.wrap_with_policy(tbptt_step, g.dtype)


def make_pretrain_step(conf: MultiLayerConfiguration, layer_idx: int):
    """Unsupervised pretrain step for layer ``layer_idx`` (reference pretrainLayer:183):
    forward (no dropout) through preceding layers, minimize the layer's pretrain loss
    wrt ITS params only."""
    g = conf.global_conf
    layer = conf.layers[layer_idx]

    def pretrain_step(params_list, state_list, layer_upd_state, x, rng, iteration):
        h = x
        for i in range(layer_idx):
            pp = conf.preprocessor(i)
            if pp is not None:
                h = pp.pre_process(h)
            h, _ = conf.layers[i].apply(params_list[i], state_list[i], h,
                                        train=False, rng=None)
        pp = conf.preprocessor(layer_idx)
        if pp is not None:
            h = pp.pre_process(h)
        h = jax.lax.stop_gradient(h)

        def lf(p):
            return layer.pretrain_loss(p, h, rng=rng)

        loss, grads = jax.value_and_grad(lf)(params_list[layer_idx])
        grads = grads_to_param_dtype(grads, params_list[layer_idx])
        grads = normalize_gradients(grads, layer.gradient_normalization,
                                    layer.gradient_normalization_threshold or 1.0)
        spec = _updater_spec(layer)
        lr = effective_lr(layer.learning_rate, g.lr_policy, iteration,
                          g.lr_policy_decay_rate, g.lr_policy_power,
                          g.lr_policy_steps, g.lr_schedule, g.max_num_iterations)
        p_new, u_new = {}, {}
        for name, grad in grads.items():
            step, ustate = updater_step_with_param(
                spec, grad, params_list[layer_idx][name],
                layer_upd_state[name], lr, iteration)
            p_new[name] = params_list[layer_idx][name] - step
            u_new[name] = ustate
        return p_new, u_new, loss

    return common.wrap_with_policy(pretrain_step, g.dtype)
