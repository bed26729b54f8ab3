"""PipelineTrainer: train a plain network config through the GPipe executor.

Round-4 verdict: parallel/pipeline.py was exact + differentiable but
standalone — no network config could train through it. This closes the gap
the way reference ParallelWrapper.java:44 wraps any net: hand a
``MultiLayerNetwork`` (e.g. models.transformer_lm) to PipelineTrainer and
``fit()`` runs the homogeneous middle of the stack — automatically detected
as the longest run of identical layer configs — as pipeline stages over the
mesh's ``stage`` axis, while the surrounding layers (embedding, output/loss)
run replicated. Gradients flow through the pipeline's ppermutes by autodiff
(the reverse pipeline), and parameter updates reuse the standard
make_train_step updater/clipping/schedule semantics, so pipelined training
is step-for-step equivalent to single-device training on the same batches
(tests/test_pipeline_trainer.py pins it).
"""
from __future__ import annotations

import time as _time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from deeplearning4j_tpu.observability.compile_tracker import (
    global_tracker as _compile_tracker,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    dump_on_unhandled as _dump_on_unhandled,
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.watchdog import beat as _wd_beat
from deeplearning4j_tpu.ops.remat import checkpoint_layer
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.pipeline import PipelineParallel
from deeplearning4j_tpu.nn.multilayer import (
    _t_staging, _t_dispatch, _t_listeners,
)


def find_block_run(layers) -> tuple:
    """Longest run of consecutive, identical (dataclass-equal) layer configs
    — the pipeline-able stack. The final (loss) layer never joins it."""
    best = (0, 0)
    i = 0
    n = len(layers) - 1  # exclude the loss layer
    while i < n:
        j = i + 1
        while j < n and layers[j] == layers[i]:
            j += 1
        if j - i > best[1] - best[0]:
            best = (i, j)
        i = j
    return best


class PipelineTrainer:
    """GPipe training for configs with a homogeneous block stack.

    ``n_microbatches`` trades bubble fraction (S-1)/(S+M-1) for per-tick
    activation size. Blocks must be stateless and dropout-free (the pipeline
    body threads no per-block state/rng); everything else about the config —
    updaters, schedules, clipping, regularization, aux losses of the non-
    pipelined layers — behaves exactly as in single-device fit().
    """

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 n_stages: Optional[int] = None, axis_name: str = "stage",
                 n_microbatches: int = 4):
        self.net = net
        conf = net.conf
        self.mesh = mesh or build_mesh(
            {axis_name: n_stages or len(jax.devices())})
        self.axis_name = axis_name
        self.n_stages = self.mesh.shape[axis_name]
        i0, i1 = find_block_run(conf.layers)
        if i1 - i0 < 2:
            raise ValueError("config has no homogeneous block stack to "
                             "pipeline (need >= 2 identical consecutive "
                             "layer configs)")
        if (i1 - i0) % self.n_stages:
            raise ValueError(f"{i1 - i0} pipeline blocks not divisible by "
                             f"{self.n_stages} stages")
        block = conf.layers[i0]
        if getattr(block, "dropout", None):
            raise ValueError("pipelined blocks must be dropout-free")
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        if block.init_state(InputType.recurrent(block.n_out or 1, 1)):
            # e.g. MoETransformerBlock: its aux_loss state would be silently
            # dropped by the stateless pipeline body — training would lose
            # the Switch load-balance term with no error
            raise ValueError("pipelined blocks must be stateless "
                             f"({type(block).__name__} publishes state)")
        for i in range(i0, i1):
            if conf.preprocessor(i) is not None:
                raise ValueError("preprocessors inside the pipelined block "
                                 "run are not supported")
        self.block_range = (i0, i1)
        self._block = block
        block_fn = lambda p, x: block.apply(p, {}, x, train=True, rng=None)[0]
        if conf.global_conf.gradient_checkpointing:
            # same remat contract as multilayer.loss_fn: backward recomputes
            # each block's forward instead of holding its activations
            block_fn = checkpoint_layer(block_fn)
        self.pipe = PipelineParallel(
            self.mesh, block_fn, n_blocks=i1 - i0, axis_name=axis_name,
            n_microbatches=n_microbatches)
        self._step = None

    # ------------------------------------------------------------------ loss
    def _pipeline_loss(self, params_list, state_list, x, y, rng, fmask=None,
                       lmask=None):
        """multilayer.loss_fn with the block run executed as a pipeline.
        Same return contract: (loss, new_state_list)."""
        from deeplearning4j_tpu.nn.multilayer import (
            _aux_losses, _regularization)

        conf = self.net.conf
        layers = conf.layers
        i0, i1 = self.block_range
        last = layers[-1]
        remat = conf.global_conf.gradient_checkpointing
        rngs = (jax.random.split(rng, len(layers))
                if rng is not None else [None] * len(layers))

        def apply_one(i, h):
            # same remat contract as multilayer.loss_fn for the layers
            # outside the pipelined run
            pp = conf.preprocessor(i)
            if pp is not None:
                h = pp.pre_process(h, fmask)
            if remat:
                def f(p, hh, _l=layers[i], _s=state_list[i], _r=rngs[i]):
                    return _l.apply(p, _s, hh, train=True, rng=_r, mask=fmask)
                return checkpoint_layer(f)(params_list[i], h)
            return layers[i].apply(params_list[i], state_list[i], h,
                                   train=True, rng=rngs[i], mask=fmask)

        h = x
        new_states = []
        for i in range(i0):
            h, ns = apply_one(i, h)
            new_states.append(ns)
        stacked = {k: jnp.stack([params_list[i][k] for i in range(i0, i1)])
                   for k in params_list[i0]}
        h = self.pipe(stacked, h)
        new_states.extend(state_list[i0:i1])
        for i in range(i1, len(layers) - 1):
            h, ns = apply_one(i, h)
            new_states.append(ns)
        pp = conf.preprocessor(len(layers) - 1)
        if pp is not None:
            h = pp.pre_process(h, fmask)
        h = last.apply_dropout(h, rngs[-1], True)
        loss = last.compute_loss(params_list[-1], h, y, lmask)
        new_states.append(state_list[-1])
        loss = loss + _aux_losses(layers, new_states)
        return loss + _regularization(conf, params_list), new_states

    # ------------------------------------------------------------------- fit
    def _make_step(self):
        from deeplearning4j_tpu.nn.multilayer import make_train_step
        from deeplearning4j_tpu.parallel.compile_seam import compile_step
        # through the seam: plain jit strategy (params replicated; the stage
        # sharding lives inside PipelineParallel's own shard_map body), with
        # rule-set-attributed CompileTracker registration
        return compile_step(
            "PipelineTrainer.train_step",
            make_train_step(self.net.conf, loss=self._pipeline_loss),
            mesh=self.mesh, rule_set="pipeline", strategy="jit",
            conf=self.net.conf)

    #: batches staged + transferred ahead of the dispatch loop (see
    #: MultiLayerNetwork.prefetch_depth); 0 = synchronous staging
    prefetch_depth: int = 2

    @_dump_on_unhandled("PipelineTrainer.fit")
    def fit(self, iterator, epochs: int = 1) -> None:
        """Reference ParallelWrapper.fit(DataSetIterator):322 shape: every
        batch runs one pipelined train step; listeners fire per iteration.
        The next batch is staged + transferred on a background thread
        (DevicePrefetcher) while the current pipelined step executes."""
        from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher

        net = self.net

        def stage(ds):
            if (getattr(ds, "features_mask", None) is not None
                    or getattr(ds, "labels_mask", None) is not None):
                # siblings fall back to net._fit_batch for masked batches;
                # the pipeline body threads no masks, so training here would
                # silently weight padded steps. Raised on the producer, the
                # error reaches the consumer AFTER every earlier batch ran —
                # same observable prefix as the synchronous loop.
                raise ValueError("PipelineTrainer does not support "
                                 "masked batches; use net.fit()")
            # lint: host-sync-in-hot-loop-ok (producer-thread staging; device_put is non-blocking)
            x = jax.device_put(np.asarray(ds.features))
            # lint: host-sync-in-hot-loop-ok (producer-thread staging; device_put is non-blocking)
            y = jax.device_put(np.asarray(ds.labels))
            return x, y

        if self._step is None:
            self._step = self._make_step()
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            pf = DevicePrefetcher(iterator, stage, depth=self.prefetch_depth,
                                  path="pipeline", wait_series=_t_staging)
            for x, y in pf:
                net.last_batch_size = int(x.shape[0]) if x.ndim else 0
                t0 = _time.perf_counter()
                (net.params_list, net.state_list, net.updater_state,
                 loss) = self._step(net.params_list, net.state_list,
                                    net.updater_state, x, y,
                                    net._next_rng(),
                                    jnp.int32(net.iteration))
                dt = _time.perf_counter() - t0
                _t_dispatch.observe(dt)
                _compile_tracker().note_step(fn="PipelineTrainer.train_step")
                _flight_recorder().record(
                    "step", path="PipelineTrainer.train_step",
                    it=net.iteration, batch=net.last_batch_size,
                    dispatch_s=dt)
                net.score_value = loss
                net.iteration += 1
                with _t_listeners.time():
                    for listener in net.listeners:
                        listener.iteration_done(net, net.iteration)
                _wd_beat(net.iteration)
