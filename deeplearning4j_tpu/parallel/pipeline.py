"""Pipeline parallelism: GPipe-style microbatched stage pipeline over a mesh.

Absent in the reference (SURVEY.md §2.4 lists data parallelism only) and a
required TPU-native capability. Homogeneous blocks (TransformerBlock stacks)
make stages identical, so the whole pipeline is ONE program under shard_map:
stage parameters live stacked on a leading axis sharded over the ``stage``
mesh axis; each tick every device applies its stage to the activation it
holds and ``ppermute``s the result to the next stage. A microbatch enters at
stage 0 each tick; after S-1 warmup ticks the last stage emits one finished
microbatch per tick (the classic GPipe schedule: S + M - 1 ticks, bubble
fraction (S-1)/(S+M-1)). The loop is a static-bound fori_loop, so the whole
schedule is differentiable — backward is the reverse pipeline, generated by
autodiff through the ppermutes.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from deeplearning4j_tpu.parallel.partition import (
    pspec as P, named_sharding as _named_sharding,
)

Array = jax.Array


def stack_block_params(params_list) -> dict:
    """Stack per-block param dicts (identical structure) along a leading
    stage axis: [{k: [..]}, ...] -> {k: [S, ..]}."""
    keys = params_list[0].keys()
    return {k: jnp.stack([p[k] for p in params_list]) for k in keys}


def unstack_block_params(stacked: dict) -> list:
    n = next(iter(stacked.values())).shape[0]
    return [{k: v[i] for k, v in stacked.items()} for i in range(n)]


def _pipeline_local(params_local, xs, *, block_fn, axis_name: str):
    """shard_map body: params_local {k: [1, ...]} (this stage's slice),
    xs [M, mb, T, F] (replicated input microbatches). Returns this stage's
    [M, mb, T, F] output buffer — only the LAST stage's holds real values;
    the caller slices that stage's shard out instead of psum-replicating
    the full stack to every device (round-4 verdict: the psum doubled
    per-device memory and paid a collective for data S-1 stages discard)."""
    idx = lax.axis_index(axis_name)
    S = lax.psum(1, axis_name)
    M = xs.shape[0]
    my_params = {k: v[0] for k, v in params_local.items()}
    perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(t, carry):
        state_in, outs = carry
        mb_idx = jnp.clip(t, 0, M - 1)
        # stage 0 ingests a fresh microbatch; others consume the permuted
        # activation from the previous stage
        x_in = jnp.where(idx == 0, xs[mb_idx], state_in)
        y = block_fn(my_params, x_in)
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        valid = jnp.logical_and(t >= S - 1, idx == S - 1)
        outs = outs.at[out_idx].set(jnp.where(valid, y, outs[out_idx]))
        state_next = lax.ppermute(y, axis_name, perm)
        return state_next, outs

    # the carried activation/outputs are device-varying (each stage holds a
    # different microbatch) — mark them so the loop carry types line up
    vary = lambda a: jax.lax.pcast(a, (axis_name,), to="varying")
    init = (vary(jnp.zeros_like(xs[0])), vary(jnp.zeros_like(xs)))
    _, outs = lax.fori_loop(0, S + xs.shape[0] - 1, tick, init)
    return outs


class PipelineParallel:
    """GPipe executor for a stack of homogeneous blocks.

    block_fn(params, x) -> y applies ONE block (e.g. a TransformerBlock's
    ``apply`` closed over its config). Stage count = mesh size along
    ``axis_name``; the number of blocks must be divisible by it (each stage
    runs blocks_per_stage blocks sequentially).
    """

    def __init__(self, mesh: Mesh, block_fn: Callable, n_blocks: int,
                 axis_name: str = "stage", n_microbatches: int = 4):
        self.mesh = mesh
        self.axis_name = axis_name
        self.n_stages = mesh.shape[axis_name]
        if n_blocks % self.n_stages:
            raise ValueError(f"{n_blocks} blocks not divisible by "
                             f"{self.n_stages} stages")
        self.blocks_per_stage = n_blocks // self.n_stages
        self.n_microbatches = n_microbatches
        self.block_fn = block_fn

    def _stage_fn(self, stage_params, x):
        """Apply this stage's blocks_per_stage blocks sequentially.
        stage_params: {k: [blocks_per_stage, ...]}."""
        def body(x, block_params):
            return self.block_fn(block_params, x), None
        y, _ = lax.scan(body, x, stage_params)
        return y

    def __call__(self, stacked_params: dict, x: Array) -> Array:
        """x: [B, T, F] -> [B, T, F] through all blocks. B must be divisible
        by n_microbatches. stacked_params: {k: [n_blocks, ...]}. Trace-safe:
        callable from inside a jitted train step (PipelineTrainer)."""
        M = self.n_microbatches
        B = x.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        # regroup [n_blocks, ...] -> [S, blocks_per_stage, ...]
        grouped = {k: v.reshape(self.n_stages, self.blocks_per_stage,
                                *v.shape[1:])
                   for k, v in stacked_params.items()}
        xs = x.reshape(M, B // M, *x.shape[1:])
        # per-stage output buffers stay sharded over the stage axis
        # ([S*M, mb, T, F] stacked); only the last stage's M microbatches
        # are real — slice them out instead of psum-replicating
        fn = jax.shard_map(
            functools.partial(_pipeline_local,
                              block_fn=self._stage_fn,
                              axis_name=self.axis_name),
            mesh=self.mesh,
            in_specs=({k: P(self.axis_name) for k in grouped}, P()),
            out_specs=P(self.axis_name),
        )
        if not isinstance(x, jax.core.Tracer):
            grouped = jax.device_put(
                grouped, {k: _named_sharding(self.mesh, P(self.axis_name))
                          for k in grouped})
        out = fn(grouped, xs)
        out = out[(self.n_stages - 1) * M:]
        return out.reshape(B, *x.shape[1:])

    def reference_forward(self, stacked_params: dict, x: Array) -> Array:
        """Sequential (no-pipeline) oracle for equivalence tests."""
        for p in unstack_block_params(stacked_params):
            x = self.block_fn(p, x)
        return x
