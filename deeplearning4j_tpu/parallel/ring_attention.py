"""Long-context attention: ring (context-parallel) and Ulysses (all-to-all).

The reference scales sequence length only via truncated BPTT + masking
(SURVEY.md §5; reference MultiLayerNetwork.doTruncatedBPTT:1140) — sequence/
context parallelism does not exist there. These are the TPU-native long-context
mechanisms required as first-class components:

* ``ring_attention``: queries stay resident; K/V shards rotate around the ICI
  ring via ``ppermute`` while each device accumulates its attention output with
  an online (flash-style) softmax — memory per device stays O(T/N), and the
  K/V transfer overlaps the local block computation in XLA's schedule.
* ``ulysses_attention``: all-to-all swaps the sequence shard for a head shard,
  computes full-sequence attention on 1/N of the heads, then swaps back.

Both are exact: outputs match single-device softmax attention to fp tolerance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from deeplearning4j_tpu.parallel.partition import (
    pspec as P, named_sharding as _named_sharding,
)
from deeplearning4j_tpu.observability.names import COLLECTIVE_BYTES_PER_STEP
from deeplearning4j_tpu.observability.metrics import (
    global_registry as _obs_registry, tree_nbytes as _tree_nbytes,
)

Array = jax.Array
_NEG = -1e30

# trace-time traffic accounting: these entry points run INSIDE jit traces,
# so a per-execution counter is impossible — instead each (re)trace sizes
# the collective from the static operand shapes and records a per-step gauge
_collective_per_step = _obs_registry().gauge(
    COLLECTIVE_BYTES_PER_STEP,
    "bytes one executed step moves through a traced collective, from "
    "static shapes at trace time, by op and site")


def attention_reference(q: Array, k: Array, v: Array, causal: bool = False,
                        scale=None, window=None) -> Array:
    """Plain full-sequence softmax attention (the correctness oracle).

    Shapes: q,k = (B, T, H, Dk), v = (B, T, H, Dv) -> (B, T, H, Dv);
    ``scale`` None is ``Dk ** -0.5``; under ``window`` (causal only) a query
    sees the ``window`` keys ending at itself.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    s = s / jnp.sqrt(jnp.float32(d)) if scale is None else s * scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        if window is not None:
            mask = jnp.logical_and(mask, jnp.arange(tq)[:, None]
                                   - jnp.arange(tk)[None, :] < window)
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _online_block(q, k, v, m_prev, l_prev, o_prev, q_off, kv_off, causal):
    """One flash-attention accumulation step against a K/V block.

    q: (B, Tq, H, D); k,v: (B, Tk, H, D); m,l: (B, H, Tq); o: (B, Tq, H, D).
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        q_pos = q_off + jnp.arange(tq)
        kv_pos = kv_off + jnp.arange(tk)
        mask = q_pos[:, None] >= kv_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG)
    m_blk = jnp.max(s, axis=-1)                      # (B, H, Tq)
    m_new = jnp.maximum(m_prev, m_blk)
    p = jnp.exp(s - m_new[..., None])                # (B, H, Tq, Tk)
    # fully-masked blocks: keep them exactly zero
    p = jnp.where(s <= _NEG, 0.0, p)
    scale = jnp.exp(m_prev - m_new)                  # (B, H, Tq)
    l_new = l_prev * scale + jnp.sum(p, axis=-1)
    o_scaled = o_prev * jnp.transpose(scale, (0, 2, 1))[..., None]
    o_new = o_scaled + jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                          batch_axis=None):
    """Per-shard body: rotate K/V around the ring, accumulate online softmax."""
    n_dev = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    q_off = my_idx * Tq

    # accumulators are device-varying (they depend on this shard's q) — mark
    # them so the fori_loop carry types line up under shard_map (over the
    # batch axis too when the leading dim is data-sharded)
    axes = (axis_name,) + ((batch_axis,) if batch_axis else ())
    vary = lambda x: jax.lax.pcast(x, axes, to="varying")
    m = vary(jnp.full((B, H, Tq), _NEG, q.dtype))
    l = vary(jnp.zeros((B, H, Tq), q.dtype))
    o = jnp.zeros_like(q)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(step, carry):
        m, l, o, k_cur, v_cur = carry
        # K/V chunk currently resident arrived from (my_idx - step) % n_dev
        src = (my_idx - step) % n_dev
        kv_off = src * Tk
        m, l, o = _online_block(q, k_cur, v_cur, m, l, o, q_off, kv_off, causal)
        # rotate for the next step (last rotation is redundant but keeps the
        # loop shape static; XLA overlaps it with the block compute)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return m, l, o, k_nxt, v_nxt

    m, l, o, _, _ = lax.fori_loop(0, n_dev, body, (m, l, o, k, v))
    l_t = jnp.transpose(l, (0, 2, 1))[..., None]     # (B, Tq, H, 1)
    return o / jnp.maximum(l_t, 1e-20)


def ring_attention_sharded(q: Array, k: Array, v: Array, mesh: Mesh,
                           axis_name: str = "sp", causal: bool = False,
                           batch_axis: str = None) -> Array:
    """Trace-safe ring attention: callable from inside a jitted train step
    (no device_put — under jit, GSPMD reshards operands to the shard_map's
    in_specs). This is what attention layers dispatch when an active
    ParallelContext declares ``seq_mode="ring"`` (parallel/context.py).
    ``batch_axis`` shards the leading (batch) dim too, so composing with
    data parallelism never replicates attention work across DP replicas."""
    spec = P(batch_axis, axis_name)
    # each ring step rotates the full K/V through ppermute once per device;
    # total traffic per executed attention = global K+V bytes
    _collective_per_step.labels(op="ppermute_kv",
                                site="ring_attention").set(
        _tree_nbytes((k, v)))
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          causal=causal, batch_axis=batch_axis),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def ring_attention(q: Array, k: Array, v: Array, mesh: Mesh,
                   axis_name: str = "sp", causal: bool = False) -> Array:
    """Exact context-parallel attention over the mesh's ``axis_name`` axis.

    Inputs are (B, T, H, D) with T sharded over ``axis_name`` (global arrays or
    host arrays; sharding is applied here). Returns output sharded the same way.
    """
    sh = _named_sharding(mesh, P(None, axis_name))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    return ring_attention_sharded(q, k, v, mesh, axis_name, causal)


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool,
                   interpret: bool = False):
    """All-to-all: (T/N, H) -> (T, H/N), full attention, swap back
    (DeepSpeed-Ulysses sequence parallelism)."""
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

    # (B, T/N, H, D) -> (B, T, H/N, D)
    qg = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    # full-sequence attention on 1/N of the heads. This body is built with
    # check_vma=False (see ulysses_attention_sharded), so the pallas flash
    # kernel ENGAGES here on TPU — O(blk*T) attention memory per device for
    # the gathered sequence; below the kernel's dispatch thresholds (or off
    # TPU) the same call runs the identical XLA math at O(T^2) scores memory.
    # interpret lets tests exercise the pallas-under-shard_map path on CPU.
    og = flash_attention(qg, kg, vg, causal, interpret)
    return lax.all_to_all(og, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention_sharded(q: Array, k: Array, v: Array, mesh: Mesh,
                              axis_name: str = "sp", causal: bool = False,
                              interpret: bool = False,
                              batch_axis: str = None) -> Array:
    """Trace-safe Ulysses attention (see ring_attention_sharded): the
    in-jit dispatch target for sequence-parallel attention layers."""
    n = mesh.shape[axis_name]
    if q.shape[2] % n != 0:  # lint: recompile-hazard-ok (trace-time config validation; head count is static under jit)
        raise ValueError(f"num heads {q.shape[2]} not divisible by axis size {n}")
    # four all-to-alls (q/k/v gather + output scatter), each moving one
    # q-sized global array across the axis
    _collective_per_step.labels(op="all_to_all",
                                site="ulysses_attention").set(
        4 * _tree_nbytes(q))
    spec = P(batch_axis, axis_name)
    # check_vma=False: pallas_call's out_shape carries no varying-mesh-axes
    # annotation, so the flash kernel inside the body can't satisfy the vma
    # checker; correctness is pinned by the =reference tests instead
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name, causal=causal,
                          interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q: Array, k: Array, v: Array, mesh: Mesh,
                      axis_name: str = "sp", causal: bool = False,
                      interpret: bool = False) -> Array:
    """Sequence-parallel attention via head-sharding all-to-all. Requires the
    head count to be divisible by the axis size."""
    sh = _named_sharding(mesh, P(None, axis_name))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    return ulysses_attention_sharded(q, k, v, mesh, axis_name, causal,
                                     interpret)
