"""Page-table gather for the paged decode plane (ops seam).

The paged decode step stores KV in a physical page pool
``[n_pages + 1, page_size, H, D]`` and resolves each slot's logical
``[max_context, H, D]`` view through its page-table row at attention
time. This module owns that gather, behind the same dispatch-gate idiom
as the int8 matmul (ops/quant.py) and the LSTM engine (ops/lstm.py):

- **Pallas path** (TPU, or interpret mode for CI): the page table rides
  scalar prefetch (``pltpu.PrefetchScalarGridSpec``), so the block
  index map reads the physical page id BEFORE the kernel body runs and
  the DMA engine streams exactly the mapped pages HBM→VMEM — the
  logical view is materialized tile by tile, never as a second dense
  copy in HBM.
- **XLA path** (CPU hosts, a jit that GSPMD partitions, kill switch):
  one fused ``take`` along the page axis.

Both paths are pure data movement over the same indices, so they are
bitwise identical by construction — the dispatch gate can never change
decoded tokens, only where the gather's bytes move. Selection:
``DL4J_PAGED_GATHER_IMPL`` = ``auto`` (default: Pallas iff the backend
is TPU) | ``pallas`` | ``xla``; ``DL4J_PAGED_GATHER_INTERPRET=1`` runs
the Pallas kernel in interpret mode (CI coverage on CPU). Every call
lands on the shared ``dl4j_pallas_dispatch_total`` counter under kernel
``paged_gather``.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def resolve_paged_impl(requested=None):
    """``(impl, interpret)`` for this host: explicit request beats env
    beats auto (Pallas iff TPU, mirroring ops/lstm.py's resolve)."""
    from deeplearning4j_tpu.ops.pallas_kernels import use_pallas
    req = requested or os.environ.get("DL4J_PAGED_GATHER_IMPL", "auto")
    if req not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"DL4J_PAGED_GATHER_IMPL must be auto|pallas|xla, got {req!r}")
    interpret = os.environ.get("DL4J_PAGED_GATHER_INTERPRET") == "1"
    if req == "xla":
        return "xla", False
    if req == "pallas":
        return "pallas", interpret
    if use_pallas() or interpret:
        return "pallas", interpret
    return "xla", False


def _gather_kernel(table_ref, pool_ref, out_ref):
    # the index map already resolved the physical page; one block copy
    out_ref[...] = pool_ref[...].reshape(out_ref.shape)


def _paged_gather_pallas(pool, table, interpret: bool):
    n_total, ps, H, D = pool.shape
    cap, P = table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(cap, P),
        in_specs=[
            # block (c, p) DMAs physical page table[c, p] — the scalar-
            # prefetched table is visible to the index map pre-kernel
            pl.BlockSpec((1, ps, H, D),
                         lambda c, p, tab: (tab[c, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, ps, H, D),
                               lambda c, p, tab: (c, p, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((cap, P, ps, H, D), pool.dtype),
        interpret=interpret,
    )(table, pool)
    return out.reshape(cap, P * ps, H, D)


def paged_gather(pool, table, *, impl=None):
    """Materialize the logical KV view ``[cap, P*page_size, H, D]`` of a
    physical ``pool [n_pages+1, page_size, H, D]`` through ``table
    [cap, P]`` (int32 physical page ids; trash-page rows are garbage the
    caller's attention mask must never select)."""
    from deeplearning4j_tpu.ops.pallas_kernels import _note_dispatch
    kind, interpret = resolve_paged_impl(impl)
    if kind == "pallas":
        _note_dispatch("paged_gather", True)
        return _paged_gather_pallas(pool, table, interpret)
    _note_dispatch("paged_gather", False)
    n_total, ps, H, D = pool.shape
    cap, P = table.shape
    return jnp.take(pool, table.reshape(-1), axis=0).reshape(
        cap, P * ps, H, D)
