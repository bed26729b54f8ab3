"""Pallas TPU kernels for hot ops, with XLA fallbacks.

Reference seam: deeplearning4j-cuda helpers (SURVEY.md §2.3) are reflection-
loaded per layer (ConvolutionLayer.java:69-76) so an accelerator backend can
take over fwd/bwd transparently. Here the seam is ``use_pallas()``: on TPU the
pallas kernels run; elsewhere (or when disabled) the mathematically identical
XLA path runs. Tests exercise the kernels in interpret mode on CPU.

Kernels:
* flash_attention — tiled online-softmax attention (forward), custom VJP with
  a recompute backward (standard flash-attention practice: trade FLOPs for
  HBM); tiles and the one-kernel backward are chosen from the shapes.
* softmax_cross_entropy — fused row-softmax + NLL loss per row.

Sharding interactions:
* inside a vma-checked shard_map trace the flash/masked kernels yield to the
  XLA math (_in_checked_shard_map) — the checker rejects pallas_call there.
  shard_map callers that want the kernel set check_vma=False
  (parallel/ring_attention.py ulysses/ring) and the kernel ENGAGES in those
  bodies; the fused xent kernel stays XLA in every shard_map body
  (_in_shard_map — its interpret lowering also trips on the body trace).
* under plain GSPMD sharded jit (ParallelWrapper sync DP, sharded serving)
  the TPU lowering REFUSES a Mosaic kernel outright ("cannot be
  automatically partitioned", JAX 0.9, found compiling for a described
  v5e:2x2): in a jit over more than one device every kernel here takes its
  XLA math (pallas_unavailable), which partitions cleanly along the batch
  axis. Multi-chip attention rides ring/ulysses_attention (sequence
  parallelism), whose shard_map bodies hold the kernel. Wrapping the batch-
  parallel kernels in a shard_map over the data axis is ROADMAP S8.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import remat

Array = jax.Array
_NEG = -1e30

#: bytes of VMEM one kernel program may plan for by default: the compiler's
#: scoped default is 16 MiB per core; the rest is headroom for Mosaic's own
#: scratch. Beside ``_VMEM_CEILING`` it is also the room the one-kernel
#: backward's whole dQ may take (``_fused_bwd_fits``)
_VMEM_BUDGET = 14 * 1024 * 1024

#: the most the TILES of a flash program may plan for (``_flash_vmem_bytes``
#: as ``_flash_tiles`` counts it: scores, operand blocks, accumulators, dQ at
#: ``blk_q`` rows), where the count passes the scoped default and the kernel
#: asks for what it says (``vmem_limit_bytes``): a quarter of a v5e core's
#: 128 MiB. The one-kernel backward adds a head's whole dQ accumulator and
#: dQ's output block, and ``_fused_bwd_fits`` holds its whole program, tiles
#: and dQ, to this and
#: ``_VMEM_BUDGET`` together: at most 46 MiB planned, at most 61.5 asked for
#: (``_flash_params``)
_VMEM_CEILING = 32 * 1024 * 1024


def _causal_mask(s, q0, k0, q_axis: int = 0, window: int = None):
    """Mask a score tile to q_pos >= k_pos, and under a ``window`` to
    q_pos - k_pos < window as well, where the tile starts at absolute
    positions (q0, k0) and its queries run along ``q_axis`` (0: S, the
    forward's; 1: S transposed, the backward's). ONE shared convention for
    the forward and the backward kernel — they must never disagree."""
    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
    keep = ahead >= k0 - q0
    if window is not None:
        keep = jnp.logical_and(keep, ahead < k0 - q0 + window)
    return jnp.where(keep, s, _NEG)


def _flatten_heads(a):
    """(B, T, H, D) -> (B*H, T, D) kernel layout: head ``h`` of sequence
    ``b`` is row ``b * H + h``, so with G key/value heads under H query heads
    row ``bh`` of the queries reads row ``bh // (H // G)`` of the keys."""
    B, T, H, D = a.shape
    return a.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _unflatten_heads(a, B, H):
    BH, T, D = a.shape
    return a.reshape(B, H, T, D).transpose(0, 2, 1, 3)


#: devices the innermost enclosing GSPMD jit is partitioned over (set by
#: parallel/compile_seam.compile_step around the trace of a "jit" step)
_PARTITIONED_OVER: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_partitioned_over", default=1)


@contextlib.contextmanager
def partitioned_trace(n_devices: int):
    """Bracket the trace of a jit that GSPMD partitions over ``n_devices``."""
    token = _PARTITIONED_OVER.set(n_devices)
    try:
        yield
    finally:
        _PARTITIONED_OVER.reset(token)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def pallas_unavailable() -> Optional[str]:
    """Why no Pallas kernel can run in the program being traced, or None —
    the part of every kernel's gate that is read off the device and the
    trace context rather than the operand shapes. Errors from the runtime
    surface: a device that cannot be asked is not a device that said no."""
    if os.environ.get("DL4J_TPU_DISABLE_PALLAS") == "1":
        return "DL4J_TPU_DISABLE_PALLAS=1"
    if not _on_tpu():
        return "no TPU"
    n = _PARTITIONED_OVER.get()
    if n > 1 and not _in_shard_map():
        return (f"traced for a jit that GSPMD partitions over {n} devices: "
                "Mosaic kernels cannot be partitioned automatically, only "
                "run inside a shard_map body")
    return None


def use_pallas() -> bool:
    """Backend seam (reference helper loading seam): True when the program
    being traced can hold a Pallas kernel (:func:`pallas_unavailable`)."""
    return pallas_unavailable() is None


# ---------------------------------------------------------------- flash attention
def _dot(a, b, contract_a: int, contract_b: int):
    """``a`` x ``b`` over the given axes, in the operands' own dtype on the
    MXU (bfloat16 stays bfloat16: an upcast product takes several passes)
    with float32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=jnp.float32)


def _causal_block_live(qi, kj, blk_q: int, blk_k: int):
    """Whether the (q-block, k-block) tile holds any unmasked score under a
    causal mask; a dead tile adds exactly nothing and is skipped."""
    return kj * blk_k <= qi * blk_q + (blk_q - 1)


def _diagonal_k_block(qi, blk_q: int, blk_k: int):
    """The last k-block a q-block's tiles are live in under a causal mask.
    An index map names it again for the dead tiles beyond it, so the
    pipeline fetches nothing nobody reads."""
    return (qi * blk_q + (blk_q - 1)) // blk_k


def _causal_block_crossed(qi, kj, blk_q: int, blk_k: int):
    """Whether the diagonal crosses the tile: its last key lies beyond its
    first query, so some score of it is masked. A tile that is not crossed
    is live and wholly below the diagonal: it needs no mask."""
    return kj * blk_k + (blk_k - 1) > qi * blk_q


#: side from which a square tile on the diagonal is computed as quarters
#: (``_per_causal_tile``): a quarter keeps at least 256 rows for the MXU
_QUARTERED_FROM = 512


def _tile_state(q0, nq: int, k0, nk: int, window: int):
    """``(live, crossed)`` of the ``nq`` queries from ``q0`` against the
    ``nk`` keys from ``k0`` under a causal mask with a ``window``: key j is
    visible to query i where ``0 <= i - j < window``. Live: some score is
    visible; crossed: some is hidden, so the tile needs the mask. Integers,
    numpy arrays (``flash_score_entries``) or traced scalars alike."""
    d_max, d_min = q0 + (nq - 1) - k0, q0 - k0 - (nk - 1)
    return ((d_max >= 0) & (d_min < window),
            (d_min < 0) | (d_max >= window))


def _quartered(blk_q: int, blk_k: int) -> bool:
    return blk_q == blk_k and blk_q >= _QUARTERED_FROM


def _per_causal_tile(tile, causal: bool, qi, kj, blk_q: int, blk_k: int,
                     window: int = None, valid=True):
    """Run ``tile(masked, rows, cols)`` over one (q-block, k-block) tile of a
    flash kernel, forward or backward; ``rows`` and ``cols`` are the static
    slices of the tile's queries and keys a call covers. A dead tile is
    skipped, a tile wholly below the diagonal takes one call without the
    mask's passes over its scores, and a tile the diagonal crosses takes the
    masked body. Square tiles are crossed where q-block == k-block, corner
    to corner: from ``_QUARTERED_FROM`` rows such a tile runs as three
    quarters (two masked ones on the diagonal, the plain one below it) and
    its dead quarter is left out.

    Under a ``window`` a tile is dead on either side of the band, plain
    inside it and crossed on either edge (``_tile_state``); a crossed
    quarterable tile runs quarter by quarter, each dead, plain or masked by
    the same rule, wherever the edges fall. ``valid`` False marks a grid step
    beyond the last block (the window's shortened block axis)."""
    whole = pl.ds(0, blk_q), pl.ds(0, blk_k)
    if not causal:
        tile(False, *whole)
        return
    if window is not None:
        q0, k0 = qi * blk_q, kj * blk_k
        live, crossed = _tile_state(q0, blk_q, k0, blk_k, window)
        live = jnp.logical_and(live, valid)
        pl.when(jnp.logical_and(live, jnp.logical_not(crossed)))(
            lambda: tile(False, *whole))
        edge = jnp.logical_and(live, crossed)
        if not _quartered(blk_q, blk_k):
            pl.when(edge)(lambda: tile(True, *whole))
            return
        half = blk_q // 2
        for r0 in (0, half):
            for c0 in (0, half):
                rows, cols = pl.ds(r0, half), pl.ds(c0, half)
                l, c = _tile_state(q0 + r0, half, k0 + c0, half, window)
                l = jnp.logical_and(edge, l)
                pl.when(jnp.logical_and(l, jnp.logical_not(c)))(
                    lambda rows=rows, cols=cols: tile(False, rows, cols))
                pl.when(jnp.logical_and(l, c))(
                    lambda rows=rows, cols=cols: tile(True, rows, cols))
        return
    crossed = _causal_block_crossed(qi, kj, blk_q, blk_k)
    pl.when(jnp.logical_not(crossed))(lambda: tile(False, *whole))
    on_diagonal = jnp.logical_and(
        crossed, _causal_block_live(qi, kj, blk_q, blk_k))
    if _quartered(blk_q, blk_k):
        half = blk_q // 2
        lo, hi = pl.ds(0, half), pl.ds(half, half)

        @pl.when(on_diagonal)
        def _quarters():
            tile(True, lo, lo)
            tile(False, hi, lo)
            tile(True, hi, hi)
    else:
        pl.when(on_diagonal)(lambda: tile(True, *whole))


def _first_live(start, window: int, blk: int):
    """The first block of ``blk`` positions that holds a position within
    ``window`` back from ``start``: a q-block's first live k-block."""
    return jnp.maximum(start - (window - 1), 0) // blk


def _live_tiles(n_q: int, blk_q: int, n_k: int, blk_k: int, window: int):
    """``(live, crossed)`` of every (q-block, k-block) tile under a window,
    as numpy matrices [n_q, n_k] (``_tile_state`` over the whole grid)."""
    import numpy as np

    q0 = (np.arange(n_q, dtype=np.int64) * blk_q)[:, None]
    k0 = (np.arange(n_k, dtype=np.int64) * blk_k)[None, :]
    return (q0, k0) + _tile_state(q0, blk_q, k0, blk_k, window)


def _window_steps(n_q: int, blk_q: int, n_k: int, blk_k: int, window: int,
                  stream_k: bool) -> int:
    """Grid steps of the streamed block axis under a window: the most live
    tiles any block of the other axis has (they lie side by side: q-blocks
    stream k-blocks from ``_first_live`` up to the diagonal; k-blocks stream
    q-blocks from the diagonal up to the window's far edge)."""
    live = _live_tiles(n_q, blk_q, n_k, blk_k, window)[2]
    return int(live.sum(axis=1 if stream_k else 0).max())


def flash_score_entries(tq: int, dk: int, dv: int, dtype, window: int = None,
                        blk_q: int = None, blk_k: int = None,
                        engaged: bool = None) -> tuple:
    """``(computed, visible)`` score entries of one head's causal forward at
    ``tq`` positions under the tiles the shapes give: what the kernel's plan
    computes (whole plain tiles, the live quarters or the whole of a crossed
    one) and what the mask leaves visible. The backward computes the same
    tiles. Where the kernel does not engage (``engaged`` None: as
    ``flash_attention`` decides on this device), the XLA math computes all
    ``tq * tq``."""
    w = tq if window is None else min(window, tq)   # causal: a window of tq
    visible = w * (w + 1) // 2 + (tq - w) * w
    if engaged is None:
        engaged = use_pallas() and tq >= _MIN_SEQ
    tiles = engaged and _flash_tiles(tq, tq, dk, dv, dtype, blk_q, blk_k)
    if not tiles or tq % tiles[0] or tq % tiles[1]:
        return tq * tq, visible
    bq, bk = tiles
    q0, k0, live, crossed = _live_tiles(tq // bq, bq, tq // bk, bk, w)
    computed = int((live & ~crossed).sum()) * bq * bk
    edge = live & crossed
    if not _quartered(bq, bk):
        return computed + int(edge.sum()) * bq * bk, visible
    half = bq // 2
    for r0 in (0, half):
        for c0 in (0, half):
            quarter = _tile_state(q0 + r0, half, k0 + c0, half, w)[0]
            computed += int((edge & quarter).sum()) * half * half
    return computed, visible


def _lanes(d: int) -> int:
    """``d`` rounded up to the 128 lanes a VMEM tile is wide."""
    return -(-d // 128) * 128


def _dq_bytes(rows: int, dk: int, itemsize: int, out_rows: int = None) -> int:
    """VMEM of dQ: the float32 accumulator of ``rows`` rows and the
    double-buffered output block of ``out_rows`` (None: as many)."""
    out_rows = rows if out_rows is None else out_rows
    return _lanes(dk) * (rows * 4 + out_rows * 2 * itemsize)


def _dq_by_key_block(causal: bool, tq: int, tk: int) -> bool:
    """Whether the one-kernel backward writes dQ one key block at a time.
    It walks the k-blocks in its outer grid axis; under a causal mask over
    equal lengths query row r sees no key past r, so the rows of k-block j
    are final once outer step j ends, under a window or a selection too.
    Otherwise a row is final only on the head's last tile."""
    return causal and tq == tk


def _flash_vmem_bytes(blk_q: int, blk_k: int, dk: int, dv: int,
                      itemsize: int, backward: bool = False,
                      dq_rows: int = 0, dq_out_rows: int = None) -> int:
    """Bytes of VMEM one program of a flash kernel plans for, from its
    shapes: the float32 score tile and the tiles derived from it that are
    alive beside it (P forward; P, dP and dS backward), their copies in the
    operands' dtype for the MXU, the float32 accumulators, the pipeline's
    double-buffered operand and output blocks, and, where the backward keeps
    a head's whole dQ (``dq_rows`` = Tq), that scratch and its output block
    of ``dq_out_rows`` rows (None: as many; ``blk_k`` where dQ is written a
    key block at a time, ``_dq_by_key_block``)."""
    dk, dv = _lanes(dk), _lanes(dv)
    tile = blk_q * blk_k
    if not backward:
        scores = tile * (2 * 4 + itemsize)
        blocks = 2 * itemsize * (blk_q * (dk + dv) + blk_k * (dk + dv))
        accum = blk_q * (dv + 2 * 128) * 4
        return scores + blocks + accum
    scores = tile * (4 * 4 + 2 * itemsize)
    blocks = 2 * itemsize * (blk_q * (dk + dv) + 2 * blk_k * (dk + dv))
    accum = blk_k * (dk + dv) * 4
    return scores + blocks + accum + _dq_bytes(dq_rows or blk_q, dk, itemsize,
                                               dq_out_rows)


def _flash_params(semantics, need: int):
    """Compiler parameters of a flash kernel whose program plans for
    ``need`` bytes of VMEM: where that passes what the compiler's scoped
    default leaves, ask for what the count says (and room for Mosaic's own
    scratch)."""
    limit = None if need <= _VMEM_BUDGET else need + need // 4 + (4 << 20)
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=limit)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, causal: bool, blk_q: int,
                      blk_k: int, scale: float, has_mask: bool,
                      window: int = None, has_select: bool = False):
    """One (batch*head, q-block, k-block) program of the online softmax.

    The k-block axis is the innermost, sequential grid axis: K/V arrive one
    (blk_k, D) block per program through the pipeline, and the running max
    ``m``, normalizer ``l`` and unnormalized output ``acc`` ride VMEM scratch
    from the first k-block (init) to the last (finalize) — so the VMEM a
    program holds is a function of the tile sizes only, never of the
    sequence length. q_ref: (1, blk_q, Dk); k_ref: (1, blk_k, Dk); v_ref:
    (1, blk_k, Dv); o_ref: (1, blk_q, Dv) — the value width may differ from
    the query/key width (latent attention: 192 and 128);
    lse_ref: (1, blk_q, 1) log-sum-exp of the scaled scores per query row —
    saved so the backward can recompute P = exp(S - lse) without a second
    online-softmax pass. With has_mask, a (1, blk_k, 1) {0,1} key-padding
    mask block precedes the outputs: masked keys get -inf logits. Under a
    causal mask a tile wholly above the diagonal is skipped and only a tile
    the diagonal crosses is masked (``_per_causal_tile``). Under a
    ``window`` the k-block axis is as long as the band is wide
    (``_window_steps``) and starts at the q-block's first live k-block. A
    row with no visible key in a tile leaves ``exp(0)`` behind; the row's
    diagonal tile, its last, wipes that with ``alpha = 0``. With has_select a
    (1, blk_q, blk_k) int8 block of the per-query selection precedes the
    outputs: a pair that is not selected gets a -inf logit in EVERY live
    tile (the selection lies within the causal half and stands for the
    mask); every row selects a key, and the first tile that holds one wipes
    what the tiles before it left, as above.
    """
    rest = list(rest)
    km_ref = rest.pop(0) if has_mask else None
    sel_ref = rest.pop(0) if has_select else None
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest
    qi = pl.program_id(1)
    step = kj = pl.program_id(2)
    if window is not None:
        kj = _first_live(qi * blk_q, window, blk_k) + step

    @pl.when(step == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def _tile(masked: bool, rows, cols):
        v_blk = v_ref[0, cols, :]
        s = _dot(q_ref[0, rows, :], k_ref[0, cols, :], 1, 1) * scale
        if has_mask:
            km_blk = km_ref[0, cols, 0].astype(jnp.float32)
            s = jnp.where(km_blk[None, :] > 0, s, _NEG)
        if has_select:
            s = jnp.where(sel_ref[0, rows, cols] != 0, s, _NEG)
        elif masked:
            s = _causal_mask(s, qi * blk_q + rows.start,
                             kj * blk_k + cols.start, window=window)
        m = m_sc[rows, :]                                 # (rows, 1)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if has_mask:
            # a row whose keys so far are ALL masked has m_new = _NEG and
            # would read exp(0); a causal row always holds key 0, in its
            # first tile, so without a key mask exp underflows to 0 itself
            p = jnp.where(s <= _NEG, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l_sc[rows, :] = l_sc[rows, :] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
        acc_sc[rows, :] = acc_sc[rows, :] * alpha + _dot(
            p.astype(v_blk.dtype), v_blk, 1, 0)
        m_sc[rows, :] = m_new

    _per_causal_tile(_tile, causal, qi, kj, blk_q, blk_k, window)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_sc[...], 1e-20)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_sc[...] + jnp.log(l_safe)


def _bh_mask(key_mask: Array, H: int) -> Array:
    """[B, Tk] {0,1} key mask -> (B*H, Tk, 1) f32 kernel operand.

    The trailing singleton is Mosaic block-layout armor for a per-key
    column: a (1, blk) block on a (B*H, X) array has sublane size 1, which
    the TPU lowering rejects unless it equals the array dim; as (B*H, X, 1)
    the block (1, blk, 1) is legal — blk is 8-divisible and the lane dim
    matches. (The backward's per-query rows, lse and delta, travel as
    (B*H, 1, T): lane-dense, and a row is what S transposed wants.)"""
    B, Tk = key_mask.shape
    return jnp.broadcast_to(key_mask.astype(jnp.float32)[:, None, :],
                            (B, H, Tk)).reshape(B * H, Tk, 1)


def _kv_row(group: int):
    """Row of the flattened keys and values that row ``bh`` of the flattened
    queries reads, ``group`` query heads to a key/value head."""
    return (lambda bh: bh) if group == 1 else (lambda bh: bh // group)


def _kv_block(causal: bool, blk_q: int, blk_k: int, window: int = None,
              group: int = 1):
    """Index map of a K/V block on a (bh, q-block, k-block) grid. Under a
    causal mask a tile above the diagonal is never computed
    (``_causal_block_live``) and names the diagonal's block again
    (``_diagonal_k_block``); under a ``window`` the k-block axis counts from
    the q-block's first live block. ``group`` query heads share a key/value
    head: ``group`` consecutive rows of the grid read the same K/V row."""
    row = _kv_row(group)
    if not causal:
        return lambda bh, i, j: (row(bh), j, 0)
    if window is None:
        return lambda bh, i, j: (
            row(bh), jnp.minimum(j, _diagonal_k_block(i, blk_q, blk_k)), 0)
    return lambda bh, i, j: (
        row(bh), jnp.minimum(j + _first_live(i * blk_q, window, blk_k),
                             _diagonal_k_block(i, blk_q, blk_k)), 0)


def _flash_tiles(tq: int, tk: int, dk: int, dv: int, dtype,
                 blk_q: int = None, blk_k: int = None,
                 backward: bool = False):
    """(blk_q, blk_k) of a flash kernel from the operands' shape: the
    widest standard tiles (``_TILE_SIZES``) that divide the sequences and
    whose program (``_flash_vmem_bytes``) fits ``_VMEM_CEILING``. A wider
    query tile streams K/V less often (a 128-row one re-reads every live K/V
    block from HBM at half the chip's ridge intensity), a wider key tile
    spreads the per-row softmax statistics over more scores, and both pay a
    tile's fixed cost over more arithmetic: on a v5e that outweighs the dead
    half a wide tile on the causal diagonal computes, down to one tile a
    sequence (T = 1,024 and 2,048 at 64 wide, PERF.md §6, PR 31). An
    explicit size is taken as given (capped at the sequence); None where a
    sequence has no standard divisor."""
    itemsize = jnp.dtype(dtype).itemsize
    for size in _TILE_SIZES:
        bq = min(blk_q, tq) if blk_q else _pick_blk(tq, size)
        bk = min(blk_k, tk) if blk_k else _pick_blk(tk, size)
        if not bq or not bk:
            return None
        if (blk_q and blk_k) or _flash_vmem_bytes(
                bq, bk, dk, dv, itemsize, backward) <= _VMEM_CEILING:
            return bq, bk
    return None


def _tiles_or_raise(tq: int, tk: int, *args, **kwargs):
    """``_flash_tiles``, or a ValueError where the sequences do not tile."""
    tiles = _flash_tiles(tq, tk, *args, **kwargs)
    if not tiles or tq % tiles[0] or tk % tiles[1]:
        raise ValueError(f"sequence lengths ({tq},{tk}) must be divisible by "
                         f"block sizes {tiles}")
    return tiles


def _head_group(q: Array, k: Array, v: Array, key_mask=None) -> int:
    """Query heads to a key/value head (1: as many of each)."""
    H, G = q.shape[2], k.shape[2]
    if v.shape[2] != G or H % G or (key_mask is not None and H != G):
        raise ValueError(f"{H} query heads over {G} key and {v.shape[2]} "
                         "value heads" + (" under a key mask"
                                          if key_mask is not None else ""))
    return H // G


def _check_select(select, causal: bool, window, key_mask, shape):
    """A selection is int8 [B, Tq, Tk] over causal self-attention, every
    selected pair within the causal half (what ``indexer.select_topk``
    makes): it stands for the mask, so neither a window nor a key mask goes
    with it."""
    if select is None:
        return
    if (not causal or window is not None or key_mask is not None
            or shape[1] != shape[2] or tuple(select.shape) != tuple(shape)
            or select.dtype != jnp.int8):
        raise ValueError(
            "a selection needs causal self-attention without a window or a "
            f"key mask and an int8 [B, T, T] matrix: causal {causal}, window "
            f"{window}, select {select.dtype}{list(select.shape)} for "
            f"{list(shape)}")


def _check_window(window, causal: bool, tq: int, tk: int, key_mask=None):
    if window is None:
        return
    if not causal or tq != tk or key_mask is not None or window < 1:
        raise ValueError(
            "a window needs causal self-attention (Tq == Tk) without a key "
            f"mask: causal {causal}, lengths ({tq},{tk}), window {window}")


def _flash_forward(q: Array, k: Array, v: Array, causal: bool,
                   blk_q: int = None, blk_k: int = None,
                   interpret: bool = False, key_mask: Array = None,
                   scale: float = None, window: int = None,
                   select: Array = None):
    """q: (B, T, H, Dk), k: (B, T, G, Dk), v: (B, T, G, Dv) -> (out (B, T, H,
    Dv), lse (B*H, Tq) f32); G divides H, and query head h reads key/value
    head ``h // (H // G)`` from where it lies, never repeated. None block
    sizes -> chosen from the shapes (``_flash_tiles``). key_mask: optional
    [B, Tk] {0,1} key-padding mask. ``scale`` None is ``Dk ** -0.5``.
    ``window``: a query sees the ``window`` keys ending at itself.
    ``select``: int8 [B, Tq, Tk], the pairs the core runs over
    (``_check_select``)."""
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    group = _head_group(q, k, v, key_mask)
    _check_window(window, causal, Tq, Tk, key_mask)
    _check_select(select, causal, window, key_mask, (B, Tq, Tk))
    blk_q, blk_k = _tiles_or_raise(Tq, Tk, D, Dv, q.dtype, blk_q, blk_k)
    scale = 1.0 / (D ** 0.5) if scale is None else scale
    qr, kr, vr = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    has_mask = key_mask is not None

    kernel = functools.partial(_flash_fwd_kernel, causal=causal, blk_q=blk_q,
                               blk_k=blk_k, scale=scale, has_mask=has_mask,
                               window=window, has_select=select is not None)
    kv = _kv_block(causal, blk_q, blk_k, window, group)
    n_k = Tk // blk_k if window is None else _window_steps(
        Tq // blk_q, blk_q, Tk // blk_k, blk_k, window, True)
    in_specs = [
        pl.BlockSpec((1, blk_q, D), lambda bh, i, j: (bh, i, 0)),
        pl.BlockSpec((1, blk_k, D), kv),
        pl.BlockSpec((1, blk_k, Dv), kv),
    ]
    operands = [qr, kr, vr]
    if has_mask:
        in_specs.append(pl.BlockSpec((1, blk_k, 1), kv))
        operands.append(_bh_mask(key_mask, H))
    if select is not None:
        # one selection for all the heads of a sequence; a dead tile names
        # the diagonal's block again, as K and V do
        in_specs.append(pl.BlockSpec(
            (1, blk_q, blk_k), lambda bh, i, j: (bh // H, i, kv(bh, i, j)[1])))
        operands.append(select)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Tq // blk_q, n_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, blk_q, Dv), lambda bh, i, j: (bh, i, 0)),
            # trailing singleton: see _bh_mask on Mosaic block-layout rules
            pl.BlockSpec((1, blk_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, 1), jnp.float32),
                        pltpu.VMEM((blk_q, Dv), jnp.float32)],
        # (batch*head, q-block) programs are independent; the k-block axis
        # carries the VMEM accumulators and must run in order
        compiler_params=_flash_params(
            ("parallel", "parallel", "arbitrary"),
            _flash_vmem_bytes(blk_q, blk_k, D, Dv, q.dtype.itemsize)),
        interpret=interpret,
    )(*operands)
    return _unflatten_heads(out, B, H), lse[:, :, 0]


def _repeat_kv(q, k, v):
    """K and V with every key/value head repeated for the query heads that
    share it: the XLA fallbacks' way (the Pallas path never repeats one)."""
    group = _head_group(q, k, v)
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def _attention_xla(q, k, v, causal, scale=None, window=None):
    # Single source of truth for the reference math (also the ring-attention
    # correctness oracle) — keep one copy so masking/scaling can't diverge.
    from deeplearning4j_tpu.parallel.ring_attention import attention_reference
    _check_window(window, causal, q.shape[1], k.shape[1])
    k, v = _repeat_kv(q, k, v)
    return attention_reference(q, k, v, causal, scale, window).astype(q.dtype)


def _selected_attention_xla(q, k, v, select, scale=None):
    """The statement of the core over a selection: softmax over each
    query's selected keys alone -> (out [B, T, H, Dv], lse [B * H, T])."""
    B, T, H, D = q.shape
    k, v = _repeat_kv(q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (D ** -0.5 if scale is None else scale)
    s = jnp.where(select[:, None] != 0, s, _NEG)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), lse.reshape(B * H, T)


def _in_shard_map() -> bool:
    """True while tracing inside ANY shard_map body, vma-checked or not (its
    mesh axes are Manual there). The fused softmax-xent kernel yields to XLA
    math in every shard_map body (its interpret lowering's while_loop carry
    trips the checker even with the guard off); the flash kernels only need
    the narrower :func:`_in_checked_shard_map` test."""
    return bool(jax.sharding.get_abstract_mesh().manual_axes)


def _in_checked_shard_map(x) -> bool:
    """True when ``x`` is device-varying under a vma-CHECKED shard_map trace
    — the contexts whose checker rejects pallas_call, so flash/masked
    dispatch must yield to XLA math. Bodies opened with ``check_vma=False``
    (parallel/ring_attention.py ulysses/ring) return False: the kernel
    engages there, which is the whole point of the sequence-parallel path."""
    return bool(jax.typeof(x).vma)


#: shortest sequence the flash kernel engages at. Short sequences lose to
#: plain XLA attention INSIDE a model: the custom call is a fusion barrier,
#: so surrounding projections lose their elementwise epilogues. Calibrated
#: 2026-07-31 on a v5e, on the kernel that held K/V whole in VMEM; record not
#: kept; re-derive in a cell (ROADMAP S5). There is no upper bound: K/V
#: stream through the grid, so VMEM per program does not grow with the
#: sequence (tests/test_tpu_aot_compile.py compiles T=16384), and XLA
#: O(T^2)-materializes scores — at long context the kernel is the
#: memory-safe path.
_MIN_SEQ = int(os.environ.get("DL4J_FLASH_MIN_SEQ", "1024"))


#: dispatch accounting: these call sites execute at TRACE time (the branch
#: is baked into the compiled program), so each increment is one compiled
#: program embedding the pallas-vs-XLA choice — retraces show up as extra
#: counts, which is exactly what an engagement dashboard wants to see. A
#: program loaded from the executable store is never traced: the store
#: records the notes of the trace it serialized (recorded_dispatch) and
#: replays them on a hit, so a warm process counts what it runs too.
from deeplearning4j_tpu.observability.names import (  # noqa: E402
    PALLAS_DISPATCH_TOTAL,
)
from deeplearning4j_tpu.observability.metrics import (  # noqa: E402
    global_registry as _obs_registry,
)

_pallas_dispatch = _obs_registry().counter(
    PALLAS_DISPATCH_TOTAL,
    "pallas-vs-XLA dispatch decisions at kernel call sites, counted per "
    "program (traced, or loaded from the executable store), by kernel and "
    "whether the pallas path engaged")

_dispatch_log: contextvars.ContextVar = contextvars.ContextVar(
    "dl4j_dispatch_log", default=None)


def _note_dispatch(kernel: str, engaged: bool) -> None:
    _pallas_dispatch.labels(kernel=kernel,
                            engaged="true" if engaged else "false").inc()
    log = _dispatch_log.get()
    if log is not None:
        log.append((kernel, engaged))


@contextlib.contextmanager
def recorded_dispatch():
    """Collect the dispatch notes of the traces run inside the block (on
    this thread) — what the executable store keeps with an entry."""
    notes: list = []
    token = _dispatch_log.set(notes)
    try:
        yield notes
    finally:
        _dispatch_log.reset(token)


def replay_dispatch(notes) -> None:
    """Count the choices a loaded executable embeds (see above)."""
    for kernel, engaged in notes:
        _note_dispatch(kernel, engaged)


def _pallas_ok(q, k, interpret: bool, force: bool = False) -> bool:
    """ONE dispatch predicate for every flash/masked entry point AND its
    custom_vjp fwd rule — they must agree, or a forward under jax.grad would
    silently take a different code path than the plain forward.

    ``force`` is the per-call ``force_pallas`` opt-in: it bypasses the
    _MIN_SEQ length heuristic but never the hard constraints — hardware
    support (``use_pallas()``/interpret), tileable sequence lengths, and the
    vma-checked shard_map guard (the checker rejects pallas_call outright;
    engaging there would crash, not run slowly)."""
    if not ((use_pallas() or interpret)
            and _tileable(q.shape[1], k.shape[1])):
        return False
    if _in_checked_shard_map(q):
        return False
    return force or interpret or max(q.shape[1], k.shape[1]) >= _MIN_SEQ


#: standard tile sides of the flash kernels, widest first: ``_flash_tiles``
#: takes the first whose program fits. 1,024 square measured fastest in
#: ``deepseek-v2-lite-ep8-train-seq4096`` (PERF.md §6, PR 31).
_TILE_SIZES = (1024, 512, 256, 128)


def _pick_blk(t: int, pref: int):
    """Largest standard block size (``_TILE_SIZES``) up to ``pref`` that
    divides ``t``. Without the fallback a 512-row preference would silently
    drop 128-divisible-but-not-512-divisible lengths (1280, 3200, ...) to
    the O(T^2) XLA path."""
    if t <= 128:
        return t
    return next((b for b in _TILE_SIZES if b <= pref and t % b == 0), None)


def _tileable(tq: int, tk: int) -> bool:
    return _pick_blk(tq, 128) is not None and _pick_blk(tk, 128) is not None


def _masked_attention_xla(q: Array, k: Array, v: Array, key_mask: Array,
                          causal: bool) -> Array:
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    s = jnp.where(key_mask[:, None, None, :] > 0, s, _NEG)
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        cm = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        s = jnp.where(cm, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    # rows whose keys are ALL masked (padded queries) -> zero output
    p = jnp.where(jnp.max(s, axis=-1, keepdims=True) <= _NEG, 0.0, p)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)


def masked_attention(q: Array, k: Array, v: Array, key_mask: Array,
                     causal: bool = False, interpret: bool = False,
                     force_pallas: bool = False) -> Array:
    """Attention with a {0,1} key/padding mask [B, Tk]: masked keys get -inf
    logits (NOT zeroed k/v — zeroing still leaves them e^0 softmax mass).
    Shapes as flash_attention: (B, T, H, D). On TPU this rides the same
    tiled Pallas kernels as flash_attention (O(blk·T) memory); elsewhere or
    on non-tileable shapes it runs the identical XLA math.

    Dispatch thresholds and ``force_pallas`` are exactly as documented on
    :func:`flash_attention` — both entry points share one predicate
    (``_pallas_ok``)."""
    return _masked_attention_vjp(q, k, v, key_mask.astype(jnp.float32),
                                 causal, interpret, force_pallas)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _masked_attention_vjp(q, k, v, key_mask, causal, interpret, force):
    ok = _pallas_ok(q, k, interpret, force)
    _note_dispatch("masked_attention", ok)
    if ok:
        return _flash_forward(q, k, v, causal, interpret=interpret,
                              key_mask=key_mask)[0]
    return _masked_attention_xla(q, k, v, key_mask, causal)


def _masked_fwd_rule(q, k, v, key_mask, causal, interpret, force):
    tiled_bwd = (_pallas_ok(q, k, interpret, force)
                 and _pallas_bwd_enabled(k.shape[1], force))
    _note_dispatch("masked_attention_bwd", tiled_bwd)
    if tiled_bwd:
        _note_dispatch("masked_attention", True)
        out, lse = _flash_forward(q, k, v, causal, interpret=interpret,
                                  key_mask=key_mask)
        return out, (q, k, v, key_mask, out, lse)
    return (_masked_attention_vjp(q, k, v, key_mask, causal, interpret,
                                  force),
            (q, k, v, key_mask, None, None))


def _masked_bwd_rule(causal, interpret, force, res, g):
    q, k, v, km, out, lse = res
    if lse is not None:
        dq, dk, dv = _flash_backward(q, k, v, out, lse, g, causal,
                                     interpret=interpret, key_mask=km)
    else:
        _, vjp = jax.vjp(
            lambda a, b, c: _masked_attention_xla(a, b, c, km, causal),
            q, k, v)
        dq, dk, dv = vjp(g)
    return dq, dk, dv, jnp.zeros_like(km)


_masked_attention_vjp.defvjp(_masked_fwd_rule, _masked_bwd_rule)


def flash_attention(q: Array, k: Array, v: Array, causal: bool = False,
                    interpret: bool = False,
                    force_pallas: bool = False,
                    scale: float = None, window: int = None,
                    select: Array = None, with_lse: bool = False):
    """Tiled attention: pallas forward on TPU (shapes that don't tile fall
    back to the identical XLA math rather than erroring), XLA elsewhere.
    ``v`` may be narrower or wider than ``q`` and ``k`` (latent attention:
    192-wide keys, 128-wide values); ``scale`` None is ``Dk ** -0.5``.
    ``k`` and ``v`` may have fewer heads than ``q`` (grouped heads: query
    head h reads key/value head ``h // (H // G)``, never repeated in memory
    on the Pallas path; dK and dV sum over the group). ``window`` (causal
    self-attention only): a query sees the ``window`` keys ending at itself;
    tiles wholly outside the band are neither fetched nor computed, forward
    or backward, and only the tiles an edge crosses are masked.
    Backward is tiled pallas too, recomputing P from the saved logsumexp
    (flash-attention practice: trade FLOPs for HBM; peak extra memory
    O(blk·T), never O(Tq·Tk)): ONE kernel for dQ, dK and dV where its
    program, the tiles and a head's whole dQ accumulator, fits the VMEM a
    kernel may ask for (``_fused_bwd_fits``), so each score tile is
    recomputed once, else a dQ and a dK/dV kernel. Under a causal mask the
    one kernel writes dQ one key block at a time, as each block's rows
    become final, and so holds one key block of dQ's output, not the whole
    head's. Set DL4J_FLASH_PALLAS_BWD=0 to use the XLA chunked-scan backward
    instead.

    Tiles are chosen from the operands' shape (``_flash_tiles``): up to
    1,024 square, smaller where the sequence or VMEM says so. Under a causal mask a dead tile is skipped and
    only a tile the diagonal crosses is masked.

    Dispatch thresholds (set for kernels since replaced, ROADMAP S5/D3; the
    one shape measured since is T = 4,096 at 192/128 wide, which engages
    forward and backward):

    * The forward kernel engages only at ``max(Tq, Tk) >=`` **_MIN_SEQ**
      (default 1024, env ``DL4J_FLASH_MIN_SEQ``). Shorter sequences run
      faster on the XLA path inside a model — the custom call is a fusion
      barrier, so neighbouring projections lose their epilogues.
    * The tiled pallas backward engages only at ``Tk >=`` **_PBWD_MIN_SEQ**
      (default 4096, env ``DL4J_FLASH_PBWD_MIN_SEQ``); below that the
      chunked lax.scan backward runs. ``DL4J_FLASH_PALLAS_BWD=0/1``
      overrides unconditionally.

    ``force_pallas=True`` is the per-call opt-in that bypasses both length
    heuristics (for workloads whose measured crossover differs — e.g. a
    sequence-parallel body whose per-shard lengths sit under the gate). It
    never overrides the hard constraints: TPU/interpret availability,
    tileable lengths, and the vma-checked shard_map guard, where
    pallas_call would be rejected outright.

    ``select`` (causal self-attention only, no window): int8 [B, T, T], 1
    where query t attends to key s, every 1 at ``s <= t`` and at least one
    a row (``indexer.select_topk``): the softmax runs over each query's
    selected keys alone, forward and backward, in every causal tile (this
    first plan skips no tile the selection leaves empty). It takes no
    gradient. ``with_lse`` (with a selection): also return the float32
    log-sum-exp of each query head's scaled selected scores, [B * H, T],
    whose cotangent is dropped: it is for use under ``stop_gradient``
    (``indexer.index_kl`` makes the core's probabilities from it).

    Where the tiled backward engages, the forward rule tags its output and
    log-sum-exp ``attn_core_out`` and ``attn_core_lse``
    (``jax.ad_checkpoint.checkpoint_name``; ``ops/remat.py``): a layer
    checkpointed through ``remat.checkpoint_layer`` keeps both, B x T x H x
    Dv of the operands' dtype and B x H x T float32, and its recomputed
    forward does not run this kernel again. Anywhere else the tags are the
    identity."""
    if select is None:
        if with_lse:
            raise ValueError("with_lse goes with a selection")
        return _flash_attention(q, k, v, causal, interpret, force_pallas,
                                scale, window)
    _check_select(select, causal, window, None,
                  (q.shape[0], q.shape[1], k.shape[1]))
    out, lse = _selected_attention(q, k, v, select, interpret, force_pallas,
                                   scale)
    return (out, lse) if with_lse else out


def _kept(out, lse):
    """A forward rule's ``out`` and ``lse`` under the names a checkpointed
    layer keeps (``ops/remat.py``): the tagged values are both what the rule
    returns and what its residuals hold, so a recomputed forward has no use
    for the forward kernel. The identity outside such a policy."""
    return (checkpoint_name(out, remat.CORE_OUT),
            checkpoint_name(lse, remat.CORE_LSE))


def flash_kept_bytes(batch: int, tq: int, heads: int, dv: int, dtype) -> tuple:
    """``(out, lse)`` bytes the forward rule tags for ``batch`` causal
    sequences of ``tq`` positions and ``heads`` query heads: zeros where the
    tiled backward does not engage on this device (the XLA backward's
    residuals hold neither)."""
    if not (use_pallas() and _tileable(tq, tq) and tq >= _MIN_SEQ
            and _pallas_bwd_enabled(tq)):
        return 0, 0
    return (batch * tq * heads * dv * jnp.dtype(dtype).itemsize,
            batch * heads * tq * 4)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, interpret, force_pallas, scale, window):
    ok = _pallas_ok(q, k, interpret, force_pallas)
    _note_dispatch("flash_attention" + _variant(q, k, window), ok)
    if ok:
        return _flash_forward(q, k, v, causal, interpret=interpret,
                              scale=scale, window=window)[0]
    return _attention_xla(q, k, v, causal, scale, window)


def _variant(q, k, window, select=None) -> str:
    """Suffix of a dispatch note's kernel name: which plan the call takes
    (``_window``, ``_select``, ``_grouped``), so a fallback of any is seen by
    name."""
    return (("_window" if window is not None else "")
            + ("_select" if select is not None else "")
            + ("_grouped" if k.shape[2] != q.shape[2] else ""))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _selected_attention(q, k, v, select, interpret, force, scale):
    """-> (out, lse) of the core over ``select`` (``flash_attention``)."""
    ok = _pallas_ok(q, k, interpret, force)
    _note_dispatch("flash_attention" + _variant(q, k, None, select), ok)
    if ok:
        return _flash_forward(q, k, v, True, interpret=interpret, scale=scale,
                              select=select)
    return _selected_attention_xla(q, k, v, select, scale)


def _selected_fwd_rule(q, k, v, select, interpret, force, scale):
    tiled_bwd = (_pallas_ok(q, k, interpret, force)
                 and _pallas_bwd_enabled(k.shape[1], force))
    variant = _variant(q, k, None, select)
    _note_dispatch("flash_attention_bwd" + variant, tiled_bwd)
    _note_dispatch("flash_attention_bwd_fused" + variant,
                   tiled_bwd and _fused_bwd_fits(
                       q.shape[1], k.shape[1], q.shape[-1], v.shape[-1],
                       q.dtype, True))
    if tiled_bwd:
        _note_dispatch("flash_attention" + variant, True)
        out, lse = _kept(*_flash_forward(q, k, v, True, interpret=interpret,
                                         scale=scale, select=select))
        return (out, lse), (q, k, v, select, out, lse)
    return (_selected_attention(q, k, v, select, interpret, force, scale),
            (q, k, v, select, None, None))


def _selected_bwd_rule(interpret, force, scale, res, g):
    q, k, v, select, out, lse = res
    g = g[0]                       # the log-sum-exp's cotangent is dropped
    if lse is not None:
        dq, dk, dv = _flash_backward(q, k, v, out, lse, g, True,
                                     interpret=interpret, scale=scale,
                                     select=select)
    else:
        _, vjp = jax.vjp(lambda a, b, c: _selected_attention_xla(
            a, b, c, select, scale)[0], q, k, v)
        dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_selected_attention.defvjp(_selected_fwd_rule, _selected_bwd_rule)


# --------------------------------------------------- pallas backward kernel
def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                      causal: bool, blk_q: int, blk_k: int, scale: float,
                      has_mask: bool, want_dq: bool, want_dkv: bool,
                      window: int = None, n_q: int = None,
                      has_select: bool = False):
    """One (q-block, k-block) tile of the backward, for whichever of dQ and
    dK/dV the call wants — ONE body, so each score tile is recomputed, masked
    and exponentiated once for every gradient it feeds:

    * ``want_dkv`` alone: grid (batch*head, k-block, q-block); Q/dO/lse/delta
      stream one block per program, dK/dV accumulate in VMEM across the
      q-blocks.
    * ``want_dq`` alone: grid (batch*head, q-block, k-block); K/V stream, dQ
      accumulates across the k-blocks (the forward's streaming shape).
    * both (the fused backward): the dK/dV grid, and dQ for the WHOLE head
      in a (Tq, Dk) float32 scratch, each tile adding to its rows. Under a
      causal mask (``_dq_by_key_block``) the output block is one k-block's
      rows, written on the last q-block of its outer step, where those rows
      are final; otherwise it is the whole head, written on its last tile.

    The tile is S TRANSPOSED, (blk_k, blk_q): P^T = exp(S^T - lse) with
    S^T = K Q^T, dP^T = V dO^T, dS^T = P^T o (dP^T - delta), so lse and delta
    are lane-dense (1, blk_q) rows broadcast down the sublanes, dV += P^T dO
    and dK += dS^T Q are plain products, and only dQ += (dS^T)^T K contracts
    the sublane axis. The score scale of dS is applied once to the float32
    accumulators instead of to every score. Dead causal tiles are skipped
    and only tiles the diagonal crosses are masked, as in the forward. Under
    a ``window`` the streamed block axis (the innermost) is as long as the
    band is wide and counts from the outer block's first live tile: the
    diagonal's q-block where q-blocks stream (a step past the ``n_q``
    q-blocks is dead), ``_first_live`` where k-blocks do. With has_select a
    (1, blk_k, blk_q) int8 block of the selection TRANSPOSED follows the
    inputs and stands for the mask in every live tile, as in the forward."""
    rest = list(rest)
    km_ref = rest.pop(0) if has_mask else None
    selt_ref = rest.pop(0) if has_select else None
    n_out = want_dq + 2 * want_dkv
    outs, scratch = rest[:n_out], rest[n_out:]
    fused = want_dq and want_dkv
    q_axis = 2 if want_dkv else 1
    qi = pl.program_id(q_axis)
    kj = pl.program_id(3 - q_axis)
    first_q, last_q = qi == 0, qi == pl.num_programs(q_axis) - 1
    first_k, last_k = kj == 0, kj == pl.num_programs(3 - q_axis) - 1
    valid = True
    if window is not None and want_dkv:
        qi = (kj * blk_k) // blk_q + qi
        valid = qi < n_q
    elif window is not None:
        kj = _first_live(qi * blk_q, window, blk_k) + kj
    if want_dq:
        dq_ref, dq_sc = outs[0], scratch[0]

        @pl.when(jnp.logical_and(first_k, first_q) if fused else first_k)
        def _init_dq():
            dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)

    if want_dkv:
        (dk_ref, dv_ref), (dk_sc, dv_sc) = outs[-2:], scratch[-2:]

        @pl.when(first_q)
        def _init():
            dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
            dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    def _tile(masked: bool, rows, cols):
        q_blk, do_blk = q_ref[0, rows, :], do_ref[0, rows, :]
        k_blk = k_ref[0, cols, :]
        st = _dot(k_blk, q_blk, 1, 1) * scale             # (cols, rows)
        if has_mask:
            st = jnp.where(km_ref[0, cols, :] > 0, st, _NEG)   # a column
        if has_select:
            st = jnp.where(selt_ref[0, cols, rows] != 0, st, _NEG)
        elif masked:
            st = _causal_mask(st, qi * blk_q + rows.start,
                              kj * blk_k + cols.start, q_axis=1,
                              window=window)
        pt = jnp.exp(st - lse_ref[0, :, rows])            # a (1, rows) row
        if has_mask:
            # masked entries clamp to P = 0 rather than exp(S - lse): for a
            # fully key-masked row lse is ~_NEG and the exponent would
            # overflow. A causal row's lse is finite: exp underflows to 0
            pt = jnp.where(st <= _NEG, 0.0, pt)
        if want_dkv:
            dv_sc[cols, :] += _dot(pt.astype(do_blk.dtype), do_blk, 1, 0)
        dpt = _dot(v_ref[0, cols, :], do_blk, 1, 1)       # (cols, rows)
        dst = (pt * (dpt - delta_ref[0, :, rows])).astype(q_blk.dtype)
        if want_dkv:
            dk_sc[cols, :] += _dot(dst, q_blk, 1, 0)
        if want_dq:
            # the fused kernel's scratch holds every row of the head
            at = pl.ds(pl.multiple_of(qi * blk_q, blk_q) + rows.start,
                       rows.size) if fused else rows
            dq_sc[at, :] += _dot(dst, k_blk, 0, 0)        # (rows, Dk)

    _per_causal_tile(_tile, causal, qi, kj, blk_q, blk_k, window, valid)

    if want_dq and fused and dq_ref.shape[1] < dq_sc.shape[0]:
        # the output block is one k-block's rows (``_dq_by_key_block``):
        # those of the outer step, kj, which a window never remaps here
        @pl.when(last_q)
        def _finalize_dq_block():
            at = pl.ds(pl.multiple_of(kj * blk_k, blk_k), blk_k)
            dq_ref[0] = (dq_sc[at, :] * scale).astype(dq_ref.dtype)
    elif want_dq:
        @pl.when(jnp.logical_and(last_k, last_q) if fused else last_k)
        def _finalize_dq():
            dq_ref[0] = (dq_sc[...] * scale).astype(dq_ref.dtype)

    if want_dkv:
        @pl.when(last_q)
        def _finalize_dkv():
            dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _fused_bwd_fits(tq: int, tk: int, dk: int, dv: int, dtype, causal: bool,
                    blk_q: int = None, blk_k: int = None) -> bool:
    """Whether ONE backward kernel serves the shape: its whole program
    (``_flash_vmem_bytes`` at the tiles the backward takes, ``_flash_tiles``,
    with a head's whole dQ: the Tq x Dk float32 accumulator and the
    double-buffered output block, one key block's rows under a causal mask,
    ``_dq_by_key_block``, else the whole head's) fits ``_VMEM_CEILING +
    _VMEM_BUDGET``, and the kernel asks for what it counts
    (``_flash_params``). Causal, in bfloat16 at 1,024-square tiles: 4,096 x
    192/128 counts 31 MiB, 8,192 x 128 28.5, 16,384 x 128 32.5, 16,384 x
    192/128 43, 32,768 x 64 or 128 40.5, and take it; 32,768 x 192/128
    counts 59 and 65,536 x 64 56.5, and keep the dQ + dK/dV pair, whose VMEM
    does not grow with the sequence. The program and the dispatch note both
    ask this."""
    tiles = _flash_tiles(tq, tk, dk, dv, dtype, blk_q, blk_k, backward=True)
    return bool(tiles) and _flash_vmem_bytes(
        *tiles, dk, dv, jnp.dtype(dtype).itemsize, True, dq_rows=tq,
        dq_out_rows=tiles[1] if _dq_by_key_block(causal, tq, tk) else None
    ) <= _VMEM_CEILING + _VMEM_BUDGET


def _flash_backward(q, k, v, out, lse, g, causal, blk_q: int = None,
                    blk_k: int = None, interpret: bool = False,
                    key_mask: Array = None, scale: float = None,
                    fused: bool = None, window: int = None,
                    select: Array = None):
    """Tiled pallas backward from the saved forward logsumexp. key_mask:
    optional [B, Tk] {0,1} key-padding mask, same semantics as forward;
    ``v``, ``out`` and ``g`` are ``Dv`` wide, ``q`` and ``k`` ``Dk``.
    ``fused`` None: one kernel where its program, a head's whole dQ
    accumulator included, fits the VMEM a kernel may ask for
    (``_fused_bwd_fits``), else the dQ + dK/dV pair. With fewer key/value
    heads than query heads every query head reads its key/value head in
    place and writes its own part of dK and dV, which are summed over the
    group afterwards (float32).
    ``window`` and ``select`` as the forward's; the kernels read the
    selection transposed (one XLA transpose of it)."""
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    group = _head_group(q, k, v, key_mask)
    _check_window(window, causal, Tq, Tk, key_mask)
    blk_q, blk_k = _tiles_or_raise(Tq, Tk, D, Dv, q.dtype, blk_q, blk_k,
                                   backward=True)
    if fused is None:
        fused = _fused_bwd_fits(Tq, Tk, D, Dv, q.dtype, causal, blk_q, blk_k)
    scale = 1.0 / (D ** 0.5) if scale is None else scale
    qr, kr, vr = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    gr, outr = _flatten_heads(g), _flatten_heads(out)
    # delta = rowsum(dO ∘ O): one cheap fused elementwise+reduce in XLA;
    # lse and delta travel as lane-dense (B*H, 1, Tq) rows
    delta = jnp.sum(gr.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1)[:, None, :]
    has_mask = key_mask is not None
    operands = [qr, kr, vr, gr, lse[:, None, :], delta] + (
        [_bh_mask(key_mask, H)] if has_mask else []) + (
        [jnp.swapaxes(select, 1, 2)] if select is not None else [])
    nq, nk = Tq // blk_q, Tk // blk_k

    def call(want_dq: bool, want_dkv: bool):
        """One pallas_call of ``_flash_bwd_kernel`` on a (bh, a, b) grid
        whose axis ``q_pos`` walks the q-blocks and whose other block axis
        walks the k-blocks."""
        q_pos = 2 if want_dkv else 1
        whole = want_dq and want_dkv   # the fused kernel: a head's dQ in VMEM
        by_block = whole and _dq_by_key_block(causal, Tq, Tk)

        # under a causal mask a dead tile (``_causal_block_live``) names the
        # nearest live tile's streamed block again, so nothing is fetched
        # for it: the k-block is held at the diagonal where the k axis
        # streams (dQ alone), the q-block at the first live one where q does.
        # Under a window the streamed axis counts from the first live block
        # and is held at the last
        def q_idx(*g):
            i, j = g[q_pos], g[3 - q_pos]
            if causal and q_pos == 2 and window is not None:
                i = jnp.minimum(i + (j * blk_k) // blk_q, nq - 1)
            elif causal and q_pos == 2:
                i = jnp.maximum(i, (j * blk_k) // blk_q)
            return i

        def k_idx(*g):
            i, j = g[q_pos], g[3 - q_pos]
            if causal and q_pos == 1 and window is not None:
                j = j + _first_live(i * blk_q, window, blk_k)
            if causal and q_pos == 1:
                j = jnp.minimum(j, _diagonal_k_block(i, blk_q, blk_k))
            return j

        kv_row = _kv_row(group)

        def q_map(*g):
            return (g[0], q_idx(*g), 0)

        def k_map(*g):
            return (kv_row(g[0]), k_idx(*g), 0)

        def km_map(*g):
            return (g[0], k_idx(*g), 0)

        def row_map(*g):
            return (g[0], 0, q_idx(*g))

        in_specs = [pl.BlockSpec((1, blk_q, D), q_map),
                    pl.BlockSpec((1, blk_k, D), k_map),
                    pl.BlockSpec((1, blk_k, Dv), k_map),
                    pl.BlockSpec((1, blk_q, Dv), q_map),
                    pl.BlockSpec((1, 1, blk_q), row_map),
                    pl.BlockSpec((1, 1, blk_q), row_map)]
        if has_mask:
            in_specs.append(pl.BlockSpec((1, blk_k, 1), km_map))
        if select is not None:
            in_specs.append(pl.BlockSpec(
                (1, blk_k, blk_q),
                lambda *g: (g[0] // H, k_idx(*g), q_idx(*g))))
        out_specs, out_shape, scratch = [], [], []
        if want_dq:
            # the fused kernel's dQ leaves one k-block (its outer axis) at a
            # time where a causal head's rows are final block by block
            out_specs.append(
                pl.BlockSpec((1, blk_k, D), lambda bh, j, i: (bh, j, 0))
                if by_block else
                pl.BlockSpec((1, Tq, D), lambda bh, a, b: (bh, 0, 0)) if whole
                else pl.BlockSpec((1, blk_q, D), lambda bh, i, j: (bh, i, 0)))
            out_shape.append(jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype))
            scratch.append(pltpu.VMEM((Tq if whole else blk_q, D),
                                      jnp.float32))
        if want_dkv:
            out_specs += [
                pl.BlockSpec((1, blk_k, D), lambda bh, j, i: (bh, j, 0)),
                pl.BlockSpec((1, blk_k, Dv), lambda bh, j, i: (bh, j, 0))]
            out_shape += [jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
                          jax.ShapeDtypeStruct((B * H, Tk, Dv), v.dtype)]
            scratch += [pltpu.VMEM((blk_k, D), jnp.float32),
                        pltpu.VMEM((blk_k, Dv), jnp.float32)]
        # batch*head programs are independent; a block axis that carries an
        # accumulator runs in order: both of them where dQ spans the head
        semantics = ("parallel",
                     "arbitrary" if whole else "parallel",
                     "arbitrary")
        if window is None:
            grid = (B * H, nk, nq) if want_dkv else (B * H, nq, nk)
        elif want_dkv:
            grid = (B * H, nk, _window_steps(nq, blk_q, nk, blk_k, window,
                                             False))
        else:
            grid = (B * H, nq, _window_steps(nq, blk_q, nk, blk_k, window,
                                             True))
        return pl.pallas_call(
            functools.partial(
                _flash_bwd_kernel, causal=causal, blk_q=blk_q, blk_k=blk_k,
                scale=scale, has_mask=has_mask, want_dq=want_dq,
                want_dkv=want_dkv, window=window, n_q=nq,
                has_select=select is not None),
            grid=grid,
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch,
            compiler_params=_flash_params(semantics, _flash_vmem_bytes(
                blk_q, blk_k, D, Dv, q.dtype.itemsize, True,
                dq_rows=Tq if whole else 0,
                dq_out_rows=blk_k if by_block else None)),
            interpret=interpret,
        )(*operands)

    if fused:
        dq, dk, dv = call(True, True)
    else:
        (dq,), (dk, dv) = call(True, False), call(False, True)
    if group > 1:
        dk, dv = (a.reshape(B * H // group, group, Tk, a.shape[-1])
                  .astype(jnp.float32).sum(1).astype(a.dtype)
                  for a in (dk, dv))
    return (_unflatten_heads(dq, B, H), _unflatten_heads(dk, B, H // group),
            _unflatten_heads(dv, B, H // group))


def _attention_bwd_chunked(q, k, v, g, causal, blk_q: int = None,
                           scale: float = None, window: int = None):
    """Chunked attention backward: lax.scan over query blocks, recomputing the
    (blk_q, Tk) score tile per step. dK/dV accumulate in f32 in the carry.

    Standard flash-attention backward identities: with P = softmax(S),
    dV = Pᵀ dO, dP = dO Vᵀ, dS = P ∘ (dP − rowsum(P ∘ dP)), dQ = dS·K·scale,
    dK = dSᵀ·Q·scale. Query rows padded up to a block multiple carry dO = 0,
    which makes their dS exactly 0, so padding contributes nothing.
    None blk_q -> 128 rows. Grouped key/value heads are repeated here and
    their gradients summed over the group; ``window`` as the forward's.
    """
    blk_q = blk_q or 128
    B, Tq, H, D = q.shape
    Tk, Dv = k.shape[1], v.shape[-1]
    _check_window(window, causal, Tq, Tk)
    kv_heads, (k, v) = k.shape[2], _repeat_kv(q, k, v)
    scale = 1.0 / (D ** 0.5) if scale is None else scale
    blk_q = min(blk_q, Tq)
    pad = (-Tq) % blk_q
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    gp = jnp.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else g
    n = (Tq + pad) // blk_q
    # (n, B, blk_q, H, D) chunk-major for scan
    qs = qp.reshape(B, n, blk_q, H, D).transpose(1, 0, 2, 3, 4)
    gs = gp.reshape(B, n, blk_q, H, Dv).transpose(1, 0, 2, 3, 4)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def chunk(carry, inp):
        dk, dv = carry
        qc, gc, idx = inp
        qc = qc.astype(jnp.float32)
        gc = gc.astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kf) * scale
        if causal:
            q_pos = idx * blk_q + jnp.arange(blk_q)
            mask = q_pos[:, None] >= jnp.arange(Tk)[None, :]
            if window is not None:
                mask = jnp.logical_and(
                    mask, q_pos[:, None] - jnp.arange(Tk)[None, :] < window)
            s = jnp.where(mask[None, None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        dp = jnp.einsum("bqhd,bkhd->bhqk", gc, vf)
        delta = jnp.sum(p * dp, axis=-1, keepdims=True)
        ds = p * (dp - delta) * scale
        dqc = jnp.einsum("bhqk,bkhd->bqhd", ds, kf)
        dk = dk + jnp.einsum("bhqk,bqhd->bkhd", ds, qc)
        dv = dv + jnp.einsum("bhqk,bqhd->bkhd", p, gc)
        return (dk, dv), dqc

    # derive the accumulator zeros from k/v (not fresh arrays) so their
    # device-varying annotation matches inside shard_map bodies
    (dk, dv), dqs = jax.lax.scan(
        chunk, ((kf * 0.0), (vf * 0.0)), (qs, gs, jnp.arange(n)))
    dq = dqs.transpose(1, 0, 2, 3, 4).reshape(B, Tq + pad, H, D)[:, :Tq]
    if kv_heads != H:
        dk, dv = (a.reshape(B, Tk, kv_heads, H // kv_heads, a.shape[-1])
                  .sum(3) for a in (dk, dv))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


#: shortest sequence the tiled pallas BACKWARD engages at (the forward has
#: its own _MIN_SEQ gate). Below this the chunked lax.scan backward wins —
#: the dq+dkv kernel pair's fixed overhead amortizes only on long
#: sequences. Calibrated 2026-07-31 on a v5e (512-wide K tiles), record not
#: kept; re-derive in a cell (ROADMAP S5).
_PBWD_MIN_SEQ = int(os.environ.get("DL4J_FLASH_PBWD_MIN_SEQ", "4096"))


def _pallas_bwd_enabled(seq_k: int = None, force: bool = False) -> bool:
    env = os.environ.get("DL4J_FLASH_PALLAS_BWD")
    if env is not None:
        return env != "0"
    return force or seq_k is None or seq_k >= _PBWD_MIN_SEQ


def _flash_fwd_rule(q, k, v, causal, interpret, force, scale, window):
    tiled_bwd = (_pallas_ok(q, k, interpret, force)
                 and _pallas_bwd_enabled(k.shape[1], force))
    variant = _variant(q, k, window)
    _note_dispatch("flash_attention_bwd" + variant, tiled_bwd)
    # which backward the program holds: one kernel, or the dQ + dK/dV pair
    _note_dispatch("flash_attention_bwd_fused" + variant,
                   tiled_bwd and _fused_bwd_fits(
                       q.shape[1], k.shape[1], q.shape[-1], v.shape[-1],
                       q.dtype, causal))
    if tiled_bwd:
        _note_dispatch("flash_attention" + variant, True)
        out, lse = _kept(*_flash_forward(q, k, v, causal,
                                         interpret=interpret, scale=scale,
                                         window=window))
        return out, (q, k, v, out, lse)
    return (_flash_attention(q, k, v, causal, interpret, force, scale,
                             window),
            (q, k, v, None, None))


def _flash_bwd_rule(causal, interpret, force, scale, window, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        return _flash_backward(q, k, v, out, lse, g, causal,
                               interpret=interpret, scale=scale,
                               window=window)
    return _attention_bwd_chunked(q, k, v, g, causal, scale=scale,
                                  window=window)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ------------------------------------------------------- fused softmax-xent
def _sm_xent_kernel(logits_ref, labels_ref, loss_ref, grad_ref):
    """Row-fused log-softmax + NLL + gradient: one pass over the logits block."""
    x = logits_ref[:].astype(jnp.float32)
    y = labels_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=1, keepdims=True)
    e = jnp.exp(x - m)
    z = jnp.sum(e, axis=1, keepdims=True)
    logp = x - m - jnp.log(z)
    loss_ref[:] = -jnp.sum(y * logp, axis=1, keepdims=True).astype(loss_ref.dtype)
    grad_ref[:] = (e / z - y).astype(grad_ref.dtype)


def _xent_rows(n: int, c: int, dtype):
    """Rows per program of the fused softmax-xent kernel, or None when no
    row block fits — the gate then leaves the shape to XLA.

    One program holds three (rows, C) blocks double-buffered by the
    pipeline (logits, labels in; grad out) plus two f32 temporaries of the
    same extent, C padded to the 128-lane tile: ``rows * C_pad * (6 *
    itemsize + 8)`` bytes — fitted to what the v5e compiler accepts and
    refuses (tests/test_tpu_aot_compile.py pins both sides). Rows stay a
    multiple of the dtype's sublane tile, or the whole (small) batch."""
    itemsize = jnp.dtype(dtype).itemsize
    per_row = -(-c // 128) * 128 * (6 * itemsize + 8)
    sublane = 8 * 4 // itemsize
    rows = [n] if n <= 256 else []
    rows += [b for b in (256, 128, 64, 32, 16, 8)
             if b < n and n % b == 0 and b % sublane == 0]
    for b in rows:
        if b * per_row <= _VMEM_BUDGET:
            return b
    return None


def softmax_cross_entropy(logits: Array, labels: Array,
                          interpret: bool = False):
    """Fused per-row loss + dlogits. Returns (loss (N,), grad (N, C)).
    Pallas on TPU where a row block of the class axis fits VMEM
    (:func:`_xent_rows`); identical XLA math elsewhere.

    Under a shard_map trace (non-empty vma on the operands — e.g.
    ParallelWrapper's local-SGD per-replica step) the pallas_call is skipped
    in favor of the XLA math: the vma checker rejects the kernel's
    out_shape and the interpret lowering its internal while_loop carry, and
    XLA fuses this row-wise chain well anyway."""
    N, C = logits.shape
    blk = _xent_rows(N, C, logits.dtype)
    engaged = ((use_pallas() or interpret) and blk is not None
               and not _in_shard_map())
    _note_dispatch("softmax_cross_entropy", engaged)
    if engaged:
        loss, grad = pl.pallas_call(
            _sm_xent_kernel,
            grid=(N // blk,),
            in_specs=[
                pl.BlockSpec((blk, C), lambda i: (i, 0)),
                pl.BlockSpec((blk, C), lambda i: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                pl.BlockSpec((blk, C), lambda i: (i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((N, 1), jnp.float32),
                jax.ShapeDtypeStruct((N, C), logits.dtype),
            ],
            interpret=interpret,
        )(logits, labels)
        return loss[:, 0], grad
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    loss = -jnp.sum(labels * logp, axis=-1)
    grad = (jnp.exp(logp) - labels).astype(logits.dtype)
    return loss, grad


# ----------------------------------------------- fused batch-norm statistics
def _add2(acc, val):
    return acc[0] + val[0], acc[1] + val[1]


def batch_norm_stats(x: Array, axes, stat_dtype):
    """Single-pass batch statistics: (mean, biased var) over ``axes``.

    ONE variadic ``lax.reduce`` accumulates sum(x) and sum(x*x) together, so
    the whole computation is a single fused pass over the tensor — unlike
    ``jnp.mean`` + ``jnp.var``, which lowers to two full passes (the second
    re-reading x to form (x - mean)^2) with a standalone f32 upcast-reduce
    fusion each on the bf16 path (23% of ResNet-50 device time, r5 profile).

    ``stat_dtype`` is the reduce operand/accumulator dtype
    (DtypePolicy.reduction_dtype): bf16 keeps the pass convert-free on bf16
    activations; f32/f64 inserts one fused upcast prologue. var clamps at 0
    against E[x^2]-mean^2 cancellation noise.
    """
    n = 1
    for a in axes:
        n *= x.shape[a]
    xs = x.astype(stat_dtype)
    zero = jnp.zeros((), stat_dtype)
    s1, s2 = jax.lax.reduce((xs, xs * xs), (zero, zero), _add2, tuple(axes))
    inv_n = jnp.asarray(1.0 / n, stat_dtype)
    mean = s1 * inv_n
    var = jnp.maximum(s2 * inv_n - mean * mean, jnp.zeros((), stat_dtype))
    return mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def batch_norm_train(x: Array, gamma: Array, beta: Array, axes, eps,
                     stat_dtype):
    """Train-mode batch norm with policy-controlled reduction precision.

    Returns ``(out, mean, var)``; ``axes`` are the leading statistic axes
    (channel axis trailing, reference BN convention). Forward: single-pass
    stats (:func:`batch_norm_stats`) + a folded ``x * scale + shift``
    elementwise pass in x.dtype — no full-tensor upcast. Backward
    (hand-written): dgamma/dbeta in ONE variadic reduce pass, dx as one
    elementwise pass, instead of autodiff's mean/var chains (several
    standalone f32 reduce fusions on the bf16 path).

    The ``mean``/``var`` outputs exist for the EMA running-state update and
    are treated as NON-differentiable — their cotangents are discarded, so
    do not differentiate through them.
    """
    out, mean, var = _bn_train_impl(x, gamma, beta, axes, eps, stat_dtype)
    return out, mean, var


def _bn_train_impl(x, gamma, beta, axes, eps, stat_dtype):
    mean, var = batch_norm_stats(x, axes, stat_dtype)
    # inv in f32-at-least: rsqrt of a bf16 var costs accuracy on a
    # channel-sized vector for no bandwidth win
    wide = jnp.promote_types(stat_dtype, jnp.float32)
    inv = jax.lax.rsqrt(var.astype(wide) + eps)
    scale = gamma.astype(wide) * inv
    shift = beta.astype(wide) - mean.astype(wide) * scale
    out = x * scale.astype(x.dtype) + shift.astype(x.dtype)
    return out, mean, var


def _bn_train_fwd(x, gamma, beta, axes, eps, stat_dtype):
    out, mean, var = _bn_train_impl(x, gamma, beta, axes, eps, stat_dtype)
    wide = jnp.promote_types(stat_dtype, jnp.float32)
    inv = jax.lax.rsqrt(var.astype(wide) + eps)
    return (out, mean, var), (x, gamma, mean, inv)


def _bn_train_bwd(axes, eps, stat_dtype, res, cts):
    x, gamma, mean, inv = res
    dy = cts[0]  # mean/var cotangents: EMA plumbing only, not differentiated
    n = 1
    for a in axes:
        n *= x.shape[a]
    wide = inv.dtype
    xhat = (x - mean.astype(x.dtype)) * inv.astype(x.dtype)
    # dbeta = sum(dy), dgamma = sum(dy * xhat): one fused variadic pass in
    # the policy reduction dtype, same shape discipline as the forward stats
    t1 = dy.astype(stat_dtype)
    t2 = (dy * xhat).astype(stat_dtype)
    zero = jnp.zeros((), stat_dtype)
    dbeta, dgamma = jax.lax.reduce((t1, t2), (zero, zero), _add2,
                                   tuple(axes))
    k = gamma.astype(wide) * inv
    inv_n = 1.0 / n
    dx = k.astype(x.dtype) * (
        dy - (dbeta.astype(wide) * inv_n).astype(x.dtype)
        - xhat * (dgamma.astype(wide) * inv_n).astype(x.dtype))
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


batch_norm_train.defvjp(_bn_train_fwd, _bn_train_bwd)
