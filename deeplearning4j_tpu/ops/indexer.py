"""A learned choice of the keys each query attends to: the kernels beside
the flash core that a sparse-attention indexer needs, with XLA fallbacks.

An indexer scores every causal (query, key) pair with a few narrow heads
over ONE shared key head,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j, :] . kI[s, :]),

keeps for each query the ``topk`` keys with the largest scores (all of them
while ``t + 1 <= topk``), and the attention core runs over those alone
(``pallas_kernels.flash_attention(select=...)``). The indexer learns from the
core: its loss is the divergence of the core's own head-averaged
probabilities over the chosen keys from the softmax of its scores there.

* :func:`index_scores` — ``I`` as a float32 ``[B, T, T]`` matrix; one Pallas
  program a (query tile, key tile), every head's product accumulated in
  VMEM, so the ``[J, T, T]`` per-head scores are never in HBM. Entries above
  the diagonal are unspecified.
* :func:`select_topk` — the exact selection as an int8 ``[B, T, T]`` 0/1
  matrix with exactly ``min(t + 1, topk)`` ones a row, ties to the lower
  key, and the log-sum-exp of each row's chosen scores. The k-th largest is
  found by bisection over the scores' bit patterns (32 counting passes over
  a block of rows held in VMEM), the tie cut by bisection over the key
  index; nothing is sorted.
* :func:`index_kl` — ``mean_t KL(p[t, .] || softmax_{chosen} I[t, .])`` with
  ``p`` the mean over the query heads of the core's probabilities, which a
  kernel makes again from the core's saved log-sum-exp, a head at a time
  into a VMEM tile. Its gradient reaches ``qI``, ``kI`` and ``w`` alone
  (two kernels over the tiles, the per-head scores made again).

On a CPU (or where the shapes do not tile) each runs the same math in XLA,
which materialises what the kernels avoid; tests ask for interpret mode by
name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import pallas_kernels as pk

Array = jax.Array
_INT_MIN = -(2 ** 31)
#: bytes of VMEM a selection program's block of score rows may take (the
#: keys' scratch is as large again, the pipeline holds two of the block)
_SELECT_BLOCK_BYTES = 8 * 1024 * 1024
#: lanes of a row a counting pass reads at a time
_SELECT_CHUNK = 2048
#: bytes of VMEM a program over one score tile plans for: a few float32
#: copies of the 512 x 1,024 tile beside its double-buffered blocks
_TILE_VMEM = 24 * 1024 * 1024


def _ok(t: int, interpret: bool, like=None) -> bool:
    """The gate of every kernel here: a device (or interpret mode), a
    sequence the tiles divide, and no vma-checked shard_map around."""
    if not ((pk.use_pallas() or interpret) and pk._tileable(t, t)):
        return False
    return like is None or not pk._in_checked_shard_map(like)


def _tiles(t: int) -> tuple:
    """(query rows, key columns) of a score tile: 512 x 1,024 where the
    sequence allows, so a float32 tile and its accumulator are 2 MiB each."""
    return pk._pick_blk(t, 512), pk._pick_blk(t, 1024)


def _diag(i, blk_q: int, blk_k: int):
    return pk._diagonal_k_block(i, blk_q, blk_k)


# ------------------------------------------------------------ index scores
def index_scores_xla(qi: Array, ki: Array, w: Array) -> Array:
    """The statement of the math: ``[B, T, T]`` float32, every pair."""
    s = jnp.einsum("btje,bse->bjts", qi, ki,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bjts,btj->bts", jnp.maximum(s, 0.0),
                      w.astype(jnp.float32))


def _scores_kernel(qi_ref, ki_ref, w_ref, o_ref, *, heads: int, blk_q: int,
                   blk_k: int):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(pk._causal_block_live(i, j, blk_q, blk_k))
    def _tile():
        k = ki_ref[0]
        acc = jnp.zeros((blk_q, blk_k), jnp.float32)
        for h in range(heads):
            s = pk._dot(qi_ref[h], k, 1, 1)
            acc = acc + w_ref[0, :, h:h + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc


def _by_head(a: Array) -> Array:
    """[B, T, J, E] -> [B * J, T, E]: a program reads its J heads as one
    block of leading rows."""
    B, T, J, E = a.shape
    return a.transpose(0, 2, 1, 3).reshape(B * J, T, E)


def index_scores(qi: Array, ki: Array, w: Array, *,
                 interpret: bool = False) -> Array:
    """``I[b, t, s] = sum_j w[b, t, j] relu(qi[b, t, j] . ki[b, s])`` for
    ``s <= t``, float32 accumulation; entries above the diagonal are
    unspecified (the kernel leaves dead tiles unwritten). qi [B, T, J, E],
    ki [B, T, E] (one key head), w [B, T, J]. A value without a gradient:
    the selection takes none, and :func:`index_kl` brings the scores' own
    backward."""
    B, T, J, E = qi.shape
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    ok = _ok(T, interpret, qi)
    pk._note_dispatch("index_scores", ok)
    if not ok:
        return index_scores_xla(qi, ki, w)
    blk_q, blk_k = _tiles(T)
    col = lambda b, i, j: (b, i, jnp.minimum(j, _diag(i, blk_q, blk_k)))
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=J, blk_q=blk_q, blk_k=blk_k),
        grid=(B, T // blk_q, T // blk_k),
        in_specs=[
            pl.BlockSpec((J, blk_q, E), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, E), lambda b, i, j: (
                b, jnp.minimum(j, _diag(i, blk_q, blk_k)), 0)),
            pl.BlockSpec((1, blk_q, J), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, blk_k), col),
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.float32),
        compiler_params=pk._flash_params(
            ("parallel", "parallel", "arbitrary"), 0),
        interpret=interpret,
    )(_by_head(qi), ki, w.astype(jnp.float32))


# ---------------------------------------------------------------- selection
def select_topk_xla(scores: Array, topk: int) -> tuple:
    """The statement of the selection: ``lax.top_k`` over each causal row
    (equal scores: the lower key first), the first ``min(t + 1, topk)``
    kept."""
    B, T, _ = scores.shape
    k = min(topk, T)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    x = jnp.where(causal, scores.astype(jnp.float32), -jnp.inf)
    _, idx = jax.lax.top_k(x, k)                              # [B, T, k]
    keep = jnp.arange(k)[None, :] < jnp.minimum(jnp.arange(T) + 1, k)[:, None]
    sel = jnp.zeros((B, T, T), jnp.int8).at[
        jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None], idx
    ].max(jnp.broadcast_to(keep, (B, T, k)).astype(jnp.int8))
    lse = jax.nn.logsumexp(jnp.where(sel > 0, x, -jnp.inf), axis=-1)
    return sel, lse


def _select_kernel(x_ref, sel_ref, lse_ref, key_sc, *, rows: int, seq: int,
                   chunk: int, topk: int):
    """One block of ``rows`` queries against all ``seq`` keys. ``key_sc``
    holds each score as the int32 whose order is the float's (``b ^
    0x7fffffff`` for a negative one), INT_MIN above the diagonal. The k-th
    largest key ``v`` is built bit by bit, the largest value that at least k
    keys reach; then the keys are rewritten in place as -1 (above ``v``),
    their column (equal to ``v``) or INT_MAX (below), and the cut ``c`` is
    the largest column with fewer than k keys under it: chosen is ``key <=
    c``. Only the chunks of columns that hold a causal key are read."""
    r0 = pl.program_id(1) * rows
    n_live = (r0 + rows + chunk - 1) // chunk
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    want = jnp.minimum(row + 1, topk)
    at = lambda c: pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    cols = lambda c: (c * chunk
                      + jax.lax.broadcasted_iota(jnp.int32, (rows, chunk), 1))

    def to_keys(c, _):
        b = pltpu.bitcast(x_ref[0, :, at(c)], jnp.int32)
        key = jnp.where(b < 0, b ^ jnp.int32(0x7fffffff), b)
        key_sc[:, at(c)] = jnp.where(cols(c) <= row, key, jnp.int32(_INT_MIN))
        return 0

    jax.lax.fori_loop(0, n_live, to_keys, 0)

    def count(pred):
        def body(c, acc):
            hit = pred(key_sc[:, at(c)]).astype(jnp.int32)
            return acc + jnp.sum(hit, axis=1, keepdims=True)
        return jax.lax.fori_loop(0, n_live, body,
                                 jnp.zeros((rows, 1), jnp.int32))

    def raise_bit(n, lo):
        cand = lo + jnp.left_shift(jnp.int32(1), 30 - n)
        return jnp.where(count(lambda k: k >= cand) >= want, cand, lo)

    sign = jnp.where(count(lambda k: k >= 0) >= want, 0, _INT_MIN)
    v = jax.lax.fori_loop(0, 31, raise_bit, sign.astype(jnp.int32))

    def to_ranks(c, _):
        key = key_sc[:, at(c)]
        key_sc[:, at(c)] = jnp.where(
            key > v, -1, jnp.where(key == v, cols(c),
                                   jnp.int32(2 ** 31 - 1)))
        return 0

    jax.lax.fori_loop(0, n_live, to_ranks, 0)
    bits = max(int(seq - 1).bit_length(), 1)

    def raise_cut(n, lo):
        cand = lo + jnp.left_shift(jnp.int32(1), bits - 1 - n)
        return jnp.where(count(lambda k: k < cand) < want, cand, lo)

    cut = jax.lax.fori_loop(0, bits, raise_cut,
                            jnp.zeros((rows, 1), jnp.int32))

    def write(c, carry):
        m, l = carry
        chosen = key_sc[:, at(c)] <= cut
        sel_ref[0, :, at(c)] = chosen.astype(jnp.int8)
        x = jnp.where(chosen, x_ref[0, :, at(c)], pk._NEG)
        m_new = jnp.maximum(m, jnp.max(x, axis=1, keepdims=True))
        p = jnp.where(chosen, jnp.exp(x - m_new), 0.0)
        return m_new, l * jnp.exp(m - m_new) + jnp.sum(p, axis=1,
                                                       keepdims=True)

    m, l = jax.lax.fori_loop(
        0, n_live, write, (jnp.full((rows, 1), pk._NEG, jnp.float32),
                           jnp.zeros((rows, 1), jnp.float32)))
    lse_ref[0] = m + jnp.log(l)

    def blank(c, _):
        sel_ref[0, :, at(c)] = jnp.zeros((rows, chunk), jnp.int8)
        return 0

    jax.lax.fori_loop(n_live, seq // chunk, blank, 0)


def _select_rows(seq: int) -> int:
    """Rows of a selection program's block: as many as ``_SELECT_BLOCK_BYTES``
    of float32 scores hold, a standard tile (at least 32, an int8 tile's
    sublanes)."""
    fit = max(_SELECT_BLOCK_BYTES // (4 * seq), 32)
    return next((r for r in (256, 128, 64, 32) if r <= fit and seq % r == 0),
                None)


def select_topk(scores: Array, topk: int, *, interpret: bool = False) -> tuple:
    """-> (``select`` int8 [B, T, T], ``lse`` float32 [B, T]): row t of
    ``select`` holds exactly ``min(t + 1, topk)`` ones, at the keys ``s <=
    t`` with the largest ``scores[b, t, s]`` (equal scores: the lower key),
    zeros elsewhere; ``lse[b, t]`` is the log-sum-exp of the chosen scores.
    Entries of ``scores`` above the diagonal are never read. No gradient.
    ``DecoderBlock`` tags the two results ``attn_select`` and
    ``attn_select_lse`` (``ops/remat.py``): a checkpointed layer keeps them,
    B x T x T bytes and B x T float32, and does not select a second time."""
    B, T, _ = scores.shape
    scores = jax.lax.stop_gradient(scores)
    rows = _select_rows(T)
    chunk = pk._pick_blk(T, _SELECT_CHUNK)
    ok = _ok(T, interpret, scores) and rows is not None and bool(chunk)
    pk._note_dispatch("select_topk", ok)
    if not ok:
        return select_topk_xla(scores, topk)
    need = rows * T * (3 * 4 + 2)       # two input blocks, the keys, int8 out
    sel, lse = pl.pallas_call(
        functools.partial(_select_kernel, rows=rows, seq=T, chunk=chunk,
                          topk=int(topk)),
        grid=(B, T // rows),
        in_specs=[pl.BlockSpec((1, rows, T), lambda b, i: (b, i, 0))],
        out_specs=[pl.BlockSpec((1, rows, T), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, rows, 1), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, T), jnp.int8),
                   jax.ShapeDtypeStruct((B, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, T), jnp.int32)],
        compiler_params=pk._flash_params(("parallel", "parallel"), need),
        interpret=interpret,
    )(scores.astype(jnp.float32))
    return sel, lse[:, :, 0]


# ------------------------------------------------------- the indexer's loss
def selected_probs_xla(q: Array, k: Array, lse: Array, select: Array,
                       scale: float) -> Array:
    """``p[b, t, s]``: the mean over the query heads of ``exp(q . k * scale
    - lse)`` at the chosen pairs, 0 elsewhere. q [B, T, H, D], k [B, T, G,
    D], lse [B * H, T]."""
    B, T, H, _ = q.shape
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse.reshape(B, H, T, 1))
    return jnp.mean(jnp.where(select[:, None] > 0, p, 0.0), axis=1)


def _kl_rows(p, scores, select, lse_i):
    """Per query, ``sum_s p (log p - log softmax_chosen(I))`` and its
    gradient in ``I`` (``softmax_chosen(I) - p`` where ``p`` sums to 1)."""
    chosen = select > 0
    logq = scores - lse_i
    kl = jnp.sum(jnp.where(jnp.logical_and(chosen, p > 0),
                           p * (jnp.log(jnp.maximum(p, 1e-37)) - logq), 0.0),
                 axis=-1, keepdims=True)
    return kl, jnp.where(chosen, jnp.exp(logq) - p, 0.0)


def index_kl_xla(qi, ki, w, select, q, k, lse, scale):
    scores = index_scores_xla(qi, ki, w)
    chosen = select > 0
    lse_i = jax.nn.logsumexp(jnp.where(chosen, scores, -jnp.inf), axis=-1,
                             keepdims=True)
    p = jax.lax.stop_gradient(selected_probs_xla(q, k, lse, select, scale))
    return jnp.mean(_kl_rows(p, scores, select, lse_i)[0])


def _kl_kernel(q_ref, k_ref, lse_ref, sel_ref, x_ref, lsei_ref, g_ref,
               rows_ref, acc_sc, *, heads: int, blk_q: int, blk_k: int,
               scale: float):
    """One (query tile, key tile, query head) program: the head's
    probabilities at the chosen pairs are added to the tile in VMEM; the
    last head turns the tile into its rows' part of the divergence and the
    gradient in the index scores."""
    i, j, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    live = pk._causal_block_live(i, j, blk_q, blk_k)
    first, last = h == 0, h == heads - 1

    @pl.when(jnp.logical_and(j == 0, first))
    def _init_rows():
        rows_ref[0] = jnp.zeros((blk_q, 1), jnp.float32)

    @pl.when(jnp.logical_and(live, first))
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(live)
    def _head():
        s = pk._dot(q_ref[0], k_ref[0], 1, 1) * scale
        p = jnp.exp(s - lse_ref[0])
        acc_sc[...] += jnp.where(sel_ref[0] != 0, p, 0.0)

    @pl.when(jnp.logical_and(live, last))
    def _finish():
        kl, g = _kl_rows(acc_sc[...] * (1.0 / heads), x_ref[0],
                         sel_ref[0].astype(jnp.int32), lsei_ref[0])
        rows_ref[0] += kl
        g_ref[0] = g.astype(g_ref.dtype)


def _kl_forward(scores, select, lse_i, q, k, lse, scale, interpret):
    """-> (kl [B, T] per query, dI [B, T, T] in q's dtype)."""
    B, T, H, D = q.shape
    G = k.shape[2]
    blk_q, blk_k = _tiles(T)
    col = lambda i, j: jnp.minimum(j, _diag(i, blk_q, blk_k))
    tile = lambda b, i, j, h: (b, i, col(i, j))
    row = lambda b, i, j, h: (b, i, 0)
    g, rows = pl.pallas_call(
        functools.partial(_kl_kernel, heads=H, blk_q=blk_q, blk_k=blk_k,
                          scale=scale),
        grid=(B, T // blk_q, T // blk_k, H),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j, h: (b * H + h, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j, h: (
                b * G + h // (H // G), col(i, j), 0)),
            pl.BlockSpec((1, blk_q, 1), lambda b, i, j, h: (b * H + h, i, 0)),
            pl.BlockSpec((1, blk_q, blk_k), tile),
            pl.BlockSpec((1, blk_q, blk_k), tile),
            pl.BlockSpec((1, blk_q, 1), row),
        ],
        out_specs=[pl.BlockSpec((1, blk_q, blk_k), tile),
                   pl.BlockSpec((1, blk_q, 1), row)],
        out_shape=[jax.ShapeDtypeStruct((B, T, T), q.dtype),
                   jax.ShapeDtypeStruct((B, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk_q, blk_k), jnp.float32)],
        compiler_params=pk._flash_params(
            ("parallel", "parallel", "arbitrary", "arbitrary"), _TILE_VMEM),
        interpret=interpret,
    )(pk._flatten_heads(q), pk._flatten_heads(k), lse[:, :, None], select,
      scores, lse_i[:, :, None])
    return rows[:, :, 0], g


def _scores_bwd_q_kernel(qi_ref, ki_ref, w_ref, g_ref, dq_ref, dw_ref, dq_sc,
                         dw_sc, *, heads: int, blk_q: int, blk_k: int):
    """dqI and dw of one query tile, the key tiles streamed: per head ``s =
    qI kI^T`` again, ``dw += rowsum(g relu(s))``, ``dqI += (g w [s > 0])
    kI``."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)
        dw_sc[...] = jnp.zeros(dw_sc.shape, jnp.float32)

    @pl.when(pk._causal_block_live(i, j, blk_q, blk_k))
    def _tile():
        k = ki_ref[0]
        g = g_ref[0].astype(jnp.float32)
        for h in range(heads):
            s = pk._dot(qi_ref[h], k, 1, 1)
            dw_sc[:, h:h + 1] += jnp.sum(g * jnp.maximum(s, 0.0), axis=1,
                                         keepdims=True)
            ds = jnp.where(s > 0, g * w_ref[0, :, h:h + 1], 0.0)
            dq_sc[h] += pk._dot(ds.astype(k.dtype), k, 1, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_sc[...]


def _scores_bwd_k_kernel(qi_ref, ki_ref, wt_ref, gt_ref, dk_ref, dk_sc, *,
                         heads: int, blk_q: int, blk_k: int):
    """dkI of one key tile, the query tiles streamed, on the TRANSPOSED
    tile (keys down, queries across: ``w`` is a lane-dense row and ``dkI +=
    ds^T qI`` a plain product): ``s^T = kI qI^T``, ``ds^T = g^T w [s^T >
    0]``."""
    j, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)

    @pl.when(pk._causal_block_live(i, j, blk_q, blk_k))
    def _tile():
        k = ki_ref[0]
        gt = gt_ref[0].astype(jnp.float32)
        for h in range(heads):
            q = qi_ref[h]
            st = pk._dot(k, q, 1, 1)
            dst = jnp.where(st > 0, gt * wt_ref[0, h:h + 1, :], 0.0)
            dk_sc[...] += pk._dot(dst.astype(q.dtype), q, 1, 0)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)


def _scores_backward(qi, ki, w, g, interpret):
    """-> (dqi, dki, dw) of ``sum(g * index_scores(qi, ki, w))``; ``g`` is 0
    above the diagonal of every tile the diagonal crosses, and tiles wholly
    above it are never read."""
    B, T, J, E = qi.shape
    blk_q, blk_k = _tiles(T)
    qr, w = _by_head(qi), w.astype(jnp.float32)
    col = lambda i, j: jnp.minimum(j, _diag(i, blk_q, blk_k))
    dq, dw = pl.pallas_call(
        functools.partial(_scores_bwd_q_kernel, heads=J, blk_q=blk_q,
                          blk_k=blk_k),
        grid=(B, T // blk_q, T // blk_k),
        in_specs=[
            pl.BlockSpec((J, blk_q, E), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, E), lambda b, i, j: (b, col(i, j), 0)),
            pl.BlockSpec((1, blk_q, J), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, blk_k),
                         lambda b, i, j: (b, i, col(i, j))),
        ],
        out_specs=[pl.BlockSpec((J, blk_q, E), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, blk_q, J), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * J, T, E), qi.dtype),
                   jax.ShapeDtypeStruct((B, T, J), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((J, blk_q, E), jnp.float32),
                        pltpu.VMEM((blk_q, J), jnp.float32)],
        compiler_params=pk._flash_params(
            ("parallel", "parallel", "arbitrary"), _TILE_VMEM),
        interpret=interpret,
    )(qr, ki, w, g)
    # the first query tile that sees key tile j: the diagonal's
    row = lambda j, i: jnp.maximum(i, (j * blk_k) // blk_q)
    dk = pl.pallas_call(
        functools.partial(_scores_bwd_k_kernel, heads=J, blk_q=blk_q,
                          blk_k=blk_k),
        grid=(B, T // blk_k, T // blk_q),
        in_specs=[
            pl.BlockSpec((J, blk_q, E), lambda b, j, i: (b, row(j, i), 0)),
            pl.BlockSpec((1, blk_k, E), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, J, blk_q), lambda b, j, i: (b, 0, row(j, i))),
            pl.BlockSpec((1, blk_k, blk_q),
                         lambda b, j, i: (b, j, row(j, i))),
        ],
        out_specs=pl.BlockSpec((1, blk_k, E), lambda b, j, i: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, E), ki.dtype),
        scratch_shapes=[pltpu.VMEM((blk_k, E), jnp.float32)],
        compiler_params=pk._flash_params(
            ("parallel", "parallel", "arbitrary"), _TILE_VMEM),
        interpret=interpret,
    )(qr, ki, jnp.swapaxes(w, 1, 2), jnp.swapaxes(g, 1, 2))
    dq = dq.reshape(B, J, T, E).transpose(0, 2, 1, 3)
    return dq, dk, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _index_kl_kernels(qi, ki, w, scores, select, lse_i, q, k, lse, scale,
                      interpret):
    return _index_kl_fwd(qi, ki, w, scores, select, lse_i, q, k, lse, scale,
                         interpret)[0]


def _index_kl_fwd(qi, ki, w, scores, select, lse_i, q, k, lse, scale,
                  interpret):
    rows, g = _kl_forward(scores, select, lse_i, q, k, lse, scale, interpret)
    return jnp.mean(rows), (qi, ki, w, g)


def _index_kl_bwd(scale, interpret, res, ct):
    """The three gradients are linear in ``g``: the mean's and the
    cotangent's factor goes onto them, not over the [T, T] matrix."""
    qi, ki, w, g = res
    factor = ct / (g.shape[0] * g.shape[1])
    dqi, dki, dw = _scores_backward(qi, ki, w, g, interpret)
    return ((dqi.astype(jnp.float32) * factor).astype(dqi.dtype),
            (dki.astype(jnp.float32) * factor).astype(dki.dtype),
            (dw * factor).astype(w.dtype)) + (None,) * 6


_index_kl_kernels.defvjp(_index_kl_fwd, _index_kl_bwd)


def index_kl(qi: Array, ki: Array, w: Array, scores: Array, select: Array,
             lse_i: Array, q: Array, k: Array, lse: Array, scale: float, *,
             interpret: bool = False) -> Array:
    """The indexer's loss of one layer, a scalar: the mean over the batch's
    queries of ``KL(p[t, .] || softmax_{chosen} I[t, .])`` with ``p[t, s]``
    the mean over the query heads of the core's probabilities ``exp(q . k *
    scale - lse)`` at the chosen pairs (a constant: no gradient reaches
    ``q``, ``k`` or ``lse``) and ``I`` the index scores of ``qi``, ``ki``,
    ``w``, which alone get a gradient. ``scores``, ``select`` and ``lse_i``
    are :func:`index_scores` of the same three and :func:`select_topk` of
    that; ``lse`` [B * H, T] is the core's (``flash_attention(with_lse=
    True)``)."""
    T = q.shape[1]
    ok = _ok(T, interpret, q)
    pk._note_dispatch("index_kl", ok)
    if not ok:
        return index_kl_xla(qi, ki, w, select, q, k, lse, scale)
    stop = jax.lax.stop_gradient
    return _index_kl_kernels(qi, ki, w, stop(scores), select, stop(lse_i),
                             stop(q), stop(k), stop(lse), float(scale),
                             interpret)
