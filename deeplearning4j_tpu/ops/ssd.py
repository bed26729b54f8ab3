"""The Mamba-2 state-space scan in its chunked form (state-space duality,
Dao & Gu, arXiv:2405.21060, the "SSD" algorithm).

For each head h, with a scalar decay a token and head,

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,    S_{-1} = 0,
    y_t = S_t C_t + D x_t,

``S`` a ``[P, N]`` state, ``x_t`` the head's ``P`` values, ``B_t`` and
``C_t`` the ``N`` values of the group the head reads (head h reads group
``h // (H / G)``). Cut into chunks of ``chunk`` tokens, the same mathematics
is four products and one short scan:

1. within a chunk, ``Y = (L * C B^T) (dt * X)`` with ``L[t, s] =
   exp(sum_{r=s+1..t} dt_r A)`` for ``s <= t`` and 0 above the diagonal;
2. each chunk's own state, its tokens decayed to the chunk's end;
3. the states carried from chunk to chunk by each chunk's whole decay
   (``lax.scan`` over the chunks);
4. the carried state's part ``C S_prev`` scaled by the decay up to each row.

``dt``, ``A``, the cumulative sums, the exponentials and the carried state
are float32 (at least); the four products take their operands in ``x``'s
dtype and accumulate in float32. A length that is not a multiple of the
chunk is padded with tokens whose ``dt`` is 0: they neither decay the state
nor add to it, and their outputs are cut away.

On a TPU the whole core of a Mamba-2 mixer, from ``W_in``'s output to the
gated and normed ``y``, runs as one Pallas kernel each way
(:func:`mamba_core`, where :func:`core_kernels_ok` admits the call): the
forward walks each sequence's chunks in order on a sequential grid axis and
keeps the carried state of a group's heads, ``[N, K P]`` float32, in VMEM;
the backward walks them in reverse, carrying the state's cotangent. The
decay and ``C B^T`` never leave VMEM and no ``lax.scan`` runs over the
chunks. :func:`ssd_scan` is the chunked statement: the path everywhere
else, and the oracle the kernels are held to.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.common import at_least_f32
from deeplearning4j_tpu.ops import pallas_kernels as pk


def ssd_scan(x, dt, A, B, C, D, chunk: int):
    """``y [Bt, T, H, P]`` (float32, at least) of the recurrence above, in
    its chunked form in ``jax.numpy``: the statement of the mathematics.

    ``x`` [Bt, T, H, P] in the products' dtype; ``dt`` [Bt, T, H], the
    step after its softplus; ``A`` [H], negative; ``B``, ``C`` [Bt, T, G,
    N]; ``D`` [H]; ``chunk`` the tokens a chunk holds."""
    Bt, T, H, P = x.shape
    G, N = B.shape[-2:]
    K = H // G
    cd, f32 = x.dtype, at_least_f32(x.dtype)
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    n, L = (T + pad) // chunk, chunk
    xc = x.reshape(Bt, n, L, G, K, P)
    bc = B.reshape(Bt, n, L, G, N).astype(cd)
    cc = C.reshape(Bt, n, L, G, N).astype(cd)
    dtc = dt.astype(f32).reshape(Bt, n, L, G, K)
    cs = jnp.cumsum(dtc * A.astype(f32).reshape(G, K), axis=2)
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, preferred_element_type=f32)

    # 1. within a chunk: L * C B^T, the decay masked before its exponential
    cst = jnp.moveaxis(cs, 2, -1)                            # [Bt, n, G, K, L]
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(causal, cst[..., :, None] - cst[..., None, :],
                              -jnp.inf))                     # [.., K, t, s]
    scores = dot("bctgn,bcsgn->bcgts", cc, bc)               # [Bt, n, G, t, s]
    mixed = (scores[:, :, :, None] * decay).astype(cd)
    xdt = (xc.astype(f32) * dtc[..., None]).astype(cd)
    y = dot("bcgkts,bcsgkp->bctgkp", mixed, xdt)

    # 2. each chunk's own state: its tokens decayed to the chunk's end
    to_end = jnp.exp(cs[:, :, -1:] - cs)
    xs = (xc.astype(f32) * (dtc * to_end)[..., None]).astype(cd)
    states = dot("bclgn,bclgkp->bcgkpn", bc, xs)            # [Bt, n, G, K, P, N]

    # 3. carried from chunk to chunk: the state before each chunk
    def carry(s, inputs):
        own, whole = inputs
        return s * whole[..., None, None] + own, s

    _, before = lax.scan(carry, jnp.zeros((Bt, G, K, P, N), f32),
                         (jnp.moveaxis(states, 1, 0),
                          jnp.moveaxis(jnp.exp(cs[:, :, -1]), 1, 0)))
    before = jnp.moveaxis(before, 0, 1)

    # 4. the carried state's part, decayed up to each row, and the skip
    y = y + dot("bctgn,bcgkpn->bctgkp", cc, before.astype(cd)) * jnp.exp(
        cs)[..., None]
    y = y + D.astype(f32).reshape(G, K, 1) * xc.astype(f32)
    return y.reshape(Bt, n * L, H, P)[:, :T]


# ------------------------------------------------------------------ kernels
def _heads_per_slab(K: int, P: int) -> int:
    """Heads of a group whose values share one slab of lanes: the most that
    divide ``K`` and fit 128 lanes (Nemotron's 64-wide heads: two), else
    one."""
    return next(h for h in range(K, 0, -1) if K % h == 0 and h * P <= 128
                or h == 1)


def _vmem_bytes(chunk: int, K: int, P: int, N: int, dtype,
                backward: bool = False) -> int:
    """VMEM a program of :func:`mamba_core` plans for: the pipeline's
    double-buffered blocks of a chunk's tokens (z, x, B, C, the gated y and
    the float32 y before the gate), of the ``_HALO`` tokens before the
    chunk, of the steps and of the state entering the chunk, backward also
    the cotangents of z, x, B and C (staged for their copies out) and of
    the steps; the carried state (or its cotangent); the taps' rows of the
    chunk and its halo, ``[_HALO + chunk, K P + 2 N]`` float32 (twice
    backward); and the chunk's temporaries, a few ``[chunk, chunk]``
    float32 tiles and slabs of 128 lanes."""
    isz, lanes = jnp.dtype(dtype).itemsize, pk._lanes
    KP, n = K * P, lanes(N)
    small = chunk * (2 * lanes(K) * 4 + 8 * 4) + 8 * lanes(KP) * 4
    blocks = chunk * (KP * isz + 2 * n * isz + KP * 4) + small + KP * n * 4
    temps = 6 * chunk * lanes(chunk) * 4 + 8 * chunk * 128 * 4
    if backward:
        blocks += chunk * (KP * 4 + KP * isz + 2 * n * isz) + small
        temps *= 2
    rows = (_HALO + chunk) * lanes(KP + 2 * n) * 4
    blocks += (chunk * KP * (2 * isz + 4) + _HALO * (KP + 2 * n) * isz
               + 8 * lanes(chunk) * 4)
    if backward:
        blocks += chunk * (2 * KP + 2 * n) * isz + 8 * lanes(chunk) * 4
    temps += (2 if backward else 1) * (rows + 4 * chunk * lanes(
        KP + 2 * n) * 4)
    return 2 * blocks + KP * n * 4 + temps


# a grid step of each kernel is one chunk of one (sequence, group) row; a
# group's heads go by slabs of lanes (``_heads_per_slab``)
def _expand(v, k0: int, hs: int, P: int, shape):
    """Columns ``k0 .. k0 + hs - 1`` of ``v`` [L, K], each over its head's
    ``P`` lanes of a slab ``shape`` [L, hs P]."""
    out = jnp.broadcast_to(v[:, k0:k0 + 1], shape)
    for i in range(1, hs):
        out = jnp.where(_head_of_lane(shape, P) == i, v[:, k0 + i:k0 + i + 1],
                        out)
    return out


def _head_of_lane(shape, P: int):
    return lax.broadcasted_iota(jnp.int32, shape, 1) // P


def _decay(cs, cst, k: int):
    """Head ``k``'s ``[t, s]`` decay ``exp(cs_t - cs_s)`` for ``s <= t``,
    masked before its exponential as the statement masks it."""
    L = cs.shape[0]
    causal = (lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= lax.broadcasted_iota(jnp.int32, (L, L), 1))
    return jnp.exp(jnp.where(causal, cs[:, k:k + 1] - cst[k:k + 1, :],
                             -jnp.inf))


def _sum_heads(v, hs: int, P: int):
    """[L, hs P] -> hs columns [L, 1]: each head's sum over its lanes."""
    if hs == 1:
        return [jnp.sum(v, axis=1, keepdims=True)]
    head = _head_of_lane(v.shape, P)
    return [jnp.sum(jnp.where(head == i, v, 0.0), axis=1, keepdims=True)
            for i in range(hs)]


def _parts(a, dtype):
    """A float32 cotangent as operands of the products' ``dtype`` whose
    products sum to its own: itself where that is float32, else its rounding
    and the rounding of what that leaves (16 of float32's 24 bits, where one
    rounding keeps 8), so a backward product rounds it no more than the
    statement's, whose cotangents enter their products in float32."""
    if dtype == jnp.float32:
        return (a,)
    hi = a.astype(dtype)
    return hi, (a - hi.astype(jnp.float32)).astype(dtype)


def _dot_parts(parts, b, ca: int, cb: int, first: bool = True):
    """``pk._dot`` of the cotangent given by its ``parts`` with ``b``, the
    cotangent on the left (``first``) or on the right."""
    dots = (pk._dot(p, b, ca, cb) if first else pk._dot(b, p, cb, ca)
            for p in parts)
    return functools.reduce(lambda u, v: u + v, dots)


def _chunk_sums(dt, cs):
    """The chunk's dt, cs, the decay to its end ``exp(cs_L - cs)``, its
    whole decay ``exp(cs_L)`` [1, K] and the weight ``dt exp(cs_L - cs)`` of
    each token in the state it leaves."""
    last = cs[-1:, :]
    to_end = jnp.exp(last - cs)
    return dt, cs, to_end, jnp.exp(last), dt * to_end


def _advance(s_ref, j: int, xf, bm, w, whole, hs: int, P: int):
    """Slab ``j``'s heads' state, held transposed ``[N, P]`` a head, to the
    chunk's end: ``S <- exp(cs_L) S + B^T (x dt exp(cs_L - cs))``."""
    W = hs * P
    lanes = slice(j * W, (j + 1) * W)
    xs = (xf * _expand(w, j * hs, hs, P, xf.shape)).astype(bm.dtype)
    s_ref[:, lanes] = (s_ref[:, lanes] * _expand(whole, j * hs, hs, P, (1, W))
                       + pk._dot(bm, xs, 0, 0))


def _fwd_chunk(x, dt, cs, cst, bm, cm, d_ref, s_ref, P: int, hs: int):
    """One chunk of the forward walk: x [L, K P] in the products' dtype, dt
    and cs [L, K], cst [K, L], B and C [L, N], d [1, K P]; ``s_ref`` the
    carried state [N, K P], advanced to the chunk's end; ``d_ref`` D over
    each head's lanes [1, 1, K P]. -> y's slabs [L, hs P] float32, the skip
    included."""
    W, K, cd = hs * P, dt.shape[-1], x.dtype
    dt, cs, _, whole, w = _chunk_sums(dt, cs)
    ecs = jnp.exp(cs)
    cb = pk._dot(cm, bm, 1, 1)                                   # [t, s]
    ys = []
    for j in range(K // hs):
        lanes = slice(j * W, (j + 1) * W)
        xf = x[:, lanes].astype(jnp.float32)
        ex = functools.partial(_expand, k0=j * hs, hs=hs, P=P, shape=xf.shape)
        xdt = (xf * ex(dt)).astype(cd)
        y = None
        for i in range(hs):
            m = (cb * _decay(cs, cst, j * hs + i)).astype(cd)
            part = pk._dot(m, xdt, 1, 0)
            y = part if y is None else jnp.where(
                _head_of_lane(xf.shape, P) == i, part, y)
        y = y + pk._dot(cm, s_ref[:, lanes].astype(cd), 1, 0) * ex(ecs)
        ys.append(y + d_ref[0, :, lanes] * xf)
        _advance(s_ref, j, xf, bm, w, whole, hs, P)
    return ys


def _bwd_chunk(x, dt, cs, cst, bm, cm, d_ref, sin, g_all, ds_ref, P: int,
               hs: int):
    """One chunk of the reverse walk (``_fwd_chunk``'s operands, ``sin`` the
    state entering the chunk [N, K P], ``g_all`` y's cotangent [L, K P]
    float32); ``ds_ref`` the cotangent of the state leaving the chunk, left
    holding that of the state entering it. -> dx's slabs [L, hs P], dB, dC
    [L, N] float32, d(dt) direct and d(cs) [L, K] and [K, L] (the parts the
    sums reach along rows and along lanes), dd's slabs [1, hs P]."""
    f32 = jnp.float32
    W, K, cd = hs * P, dt.shape[-1], x.dtype
    L = x.shape[0]
    col = lax.broadcasted_iota(jnp.int32, (L, K), 1)
    row = lax.broadcasted_iota(jnp.int32, (K, L), 0)
    last_row = lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    dt, cs, to_end, whole, w = _chunk_sums(dt, cs)
    ecs = jnp.exp(cs)
    cb = pk._dot(cm, bm, 1, 1)                                   # [t, s]
    dcb = jnp.zeros((L, L), f32)
    dC = jnp.zeros(cm.shape, f32)
    dB = jnp.zeros(bm.shape, f32)
    ddt = jnp.zeros((L, K), f32)
    dcs = jnp.zeros((L, K), f32)
    dcst = jnp.zeros((K, L), f32)
    dx_slabs, dd_slabs = [], []
    for j in range(K // hs):
        lanes = slice(j * W, (j + 1) * W)
        xf = x[:, lanes].astype(f32)
        g = g_all[:, lanes]
        gp = _parts(g, cd)
        ex = functools.partial(_expand, k0=j * hs, hs=hs, P=P, shape=xf.shape)
        head = _head_of_lane(xf.shape, P)
        dte, we = ex(dt), ex(w)
        xdt = (xf * dte).astype(cd)
        # within the chunk: y = (C B^T * decay) (dt x), a head at a time
        dxdt = None
        zs = []
        for i in range(hs):
            dec = _decay(cs, cst, j * hs + i)
            mf = cb * dec
            gk = gp if hs == 1 else [jnp.where(head == i, p, 0).astype(cd)
                                     for p in gp]
            dm = _dot_parts(gk, xdt, 1, 1)                       # [t, s]
            part = _dot_parts(gp, mf.astype(cd), 0, 0, first=False)  # [s, W]
            dxdt = part if dxdt is None else jnp.where(head == i, part, dxdt)
            dcb = dcb + dm * dec
            zs.append(dm * mf)
        # the carried state's part: y += (C S^T) exp(cs)
        sinj = sin[:, lanes]                                     # [N, W]
        sc = sinj.astype(cd)
        ge = g * ex(ecs)
        gep = _parts(ge, cd)
        dC = dC + _dot_parts(gep, sc, 1, 1)
        dsc = _dot_parts(gep, cm, 0, 0, first=False)             # [N, W]
        into = ge * pk._dot(cm, sc, 1, 0)
        # the chunk's own state: S_out = exp(cs_L) S + B^T (x w)
        dso = ds_ref[:, lanes]
        dsop = _parts(dso, cd)
        dxs = _dot_parts(dsop, bm, 0, 1, first=False)            # [L, W]
        dB = dB + _dot_parts(dsop, (xf * we).astype(cd), 1, 1, first=False)
        dx_slabs.append(dxdt * dte + dxs * we + d_ref[0, :, lanes] * g)
        dd_slabs.append(jnp.sum(g * xf, axis=0, keepdims=True))
        sums = zip(_sum_heads(dxdt * xf, hs, P), _sum_heads(dxs * xf, hs, P),
                   _sum_heads(into, hs, P), zs, _sum_heads(jnp.sum(
                       dso * sinj, axis=0, keepdims=True), hs, P))
        for i, (r_dt, r_w, r_e, z, r_s) in enumerate(sums):
            k = j * hs + i
            du = r_w * w[:, k:k + 1]
            at_end = (jnp.sum(du, axis=0, keepdims=True)
                      + r_s * whole[:, k:k + 1])
            ddt = jnp.where(col == k, r_dt + r_w * to_end[:, k:k + 1], ddt)
            dcs = jnp.where(col == k, jnp.sum(z, axis=1, keepdims=True) + r_e
                            - du + jnp.where(last_row, at_end, 0.0), dcs)
            dcst = jnp.where(row == k, -jnp.sum(z, axis=0, keepdims=True),
                             dcst)
        ds_ref[:, lanes] = dso * ex(whole, shape=(1, W)) + dsc
    dcbp = _parts(dcb, cd)
    dC = dC + _dot_parts(dcbp, bm, 1, 0)
    dB = dB + _dot_parts(dcbp, cm, 0, 0)
    return dx_slabs, dB, dC, ddt, dcs, dcst, dd_slabs


# ------------------------------------------------- the whole mixer's core
#: rows of the token blocks the taps read before a chunk: the backward's
#: block of the tokens before it, the forward's carried copy of its last
#: rows (a bfloat16 block's sublane tile)
_HALO = 16


class Core(NamedTuple):
    """A Mamba-2 core's geometry (``DecoderBlock``'s ``ssm_*`` fields):
    groups, heads a group, a head's width, the state's width, a chunk's
    tokens, the taps, the norm's epsilon, the products' dtype; and, as the
    kernels see it, the sequence's tokens before its padding."""
    G: int
    K: int
    P: int
    N: int
    chunk: int
    taps: int
    eps: float
    cd: jnp.dtype
    T: int = 0


def core_kernels_ok(zxd, core: Core) -> bool:
    """Whether :func:`mamba_core` runs the Mamba-2 core on ``W_in``'s output
    ``zxd`` [Bt, T, 2 d + 2 G N + H]; the choice is booked on
    ``dl4j_pallas_dispatch_total{kernel="mamba_core"}``, and on
    ``kernel="mamba_core_bwd"`` as the plan a backward of the call takes.
    The gate, read from what the trace shows: a TPU, and no jit that GSPMD
    partitions nor a vma-checked ``shard_map`` around
    (``pk.pallas_unavailable``); ``zxd`` and the products in bfloat16 or
    float32; a group's channels, B and C whole blocks of 128 lanes at their
    place in ``zxd``; a chunk of whole blocks of 128 tokens (the lanes of
    the transposed cotangent the backward copies out); taps that reach no
    further back than the halo; a group's lanes that cut into aligned slabs
    of heads; and a program whose counted VMEM fits the budget."""
    ok = _core_ok(zxd, core)
    pk._note_dispatch("mamba_core", ok)
    pk._note_dispatch("mamba_core_bwd", ok)
    return ok


def _core_ok(zxd, core: Core) -> bool:
    if pk.pallas_unavailable() is not None or pk._in_checked_shard_map(zxd):
        return False
    G, K, P, N, chunk = core[:5]
    W = K * P
    ok_dtype = lambda t: jnp.dtype(t) in (jnp.bfloat16, jnp.float32)
    if not (ok_dtype(zxd.dtype) and ok_dtype(core.cd)) or chunk % 128:
        return False
    if W % 128 or N % 128 or 2 * G * W % N or not 1 <= core.taps <= _HALO:
        return False
    width = _heads_per_slab(K, P) * P
    if not (width == W or width % 128 == 0) or (K > 1 and P % 8):
        return False
    return _vmem_bytes(chunk, K, P, N, core.cd,
                       backward=True) <= pk._VMEM_BUDGET


def mamba_core(zxd, conv_w, conv_b, dt_bias, A_log, D, norm_g, core: Core,
               interpret: bool = False):
    """``DecoderBlock._mamba_part``'s core through the kernels: ``zxd`` =
    ``[z | x | B | C | dt]``, ``W_in``'s output [Bt, T, 2 d + 2 G N + H],
    and the block's leaves -> the gated, normed ``y`` [Bt, T, d] in
    ``zxd``'s dtype. A grid step is one chunk of one group of one sequence;
    the kernels read a group's columns of ``zxd`` in place, and make the
    taps with their bias and SiLU, the steps, the scan, the skip, the gate
    and the group's norm in VMEM. Here only the steps' columns are laid out
    by group and chunk, and the leaves by group."""
    Bt, T, _ = zxd.shape
    G, K, P, N, L, taps = core[:6]
    W, f32 = K * P, jnp.float32
    pad = -T % L
    if pad:
        zxd = jnp.pad(zxd, ((0, 0), (0, pad), (0, 0)))
    xbc = jnp.concatenate([conv_w, conv_b[None]]).astype(f32)
    by_group = lambda a, w: jnp.swapaxes(a.reshape(taps + 1, G, w), 0, 1)
    d = G * W
    w = jnp.concatenate([by_group(xbc[:, :d], W),
                         by_group(xbc[:, d:d + G * N], N),
                         by_group(xbc[:, d + G * N:], N)], axis=-1)
    col = lambda v: v.astype(f32).reshape(G, K, 1)
    skip = jnp.repeat(D.astype(f32).reshape(G, K), P, axis=1)
    y = _core(zxd, w, col(dt_bias), col(A_log), skip.reshape(G, 1, W),
              norm_g.astype(f32).reshape(G, 1, W), core._replace(T=T),
              interpret)
    return y[:, :T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _core(zxd, w, dt_bias, a_log, d, g, core, interpret):
    """y [Bt, Tp, d] of ``zxd`` padded to whole chunks and the leaves by
    group: ``w`` [G, taps + 1, W + 2 N] the taps of the group's x, B and C
    then their bias; ``dt_bias``, ``a_log`` [G, K, 1]; ``d`` and the norm's
    scale ``g`` [G, 1, W] float32."""
    return _core_forward(zxd, w, dt_bias, a_log, d, g, core, interpret)


def _core_fwd(zxd, w, dt_bias, a_log, d, g, core, interpret):
    y, y_pre, states = _core_forward(zxd, w, dt_bias, a_log, d, g, core,
                                     interpret, residuals=True)
    return y, (zxd, w, dt_bias, a_log, d, g, y_pre, states)


def _core_bwd(core, interpret, res, gy):
    zxd, w, dt_bias, a_log, d, g, y_pre, states = res
    dzxd_t, ddt, *leaves = _core_backward(
        zxd, w, dt_bias, a_log, d, g, y_pre, states, gy, core, interpret)
    Bt, G, n, K, L = ddt.shape
    ddt = jnp.transpose(ddt, (0, 1, 3, 2, 4)).reshape(Bt, G * K, n * L)
    dzxd_t = lax.dynamic_update_slice(dzxd_t, ddt.astype(dzxd_t.dtype),
                                      (0, dzxd_t.shape[1] - G * K, 0))
    # tokens minor: the layout W_in's weight gradient, which sums over the
    # tokens, reads at its pace (a transpose XLA lays out as a bitcast)
    return (jnp.swapaxes(dzxd_t, 1, 2), *(a.sum(axis=0) for a in leaves))


_core.defvjp(_core_fwd, _core_bwd)


def _dt_by_chunk(zxd, core: Core):
    """The steps' columns of ``zxd`` [Bt, Tp, G K] -> [Bt, G, n, K, chunk]:
    a chunk's steps of a group along lanes."""
    Bt, Tp, _ = zxd.shape
    G, K, L = core.G, core.K, core.chunk
    dt = zxd[..., zxd.shape[-1] - G * K:].reshape(Bt, Tp // L, L, G, K)
    return jnp.transpose(dt, (0, 3, 1, 4, 2))


def _core_specs(zxd, core: Core, reverse=False):
    """BlockSpec makers over the grid ``(Bt, G, n)``: a chunk's tokens of
    the group's z, x, B or C in place in ``zxd``, and the ``_HALO`` tokens
    before the chunk; a chunk's tokens of the group's columns of an array
    laid out by group; a chunk of a per-chunk array; a group's leaf; a
    (sequence, group) row's accumulator. Walked from the last chunk where
    ``reverse``."""
    G, K, P, N, L = core[:5]
    W, n = K * P, zxd.shape[1] // L
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    # z, x: blocks of W lanes; B, C: of N lanes, after the 2 G W of z and x
    first = {"z": (W, 0), "x": (W, G), "B": (N, 2 * G * W // N),
             "C": (N, 2 * G * W // N + G)}
    part = lambda p: pl.BlockSpec(
        (1, L, first[p][0]), lambda b, g, c: (b, at(c), first[p][1] + g))
    halo = lambda p: pl.BlockSpec(
        (1, _HALO, first[p][0]),
        lambda b, g, c: (b, jnp.maximum(at(c) * (L // _HALO) - 1, 0),
                         first[p][1] + g))
    by_group = lambda w: pl.BlockSpec((1, L, w),
                                      lambda b, g, c: (b, at(c), g))
    per_chunk = lambda *s: pl.BlockSpec(
        (1, 1, 1) + s, lambda b, g, c: (b, g, at(c)) + (0,) * len(s))
    leaf = lambda *s: pl.BlockSpec((1,) + s, lambda b, g, c: (g, 0, 0))
    row = lambda *s: pl.BlockSpec((1, 1) + s, lambda b, g, c: (b, g, 0, 0))
    return part, halo, by_group, per_chunk, leaf, row


def _core_in_specs(zxd, w, core: Core, reverse=False):
    """The specs of the operands both kernels read (``_core_forward``'s
    order), and of the halo of x, B and C."""
    part, halo, _, per_chunk, leaf, _ = _core_specs(zxd, core, reverse)
    W = core.K * core.P
    return ([part("z"), part("x"), part("B"), part("C"),
             per_chunk(core.K, core.chunk), leaf(*w.shape[1:]),
             leaf(core.K, 1), leaf(core.K, 1), leaf(1, W), leaf(1, W)],
            [halo("x"), halo("B"), halo("C")])


def _core_params(core: Core, backward=False):
    G, K, P, N, L = core[:5]
    return pk._flash_params(
        ("parallel", "parallel", "arbitrary"),
        _vmem_bytes(L, K, P, N, core.cd, backward=backward))


def _core_forward(zxd, w, dt_bias, a_log, d, g, core: Core, interpret,
                  residuals=False):
    """y [Bt, Tp, d] in ``zxd``'s dtype; and where ``residuals`` the
    backward's: the float32 y before the gate [Bt, Tp, d] and the state
    entering each chunk [Bt, G, n, N, W] float32."""
    Bt, Tp, _ = zxd.shape
    G, K, P, N, L = core[:5]
    W, n, f32 = K * P, Tp // L, jnp.float32
    _, _, by_group, per_chunk, _, _ = _core_specs(zxd, core)
    in_specs, _ = _core_in_specs(zxd, w, core)
    out_specs = [by_group(W)]
    out_shape = [jax.ShapeDtypeStruct((Bt, Tp, G * W), zxd.dtype)]
    if residuals:
        out_specs += [by_group(W), per_chunk(N, W)]
        out_shape += [jax.ShapeDtypeStruct((Bt, Tp, G * W), f32),
                      jax.ShapeDtypeStruct((Bt, G, n, N, W), f32)]
    with jax.named_scope("scan"):
        out = pl.pallas_call(
            functools.partial(_core_fwd_kernel, core=core,
                              hs=_heads_per_slab(K, P)),
            grid=(Bt, G, n),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((N, W), f32),
                            pltpu.VMEM((_HALO + L, W + 2 * N), f32)],
            compiler_params=_core_params(core),
            interpret=interpret,
        )(zxd, zxd, zxd, zxd, _dt_by_chunk(zxd, core), w, dt_bias, a_log, d,
          g)
    return tuple(out) if residuals else out[0]


def _core_backward(zxd, w, dt_bias, a_log, d, g, y_pre, states, gy,
                   core: Core, interpret):
    """-> the cotangent of ``zxd`` transposed, [Bt, 2 d + 2 G N + H, Tp],
    but for its steps' rows, which the kernel leaves unwritten (it copies
    each chunk's cotangents of the group's z, x, B and C to their places
    itself); that of the steps [Bt, G, n, K, chunk]; and each (sequence,
    group) row's sums [Bt, G, ...] of the gradients of ``w``, ``dt_bias``,
    ``a_log``, ``d`` and ``g``."""
    Bt, Tp, _ = zxd.shape
    G, K, P, N, L = core[:5]
    W, n, f32 = K * P, Tp // L, jnp.float32
    _, _, by_group, per_chunk, _, row = _core_specs(zxd, core, reverse=True)
    in_specs, halo_specs = _core_in_specs(zxd, w, core, reverse=True)
    out = lambda *s: jax.ShapeDtypeStruct(s, zxd.dtype)
    acc = lambda *s: jax.ShapeDtypeStruct((Bt, G) + s, f32)
    with jax.named_scope("scan"):
        return pl.pallas_call(
            functools.partial(_core_bwd_kernel, core=core,
                              hs=_heads_per_slab(K, P)),
            grid=(Bt, G, n),
            in_specs=in_specs + halo_specs + [per_chunk(N, W), by_group(W),
                                              by_group(W)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), per_chunk(K, L),
                       row(*w.shape[1:]), row(K, 1), row(K, 1), row(1, W),
                       row(1, W)],
            out_shape=[out(Bt, zxd.shape[2], Tp), out(Bt, G, n, K, L),
                       acc(*w.shape[1:]), acc(K, 1), acc(K, 1), acc(1, W),
                       acc(1, W)],
            scratch_shapes=[pltpu.VMEM((N, W), f32),
                            pltpu.VMEM((_HALO + L, W + 2 * N), f32),
                            pltpu.VMEM((L + _HALO, W + 2 * N), f32),
                            pltpu.VMEM((2 * W + 2 * N, L), zxd.dtype),
                            pltpu.SemaphoreType.DMA((4,))],
            compiler_params=_core_params(core, backward=True),
            interpret=interpret,
        )(zxd, zxd, zxd, zxd, _dt_by_chunk(zxd, core), w, dt_bias, a_log, d,
          g, zxd, zxd, zxd, states, y_pre, gy)


def _tri(L: int, lower: bool):
    """[L, L] 0/1 in bfloat16: ``r >= c`` (``lower``) or ``r <= c``."""
    r = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    c = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    return ((r >= c) if lower else (r <= c)).astype(jnp.bfloat16)


def _sum_along_lanes(v, mask):
    """``v`` [K, L] float32 times the 0/1 ``mask`` [L, L] as float32 sums:
    ``v`` enters as three bfloat16 parts (8 bits each of its 24), so each
    product is exact and only the sums round, as a cumulative sum's do."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return _dot_parts((hi, mid, lo), mask, 1, 0)


def _prologue(ext, x_ref, b_ref, c_ref, dt_ref, w_ref, dtb_ref, alog_ref,
              chunk_id, core: Core):
    """A chunk's taps and steps, ``ext`` [_HALO + L, W + 2 N] float32
    holding the raw x, B and C of the ``_HALO`` tokens before the chunk in
    its first rows: it takes the chunk's raw x | B | C below them. -> (the
    taps' sum before the SiLU [L, W + 2 N], x [L, W], B and C [L, N] in the
    products' dtype; the steps' pre-activation, the steps and A's values,
    [K, L] and [K, 1] float32; dt and cs [L, K]; cs along lanes [K, L])."""
    G, K, P, N, L, taps = core[:6]
    W, f32 = K * P, jnp.float32
    ext[_HALO:, :W] = x_ref[0].astype(f32)
    ext[_HALO:, W:W + N] = b_ref[0].astype(f32)
    ext[_HALO:, W + N:] = c_ref[0].astype(f32)
    wt = w_ref[0]
    first = _HALO - (taps - 1)
    pre = wt[taps:taps + 1] + sum(
        wt[j:j + 1] * ext[first + j:first + j + L, :] for j in range(taps))
    xbc = jax.nn.silu(pre).astype(core.cd)
    dtr = dt_ref[0, 0, 0].astype(f32) + dtb_ref[0]
    dts = jax.nn.softplus(dtr)
    if core.T % L:
        at = chunk_id * L + lax.broadcasted_iota(jnp.int32, dts.shape, 1)
        dts = jnp.where(at < core.T, dts, 0.0)
    A = -jnp.exp(alog_ref[0])
    cst = _sum_along_lanes(dts * A, _tri(L, lower=False))
    return (pre, xbc[:, :W], xbc[:, W:W + N], xbc[:, W + N:], dtr, dts, A,
            dts.T, cst.T, cst)


def _epilogue(y, zf, eps: float):
    """The gate and the group's norm of y [L, W] float32 before its scale:
    ``u / rms(u)`` of ``u = y silu(z)``; u and ``1 / rms(u)`` too."""
    u = y * jax.nn.silu(zf)
    inv = lax.rsqrt(jnp.mean(u * u, axis=1, keepdims=True) + eps)
    return u * inv, u, inv


def _dsilu(v):
    s = jax.nn.sigmoid(v)
    return s * (1.0 + v * (1.0 - s))


def _core_fwd_kernel(z_ref, x_ref, b_ref, c_ref, dt_ref, w_ref, dtb_ref,
                     alog_ref, d_ref, g_ref, y_ref, *refs, core: Core,
                     hs: int):
    """One step of the walk; ``refs`` the outputs of the float32 y before
    the gate and of the states entering each chunk, where the call writes
    them, then the carried state and the taps' rows."""
    *res, s_ref, ext = refs
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        ext[:_HALO] = jnp.zeros((_HALO, ext.shape[1]), jnp.float32)

    if res:
        res[1][0, 0, 0] = s_ref[...]
    L = core.chunk
    _, x, bm, cm, _, _, _, dt, cs, cst = _prologue(
        ext, x_ref, b_ref, c_ref, dt_ref, w_ref, dtb_ref, alog_ref, c, core)
    ext[:_HALO] = ext[L:]
    ys = _fwd_chunk(x, dt, cs, cst, bm, cm, d_ref, s_ref, core.P, hs)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    if res:
        res[0][0] = y
    normed, _, _ = _epilogue(y, z_ref[0].astype(jnp.float32), core.eps)
    y_ref[0] = (normed * g_ref[0]).astype(y_ref.dtype)


def _core_bwd_kernel(z_ref, x_ref, b_ref, c_ref, dt_ref, w_ref, dtb_ref,
                     alog_ref, d_ref, g_ref, hx_ref, hb_ref, hc_ref, st_ref,
                     yp_ref, gy_ref, dzxd_t_ref, ddt_ref, dw_ref, ddtb_ref,
                     dalog_ref, dd_ref, dg_ref, ds_ref, ext, dext, out, sems,
                     *, core: Core, hs: int):
    """One step of the reverse walk. ``ds_ref`` holds the cotangent of the
    state leaving the chunk; ``dext`` [L + _HALO, W + 2 N] the cotangent of
    the taps' sum of this chunk and, below it, of the first rows of the
    chunk after; ``out`` the chunk's cotangents of z, x, B and C,
    transposed, which leave by copies to their rows of ``dzxd_t_ref`` (in
    HBM)."""
    G, K, P, N, L, taps = core[:6]
    W, f32 = K * P, jnp.float32
    step = pl.program_id(2)
    c = pl.num_programs(2) - 1 - step

    @pl.when(step == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dext[L:] = jnp.zeros((_HALO, dext.shape[1]), f32)
        for r in (dw_ref, ddtb_ref, dalog_ref, dd_ref, dg_ref):
            r[...] = jnp.zeros_like(r)

    halo = jnp.concatenate([hx_ref[0], hb_ref[0], hc_ref[0]], axis=1)
    ext[:_HALO] = jnp.where(c == 0, 0.0, halo.astype(f32))
    pre, x, bm, cm, dtr, dts, A, dt, cs, cst = _prologue(
        ext, x_ref, b_ref, c_ref, dt_ref, w_ref, dtb_ref, alog_ref, c, core)
    # the gate and the norm
    y, zf, g = yp_ref[0], z_ref[0].astype(f32), g_ref[0]
    go = gy_ref[0].astype(f32)
    normed, u, inv = _epilogue(y, zf, core.eps)
    dg_ref[0, 0] += jnp.sum(go * normed, axis=0, keepdims=True)
    dn = go * g
    du = inv * dn - u * (inv * inv * inv
                         * jnp.mean(dn * u, axis=1, keepdims=True))
    dy = du * jax.nn.silu(zf)
    out[:W] = (du * y * _dsilu(zf)).T.astype(out.dtype)
    # the scan
    dxs, dB, dC, ddt, dcs, dcst, dds = _bwd_chunk(
        x, dt, cs, cst, bm, cm, d_ref, st_ref[0, 0, 0], dy, ds_ref, P, hs)
    dd_ref[0, 0] += dds[0] if len(dds) == 1 else jnp.concatenate(dds, 1)
    # the steps: cs sums dt A along the chunk, so d(dt A) sums d(cs) back
    dcs = _sum_along_lanes(dcs.T + dcst, _tri(L, lower=True))
    ddts = ddt.T + dcs * A
    dalog_ref[0, 0] += jnp.sum(dcs * dts, axis=1, keepdims=True) * A
    ddtr = ddts * jax.nn.sigmoid(dtr)
    if core.T % L:
        at = c * L + lax.broadcasted_iota(jnp.int32, ddtr.shape, 1)
        ddtr = jnp.where(at < core.T, ddtr, 0.0)
    ddtb_ref[0, 0] += jnp.sum(ddtr, axis=1, keepdims=True)
    ddt_ref[0, 0, 0] = ddtr.astype(ddt_ref.dtype)
    # the taps: each sums the chunk's rows and the taps - 1 before it
    dx = dxs[0] if len(dxs) == 1 else jnp.concatenate(dxs, axis=1)
    dpre = jnp.concatenate([dx, dB, dC], axis=1) * _dsilu(pre)
    dext[:L] = dpre
    first = _HALO - (taps - 1)
    dw = [jnp.sum(dpre * ext[first + j:first + j + L, :], axis=0,
                  keepdims=True) for j in range(taps)]
    dw_ref[0, 0] += jnp.concatenate(dw + [jnp.sum(dpre, axis=0,
                                                  keepdims=True)], axis=0)
    wt = w_ref[0]
    draw = sum(wt[j:j + 1] * dext[taps - 1 - j:taps - 1 - j + L, :]
               for j in range(taps))
    dext[L:] = dext[:_HALO]
    out[W:] = draw.T.astype(out.dtype)
    seq, group = pl.program_id(0), pl.program_id(1)
    tokens = pl.ds(pl.multiple_of(c * L, L), L)
    # z, x, B and C: (first row in ``out``, first column of the part in
    # ``zxd``, the group's width)
    copies = [pltpu.make_async_copy(
        out.at[pl.ds(src, width)],
        dzxd_t_ref.at[seq, pl.ds(
            pl.multiple_of(first + group * width, 128), width), tokens],
        sems.at[i])
        for i, (src, first, width) in enumerate((
            (0, 0, W), (W, G * W, W), (2 * W, 2 * G * W, N),
            (2 * W + N, 2 * G * W + G * N, N)))]
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
