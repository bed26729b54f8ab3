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

On a TPU the same mathematics runs as Pallas kernels (:func:`ssd_scan`
decides from what the trace shows): the forward walks each sequence's chunks
in order on a sequential grid axis and keeps the carried state of a group's
heads, ``[N, K P]`` float32, in VMEM, and where a backward follows also writes
the state entering each chunk; the backward walks them in reverse, carrying
the state's cotangent. The decay and ``C B^T`` never leave VMEM and no ``lax.scan`` runs
over the chunks. :func:`_ssd_scan_xla` is the statement the kernels are held
to, and the fallback everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.common import at_least_f32
from deeplearning4j_tpu.ops import pallas_kernels as pk


def ssd_scan(x, dt, A, B, C, D, chunk: int):
    """``y [Bt, T, H, P]`` (float32, at least) of the recurrence above.

    ``x`` [Bt, T, H, P] in the products' dtype; ``dt`` [Bt, T, H], the
    step after its softplus; ``A`` [H], negative; ``B``, ``C`` [Bt, T, G,
    N]; ``D`` [H]; ``chunk`` the tokens a chunk holds. The kernels where
    :func:`_kernels_ok` admits the call, else :func:`_ssd_scan_xla`; the
    choice is booked on ``dl4j_pallas_dispatch_total{kernel="ssd_scan"}``,
    and on ``kernel="ssd_scan_bwd"`` as the plan a backward of the call
    takes."""
    ok = _kernels_ok(x, B, chunk)
    pk._note_dispatch("ssd_scan", ok)
    pk._note_dispatch("ssd_scan_bwd", ok)
    if ok:
        return _ssd_scan_kernels(x, dt, A, B, C, D, chunk)
    return _ssd_scan_xla(x, dt, A, B, C, D, chunk)


def _ssd_scan_xla(x, dt, A, B, C, D, chunk: int):
    """The chunked form in ``jax.numpy`` (:func:`ssd_scan`'s arguments):
    the statement of the mathematics."""
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
    """VMEM a program plans for: the pipeline's double-buffered blocks of a
    chunk's tokens and of the state entering the chunk, the carried state
    (or its cotangent) and the chunk's temporaries, a few ``[chunk, chunk]``
    float32 tiles and slabs of 128 lanes."""
    isz, lanes = jnp.dtype(dtype).itemsize, pk._lanes
    KP, n = K * P, lanes(N)
    small = chunk * (2 * lanes(K) * 4 + 8 * 4) + 8 * lanes(KP) * 4
    blocks = chunk * (KP * isz + 2 * n * isz + KP * 4) + small + KP * n * 4
    temps = 6 * chunk * lanes(chunk) * 4 + 8 * chunk * 128 * 4
    if backward:
        blocks += chunk * (KP * 4 + KP * isz + 2 * n * isz) + small
        temps *= 2
    return 2 * blocks + KP * n * 4 + temps


def _kernels_ok(x, B, chunk: int) -> bool:
    """The kernels' gate, read from what the trace shows: a TPU, and no jit
    that GSPMD partitions nor a vma-checked ``shard_map`` around
    (``pk.pallas_unavailable``); ``x`` in bfloat16 or float32; a chunk of a
    multiple of 8 rows; a group's lanes that cut into aligned slabs of
    heads; and a program whose counted VMEM fits the budget."""
    if pk.pallas_unavailable() is not None or pk._in_checked_shard_map(x):
        return False
    if x.dtype not in (jnp.bfloat16, jnp.float32) or chunk % 8:
        return False
    K, P, N = x.shape[2] // B.shape[2], x.shape[3], B.shape[3]
    width = _heads_per_slab(K, P) * P
    if not (width == K * P or width % 128 == 0) or (K > 1 and P % 8):
        return False
    return _vmem_bytes(chunk, K, P, N, x.dtype,
                       backward=True) <= pk._VMEM_BUDGET


def _ssd_scan_kernels(x, dt, A, B, C, D, chunk: int, interpret=False):
    """:func:`ssd_scan` through the kernels: the operands laid out a
    (sequence, group) row at a time, the chunk-local cumulative sums of
    ``dt A`` made here as the statement makes them (their gradient's reverse
    sum stays in XLA), ``D`` repeated over each head's lanes."""
    Bt, T, H, P = x.shape
    G, N = B.shape[-2:]
    K = H // G
    cd, f32 = x.dtype, at_least_f32(x.dtype)
    pad = -T % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    n, Tp = (T + pad) // chunk, T + pad
    dt = dt.astype(f32)
    cs = jnp.cumsum(dt.reshape(Bt, n, chunk, G, K)
                    * A.astype(f32).reshape(G, K), axis=2)

    def by_row(a):                            # [Bt, Tp, G, ...] -> [R, Tp, -1]
        a = jnp.moveaxis(a.reshape(Bt, Tp, G, -1), 2, 1)
        return a.reshape(Bt * G, Tp, a.shape[-1])

    d = jnp.repeat(D.astype(f32).reshape(G, K), P, axis=1).reshape(G, 1, K * P)
    y = _scan(by_row(x), by_row(dt), by_row(cs), by_row(B.astype(cd)),
              by_row(C.astype(cd)), d, chunk, interpret)
    y = jnp.moveaxis(y.reshape(Bt, G, Tp, H // G, P), 1, 2)
    return y.reshape(Bt, Tp, H, P)[:, :T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, cs, B, C, d, chunk, interpret):
    """y [R, Tp, K P] of rows laid out by ``_ssd_scan_kernels``: x [R, Tp, K
    P], dt and cs [R, Tp, K] float32, B and C [R, Tp, N], d [G, 1, K P]."""
    return _forward(x, dt, cs, B, C, d, chunk, interpret)


def _scan_fwd(x, dt, cs, B, C, d, chunk, interpret):
    y, states = _forward(x, dt, cs, B, C, d, chunk, interpret, states=True)
    return y, (x, dt, cs, B, C, d, states)


def _scan_bwd(chunk, interpret, res, g):
    x, dt, cs, B, C, d, states = res
    dx, dB, dC, ddt, dcs, dcs_t, dd = _backward(x, dt, cs, B, C, d, states,
                                                g, chunk, interpret)
    R, Tp, K = dt.shape
    dcs = dcs + jnp.swapaxes(dcs_t, 2, 3).reshape(R, Tp, K)
    dd = dd.reshape(R // d.shape[0], *d.shape).sum(axis=0)
    return dx, ddt, dcs, dB, dC, dd


_scan.defvjp(_scan_fwd, _scan_bwd)


def _layout(x, dt, B, chunk):
    """(R, sequence's chunks, chunk, K, P, N, heads a slab)."""
    R, Tp, KP = x.shape
    K, N = dt.shape[-1], B.shape[-1]
    return R, Tp // chunk, chunk, K, KP // K, N, _heads_per_slab(K, KP // K)


def _specs(n, L, G, reverse=False):
    """BlockSpec makers over the grid ``(R, n)``: a chunk of tokens, a
    chunk-major row, D's lanes; walked backwards from the last chunk where
    ``reverse``."""
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    tok = lambda w: pl.BlockSpec((1, L, w), lambda r, c: (r, at(c), 0))
    per_chunk = lambda a, b: pl.BlockSpec((1, 1, a, b),
                                          lambda r, c: (r, at(c), 0, 0))
    lanes = lambda w: pl.BlockSpec((1, 1, w), lambda r, c: (r % G, 0, 0))
    return tok, per_chunk, lanes


def _by_chunk(cs, chunk):
    """[R, Tp, K] -> [R, n, K, chunk]: each chunk's sums along lanes."""
    R, Tp, K = cs.shape
    return jnp.swapaxes(cs.reshape(R, Tp // chunk, chunk, K), 2, 3)


def _params(need):
    return pk._flash_params(("parallel", "arbitrary"), need)


def _forward(x, dt, cs, B, C, d, chunk, interpret, states=False):
    """y [R, Tp, K P]; and where ``states``, the state entering each chunk
    too, [R, n, N, K P] float32, the backward's residual."""
    R, n, L, K, P, N, hs = _layout(x, dt, B, chunk)
    tok, per_chunk, lanes = _specs(n, L, d.shape[0])
    out_specs = [tok(K * P)]
    out_shape = [jax.ShapeDtypeStruct(x.shape, at_least_f32(x.dtype))]
    if states:
        out_specs.append(per_chunk(N, K * P))
        out_shape.append(jax.ShapeDtypeStruct((R, n, N, K * P), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, hs=hs),
        grid=(R, n),
        in_specs=[tok(K * P), tok(K), tok(K), per_chunk(K, L), tok(N),
                  tok(N), lanes(K * P)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, K * P), jnp.float32)],
        compiler_params=_params(_vmem_bytes(L, K, P, N, x.dtype)),
        interpret=interpret,
    )(x, dt, cs, _by_chunk(cs, L), B, C, d)
    return tuple(out) if states else out[0]


def _backward(x, dt, cs, B, C, d, states, g, chunk, interpret):
    """-> dx, dB, dC, d(dt) direct, d(cs) [R, Tp, K] and [R, n, K, chunk]
    (the parts the sums reach along rows and along lanes), dd [R, 1, K P]."""
    R, n, L, K, P, N, hs = _layout(x, dt, B, chunk)
    tok, per_chunk, lanes = _specs(n, L, d.shape[0], reverse=True)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, hs=hs),
        grid=(R, n),
        in_specs=[tok(K * P), tok(K), tok(K), per_chunk(K, L), tok(N),
                  tok(N), lanes(K * P), per_chunk(N, K * P), tok(K * P)],
        out_specs=[tok(K * P), tok(N), tok(N), tok(K), tok(K),
                   per_chunk(K, L),
                   pl.BlockSpec((1, 1, K * P), lambda r, c: (r, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   jax.ShapeDtypeStruct(dt.shape, f32),
                   jax.ShapeDtypeStruct(dt.shape, f32),
                   jax.ShapeDtypeStruct((R, n, K, L), f32),
                   jax.ShapeDtypeStruct((R, 1, K * P), f32)],
        scratch_shapes=[pltpu.VMEM((N, K * P), f32)],
        compiler_params=_params(_vmem_bytes(L, K, P, N, x.dtype,
                                            backward=True)),
        interpret=interpret,
    )(x, dt, cs, _by_chunk(cs, L), B, C, d, states, g.astype(f32))


# a grid step of each kernel is one chunk of one row; a group's heads go by
# slabs of lanes (``_heads_per_slab``)
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


def _chunk_sums(dt_ref, cs_ref):
    """The chunk's dt, cs, the decay to its end ``exp(cs_L - cs)``, its
    whole decay ``exp(cs_L)`` [1, K] and the weight ``dt exp(cs_L - cs)`` of
    each token in the state it leaves."""
    dt, cs = dt_ref[0], cs_ref[0]
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


def _fwd_kernel(x_ref, dt_ref, cs_ref, cst_ref, b_ref, c_ref, d_ref, y_ref,
                *refs, P: int, hs: int):
    """One step of the walk; ``refs`` the output of the states entering each
    chunk, where the call writes it, then the carried state."""
    *st_ref, s_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    if st_ref:
        st_ref[0][0, 0] = s_ref[...]
    W, K, cd = hs * P, dt_ref.shape[-1], x_ref.dtype
    cm, bm = c_ref[0], b_ref[0]
    dt, cs, _, whole, w = _chunk_sums(dt_ref, cs_ref)
    cst, ecs = cst_ref[0, 0], jnp.exp(cs)
    cb = pk._dot(cm, bm, 1, 1)                                   # [t, s]
    for j in range(K // hs):
        lanes = slice(j * W, (j + 1) * W)
        xf = x_ref[0, :, lanes].astype(jnp.float32)
        ex = functools.partial(_expand, k0=j * hs, hs=hs, P=P, shape=xf.shape)
        xdt = (xf * ex(dt)).astype(cd)
        y = None
        for i in range(hs):
            m = (cb * _decay(cs, cst, j * hs + i)).astype(cd)
            part = pk._dot(m, xdt, 1, 0)
            y = part if y is None else jnp.where(
                _head_of_lane(xf.shape, P) == i, part, y)
        y = y + pk._dot(cm, s_ref[:, lanes].astype(cd), 1, 0) * ex(ecs)
        y_ref[0, :, lanes] = (y + d_ref[0, :, lanes] * xf).astype(y_ref.dtype)
        _advance(s_ref, j, xf, bm, w, whole, hs, P)


def _bwd_kernel(x_ref, dt_ref, cs_ref, cst_ref, b_ref, c_ref, d_ref, st_ref,
                g_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref, dcst_ref,
                dd_ref, ds_ref, *, P: int, hs: int):
    """One step of the reverse walk; ``ds_ref`` holds the cotangent of the
    state leaving the chunk, and leaves holding that of the state entering
    it."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    f32 = jnp.float32
    W, K, cd = hs * P, dt_ref.shape[-1], x_ref.dtype
    L = x_ref.shape[1]
    col = lax.broadcasted_iota(jnp.int32, (L, K), 1)
    row = lax.broadcasted_iota(jnp.int32, (K, L), 0)
    last_row = lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1
    cm, bm = c_ref[0], b_ref[0]
    dt, cs, to_end, whole, w = _chunk_sums(dt_ref, cs_ref)
    cst, ecs = cst_ref[0, 0], jnp.exp(cs)
    cb = pk._dot(cm, bm, 1, 1)                                   # [t, s]
    dcb = jnp.zeros((L, L), f32)
    dC = jnp.zeros(cm.shape, f32)
    dB = jnp.zeros(bm.shape, f32)
    ddt = jnp.zeros((L, K), f32)
    dcs = jnp.zeros((L, K), f32)
    dcst = jnp.zeros((K, L), f32)
    for j in range(K // hs):
        lanes = slice(j * W, (j + 1) * W)
        xf = x_ref[0, :, lanes].astype(f32)
        g = g_ref[0, :, lanes]
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
        sin = st_ref[0, 0, :, lanes]                             # [N, W]
        sc = sin.astype(cd)
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
        dx = dxdt * dte + dxs * we + d_ref[0, :, lanes] * g
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        dd_ref[0, :, lanes] += jnp.sum(g * xf, axis=0, keepdims=True)
        sums = zip(_sum_heads(dxdt * xf, hs, P), _sum_heads(dxs * xf, hs, P),
                   _sum_heads(into, hs, P), zs, _sum_heads(jnp.sum(
                       dso * sin, axis=0, keepdims=True), hs, P))
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
    dc_ref[0] = (dC + _dot_parts(dcbp, bm, 1, 0)).astype(dc_ref.dtype)
    db_ref[0] = (dB + _dot_parts(dcbp, cm, 0, 0)).astype(db_ref.dtype)
    ddt_ref[0] = ddt
    dcs_ref[0] = dcs
    dcst_ref[0, 0] = dcst
