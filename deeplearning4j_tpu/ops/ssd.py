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
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.common import at_least_f32


def ssd_scan(x, dt, A, B, C, D, chunk: int):
    """``y [Bt, T, H, P]`` (float32, at least) of the recurrence above.

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
