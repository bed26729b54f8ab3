"""Attention and transformer layers (TPU-native additions).

The reference's sequence modeling stops at LSTM + truncated BPTT (SURVEY.md
§5); long-context attention is a required first-class TPU capability here.
These layers ride the accelerated seam: ``flash_attention`` (Pallas tiled
kernel on TPU, identical XLA math elsewhere — ops/pallas_kernels.py), and
under a sequence-parallel mesh the same math runs as ring or Ulysses
attention (parallel/ring_attention.py).

Layout: [batch, time, features] like the recurrent layers.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common import accum_dtype, at_least_f32, get_policy
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.serde import register_config

Array = jax.Array


def attend(q: Array, k: Array, v: Array, causal: bool, mask=None,
           scale=None, window=None, select=None, with_lse: bool = False):
    """The ONE attention-core dispatch every attention-bearing layer uses.

    Single device (no active ParallelContext): flash_attention (Pallas on
    TPU) or masked_attention. Under a trainer-published sequence-parallel
    context (parallel/context.py) the same math runs distributed over the
    mesh's sequence axis — Ulysses all_to_all by default, ring ppermute on
    request — so a plain ``transformer_lm`` config becomes long-context
    sequence-parallel through fit() alone, the way reference
    ParallelWrapper.java:44 wraps any net without touching model code.
    Masked (variable-length) batches fall back to the dense masked kernel:
    correctness over parallelism, mirroring ParallelWrapper's own fallback
    for semantics its sharded step doesn't cover. ``v`` may have another
    width than ``q`` and ``k``, and ``scale`` (None: ``Dk ** -0.5``) another
    value: latent attention's core. ``window`` (a query sees the ``window``
    keys ending at itself) and fewer key/value heads than query heads
    (``k``, ``v`` [B, T, G, D]: query head h reads head ``h // (H // G)``)
    reach the single-device core as they are, and so does ``select`` (int8
    [B, T, T], the keys each query attends to, as ``ops.indexer.select_topk``
    chooses them: the softmax runs over those alone); ``with_lse`` then
    returns ``(out, lse [B * H, T])``, the log-sum-exp for use under
    ``stop_gradient`` (``flash_attention``).
    """
    from deeplearning4j_tpu.ops.pallas_kernels import (
        flash_attention, masked_attention,
    )
    from deeplearning4j_tpu.parallel import context as pctx

    ctx = pctx.current()
    if (scale is not None or v.shape[-1] != q.shape[-1]
            or window is not None or k.shape[2] != q.shape[2]
            or select is not None):
        # latent attention (values narrower than keys, a score scale of its
        # own), a window, grouped key/value heads, a selection of keys per
        # query: the single-device core only; neither the sequence-parallel
        # bodies nor the key-masked kernel know those shapes yet
        if mask is not None or (ctx is not None and ctx.seq_axis is not None):
            raise NotImplementedError(
                "attention with its own scale or value width (latent "
                "attention), a window, a selection of keys per query, or "
                "fewer key/value heads than query heads runs unmasked on "
                "one device only")
        return flash_attention(q, k, v, causal, False, False, scale, window,
                               select, with_lse)
    if ctx is not None and ctx.seq_axis is not None and mask is None:
        from deeplearning4j_tpu.parallel.ring_attention import (
            ring_attention_sharded, ulysses_attention_sharded)
        if ctx.seq_mode == "ring":
            return ring_attention_sharded(q, k, v, ctx.mesh, ctx.seq_axis,
                                          causal, batch_axis=ctx.data_axis)
        return ulysses_attention_sharded(q, k, v, ctx.mesh, ctx.seq_axis,
                                         causal, ctx.interpret,
                                         batch_axis=ctx.data_axis)
    if mask is not None:
        return masked_attention(q, k, v, mask, causal)
    return flash_attention(q, k, v, causal)


def rms_norm(x: Array, g: Array, eps: float = 1e-6) -> Array:
    """``x * rsqrt(mean(x^2) + eps) * g`` over the last axis, the statistic
    in at least float32 whatever dtype the activations flow in."""
    xf = x.astype(at_least_f32(x.dtype))
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv).astype(x.dtype) * g.astype(x.dtype)


def rope_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """The ``dim // 2`` rotary frequencies. ``scaling`` None is plain RoPE;
    ``{"type": "yarn", "factor", "original_max_position_embeddings",
    "beta_fast", "beta_slow"}`` blends interpolated and extrapolated
    frequencies with YaRN's linear ramp (Peng et al., arXiv:2309.00071, as
    DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` computes them)."""
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** pos
    if not scaling:
        return extra
    if scaling.get("type") != "yarn":
        raise ValueError(f"unknown rope scaling {scaling.get('type')!r}")
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))),
               dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0, 1)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(x: Array, inv_freq, halves: bool = False) -> Array:
    """Rotate ``x`` [B, T, H, D] by position: the pair ``(x[2i], x[2i+1])``
    turns by ``t * inv_freq[i]``, and the result comes out as DeepSeek-V2's
    ``apply_rotary_pos_emb`` leaves it, every first element before every
    second (queries and keys alike, so their products are untouched).
    ``halves``: the pair is ``(x[i], x[i + D/2])`` instead and stays where
    it was (``x * cos + rotate_half(x) * sin``). Angles and the rotation are
    float32."""
    t = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = t[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(at_least_f32(x.dtype))
    half = x.shape[-1] // 2
    a, b = ((xf[..., :half], xf[..., half:]) if halves
            else (xf[..., 0::2], xf[..., 1::2]))
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


@register_config("SelfAttention")
@dataclasses.dataclass
class SelfAttentionLayer(FeedForwardLayer):
    """Multi-head self-attention with fused QKV projection.

    n_out is the model width; params: "Wqkv" [F, 3F] fused projection (one
    MXU matmul), "Wo" [F, F], "b" [F]. The attention core is flash_attention.
    """

    n_heads: int = 4
    causal: bool = False

    def set_n_in(self, itype: InputType) -> None:
        if not self.n_in:
            self.n_in = itype.size if itype.kind == "recurrent" else itype.flat_size()
        if not self.n_out:
            self.n_out = self.n_in

    def init_params(self, key, itype: InputType) -> dict:
        if self.n_out % self.n_heads:
            raise ValueError(f"n_out {self.n_out} not divisible by "
                             f"n_heads {self.n_heads}")
        k1, k2 = jax.random.split(key)
        return {"Wqkv": self._init_w(k1, (self.n_in, 3 * self.n_out)),
                "Wo": self._init_w(k2, (self.n_out, self.n_out)),
                "b": self._init_b((self.n_out,))}

    def regularizable_params(self):
        return ("Wqkv", "Wo")

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        pol = get_policy()
        x = self.apply_dropout(x, rng, train)
        B, T, _ = x.shape
        H = self.n_heads
        D = self.n_out // H
        qkv = jnp.matmul(x.astype(pol.compute_dtype),
                         params["Wqkv"].astype(pol.compute_dtype),
                         preferred_element_type=accum_dtype(pol.compute_dtype))
        q, k, v = jnp.split(qkv.astype(pol.output_dtype), 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        o = attend(q, k, v, self.causal, mask)
        o = o.reshape(B, T, self.n_out)
        out = jnp.matmul(o.astype(pol.compute_dtype),
                         params["Wo"].astype(pol.compute_dtype),
                         preferred_element_type=accum_dtype(pol.compute_dtype))
        out = out.astype(pol.output_dtype) + params["b"].astype(pol.output_dtype)
        return self.act_fn()(out), state


@register_config("TransformerBlock")
@dataclasses.dataclass
class TransformerBlock(FeedForwardLayer):
    """Pre-LN transformer block: LN -> MHA -> residual, LN -> MLP -> residual.

    Homogeneous width (n_in == n_out == model width) so blocks stack and can
    be pipeline-parallelized as identical stages (parallel/pipeline.py).
    Params: ln1/ln2 scales+biases, attention Wqkv/Wo/bo, MLP W1/b1/W2/b2.
    """

    n_heads: int = 4
    ffn_multiplier: int = 4
    causal: bool = True

    def set_n_in(self, itype: InputType) -> None:
        if not self.n_in:
            self.n_in = itype.size if itype.kind == "recurrent" else itype.flat_size()
        if not self.n_out:
            self.n_out = self.n_in

    def init_params(self, key, itype: InputType) -> dict:
        F = self.n_out
        if F % self.n_heads:
            raise ValueError(f"width {F} not divisible by heads {self.n_heads}")
        ks = jax.random.split(key, 4)
        hidden = self.ffn_multiplier * F
        return {
            "ln1_g": jnp.ones((F,), jnp.float32),
            "ln1_b": jnp.zeros((F,), jnp.float32),
            "Wqkv": self._init_w(ks[0], (F, 3 * F)),
            "Wo": self._init_w(ks[1], (F, F)),
            "bo": jnp.zeros((F,), jnp.float32),
            "ln2_g": jnp.ones((F,), jnp.float32),
            "ln2_b": jnp.zeros((F,), jnp.float32),
            "W1": self._init_w(ks[2], (F, hidden)),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "W2": self._init_w(ks[3], (hidden, F)),
            "b2": jnp.zeros((F,), jnp.float32),
        }

    def regularizable_params(self):
        return ("Wqkv", "Wo", "W1", "W2")

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    @staticmethod
    def _ln(x, g, b, eps=1e-5):
        # statistics in at least float32 even when activations flow as bf16
        xf = x.astype(at_least_f32(x.dtype))
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        xhat = ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
        return xhat * g.astype(x.dtype) + b.astype(x.dtype)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        pol = get_policy()
        B, T, F = x.shape
        H = self.n_heads
        D = F // H
        h = self._ln(x, params["ln1_g"], params["ln1_b"])
        qkv = jnp.matmul(h.astype(pol.compute_dtype),
                         params["Wqkv"].astype(pol.compute_dtype),
                         preferred_element_type=accum_dtype(pol.compute_dtype))
        q, k, v = jnp.split(qkv.astype(pol.output_dtype), 3, axis=-1)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, H, D)
        v = v.reshape(B, T, H, D)
        # padded keys must not absorb softmax mass (LN/MLP are per-token on
        # the last axis, so attention is the only cross-token leak); attend
        # also dispatches sequence-parallel under an active ParallelContext
        o = attend(q, k, v, self.causal, mask)
        o = o.reshape(B, T, F)
        att = jnp.matmul(o.astype(pol.compute_dtype),
                         params["Wo"].astype(pol.compute_dtype),
                         preferred_element_type=accum_dtype(pol.compute_dtype))
        x = x + att.astype(pol.output_dtype) + params["bo"].astype(pol.output_dtype)
        h = self._ln(x, params["ln2_g"], params["ln2_b"])
        h = jnp.matmul(h.astype(pol.compute_dtype),
                       params["W1"].astype(pol.compute_dtype),
                       preferred_element_type=accum_dtype(pol.compute_dtype))
        h = jax.nn.gelu(h.astype(pol.output_dtype) + params["b1"].astype(pol.output_dtype))
        h = self.apply_dropout(h, rng, train)
        h = jnp.matmul(h.astype(pol.compute_dtype),
                       params["W2"].astype(pol.compute_dtype),
                       preferred_element_type=accum_dtype(pol.compute_dtype))
        return x + h.astype(pol.output_dtype) + params["b2"].astype(pol.output_dtype), state
