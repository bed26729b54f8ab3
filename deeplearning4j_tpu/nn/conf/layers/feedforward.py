"""Dense-family layers: Dense, Output, Loss, Activation, Dropout, Embedding,
AutoEncoder, RBM.

Reference impls: nn/layers/feedforward/dense/DenseLayer.java, nn/layers/OutputLayer.java,
nn/layers/feedforward/embedding/EmbeddingLayer.java,
nn/layers/feedforward/autoencoder/AutoEncoder.java, nn/layers/feedforward/rbm/RBM.java.
Forward math is a jnp matmul (MXU) + fused activation; backprop is autodiff.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common import accum_dtype, get_policy
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer, Layer, PretrainLayer
from deeplearning4j_tpu.nn.conf.serde import register_config
from deeplearning4j_tpu.ops.losses import get_loss

Array = jax.Array


def _dense(params: dict, x: Array) -> Array:
    """x @ W + b with the configured MXU compute dtype.

    ``preferred_element_type`` follows the policy's grad_accum_dtype: JAX's
    transpose rule carries it into the dW/dx contractions, pinning wide
    accumulation of the weight gradients without a post-hoc upcast-reduce.
    """
    pol = get_policy()
    w = params["W"].astype(pol.compute_dtype)
    out = jnp.matmul(x.astype(pol.compute_dtype), w,
                     preferred_element_type=accum_dtype(pol.compute_dtype))
    if "b" not in params:       # a layer built with has_bias=False
        return out.astype(pol.output_dtype)
    return (out.astype(pol.compute_dtype)
            + params["b"].astype(pol.compute_dtype)).astype(pol.output_dtype)


@register_config("Dense")
@dataclasses.dataclass
class DenseLayer(FeedForwardLayer):
    """Fully-connected layer (reference nn/conf/layers/DenseLayer.java)."""

    def init_params(self, key, itype: InputType) -> dict:
        return {"W": self._init_w(key, (self.n_in, self.n_out)),
                "b": self._init_b((self.n_out,))}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, rng, train)
        return self.act_fn()(_dense(params, x)), state


@register_config("Output")
@dataclasses.dataclass
class OutputLayer(FeedForwardLayer):
    """Dense layer + loss function; terminates backprop
    (reference nn/conf/layers/OutputLayer.java, nn/layers/OutputLayer.java)."""

    loss: str = "mcxent"

    def has_loss(self) -> bool:
        return True

    def init_params(self, key, itype: InputType) -> dict:
        return {"W": self._init_w(key, (self.n_in, self.n_out)),
                "b": self._init_b((self.n_out,))}

    def preout(self, params, x):
        return _dense(params, x)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, rng, train)
        return self.act_fn()(_dense(params, x)), state

    def compute_loss(self, params, x, labels, mask=None) -> Array:
        return get_loss(self.loss)(labels, _dense(params, x), self.act_fn(), mask)


@register_config("Loss")
@dataclasses.dataclass
class LossLayer(Layer):
    """Parameter-free loss layer (reference nn/conf/layers/LossLayer.java)."""

    loss: str = "mcxent"

    def has_loss(self) -> bool:
        return True

    def regularizable_params(self):
        return ()

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.act_fn()(x), state

    def compute_loss(self, params, x, labels, mask=None) -> Array:
        return get_loss(self.loss)(labels, x, self.act_fn(), mask)


@register_config("Activation")
@dataclasses.dataclass
class ActivationLayer(Layer):
    """Standalone activation (reference nn/conf/layers/ActivationLayer.java)."""

    def regularizable_params(self):
        return ()

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.act_fn()(x), state


@register_config("Dropout")
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Standalone dropout (reference nn/conf/layers/DropoutLayer.java).
    ``dropout`` is the retain probability, matching the reference."""

    def regularizable_params(self):
        return ()

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.apply_dropout(x, rng, train), state


@register_config("Embedding")
@dataclasses.dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Index -> vector lookup (reference nn/conf/layers/EmbeddingLayer.java:
    expects integer-index input, mathematically a one-hot matmul but implemented as a
    gather — on TPU a gather from an [vocab, dim] table in HBM)."""

    #: False: the table alone, no "b" leaf (current language models)
    has_bias: bool = True
    #: factor on the looked-up vector (``sqrt(n_out)`` where a model scales
    #: its embedding's output), applied in the table's dtype
    output_scale: float = 1.0

    def init_params(self, key, itype: InputType) -> dict:
        p = {"W": self._init_w(key, (self.n_in, self.n_out))}
        if self.has_bias:
            p["b"] = self._init_b((self.n_out,))
        return p

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # integer [B] or [B, T] input is ids, taken as they are: no guess.
        # Otherwise one-hot input: rank >= 3 ([B, T, V] sequences), or a
        # floating-point [B, V] matrix
        ids = jnp.issubdtype(x.dtype, jnp.integer) and x.ndim <= 2
        one_hot = (not ids and x.shape[-1] == self.n_in and self.n_in > 1
                   and (x.ndim >= 3
                        or jnp.issubdtype(x.dtype, jnp.floating)))
        if one_hot:
            idx = jnp.argmax(x, axis=-1).astype(jnp.int32)
        else:
            idx = x.astype(jnp.int32)
            if idx.ndim > 1 and idx.shape[-1] == 1:
                idx = idx[..., 0]
        pol = get_policy()
        emb = params["W"][idx]
        if "b" in params:
            emb = emb + params["b"]
        if self.output_scale != 1.0:
            emb = emb * jnp.asarray(self.output_scale, emb.dtype)
        return self.act_fn()(emb.astype(pol.output_dtype)), state


@register_config("AutoEncoder")
@dataclasses.dataclass
class AutoEncoder(PretrainLayer):
    """Denoising autoencoder (reference nn/layers/feedforward/autoencoder/AutoEncoder.java):
    encode = act(xW+b), decode = act(hW^T+vb); pretrain objective = reconstruction loss
    on corrupted input (corruption_level = probability an input unit is zeroed)."""

    corruption_level: float = 0.3
    sparsity: float = 0.0
    pretrain_loss_fn: str = "mse"

    def init_params(self, key, itype: InputType) -> dict:
        k1, _ = jax.random.split(key)
        return {"W": self._init_w(k1, (self.n_in, self.n_out)),
                "b": self._init_b((self.n_out,)),
                "vb": self._init_b((self.n_in,))}

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, rng, train)
        return self.act_fn()(_dense(params, x)), state

    def encode(self, params, x):
        return self.act_fn()(_dense(params, x))

    def decode(self, params, h):
        return self.act_fn()(jnp.matmul(h, params["W"].T) + params["vb"])

    def pretrain_loss(self, params, x, *, rng):
        if self.corruption_level > 0:
            keep = jax.random.bernoulli(rng, 1.0 - self.corruption_level, x.shape)
            corrupted = jnp.where(keep, x, 0.0)
        else:
            corrupted = x
        recon = self.decode(params, self.encode(params, corrupted))
        loss = get_loss(self.pretrain_loss_fn)(x, recon, lambda v: v, None)
        if self.sparsity > 0:
            h_mean = jnp.mean(self.encode(params, x), axis=0)
            rho = self.sparsity
            h_c = jnp.clip(h_mean, 1e-7, 1 - 1e-7)
            loss = loss + jnp.sum(rho * jnp.log(rho / h_c)
                                  + (1 - rho) * jnp.log((1 - rho) / (1 - h_c)))
        return loss


@register_config("RBM")
@dataclasses.dataclass
class RBM(PretrainLayer):
    """Restricted Boltzmann machine trained by CD-k
    (reference nn/layers/feedforward/rbm/RBM.java, 501 LoC: gibbhVh, contrastive
    divergence in computeGradientAndScore). Supervised forward = propUp.

    The CD gradient is not a true autodiff gradient; pretraining computes the CD-k
    parameter deltas directly (positive phase minus negative phase), expressed as a
    surrogate loss whose autodiff gradient equals the CD update so the standard
    pretrain machinery applies.
    """

    k: int = 1
    visible_unit: str = "binary"   # binary | gaussian
    hidden_unit: str = "binary"

    def init_params(self, key, itype: InputType) -> dict:
        return {"W": self._init_w(key, (self.n_in, self.n_out)),
                "b": self._init_b((self.n_out,)),     # hidden bias
                "vb": self._init_b((self.n_in,))}     # visible bias

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, rng, train)
        return self.act_fn()(_dense(params, x)), state

    def prop_up(self, params, v):
        return jax.nn.sigmoid(jnp.matmul(v, params["W"]) + params["b"])

    def prop_down(self, params, h):
        pre = jnp.matmul(h, params["W"].T) + params["vb"]
        return pre if self.visible_unit == "gaussian" else jax.nn.sigmoid(pre)

    def pretrain_loss(self, params, x, *, rng):
        def sample(key, p):
            return jax.random.bernoulli(key, p).astype(p.dtype)

        keys = jax.random.split(rng, 2 * self.k + 1)
        ph = self.prop_up(params, x)
        # Gibbs chain, gradients stopped (CD treats the chain as data)
        vk = x
        hk = sample(keys[0], ph)
        for i in range(self.k):
            vk = self.prop_down(params, hk)
            if self.visible_unit == "binary":
                vk = sample(keys[2 * i + 1], vk)
            hk_prob = self.prop_up(params, vk)
            hk = sample(keys[2 * i + 2], hk_prob) if i < self.k - 1 else hk_prob
        vk = jax.lax.stop_gradient(vk)
        hk = jax.lax.stop_gradient(hk)
        ph_d = jax.lax.stop_gradient(ph)
        n = x.shape[0]
        # Surrogate whose gradient wrt params is the negative CD update:
        #   dW = <v+ h+> - <v- h->, dvb = <v+> - <v->, db = <h+> - <h->
        w_term = (jnp.sum(jnp.matmul(x.T, ph_d) * params["W"])
                  - jnp.sum(jnp.matmul(vk.T, hk) * params["W"])) / n
        vb_term = jnp.sum((jnp.mean(x, 0) - jnp.mean(vk, 0)) * params["vb"])
        b_term = jnp.sum((jnp.mean(ph_d, 0) - jnp.mean(hk, 0)) * params["b"])
        return -(w_term + vb_term + b_term)
