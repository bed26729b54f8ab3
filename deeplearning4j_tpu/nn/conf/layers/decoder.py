"""One pre-norm decoder block whose parts are fields, and a norm layer.

``DecoderBlock`` is ``h = x + A(N(x)); y = h + F(N(h))`` with the norm ``N``,
the attention ``A`` and the feed-forward ``F`` each chosen by a field, so a
current language model is a list of these blocks with its published sizes
and not a class of its own:

* ``norm``: ``"rms"`` (no bias).
* ``attention``: ``"mla"``, latent attention: keys and values come from a
  ``kv_rank``-wide normed latent, every head's key carries one shared rotary
  part, queries and keys are ``qk_nope_dim + qk_rope_dim`` wide and values
  ``v_dim``; causal, through ``attention.attend``. ``rope_theta`` turns the
  rotary embedding on.
* ``ffn``: ``"swiglu"`` (``(silu(u Wg) * (u Wu)) Wd``, no biases) or
  ``"moe"``: softmax routing over ``n_experts`` router outputs, the
  ``experts_per_token`` largest taken greedily and weighted by their
  probability (not renormalised), a shared expert computed for every token,
  and the routed experts this chip holds (``experts_held``, a ``[first,
  end)`` range of expert ids; None: all) through the dropless grouped
  dispatch of ``moe.grouped_expert_ffn``. What absent experts would add is
  left out: a chip's share of an expert-parallel layer, without the
  exchange.

``norm`` and ``attention`` know one value each, the one a model here uses:
a second is a branch in ``_norm`` or ``attention_part`` beside its first
caller, not before it (``TransformerBlock`` is the layer-norm, one-head-width
block).

An expert layer's state carries ``aux_loss`` (the sequence-wise balance term
of DeepSeek-V2's ``seq_aux`` branch, over all router outputs; the fit loop
adds ``aux_loss_weight`` times it) and ``moe_rows`` (int32 [3]: pairs routed
here, rows the grouped products ran over, the largest expert's rows), which
the K-step program hands back with its losses.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common import at_least_f32
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import (
    apply_rope, attend, rms_norm, rope_inv_freq, yarn_mscale)
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.layers.feedforward import _dense
from deeplearning4j_tpu.nn.conf.layers.moe import grouped_expert_ffn
from deeplearning4j_tpu.nn.conf.serde import register_config

_NORMS, _ATTENTIONS, _FFNS = ("rms",), ("mla",), ("swiglu", "moe")


def _mm(x, w):
    """``x @ w`` in the policy's compute dtype, out in its output dtype: the
    dense layers' product without a bias."""
    return _dense({"W": w}, x)


def swiglu(u, w_gate, w_up, w_down):
    """``(silu(u Wg) * (u Wu)) Wd``; the gate's sigmoid in float32."""
    g = _mm(u, w_gate)
    act = jax.nn.silu(g.astype(at_least_f32(g.dtype))).astype(g.dtype)
    return _mm(act * _mm(u, w_up), w_down)


@register_config("RMSNorm")
@dataclasses.dataclass
class RMSNormLayer(FeedForwardLayer):
    """``x * rsqrt(mean(x^2) + eps) * g`` over the feature axis: the norm
    before a language model's head. Param: "g" [F]."""

    eps: float = 1e-6

    def set_n_in(self, itype: InputType) -> None:
        if not self.n_in:
            self.n_in = (itype.size if itype.kind == "recurrent"
                         else itype.flat_size())
        if not self.n_out:
            self.n_out = self.n_in

    def init_params(self, key, itype: InputType) -> dict:
        return {"g": jnp.ones((self.n_out,), jnp.float32)}

    def regularizable_params(self):
        return ()

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["g"], self.eps), state


@register_config("DecoderBlock")
@dataclasses.dataclass
class DecoderBlock(FeedForwardLayer):
    norm: str = "rms"
    norm_eps: float = 1e-6
    attention: str = "mla"
    n_heads: int = 4
    #: latent width, query/key widths without and with rotation,
    #: value width
    kv_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_dim: int = 0
    #: rotary embedding: None = none; ``rope_scaling`` as
    #: ``attention.rope_inv_freq`` takes it (YaRN also sets the score scale:
    #: ``mscale_all_dim``)
    rope_theta: Optional[float] = None
    rope_scaling: Optional[dict] = None
    ffn: str = "swiglu"
    ffn_hidden: int = 0
    #: "moe": router outputs, choices a token, a routed expert's width, the
    #: shared expert's width (0: none), the expert ids held here
    n_experts: int = 0
    experts_per_token: int = 1
    expert_hidden: int = 0
    shared_hidden: int = 0
    experts_held: Optional[list] = None
    aux_loss_weight: float = 0.001
    #: residual-stream blocks take no output nonlinearity (see
    #: MoETransformerBlock.activation)
    activation: Optional[str] = "identity"

    # ------------------------------------------------------------ geometry
    def __post_init__(self):
        for field, known in (("norm", _NORMS), ("attention", _ATTENTIONS),
                             ("ffn", _FFNS)):
            if getattr(self, field) not in known:
                raise ValueError(f"DecoderBlock.{field} = "
                                 f"{getattr(self, field)!r}; known: {known}")

    def set_n_in(self, itype: InputType) -> None:
        if not self.n_in:
            self.n_in = (itype.size if itype.kind == "recurrent"
                         else itype.flat_size())
        if not self.n_out:
            self.n_out = self.n_in

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def _held(self) -> tuple:
        first, end = self.experts_held or (0, self.n_experts)
        if not 0 <= first < end <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range within {self.n_experts} experts")
        return int(first), int(end)

    def _qk_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    def _score_scale(self) -> float:
        scale = self._qk_dim() ** -0.5
        sc = self.rope_scaling
        if sc and sc.get("mscale_all_dim"):
            scale *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
        return scale

    # -------------------------------------------------------------- params
    def init_params(self, key, itype: InputType) -> dict:
        F, H = self.n_out, self.n_heads
        ks = iter(jax.random.split(key, 16))
        w = lambda *shape: self._init_w(next(ks), shape)
        p = {}
        for n in ("norm1", "norm2"):
            p[n + "_g"] = jnp.ones((F,), jnp.float32)
        p["Wq"] = w(F, H * self._qk_dim())
        p["Wkva"] = w(F, self.kv_rank + self.qk_rope_dim)
        p["kv_norm_g"] = jnp.ones((self.kv_rank,), jnp.float32)
        p["Wkvb"] = w(self.kv_rank, H * (self.qk_nope_dim + self.v_dim))
        p["Wo"] = w(H * self.v_dim, F)
        if self.ffn == "swiglu":
            p["Wg"], p["Wu"] = w(F, self.ffn_hidden), w(F, self.ffn_hidden)
            p["Wd"] = w(self.ffn_hidden, F)
        else:
            first, end = self._held()
            G, He = end - first, self.expert_hidden
            stack = lambda a, b: jax.vmap(
                lambda k: self._init_w(k, (a, b)))(
                    jax.random.split(next(ks), G))
            p["Wr"] = w(F, self.n_experts)
            p["Eg"], p["Eu"], p["Ed"] = stack(F, He), stack(F, He), stack(He, F)
            if self.shared_hidden:
                p["Sg"], p["Su"] = (w(F, self.shared_hidden),
                                    w(F, self.shared_hidden))
                p["Sd"] = w(self.shared_hidden, F)
        return p

    def regularizable_params(self):
        return ("Wq", "Wkva", "Wkvb", "Wo", "Wg", "Wu", "Wd", "Eg",
                "Eu", "Ed", "Sg", "Su", "Sd")

    def init_state(self, itype: InputType) -> dict:
        if self.ffn != "moe":
            return {}
        return {"aux_loss": jnp.zeros((), jnp.float32),
                "moe_rows": jnp.zeros((3,), jnp.int32)}

    # --------------------------------------------------------------- parts
    def _norm(self, params, name, x):
        return rms_norm(x, params[name + "_g"], self.norm_eps)

    def attention_part(self, params, u, mask=None):
        """``A(u)``: u [B, T, F] normed input -> [B, T, F]."""
        B, T, _ = u.shape
        H = self.n_heads
        dn, dr, dv, r = (self.qk_nope_dim, self.qk_rope_dim, self.v_dim,
                         self.kv_rank)
        q = _mm(u, params["Wq"]).reshape(B, T, H, dn + dr)
        kva = _mm(u, params["Wkva"])
        c = rms_norm(kva[..., :r], params["kv_norm_g"], self.norm_eps)
        k_pe = kva[..., r:].reshape(B, T, 1, dr)
        kv = _mm(c, params["Wkvb"]).reshape(B, T, H, dn + dv)
        q_pe = q[..., dn:]
        if self.rope_theta:
            freq = rope_inv_freq(dr, self.rope_theta, self.rope_scaling)
            q_pe, k_pe = apply_rope(q_pe, freq), apply_rope(k_pe, freq)
        q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe, (B, T, H, dr))], axis=-1)
        v = kv[..., dn:]
        with jax.named_scope("core"):
            o = attend(q, k, v, True, mask, scale=self._score_scale())
        return _mm(o.reshape(B, T, H * dv), params["Wo"])

    def route(self, params, u):
        """-> (choice [B, T, k] int32 over all experts, weight [B, T, k],
        probs [B, T, E] float32): softmax over every router output, the k
        largest taken greedily, each weighted by its own probability."""
        f32 = at_least_f32(u.dtype)
        logits = jnp.matmul(u.astype(f32), params["Wr"].astype(f32),
                            precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        weight, choice = jax.lax.top_k(probs, self.experts_per_token)
        return choice.astype(jnp.int32), weight, probs

    def seq_aux_term(self, choice, probs):
        """DeepSeek-V2's ``seq_aux`` balance term before its weight: per
        sequence, ``sum_e f_e * P_e`` with ``f_e`` the share of the
        sequence's choices that fell on expert e times the number of experts
        and ``P_e`` the sequence's mean probability of e; then the mean over
        sequences."""
        E = self.n_experts
        T, k = choice.shape[1], choice.shape[2]
        hits = jnp.sum(jax.nn.one_hot(choice, E, dtype=probs.dtype),
                       axis=(1, 2))                       # [B, E]
        f = hits * (E / (T * k))
        return jnp.mean(jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))

    def routed_part(self, params, u2d, choice2d, weight2d):
        """The held experts' part of the layer for [S, F] tokens:
        ``(y [S, F], rows int32 [3])``."""
        return grouped_expert_ffn(u2d, choice2d, weight2d, params["Eg"],
                                  params["Eu"], params["Ed"], self._held()[0])

    def shared_part(self, params, u):
        return swiglu(u, params["Sg"], params["Su"], params["Sd"])

    # --------------------------------------------------------------- apply
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        B, T, F = x.shape
        with jax.named_scope("attn"):
            h = x + self.attention_part(params, self._norm(params, "norm1", x),
                                        mask)
        u = self._norm(params, "norm2", h)
        if self.ffn == "swiglu":
            with jax.named_scope("ffn"):
                y = h + swiglu(u, params["Wg"], params["Wu"], params["Wd"])
            return self.act_fn()(y), state
        with jax.named_scope("moe/router"):
            choice, weight, probs = self.route(params, u)
            aux = self.seq_aux_term(choice, probs)
        k = self.experts_per_token
        routed, rows = self.routed_part(params, u.reshape(B * T, F),
                                        choice.reshape(B * T, k),
                                        weight.reshape(B * T, k))
        f = routed.reshape(B, T, F)
        if self.shared_hidden:
            with jax.named_scope("moe/shared"):
                f = f + self.shared_part(params, u)
        new_state = {"aux_loss": aux if train else jnp.zeros_like(aux),
                     "moe_rows": rows}
        return self.act_fn()(h + f), new_state
