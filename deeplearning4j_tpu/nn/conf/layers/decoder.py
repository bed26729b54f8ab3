"""One decoder block whose parts are fields, and a norm layer.

``DecoderBlock`` is ``h = x + A(N(x)); y = h + F(N(h))`` with the norm ``N``,
the attention ``A`` and the feed-forward ``F`` each chosen by a field, so a
current language model is a list of these blocks with its published sizes
and not a class of its own:

* ``norm``: ``"rms"`` (no bias). ``norm_placement``: ``"pre"`` as above, or
  ``"sandwich"``, a norm before *and after* each branch, ``h = x +
  N(A(N(x))); y = h + N(F(N(h)))``, four scales of their own.
* ``attention``: ``"mla"``, latent attention: keys and values come from a
  ``kv_rank``-wide normed latent, every head's key carries one shared rotary
  part, queries and keys are ``qk_nope_dim + qk_rope_dim`` wide and values
  ``v_dim``; causal, through ``attention.attend``. ``rope_theta`` turns the
  rotary embedding on. ``"gqa"``: separate query, key and value projections
  to ``n_heads`` query and ``n_kv_heads`` key/value heads ``head_dim`` wide
  (query head h reads key/value head ``h // (n_heads // n_kv_heads)``), an
  RMS norm over each head's query and key (one scale vector for all heads),
  the rotary embedding on the whole head in halves where ``rope_theta`` is
  set, a causal mask cut to the last ``window`` keys where that is set, and
  (``output_gate``, on by default) an output gate ``sigmoid(u Wz)`` on the
  core's result. ``index_heads`` > 0 adds a learned indexer that chooses
  each query's keys (``ops/indexer.py``): on the block's normed input with
  the gradient stopped, ``qI = rope(u WqI)`` (``index_heads`` heads
  ``index_dim`` wide), ``kI = rope(rms(u WkI))`` (ONE key head),
  ``w = u Ww * index_heads^-0.5 * index_dim^-0.5``, ``I[t, s] = sum_j
  w[t, j] relu(qI[t, j] . kI[s])``; the core runs over each query's
  ``min(t + 1, index_topk)`` keys with the largest ``I`` alone, and the
  indexer's four leaves learn from ``index_loss``, the mean over queries of
  ``KL(p || softmax_chosen I)`` with ``p`` the core's own head-averaged
  probabilities (a constant), and from nothing else; the trunk gets no
  gradient from it. ``"short_conv"``: no keys, values or core, a gated
  short convolution along the sequence (``short_conv``): ``[Bg | Cg | X] =
  u W_in`` (three ``F``-wide streams), ``V = Bg * X``, a causal depthwise
  convolution of ``conv_kernel`` taps a channel, ``Z_t = sum_j w_j
  V_{t-L+1+j}`` (the last tap weighs the token itself; positions before the
  first read 0), ``A(u) = (Cg * Z) W_out``. ``"mamba2"``: a Mamba-2
  state-space mixer (``_mamba_part``): ``[z | xBC | dt] = u W_in``, a causal
  depthwise convolution of ``conv_kernel`` taps over ``xBC`` with a bias and
  SiLU, ``[x | B | C]`` from it (``ssm_heads`` heads ``ssm_head_dim`` wide;
  ``ssm_groups`` groups of ``ssm_state``-wide B and C), ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``, the scan ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` in
  chunks of ``ssm_chunk`` tokens (``ops/ssd.py``), ``y * silu(z)`` RMS-normed
  over each of ``ssm_groups`` groups of channels, then ``W_out``.
  ``"none"``: no mixer; the block is ``y = x + F(N(x))``. ``qk_norm`` False
  drops ``"gqa"``'s per-head query and key norms.
* ``ffn``: ``"swiglu"`` (``(silu(u Wg) * (u Wu)) Wd``, no biases), ``"none"``
  (no feed-forward; the block is ``y = x + A(N(x))``) or
  ``"moe"``: routing over ``n_experts`` router outputs, a shared expert
  computed for every token, and the routed experts this chip holds
  (``experts_held``, a ``[first, end)`` range of expert ids; None: all)
  through the dropless grouped dispatch of ``moe.grouped_expert_ffn``
  (``dispatch_eighths``: its usual buffer's rows in eighths of all (token,
  choice) pairs, 2 by default). What absent experts would add is left out:
  a chip's share of an expert-parallel layer, without the exchange. ``router``: ``"softmax"``,
  the ``experts_per_token`` largest probabilities taken greedily and
  weighted by themselves (``router_renorm``: divided by their sum), with
  an auxiliary loss; or
  ``"sigmoid_bias"``: sigmoid scores, the largest of ``score + bias`` taken,
  weighted by their scores (without the bias) renormalised to
  ``route_scale``, no auxiliary loss, and the bias moved against each
  output's load after every training step. ``expert_act``: every expert,
  routed and shared, a gated SiLU (``"swiglu"``, three matrices) or
  ``"relu2"``, ``relu(u Wu)^2 Wd`` (two).

A single-branch block has the one norm of its branch (``norm1`` before a
mixer, ``norm2`` before a feed-forward) and no params or state of the other.

Each field's second value is a branch in ``_norm``, ``attention_part`` or
``route`` beside its first caller, not a class of its own
(``TransformerBlock`` is the layer-norm, one-head-width block).

An expert layer's state carries ``moe_rows`` (int32 [3]: pairs routed here,
rows the grouped products ran over, the largest expert's rows), which the
K-step program hands back with its losses, and by its router ``aux_loss``
(the sequence-wise balance term of DeepSeek-V2's ``seq_aux`` branch, over all
router outputs; the fit loop adds ``aux_loss_weight`` times it) or
``router_bias`` (float32 [n_experts], from 0): state a step changes without
a gradient, as batch norm's running statistics are. A block with an indexer
also carries ``index_loss``, which the fit loop adds ``index_loss_weight``
times (``multilayer._aux_losses``). After a training step
``bias += d - mean(d)`` with ``d = bias_update_rate * sign(mean(c) - c)``
and ``c`` the step's (token, choice) pairs on each output, over this chip's
tokens (a deployment sums ``c`` over its chips first).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.common import at_least_f32, get_policy
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import (
    apply_rope, attend, rms_norm, rope_inv_freq, yarn_mscale)
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.layers.feedforward import _dense
from deeplearning4j_tpu.nn.conf.layers.moe import grouped_expert_ffn
from deeplearning4j_tpu.nn.conf.serde import register_config
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.observability.names import (
    ATTN_INDEX_PAIRS_SCORED_TOTAL, ATTN_PAIRS_SELECTED_TOTAL,
    ATTN_SCORE_ENTRIES_COMPUTED_TOTAL, ATTN_SCORE_ENTRIES_VISIBLE_TOTAL,
    SHORT_CONV_TOKENS_TOTAL, SSM_TOKENS_TOTAL,
)
from deeplearning4j_tpu.ops import indexer, remat, ssd

_NORMS, _FFNS = ("rms",), ("swiglu", "moe", "none")
_ATTENTIONS = ("mla", "gqa", "short_conv", "mamba2", "none")
_PLACEMENTS, _ROUTERS = ("pre", "sandwich"), ("softmax", "sigmoid_bias")
_EXPERT_ACTS = ("swiglu", "relu2")

# what a block counts a step (``DecoderBlock.step_counts``): the fit loop
# books each of its dispatched steps under the block's index in the network
for _name, _help in (
        (ATTN_SCORE_ENTRIES_COMPUTED_TOTAL, "attention score entries the "
         "forward cores of dispatched steps computed (the flash kernel's "
         "tiles x their area, or the whole square on the XLA path), by "
         "decoder block"),
        (ATTN_SCORE_ENTRIES_VISIBLE_TOTAL, "attention score entries the "
         "masks of the same cores leave visible, by decoder block"),
        (ATTN_INDEX_PAIRS_SCORED_TOTAL, "(query, key) pairs the indexers of "
         "dispatched steps scored (every causal pair of a sequence), by "
         "decoder block"),
        (ATTN_PAIRS_SELECTED_TOTAL, "(query, key) pairs the same indexers "
         "selected for the core (min(t + 1, topk) a query), by decoder "
         "block"),
        (SHORT_CONV_TOKENS_TOTAL, "tokens the short-convolution mixers of "
         "dispatched steps ran through, by decoder block"),
        (SSM_TOKENS_TOTAL, "tokens the state-space scans of dispatched steps "
         "ran through, by decoder block")):
    global_registry().counter(_name, _help)


def _mm(x, w):
    """``x @ w`` in the policy's compute dtype, out in its output dtype: the
    dense layers' product without a bias."""
    return _dense({"W": w}, x)


def swiglu(u, w_gate, w_up, w_down):
    """``(silu(u Wg) * (u Wu)) Wd``; the gate's sigmoid in float32."""
    g = _mm(u, w_gate)
    act = jax.nn.silu(g.astype(at_least_f32(g.dtype))).astype(g.dtype)
    return _mm(act * _mm(u, w_up), w_down)


def relu2(u, w_up, w_down):
    """``relu(u Wu)^2 Wd``; the square in float32."""
    up = _mm(u, w_up)
    r = jax.nn.relu(up.astype(at_least_f32(up.dtype)))
    return _mm((r * r).astype(up.dtype), w_down)


def short_conv(u, w_in, conv_w, w_out):
    """``(Cg * Z) W_out`` with ``[Bg | Cg | X] = u W_in`` and ``Z_t = sum_j
    conv_w[j] * (Bg * X)_{t-L+1+j}`` over the ``L`` taps of ``conv_w`` [L,
    F], one filter a channel, causal (positions before the first read 0).
    The gates and the taps, one fusion, run under the scope ``conv`` in
    float32; the result is in the policy's output dtype."""
    bcx = _mm(u, w_in)
    T, L = u.shape[1], conv_w.shape[0]
    with jax.named_scope("conv"):
        f32 = at_least_f32(bcx.dtype)
        b, c, x = jnp.split(bcx.astype(f32), 3, axis=-1)
        v = jnp.pad(b * x, ((0, 0), (L - 1, 0), (0, 0)))
        z = sum(conv_w[j].astype(f32) * v[:, j:j + T] for j in range(L))
        y = (c * z).astype(bcx.dtype)
    return _mm(y, w_out)


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
    norm_placement: str = "pre"
    attention: str = "mla"
    n_heads: int = 4
    #: "gqa": key/value heads, the heads' width, the keys a query sees
    #: counting itself (None: all before it)
    n_kv_heads: int = 0
    head_dim: int = 0
    window: Optional[int] = None
    #: "gqa": ``sigmoid(u Wz)`` on the core's result (False: no ``Wz``)
    output_gate: bool = True
    #: "gqa": the indexer's heads (0: none), their width, the keys a query
    #: keeps, and the weight of its loss in the step's
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_loss_weight: float = 1.0
    #: "gqa": an RMS norm over each head's query and key (False: none)
    qk_norm: bool = True
    #: "short_conv", "mamba2": the convolution's taps, the token's own
    #: counted
    conv_kernel: int = 3
    #: "mamba2": heads, a head's width, the state's width, the groups of B
    #: and C (head h reads group ``h // (ssm_heads / ssm_groups)``; also the
    #: groups of the gated norm), tokens a chunk of the scan
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
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
    #: every expert, routed and shared: "swiglu" or "relu2"
    expert_act: str = "swiglu"
    router: str = "softmax"
    aux_loss_weight: float = 0.001
    #: "softmax": the chosen probabilities divided by their sum
    router_renorm: bool = False
    #: rows of the usual dispatch buffer in eighths of all (token, choice)
    #: pairs (``moe.grouped_expert_ffn``): 2 holds twice an even router's
    #: share of an eighth of the experts; a layer that gets more takes the
    #: slow full-size buffer for that step
    dispatch_eighths: int = 2
    #: "sigmoid_bias": what the chosen scores add up to, and the bias's step
    route_scale: float = 1.0
    bias_update_rate: float = 0.001
    #: residual-stream blocks take no output nonlinearity (see
    #: MoETransformerBlock.activation)
    activation: Optional[str] = "identity"

    # ------------------------------------------------------------ geometry
    def __post_init__(self):
        for field, known in (("norm", _NORMS), ("attention", _ATTENTIONS),
                             ("ffn", _FFNS), ("router", _ROUTERS),
                             ("norm_placement", _PLACEMENTS),
                             ("expert_act", _EXPERT_ACTS)):
            if getattr(self, field) not in known:
                raise ValueError(f"DecoderBlock.{field} = "
                                 f"{getattr(self, field)!r}; known: {known}")
        if self.index_heads and (self.attention != "gqa" or self.window
                                 or self.index_dim < 2
                                 or self.index_topk < 1):
            raise ValueError(
                "an indexer goes with \"gqa\" attention without a window, "
                f"and needs index_dim and index_topk: {self.index_heads} "
                f"heads of {self.index_dim}, topk {self.index_topk}, "
                f"attention {self.attention!r}, window {self.window}")
        if self.attention == "short_conv" and (self.window
                                               or self.conv_kernel < 1):
            raise ValueError(
                "a short convolution takes no window and at least one tap: "
                f"window {self.window}, conv_kernel {self.conv_kernel}")
        if self.attention == self.ffn == "none":
            raise ValueError("a block needs a mixer or a feed-forward")
        if "none" in (self.attention, self.ffn) and (
                self.norm_placement != "pre"):
            raise ValueError("a single-branch block takes \"pre\" norms: "
                             f"norm_placement {self.norm_placement!r}")
        if self.attention == "mamba2" and (
                self.window or self.index_heads or self.rope_theta
                or self.conv_kernel < 1 or self.ssm_chunk < 1
                or not self.ssm_heads or not self.ssm_head_dim
                or not self.ssm_state or self.ssm_groups < 1
                or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                "a Mamba-2 mixer takes no window, indexer or rotary "
                "embedding, and needs heads over its groups, a width, a "
                f"state, taps and a chunk: window {self.window}, index_heads "
                f"{self.index_heads}, rope_theta {self.rope_theta}, "
                f"{self.ssm_heads} heads of {self.ssm_head_dim} over "
                f"{self.ssm_groups} groups, state {self.ssm_state}, "
                f"conv_kernel {self.conv_kernel}, chunk {self.ssm_chunk}")

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
        if self.attention == "gqa":
            return self.head_dim
        return self.qk_nope_dim + self.qk_rope_dim

    def _v_dim(self) -> int:
        return self.head_dim if self.attention == "gqa" else self.v_dim

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
        branches = (("norm1", self.attention), ("norm2", self.ffn))
        for n in [n for n, part in branches if part != "none"] + (
                ["post1", "post2"] if self.norm_placement == "sandwich"
                else []):
            p[n + "_g"] = jnp.ones((F,), jnp.float32)
        if self.attention == "short_conv":
            p["W_in"] = w(F, 3 * F)
            p["conv_w"] = w(self.conv_kernel, F)
            p["W_out"] = w(F, F)
        elif self.attention == "mamba2":
            p.update(self._mamba_params(next(ks)))
        elif self.attention != "none":
            p["Wq"] = w(F, H * self._qk_dim())
        if self.attention == "gqa":
            D, G = self.head_dim, self.n_kv_heads
            if not G or H % G:
                raise ValueError(f"{H} query heads over {G} key/value heads")
            p["Wk"], p["Wv"] = w(F, G * D), w(F, G * D)
            if self.output_gate:
                p["Wz"] = w(F, H * D)
            p["Wo"] = w(H * D, F)
            if self.qk_norm:
                p["q_norm_g"] = jnp.ones((D,), jnp.float32)
                p["k_norm_g"] = jnp.ones((D,), jnp.float32)
        elif self.attention == "mla":
            p["Wkva"] = w(F, self.kv_rank + self.qk_rope_dim)
            p["kv_norm_g"] = jnp.ones((self.kv_rank,), jnp.float32)
            p["Wkvb"] = w(self.kv_rank, H * (self.qk_nope_dim + self.v_dim))
            p["Wo"] = w(H * self.v_dim, F)
        if self.ffn == "swiglu":
            p["Wg"], p["Wu"] = w(F, self.ffn_hidden), w(F, self.ffn_hidden)
            p["Wd"] = w(self.ffn_hidden, F)
        elif self.ffn == "moe":
            first, end = self._held()
            G, He = end - first, self.expert_hidden
            stack = lambda a, b: jax.vmap(
                lambda k: self._init_w(k, (a, b)))(
                    jax.random.split(next(ks), G))
            gated = self.expert_act == "swiglu"
            p["Wr"] = w(F, self.n_experts)
            if gated:
                p["Eg"] = stack(F, He)
            p["Eu"], p["Ed"] = stack(F, He), stack(He, F)
            if self.shared_hidden:
                if gated:
                    p["Sg"] = w(F, self.shared_hidden)
                p["Su"] = w(F, self.shared_hidden)
                p["Sd"] = w(self.shared_hidden, F)
        if self.index_heads:
            J, E = self.index_heads, self.index_dim
            p["WqI"], p["WkI"], p["Ww"] = w(F, J * E), w(F, E), w(F, J)
            p["kI_norm_g"] = jnp.ones((E,), jnp.float32)
        return p

    def _mamba_params(self, key) -> dict:
        """A Mamba-2 mixer's leaves: ``W_in`` [F, 2 d + 2 G N + H] (``d`` =
        heads x width; ``z``, ``xBC``, ``dt`` in that order), the taps
        ``conv_w`` [L, d + 2 G N] and their bias ``conv_b`` (0), ``dt_bias``
        (softplus^-1 of a step drawn log-uniform in [0.001, 0.1], floored at
        1e-4), ``A_log`` (log U[1, 16]), ``D`` (1), the gated norm's scale
        ``ssm_norm_g`` (1) and ``W_out`` [d, F]."""
        F, H = self.n_out, self.ssm_heads
        d = H * self.ssm_head_dim
        conv_dim = d + 2 * self.ssm_groups * self.ssm_state
        k_in, k_conv, k_dt, k_a, k_out = jax.random.split(key, 5)
        lo, hi = jnp.log(1e-3), jnp.log(1e-1)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(k_dt, (H,)) * (hi - lo)
                                 + lo), 1e-4)
        return {"W_in": self._init_w(k_in, (F, d + conv_dim + H)),
                "conv_w": self._init_w(k_conv, (self.conv_kernel, conv_dim)),
                "conv_b": jnp.zeros((conv_dim,), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(k_a, (H,), minval=1.0,
                                                    maxval=16.0)),
                "D": jnp.ones((H,), jnp.float32),
                "ssm_norm_g": jnp.ones((d,), jnp.float32),
                "W_out": self._init_w(k_out, (d, F))}

    def regularizable_params(self):
        return ("Wq", "Wkva", "Wkvb", "Wk", "Wv", "Wz", "Wo", "Wg", "Wu",
                "Wd", "Eg", "Eu", "Ed", "Sg", "Su", "Sd", "WqI", "WkI", "Ww",
                "W_in", "conv_w", "W_out")

    def init_state(self, itype: InputType) -> dict:
        index = ({"index_loss": jnp.zeros((), jnp.float32)}
                 if self.index_heads else {})
        if self.ffn != "moe":
            return index
        rows = {"moe_rows": jnp.zeros((3,), jnp.int32), **index}
        if self.router == "sigmoid_bias":
            return {"router_bias": jnp.zeros((self.n_experts,), jnp.float32),
                    **rows}
        return {"aux_loss": jnp.zeros((), jnp.float32), **rows}

    # --------------------------------------------------------------- parts
    def _norm(self, params, name, x):
        return rms_norm(x, params[name + "_g"], self.norm_eps)

    def _index_part(self, params, u):
        """The indexer's operands from the normed input, no gradient to it:
        ``(qI [B, T, J, E], kI [B, T, E], w [B, T, J] float32)``."""
        B, T, _ = u.shape
        J, E = self.index_heads, self.index_dim
        u = jax.lax.stop_gradient(u)
        qi = _mm(u, params["WqI"]).reshape(B, T, J, E)
        ki = rms_norm(_mm(u, params["WkI"]), params["kI_norm_g"],
                      self.norm_eps).reshape(B, T, 1, E)
        if self.rope_theta:
            freq = rope_inv_freq(E, self.rope_theta, self.rope_scaling)
            qi, ki = apply_rope(qi, freq, True), apply_rope(ki, freq, True)
        w = _mm(u, params["Ww"]).astype(jnp.float32) * (J * E) ** -0.5
        return qi, ki.reshape(B, T, E), w

    def _gqa_part(self, params, u, mask):
        """-> ``(A(u), index_loss or None)``."""
        B, T, _ = u.shape
        H, G, D = self.n_heads, self.n_kv_heads, self.head_dim
        qk = lambda t, g: (rms_norm(t, params[g], self.norm_eps)
                           if self.qk_norm else t)
        q = qk(_mm(u, params["Wq"]).reshape(B, T, H, D), "q_norm_g")
        k = qk(_mm(u, params["Wk"]).reshape(B, T, G, D), "k_norm_g")
        v = _mm(u, params["Wv"]).reshape(B, T, G, D)
        if self.output_gate:
            z = _mm(u, params["Wz"])
        if self.rope_theta:
            freq = rope_inv_freq(D, self.rope_theta, self.rope_scaling)
            q, k = apply_rope(q, freq, True), apply_rope(k, freq, True)
        index_loss = None
        if self.index_heads:
            # the indexer's projections are dense products of ``attn``; its
            # scope holds the kernels alone (scores, selection, loss)
            qi, ki, w = self._index_part(params, u)
            with jax.named_scope("indexer"):
                scores = indexer.index_scores(qi, ki, w)
                with jax.named_scope("select"):
                    select, lse_i = indexer.select_topk(scores,
                                                        self.index_topk)
                    # a checkpointed layer keeps both (ops/remat.py): the
                    # recomputed forward does not select a second time
                    select = checkpoint_name(select, remat.SELECT)
                    lse_i = checkpoint_name(lse_i, remat.SELECT_LSE)
            with jax.named_scope("core"):
                o, lse = attend(q, k, v, True, mask, select=select,
                                with_lse=True)
            with jax.named_scope("indexer"):
                index_loss = indexer.index_kl(qi, ki, w, scores, select,
                                              lse_i, q, k, lse, D ** -0.5)
        else:
            with jax.named_scope("core"):
                o = attend(q, k, v, True, mask, window=self.window)
        o = o.reshape(B, T, H * D)
        if self.output_gate:
            o = o * jax.nn.sigmoid(
                z.astype(at_least_f32(z.dtype))).astype(z.dtype)
        return _mm(o, params["Wo"]), index_loss

    def _mamba_part(self, params, u):
        """A Mamba-2 mixer: the two projections under ``attn``; everything
        from ``W_in``'s output to the gated, normed ``y`` under ``attn/ssd``
        in float32 (the scan's products in the policy's compute dtype). On a
        TPU that is two Pallas kernels, one each way
        (``ssd.mamba_core``), which run under ``attn/ssd/scan``; where
        their gate refuses (the CPU, a GSPMD jit, a geometry they do not
        take), the statement in ``jax.numpy``, ``_mamba_groups``."""
        zxd = _mm(u, params["W_in"])
        with jax.named_scope("ssd"):
            core = ssd.Core(self.ssm_groups, self.ssm_heads // self.ssm_groups,
                            self.ssm_head_dim, self.ssm_state, self.ssm_chunk,
                            self.conv_kernel, self.norm_eps,
                            get_policy().compute_dtype)
            if ssd.core_kernels_ok(zxd, core):
                y = ssd.mamba_core(zxd, *(params[n] for n in (
                    "conv_w", "conv_b", "dt_bias", "A_log", "D",
                    "ssm_norm_g")), core)
            else:
                y = self._mamba_groups(params, zxd)
        return _mm(y, params["W_out"])

    def _mamba_groups(self, params, zxd):
        """``_mamba_part``'s core in ``jax.numpy`` from ``W_in``'s output
        ``zxd`` (the statement the kernels are held to), the chunked scan
        under ``scan`` (``ssd.ssd_scan``, itself in ``jax.numpy``: no
        kernel runs here). Nothing in it mixes the groups (a group's
        channels of ``x``, its ``B`` and ``C``, its heads' steps, its
        channels of ``z`` and of the norm), so it runs one group at a
        time (``lax.map``, each group checkpointed): a block's backward
        holds one group's intermediates, not all of them."""
        B, T, _ = zxd.shape
        H, G, N = self.ssm_heads, self.ssm_groups, self.ssm_state
        d = H * self.ssm_head_dim
        cuts = (d, 2 * d, 2 * d + G * N, 2 * d + 2 * G * N)
        by_group = lambda a: jnp.moveaxis(
            a.reshape(*a.shape[:-1], G, a.shape[-1] // G), -2, 0)
        z, x, b, c, dt = map(by_group, jnp.split(zxd, cuts, axis=-1))
        xbc = (d, d + G * N)
        taps = map(by_group, jnp.split(params["conv_w"], xbc, axis=-1))
        bias = map(by_group, jnp.split(params["conv_b"], xbc, axis=-1))
        heads = (params[n].reshape(G, H // G)
                 for n in ("dt_bias", "A_log", "D"))
        y = jax.lax.map(jax.checkpoint(self._mamba_group), (
            z, x, b, c, dt, *taps, *bias, *heads,
            params["ssm_norm_g"].reshape(G, d // G)))
        return jnp.moveaxis(y, 0, 2).reshape(B, T, d)

    def _mamba_group(self, args):
        """One group's part of ``_mamba_groups``: z, x [B, T, d / G], b, c [B,
        T, N], dt [B, T, H / G] and the group's leaves -> the gated, normed
        ``y`` [B, T, d / G] in ``z``'s dtype."""
        (z, x, b, c, dt, wx, wb, wc, bx, bb, bc, dt_bias, a_log, dd,
         g) = args
        B, T, _ = x.shape
        f32, cd = at_least_f32(z.dtype), get_policy().compute_dtype
        L = self.conv_kernel

        def conv(v, w, bias):
            v = jnp.pad(v.astype(f32), ((0, 0), (L - 1, 0), (0, 0)))
            return jax.nn.silu(bias.astype(f32) + sum(
                w[j].astype(f32) * v[:, j:j + T] for j in range(L))).astype(cd)

        x, b, c = conv(x, wx, bx), conv(b, wb, bb), conv(c, wc, bc)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        with jax.named_scope("scan"):
            y = ssd.ssd_scan(x.reshape(B, T, -1, self.ssm_head_dim), dt,
                             -jnp.exp(a_log.astype(f32)), b[:, :, None],
                             c[:, :, None], dd, self.ssm_chunk)
        y = y.reshape(B, T, -1) * jax.nn.silu(z.astype(f32))
        return rms_norm(y, g, self.norm_eps).astype(z.dtype)

    def attention_part(self, params, u, mask=None):
        """``A(u)``: u [B, T, F] normed input -> [B, T, F]; a block with an
        indexer returns ``(A(u), index_loss)``."""
        if self.attention == "short_conv":
            return short_conv(u, params["W_in"], params["conv_w"],
                              params["W_out"])
        if self.attention == "mamba2":
            return self._mamba_part(params, u)
        if self.attention == "gqa":
            a, index_loss = self._gqa_part(params, u, mask)
            return (a, index_loss) if self.index_heads else a
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

    def route(self, params, u, bias=None):
        """-> (choice [B, T, k] int32 over all experts, weight [B, T, k],
        probs [B, T, E] float32): softmax over every router output, the k
        largest taken greedily, each weighted by its own probability
        (``router_renorm``: divided by the sum of the k). With
        the ``"sigmoid_bias"`` router: sigmoid scores, the k largest of
        ``score + bias`` taken, weighted by their scores renormalised to
        ``route_scale`` (the bias chooses and weighs nothing)."""
        f32 = at_least_f32(u.dtype)
        logits = jnp.matmul(u.astype(f32), params["Wr"].astype(f32),
                            precision=jax.lax.Precision.HIGHEST)
        if self.router == "sigmoid_bias":
            scores = jax.nn.sigmoid(logits)
            _, choice = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias),
                self.experts_per_token)
            weight = jnp.take_along_axis(scores, choice, axis=-1)
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                               + 1e-20) * self.route_scale
            return choice.astype(jnp.int32), weight, scores
        probs = jax.nn.softmax(logits, axis=-1)
        weight, choice = jax.lax.top_k(probs, self.experts_per_token)
        if self.router_renorm:
            weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
        return choice.astype(jnp.int32), weight, probs

    def next_bias(self, bias, choice):
        """The ``"sigmoid_bias"`` router's bias after a step that made
        ``choice``: against each output's load, re-centred."""
        load = jnp.sum(jax.nn.one_hot(choice.reshape(-1), self.n_experts,
                                      dtype=jnp.float32), axis=0)
        d = self.bias_update_rate * jnp.sign(jnp.mean(load) - load)
        return bias + d - jnp.mean(d)

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
        return grouped_expert_ffn(u2d, choice2d, weight2d, params.get("Eg"),
                                  params["Eu"], params["Ed"], self._held()[0],
                                  self.dispatch_eighths)

    def shared_part(self, params, u):
        if self.expert_act == "relu2":
            return relu2(u, params["Su"], params["Sd"])
        return swiglu(u, params["Sg"], params["Su"], params["Sd"])

    # --------------------------------------------------------------- apply
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        B, T, F = x.shape
        sandwich = self.norm_placement == "sandwich"
        index, h = {}, x
        if self.attention != "none":
            with jax.named_scope("attn"):
                a = self.attention_part(params,
                                        self._norm(params, "norm1", x), mask)
                if self.index_heads:
                    a, index_loss = a
                    index = {"index_loss": (index_loss if train
                                            else jnp.zeros_like(index_loss))}
                h = x + (self._norm(params, "post1", a) if sandwich else a)
        if self.ffn == "none":
            return self.act_fn()(h), {**state, **index}
        u = self._norm(params, "norm2", h)
        if self.ffn == "swiglu":
            with jax.named_scope("ffn"):
                f = swiglu(u, params["Wg"], params["Wu"], params["Wd"])
                y = h + (self._norm(params, "post2", f) if sandwich else f)
            return self.act_fn()(y), {**state, **index}
        with jax.named_scope("moe/router"):
            choice, weight, probs = self.route(params, u,
                                               state.get("router_bias"))
            if self.router == "softmax":
                aux = self.seq_aux_term(choice, probs)
        k = self.experts_per_token
        routed, rows = self.routed_part(params, u.reshape(B * T, F),
                                        choice.reshape(B * T, k),
                                        weight.reshape(B * T, k))
        f = routed.reshape(B, T, F)
        if self.shared_hidden:
            with jax.named_scope("moe/shared"):
                f = f + self.shared_part(params, u)
        if sandwich:
            f = self._norm(params, "post2", f)
        if self.router == "sigmoid_bias":
            bias = state["router_bias"]
            if train:
                with jax.named_scope("update"):
                    bias = self.next_bias(bias, choice)
            new_state = {"router_bias": bias, "moe_rows": rows, **index}
        else:
            new_state = {"aux_loss": aux if train else jnp.zeros_like(aux),
                         "moe_rows": rows, **index}
        return self.act_fn()(h + f), new_state

    def attn_score_entries(self, batch: int, seq: int, dtype) -> tuple:
        """``(computed, visible)`` score entries of one step's forward core
        over ``batch`` sequences of ``seq`` tokens in ``dtype``: what the
        flash kernel's plan computes under this block's mask and what the
        mask leaves visible (``pallas_kernels.flash_score_entries``). A
        block with an indexer computes the causal plan's tiles and leaves
        the selected pairs visible; a short convolution, a Mamba-2 mixer
        and a block without a mixer have no core and compute none."""
        from deeplearning4j_tpu.ops.pallas_kernels import flash_score_entries

        if not self._has_core():
            return 0, 0
        computed, visible = flash_score_entries(
            seq, self._qk_dim(), self._v_dim(), dtype, self.window)
        heads = batch * self.n_heads
        if self.index_heads:
            visible = self._index_pairs(seq)[1]
        return heads * computed, heads * visible

    def remat_kept_bytes(self, batch: int, seq: int, dtype) -> dict:
        """Bytes by name (``ops/remat.py::KEPT``) that this block keeps from
        its forward to its backward under ``gradient_checkpointing``, besides
        its input, for one step over ``batch`` sequences of ``seq`` tokens in
        ``dtype``: the core's output and log-sum-exp where the flash kernels
        engage forward and backward on this device, an indexer's int8
        selection and its log-sum-exp; a block without a core keeps
        nothing."""
        from deeplearning4j_tpu.ops.pallas_kernels import flash_kept_bytes

        if not self._has_core():
            return {}
        out, lse = flash_kept_bytes(batch, seq, self.n_heads, self._v_dim(),
                                    dtype)
        kept = {remat.CORE_OUT: out, remat.CORE_LSE: lse}
        if self.index_heads:
            kept[remat.SELECT] = batch * seq * seq
            kept[remat.SELECT_LSE] = batch * seq * 4
        return kept

    def step_counts(self, batch: int, seq: int, dtype) -> dict:
        """What one step over ``batch`` sequences of ``seq`` tokens in
        ``dtype`` counts in this block, by counter name, and only what is
        not zero: the tokens a short convolution or a state-space scan runs
        through; the score entries a core computes and leaves visible
        (``attn_score_entries``), and an indexer's (query, key) pairs,
        every causal one scored and query t's ``min(t + 1, index_topk)``
        selected. A block without a mixer counts nothing. The fit loop
        books each a dispatched step under the block's index."""
        counts = {}
        if self.attention == "short_conv":
            counts[SHORT_CONV_TOKENS_TOTAL] = batch * seq
        elif self.attention == "mamba2":
            counts[SSM_TOKENS_TOTAL] = batch * seq
        computed, visible = self.attn_score_entries(batch, seq, dtype)
        if computed:
            counts[ATTN_SCORE_ENTRIES_COMPUTED_TOTAL] = computed
            counts[ATTN_SCORE_ENTRIES_VISIBLE_TOTAL] = visible
            if self.index_heads:
                scored, selected = self._index_pairs(seq)
                counts[ATTN_INDEX_PAIRS_SCORED_TOTAL] = batch * scored
                counts[ATTN_PAIRS_SELECTED_TOTAL] = batch * selected
        return counts

    def _index_pairs(self, seq: int) -> tuple:
        """``(scored, selected)`` (query, key) pairs of an indexer over one
        sequence of ``seq`` tokens."""
        k = min(self.index_topk, seq)
        return seq * (seq + 1) // 2, k * (k + 1) // 2 + (seq - k) * k

    def _has_core(self) -> bool:
        return self.attention in ("mla", "gqa")
