"""Recurrent layers: LSTM (Graves variant with peepholes), bidirectional LSTM,
RnnOutputLayer.

Reference: nn/layers/recurrent/LSTMHelpers.java (activateHelper:58,
backpropGradientHelper:248 — hand-written BPTT) and GravesLSTM/GravesBidirectionalLSTM
configs. TPU-native: the time recursion runs through the three-variant recurrent
engine in ``ops/lstm.py`` (fused scan / Pallas persistent cell / reference scan,
selected by ``DL4J_LSTM_IMPL`` + calibrated thresholds at trace time);
backprop-through-time is autodiff through the scan body or the kernel's custom
VJP — this *is* the accelerated LSTM path the cuDNN-helper seam
(CudnnLSTMHelper) would otherwise provide (SURVEY.md §2.3 note).

Layout: [batch, time, features] (reference uses [batch, features, time]).
Param names: "W" [n_in,4H] input weights, "RW" [H,4H] recurrent, "b" [4H],
"pI"/"pF"/"pO" [H] peepholes (Graves 2013). Gate order: input, forget, cell(g), output.
State pytree carries the streaming-inference hidden state for rnn_time_step
(reference rnnTimeStep:2196 stateMap) — functional instead of mutable.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.layers.feedforward import _dense
from deeplearning4j_tpu.nn.conf.serde import register_config
from deeplearning4j_tpu.ops.losses import get_loss
from deeplearning4j_tpu.ops.lstm import lstm_sequence
# back-compat alias: the scan implementation (now the engine's reference
# oracle) used to live here
from deeplearning4j_tpu.ops.lstm import lstm_scan as _lstm_scan  # noqa: F401

Array = jax.Array


@register_config("LSTM")
@dataclasses.dataclass
class LSTM(FeedForwardLayer):
    """Standard LSTM (no peepholes)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"
    peephole: bool = False

    def set_n_in(self, itype: InputType) -> None:
        if not self.n_in:
            self.n_in = itype.size if itype.kind == "recurrent" else itype.flat_size()

    def init_params(self, key, itype: InputType) -> dict:
        k1, k2 = jax.random.split(key)
        h = self.n_out
        b = jnp.zeros((4 * h,), jnp.float32)
        b = b.at[h:2 * h].set(self.forget_gate_bias_init)
        params = {"W": self._init_w(k1, (self.n_in, 4 * h)),
                  "RW": self._init_w(k2, (h, 4 * h)),
                  "b": b}
        if self.peephole:
            params["pI"] = jnp.zeros((h,), jnp.float32)
            params["pF"] = jnp.zeros((h,), jnp.float32)
            params["pO"] = jnp.zeros((h,), jnp.float32)
        return params

    def regularizable_params(self):
        return ("W", "RW")

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def _acts(self):
        from deeplearning4j_tpu.ops.activations import get_activation
        return get_activation(self.activation or "tanh"), get_activation(self.gate_activation)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # Training/inference over full sequences starts from zero state each batch
        # (reference LSTMHelpers.activateHelper); streaming state is apply_streaming.
        x = self.apply_dropout(x, rng, train)
        act, gate = self._acts()
        B = x.shape[0]
        zeros = jnp.zeros((B, self.n_out), x.dtype)
        ys, _ = lstm_sequence(params, x, act, gate, zeros, zeros,
                              self.peephole, mask,
                              act_name=self.activation or "tanh",
                              gate_name=self.gate_activation)
        return ys, state

    def apply_streaming(self, params, state, x, *, mask=None):
        """rnnTimeStep equivalent: carry (h,c) across calls (reference
        MultiLayerNetwork.rnnTimeStep:2196). Routed through the same engine
        as full sequences, so serving single steps take the fused cell and a
        T-step rnnTimeStep loop reproduces the fused-scan forward bitwise."""
        act, gate = self._acts()
        B = x.shape[0]
        h0 = state.get("h", jnp.zeros((B, self.n_out), x.dtype))
        c0 = state.get("c", jnp.zeros((B, self.n_out), x.dtype))
        ys, (h, c) = lstm_sequence(params, x, act, gate, h0, c0,
                                   self.peephole, mask,
                                   act_name=self.activation or "tanh",
                                   gate_name=self.gate_activation)
        return ys, {"h": h, "c": c}


@register_config("GravesLSTM")
@dataclasses.dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (Graves 2013; reference GravesLSTM.java)."""

    peephole: bool = True


@register_config("GravesBidirectionalLSTM")
@dataclasses.dataclass
class GravesBidirectionalLSTM(LSTM):
    """Bidirectional Graves LSTM (reference GravesBidirectionalLSTM.java). Output is the
    SUM of forward and backward passes, matching the reference's ADD mode."""

    peephole: bool = True

    def init_params(self, key, itype: InputType) -> dict:
        kf, kb = jax.random.split(key)
        fwd = LSTM.init_params(self, kf, itype)
        bwd = LSTM.init_params(self, kb, itype)
        return ({f"F{k}": v for k, v in fwd.items()}
                | {f"B{k}": v for k, v in bwd.items()})

    def regularizable_params(self):
        return ("FW", "FRW", "BW", "BRW")

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, rng, train)
        act, gate = self._acts()
        B = x.shape[0]
        zeros = jnp.zeros((B, self.n_out), x.dtype)
        fwd_p = {k[1:]: v for k, v in params.items() if k.startswith("F")}
        bwd_p = {k[1:]: v for k, v in params.items() if k.startswith("B")}
        names = dict(act_name=self.activation or "tanh",
                     gate_name=self.gate_activation)
        ys_f, _ = lstm_sequence(fwd_p, x, act, gate, zeros, zeros,
                                self.peephole, mask, **names)
        x_rev = jnp.flip(x, axis=1)
        mask_rev = jnp.flip(mask, axis=1) if mask is not None else None
        ys_b, _ = lstm_sequence(bwd_p, x_rev, act, gate, zeros, zeros,
                                self.peephole, mask_rev, **names)
        return ys_f + jnp.flip(ys_b, axis=1), state


@register_config("RnnOutput")
@dataclasses.dataclass
class RnnOutputLayer(FeedForwardLayer):
    """Time-distributed output layer with loss (reference nn/conf/layers/RnnOutputLayer.java):
    dense applied at every timestep of [B,T,F], loss masked by the time-series mask."""

    loss: str = "mcxent"
    #: False: logits = h W, no "b" leaf (current language-model heads)
    has_bias: bool = True

    def has_loss(self) -> bool:
        return True

    def set_n_in(self, itype: InputType) -> None:
        if not self.n_in:
            self.n_in = itype.size if itype.kind == "recurrent" else itype.flat_size()

    def init_params(self, key, itype: InputType) -> dict:
        p = {"W": self._init_w(key, (self.n_in, self.n_out))}
        if self.has_bias:
            p["b"] = self._init_b((self.n_out,))
        return p

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def preout(self, params, x):
        return _dense(params, x)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, rng, train)
        return self.act_fn()(_dense(params, x)), state

    def compute_loss(self, params, x, labels, mask=None) -> Array:
        return get_loss(self.loss)(labels, _dense(params, x), self.act_fn(), mask)
