"""Nemotron-3-Nano-30B-A3B (NVIDIA; ``model_type: "nemotron_h"``, config.json
of huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) as a list of
general layers.

An unscaled embedding, decoder blocks of ONE branch each, ``y = x + P(N(x))``
with an RMS norm of its own before the branch, a final RMS norm and an
untied bias-free head over integer labels. The branch is the letter of
``hybrid_override_pattern`` that names the layer:

* ``M``: a Mamba-2 mixer (``DecoderBlock.attention = "mamba2"``): 64 heads
  of 64 over a 128-wide state, 8 groups of B and C, 4 causal taps with a
  bias over ``xBC``, the scan in chunks of 128, the output gated by
  ``silu(z)`` and RMS-normed over each of the 8 groups;
* ``E``: 128 routed experts 1,856 wide, 6 a token, by sigmoid scores plus a
  bias that only chooses, the chosen scores renormalised to
  ``routed_scaling_factor`` 2.5, beside a shared expert 3,712 wide; every
  expert is a squared ReLU ``relu(u Wu)^2 Wd``, and the bias moves against
  each expert's load after every step;
* ``*``: causal grouped attention, 32 query heads over 2 key/value heads of
  128, no rotary embedding, no q/k norm, no output gate.

Every width is an argument with the published value as its default;
``pattern``, ``experts_held`` and ``vocab_rows`` say how much of the model
this chip holds (its share of an expert-parallel deployment, the layers left
out lying on further pipeline stages).

What "supported" covers: training through ``MultiLayerNetwork.fit_iterator``
with integer token ids ``[B, T]`` in and integer labels ``[B, T]`` out, on
one chip, an expert-parallel share without its exchange (the bias follows
this chip's tokens' loads). Not serving (the decode engine keeps no
state-space state or taps), not the state across chips under sequence
parallelism, not packed documents (the scan and the taps do not restart at a
document's first token).
"""
from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DecoderBlock, EmbeddingLayer, RMSNormLayer, RnnOutputLayer)
from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: the source's ``hybrid_override_pattern``: 23 M, 23 E, 6 *
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def nemotron_h(pattern: Optional[str] = None,
               experts_held: Optional[Sequence[int]] = None,
               vocab_rows: int = 131072, *,
               hidden_size: int = 2688, n_heads: int = 32,
               n_kv_heads: int = 2, head_dim: int = 128,
               ssm_heads: int = 64, ssm_head_dim: int = 64,
               ssm_state: int = 128, ssm_groups: int = 8,
               ssm_chunk: int = 128, conv_kernel: int = 4,
               moe_intermediate_size: int = 1856,
               shared_intermediate_size: int = 3712,
               n_router_outputs: int = 128, experts_per_token: int = 6,
               routed_scaling_factor: float = 2.5,
               load_balance_coeff: float = 0.001,
               rms_norm_eps: float = 1e-5, seq_len: int = 16384,
               learning_rate: float = 1e-5,
               gradient_checkpointing: bool = True,
               weight_init: str = "xavier",
               seed: int = 12345) -> MultiLayerConfiguration:
    """``pattern``: a letter for each layer held, ``M``, ``E`` or ``*``
    (None: the published 52). ``experts_held``: the ``[first, end)`` expert
    ids this chip holds of every expert layer (None: all
    ``n_router_outputs``); the router always scores all of them.
    ``vocab_rows``: rows of the embedding and columns of the head held here;
    ids and labels are drawn below it."""
    if pattern is None:
        pattern = PUBLISHED_PATTERN
    if not pattern or set(pattern) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(f"pattern {pattern!r}: a letter a layer, "
                         f"{MAMBA!r}, {EXPERTS!r} or {ATTENTION!r}")
    lb = (NeuralNetConfiguration.builder()
          .seed(seed)
          .learning_rate(learning_rate)
          .updater("adam")
          .weight_init(weight_init)
          .gradient_checkpointing(gradient_checkpointing)
          .list())
    lb.layer(EmbeddingLayer(n_in=vocab_rows, n_out=hidden_size,
                            has_bias=False, activation="identity"))
    for letter in pattern:
        block = dict(n_in=hidden_size, n_out=hidden_size, norm="rms",
                     norm_eps=rms_norm_eps)
        if letter == MAMBA:
            lb.layer(DecoderBlock(
                attention="mamba2", ffn="none", ssm_heads=ssm_heads,
                ssm_head_dim=ssm_head_dim, ssm_state=ssm_state,
                ssm_groups=ssm_groups, ssm_chunk=ssm_chunk,
                conv_kernel=conv_kernel, **block))
        elif letter == ATTENTION:
            lb.layer(DecoderBlock(
                attention="gqa", ffn="none", n_heads=n_heads,
                n_kv_heads=n_kv_heads, head_dim=head_dim, qk_norm=False,
                output_gate=False, **block))
        else:
            lb.layer(DecoderBlock(
                attention="none", ffn="moe", router="sigmoid_bias",
                n_experts=n_router_outputs,
                experts_per_token=experts_per_token,
                expert_hidden=moe_intermediate_size,
                shared_hidden=shared_intermediate_size, expert_act="relu2",
                experts_held=(list(experts_held) if experts_held is not None
                              else None),
                route_scale=routed_scaling_factor,
                bias_update_rate=load_balance_coeff, **block))
    lb.layer(RMSNormLayer(n_in=hidden_size, n_out=hidden_size,
                          eps=rms_norm_eps, activation="identity"))
    lb.layer(RnnOutputLayer(n_in=hidden_size, n_out=vocab_rows, loss="mcxent",
                            activation="softmax", has_bias=False))
    lb.set_input_type(InputType.recurrent(vocab_rows, seq_len))
    return lb.build()
