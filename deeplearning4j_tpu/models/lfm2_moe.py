"""LFM2-24B-A2B (LiquidAI; ``model_type: "lfm2_moe"``, config.json of
huggingface.co/LiquidAI/LFM2-24B-A2B) as a list of general layers.

An unscaled embedding, decoder blocks with one norm before each branch, an
RMS norm and an untied bias-free head over integer labels. By
``layer_types`` a block's token mixer is either a gated short convolution
(``"conv"``: ``DecoderBlock.attention = "short_conv"``, 3 taps a channel,
three 2,048-wide streams from one projection, no keys or values) or full
causal attention (``"full_attention"``: 32 query heads over 8 key/value
heads of 64, an RMS norm over each head's query and key, the rotary
embedding in halves at theta 1e6, no output gate). The first
``n_dense_layers`` blocks have a SwiGLU feed-forward 11,776 wide; the
others route 4 of 64 experts 1,536 wide by sigmoid scores plus a bias that
only chooses, renormalise the chosen scores to ``routed_scaling_factor``,
have no shared expert, and move the bias against each expert's load after
every step. Every width is an argument with the published value as its
default; ``layer_types``, ``n_dense_layers``, ``experts_held`` and
``vocab_rows`` say how much of the model this chip holds (its share of an
expert-parallel deployment, the layers left out lying on further pipeline
stages).

What "supported" covers: training through ``MultiLayerNetwork.fit_iterator``
with integer token ids ``[B, T]`` in and integer labels ``[B, T]`` out, on
one chip, an expert-parallel share without its exchange (the bias follows
this chip's tokens' loads). Not serving (the decode engine keeps no
convolution state), not the convolution's state across chips under sequence
parallelism, not packed documents (the convolution does not restart at a
document's first token).
"""
from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DecoderBlock, EmbeddingLayer, RMSNormLayer, RnnOutputLayer)
from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration

CONV, FULL = "conv", "full_attention"


def published_layer_types(n_layers: int = 40) -> list:
    """The source's pattern: full attention at layer 2 and every fourth
    after it, a short convolution elsewhere."""
    return [FULL if i >= 2 and (i - 2) % 4 == 0 else CONV
            for i in range(n_layers)]


def lfm2_moe(n_layers: int = 40,
             layer_types: Optional[Sequence[str]] = None,
             n_dense_layers: int = 2,
             experts_held: Optional[Sequence[int]] = None,
             vocab_rows: int = 65536, *,
             hidden_size: int = 2048, n_heads: int = 32,
             n_kv_heads: int = 8, head_dim: int = 64,
             conv_kernel: int = 3, intermediate_size: int = 11776,
             moe_intermediate_size: int = 1536,
             n_router_outputs: int = 64, experts_per_token: int = 4,
             routed_scaling_factor: float = 1.0,
             load_balance_coeff: float = 0.001,
             rms_norm_eps: float = 1e-5, rope_theta: float = 1e6,
             seq_len: int = 32768, learning_rate: float = 1e-5,
             gradient_checkpointing: bool = True,
             weight_init: str = "xavier",
             seed: int = 12345) -> MultiLayerConfiguration:
    """``layer_types``: ``"conv"`` or ``"full_attention"`` for each layer
    held (None: the published pattern over ``n_layers``).
    ``n_dense_layers``: the leading layers with the dense feed-forward.
    ``experts_held``: the ``[first, end)`` expert ids this chip holds of
    every expert layer (None: all ``n_router_outputs``); the router always
    scores all of them. ``vocab_rows``: rows of the embedding and columns
    of the head held here; ids and labels are drawn below it."""
    if layer_types is None:
        layer_types = published_layer_types(n_layers)
    if len(layer_types) != n_layers or set(layer_types) - {CONV, FULL}:
        raise ValueError(f"layer_types {list(layer_types)} for {n_layers} "
                         "layers")
    lb = (NeuralNetConfiguration.builder()
          .seed(seed)
          .learning_rate(learning_rate)
          .updater("adam")
          .weight_init(weight_init)
          .gradient_checkpointing(gradient_checkpointing)
          .list())
    lb.layer(EmbeddingLayer(n_in=vocab_rows, n_out=hidden_size,
                            has_bias=False, activation="identity"))
    for i, kind in enumerate(layer_types):
        if kind == CONV:
            mixer = dict(attention="short_conv", conv_kernel=conv_kernel)
        else:
            mixer = dict(attention="gqa", n_heads=n_heads,
                         n_kv_heads=n_kv_heads, head_dim=head_dim,
                         output_gate=False, rope_theta=rope_theta)
        block = dict(n_in=hidden_size, n_out=hidden_size, norm="rms",
                     norm_eps=rms_norm_eps, **mixer)
        if i < n_dense_layers:
            lb.layer(DecoderBlock(ffn="swiglu", ffn_hidden=intermediate_size,
                                  **block))
        else:
            lb.layer(DecoderBlock(
                ffn="moe", router="sigmoid_bias", n_experts=n_router_outputs,
                experts_per_token=experts_per_token,
                expert_hidden=moe_intermediate_size, shared_hidden=0,
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
