"""Trinity-Mini (Arcee; ``model_type: "afmoe"``, config.json of
huggingface.co/arcee-ai/Trinity-Mini) as a list of general layers.

An embedding whose output is scaled by ``sqrt(hidden_size)``,
``n_dense_layers`` dense decoder blocks, expert decoder blocks, an RMS norm
and an untied bias-free head over integer labels. Every block has a norm
before and after each of its two branches; attention is 32 query heads over 4
key/value heads, an RMS norm over each head's query and key, an output gate,
and by ``layer_types`` either a 2,048-key window with rotary embedding or
full causal attention without any. An expert block routes 8 of 128 experts by
sigmoid scores plus a bias that only chooses, renormalises the chosen scores
to ``route_scale``, adds one shared expert, and moves the bias against each
expert's load after every step; there is no auxiliary loss. Every width is an
argument with the published value as its default; ``n_layers``,
``experts_held`` and ``vocab_rows`` say how much of the model this chip holds
(its share of an expert-parallel deployment, the layers left out lying on
further pipeline stages).

What "supported" covers: training through ``MultiLayerNetwork.fit_iterator``
with integer token ids ``[B, T]`` in and integer labels ``[B, T]`` out, on
one chip, an expert-parallel share without its exchange (the bias follows
this chip's tokens' loads, where a deployment sums them over its chips
first). Not serving (the decode engine's cache has one length for all
layers), not the exchange between chips, not packed documents.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DecoderBlock, EmbeddingLayer, RMSNormLayer, RnnOutputLayer)
from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration

SLIDING, FULL = "sliding_attention", "full_attention"


def trinity_mini(n_layers: int = 32,
                 experts_held: Optional[Sequence[int]] = None,
                 vocab_rows: int = 200192, *,
                 hidden_size: int = 2048, n_heads: int = 32,
                 n_kv_heads: int = 4, head_dim: int = 128,
                 sliding_window: int = 2048,
                 layer_types: Optional[Sequence[str]] = None,
                 global_attn_every_n_layers: int = 4,
                 intermediate_size: int = 6144,
                 moe_intermediate_size: int = 1024,
                 n_router_outputs: int = 128, experts_per_token: int = 8,
                 n_shared_experts: int = 1, n_dense_layers: int = 2,
                 route_scale: float = 2.826,
                 load_balance_coeff: float = 0.001,
                 rms_norm_eps: float = 1e-5, rope_theta: float = 10000.0,
                 mup_enabled: bool = True, seq_len: int = 8192,
                 learning_rate: float = 1e-4,
                 gradient_checkpointing: bool = True,
                 weight_init: str = "xavier",
                 seed: int = 12345) -> MultiLayerConfiguration:
    """``experts_held``: the ``[first, end)`` expert ids this chip holds of
    every expert layer (None: all ``n_router_outputs``); the router always
    scores all of them. ``vocab_rows``: rows of the embedding and columns of
    the head held here; ids and labels are drawn below it. ``layer_types``:
    one of ``"sliding_attention"``/``"full_attention"`` a layer held (None:
    every ``global_attn_every_n_layers``-th full, the published pattern)."""
    if layer_types is None:
        layer_types = [FULL if (i + 1) % global_attn_every_n_layers == 0
                       else SLIDING for i in range(n_layers)]
    if len(layer_types) != n_layers or set(layer_types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types {list(layer_types)} for {n_layers} "
                         "layers")
    lb = (NeuralNetConfiguration.builder()
          .seed(seed)
          .learning_rate(learning_rate)
          .updater("adam")
          .weight_init(weight_init)
          .gradient_checkpointing(gradient_checkpointing)
          .list())
    lb.layer(EmbeddingLayer(
        n_in=vocab_rows, n_out=hidden_size, has_bias=False,
        activation="identity",
        output_scale=math.sqrt(hidden_size) if mup_enabled else 1.0))
    for i, kind in enumerate(layer_types):
        sliding = kind == SLIDING
        block = dict(
            n_in=hidden_size, n_out=hidden_size, norm="rms",
            norm_eps=rms_norm_eps, norm_placement="sandwich",
            attention="gqa", n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, window=sliding_window if sliding else None,
            rope_theta=rope_theta if sliding else None)
        if i < n_dense_layers:
            lb.layer(DecoderBlock(ffn="swiglu", ffn_hidden=intermediate_size,
                                  **block))
        else:
            lb.layer(DecoderBlock(
                ffn="moe", router="sigmoid_bias", n_experts=n_router_outputs,
                experts_per_token=experts_per_token,
                expert_hidden=moe_intermediate_size,
                shared_hidden=n_shared_experts * moe_intermediate_size,
                experts_held=(list(experts_held) if experts_held is not None
                              else None),
                route_scale=route_scale, bias_update_rate=load_balance_coeff,
                **block))
    lb.layer(RMSNormLayer(n_in=hidden_size, n_out=hidden_size,
                          eps=rms_norm_eps, activation="identity"))
    lb.layer(RnnOutputLayer(n_in=hidden_size, n_out=vocab_rows, loss="mcxent",
                            activation="softmax", has_bias=False))
    lb.set_input_type(InputType.recurrent(vocab_rows, seq_len))
    return lb.build()
