"""DeepSeek-V2-Lite (arXiv:2405.04434; config.json of
huggingface.co/deepseek-ai/DeepSeek-V2-Lite) as a list of general layers.

Embedding, ``first_k_dense`` dense decoder blocks, expert decoder blocks, an
RMS norm and a bias-free head over integer labels: latent attention with YaRN
rotary frequencies, a SwiGLU feed-forward in the dense block, and in every
other block 64 routed experts of which a token takes 6 (softmax scores,
greedy, not renormalised) beside one shared SwiGLU as wide as the 2 shared
experts. Every width is an argument with the published value as its default;
``n_layers``, ``experts_held`` and ``vocab_rows`` say how much of the model
this chip holds (its share of an expert-parallel deployment, the layers left
out lying on further pipeline stages).

What "supported" covers: training through ``MultiLayerNetwork.fit_iterator``
with integer token ids ``[B, T]`` in and integer labels ``[B, T]`` out, on
one chip, an expert-parallel share without its exchange. Not serving (the
decode engine has no latent cache), not the exchange between chips.
"""
from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DecoderBlock, EmbeddingLayer, RMSNormLayer, RnnOutputLayer)
from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration

_YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
         "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
         "mscale_all_dim": 0.707}


def deepseek_v2_lite(n_layers: int = 27,
                     experts_held: Optional[Sequence[int]] = None,
                     vocab_rows: int = 102400, *,
                     hidden_size: int = 2048, n_heads: int = 16,
                     kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
                     qk_rope_head_dim: int = 64, v_head_dim: int = 128,
                     intermediate_size: int = 10944,
                     moe_intermediate_size: int = 1408,
                     n_router_outputs: int = 64, experts_per_token: int = 6,
                     n_shared_experts: int = 2, first_k_dense: int = 1,
                     rms_norm_eps: float = 1e-6, rope_theta: float = 10000.0,
                     rope_scaling: Optional[dict] = None,
                     aux_loss_weight: float = 0.001, seq_len: int = 4096,
                     learning_rate: float = 1e-4,
                     gradient_checkpointing: bool = True,
                     weight_init: str = "xavier",
                     seed: int = 12345) -> MultiLayerConfiguration:
    """``experts_held``: the ``[first, end)`` expert ids this chip holds of
    every expert layer (None: all ``n_router_outputs``); the router always
    scores all of them. ``vocab_rows``: rows of the embedding and columns of
    the head held here; ids and labels are drawn below it. ``rope_scaling``
    None is the published YaRN setting."""
    scaling = dict(_YARN if rope_scaling is None else rope_scaling)
    lb = (NeuralNetConfiguration.builder()
          .seed(seed)
          .learning_rate(learning_rate)
          .updater("adam")
          .weight_init(weight_init)
          .gradient_checkpointing(gradient_checkpointing)
          .list())
    lb.layer(EmbeddingLayer(n_in=vocab_rows, n_out=hidden_size,
                            has_bias=False, activation="identity"))
    attention = dict(
        norm="rms", norm_eps=rms_norm_eps, attention="mla", n_heads=n_heads,
        kv_rank=kv_lora_rank, qk_nope_dim=qk_nope_head_dim,
        qk_rope_dim=qk_rope_head_dim, v_dim=v_head_dim,
        rope_theta=rope_theta, rope_scaling=scaling)
    for i in range(n_layers):
        if i < first_k_dense:
            lb.layer(DecoderBlock(n_in=hidden_size, n_out=hidden_size,
                                  ffn="swiglu", ffn_hidden=intermediate_size,
                                  **attention))
        else:
            lb.layer(DecoderBlock(
                n_in=hidden_size, n_out=hidden_size, ffn="moe",
                n_experts=n_router_outputs,
                experts_per_token=experts_per_token,
                expert_hidden=moe_intermediate_size,
                shared_hidden=n_shared_experts * moe_intermediate_size,
                experts_held=(list(experts_held) if experts_held is not None
                              else None),
                aux_loss_weight=aux_loss_weight, **attention))
    lb.layer(RMSNormLayer(n_in=hidden_size, n_out=hidden_size,
                          eps=rms_norm_eps, activation="identity"))
    lb.layer(RnnOutputLayer(n_in=hidden_size, n_out=vocab_rows, loss="mcxent",
                            activation="softmax", has_bias=False))
    lb.set_input_type(InputType.recurrent(vocab_rows, seq_len))
    return lb.build()
