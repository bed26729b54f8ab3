"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye; ``model_type:
"KeyeVL2"``, config.json of huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B) as
a list of general layers.

An unscaled embedding, ``n_layers`` alike pre-norm expert decoder blocks, an
RMS norm and an untied bias-free head over integer labels. A block's
attention is 32 query heads over 4 key/value heads of 128 with an RMS norm
over each head's query and key and the rotary embedding in halves (theta
1e7), no output gate, and a DeepSeek-Sparse-Attention indexer
(``sa_config``): 16 heads of 64 over one shared key head score every causal
pair, each query keeps its 2,048 best keys, the core runs over those alone,
and the indexer learns from the core's own probabilities through a loss of
its own (``DecoderBlock.index_heads``, ``ops/indexer.py``). The feed-forward
routes 8 of 128 experts 768 wide by softmax probabilities divided by their
sum, with no shared expert, balanced by the sequence-wise auxiliary term.
Every width is an argument with the published value as its default;
``n_layers``, ``experts_held`` and ``vocab_rows`` say how much of the model
this chip holds (its share of an expert-parallel deployment, the layers left
out lying on further pipeline stages).

What "supported" covers: training the language model through
``MultiLayerNetwork.fit_iterator`` with integer token ids ``[B, T]`` in and
integer labels ``[B, T]`` out, on one chip, an expert-parallel share without
its exchange. Text positions only (the three ``mrope_section`` axes then
carry one position: an ordinary rotary embedding). Not the vision tower, not
serving (no indexer key cache, no selection in the paged core), not
selection under sequence parallelism.
"""
from __future__ import annotations

from typing import Optional, Sequence

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DecoderBlock, EmbeddingLayer, RMSNormLayer, RnnOutputLayer)
from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration


#: eighths of all (token, choice) pairs the usual dispatch buffer holds. With
#: unscaled embeddings and unit-norm queries and keys a block's attention
#: output outweighs its token's embedding fivefold at initialisation, the
#: router sees nearly the same input for every token, and most tokens choose
#: the same few experts: an expert layer then gets about one eighth of all
#: pairs for each of those experts that it holds (two at a time in one layer
#: of a few), not the even eighth in all. Three eighths hold a layer with
#: three of them (PERF.md §6, PR 35: at two the full-size buffer ran in
#: 10–30 % of a layer's steps and a seed's rate swung by 2 %).
DISPATCH_EIGHTHS = 3


def keye_vl2_lm(n_layers: int = 48,
                experts_held: Optional[Sequence[int]] = None,
                vocab_rows: int = 151936, *,
                hidden_size: int = 2048, n_heads: int = 32,
                n_kv_heads: int = 4, head_dim: int = 128,
                index_n_heads: int = 16, index_head_dim: int = 64,
                index_topk: int = 2048, index_loss_weight: float = 1.0,
                moe_intermediate_size: int = 768,
                n_router_outputs: int = 128, experts_per_token: int = 8,
                norm_topk_prob: bool = True, aux_loss_weight: float = 0.001,
                rms_norm_eps: float = 1e-6, rope_theta: float = 1e7,
                seq_len: int = 16384, learning_rate: float = 1e-5,
                gradient_checkpointing: bool = True,
                weight_init: str = "xavier",
                seed: int = 12345) -> MultiLayerConfiguration:
    """``experts_held``: the ``[first, end)`` expert ids this chip holds of
    every layer (None: all ``n_router_outputs``); the router always scores
    all of them. ``vocab_rows``: rows of the embedding and columns of the
    head held here; ids and labels are drawn below it. ``index_n_heads`` 0
    builds the blocks without the indexer (a dense causal core)."""
    lb = (NeuralNetConfiguration.builder()
          .seed(seed)
          .learning_rate(learning_rate)
          .updater("adam")
          .weight_init(weight_init)
          .gradient_checkpointing(gradient_checkpointing)
          .list())
    lb.layer(EmbeddingLayer(n_in=vocab_rows, n_out=hidden_size,
                            has_bias=False, activation="identity"))
    for _ in range(n_layers):
        lb.layer(DecoderBlock(
            n_in=hidden_size, n_out=hidden_size, norm="rms",
            norm_eps=rms_norm_eps, attention="gqa", n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, output_gate=False,
            rope_theta=rope_theta, index_heads=index_n_heads,
            index_dim=index_head_dim, index_topk=index_topk,
            index_loss_weight=index_loss_weight,
            ffn="moe", router="softmax", router_renorm=norm_topk_prob,
            n_experts=n_router_outputs, experts_per_token=experts_per_token,
            expert_hidden=moe_intermediate_size, shared_hidden=0,
            experts_held=(list(experts_held) if experts_held is not None
                          else None),
            aux_loss_weight=aux_loss_weight,
            dispatch_eighths=DISPATCH_EIGHTHS))
    lb.layer(RMSNormLayer(n_in=hidden_size, n_out=hidden_size,
                          eps=rms_norm_eps, activation="identity"))
    lb.layer(RnnOutputLayer(n_in=hidden_size, n_out=vocab_rows, loss="mcxent",
                            activation="softmax", has_bias=False))
    lb.set_input_type(InputType.recurrent(vocab_rows, seq_len))
    return lb.build()
