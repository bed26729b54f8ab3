"""Operations and bytes of a gated short convolution, the token mixer of
LFM2's ``"conv"`` layers without its two projections, from shapes and
counts alone (``costs.py``'s rules: forward and backward, recomputed work
not counted); ``costs.least_seconds`` turns the pair into the roofline's
time. Also which decoder blocks a configuration's ``layer_types`` makes
convolutions or full attention, and the scope of their mixers."""
from __future__ import annotations

CONV, FULL = "conv", "full_attention"
#: the gates and the taps (``DecoderBlock``'s ``short_conv``)
SCOPE = r"/attn/conv(/|$)"
#: tokens the program ran through each convolution, by block
TOKENS = "dl4j_short_conv_tokens_total"


def gated_conv(tokens: float, width: int, taps: int,
               itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of ``tokens`` through one block's gates and
    causal depthwise convolution of ``taps`` taps over ``width`` channels,
    forward and backward. Operations: ``V = Bg * X``, the taps' multiply-adds
    and ``Cg * Z``, ``taps + 1`` multiply-accumulates a channel and token,
    and twice that for the gradients. Bytes: forward reads ``Bg``, ``Cg``,
    ``X`` and writes ``Cg * Z``; backward reads the output's gradient,
    ``Bg``, ``Cg``, ``X`` and writes their three gradients: 11 tensors of
    ``tokens x width`` (the taps' own ``taps x width`` are nothing beside
    them)."""
    flops = 3 * 2.0 * tokens * width * (taps + 1)
    return flops, 11 * tokens * width * itemsize


def blocks_of(kwargs: dict, kind: str) -> list:
    """The decoder blocks (0-based) whose ``layer_types`` entry is
    ``kind``; empty where the configuration names no ``conv`` layer (a
    configuration of another family)."""
    kinds = kwargs.get("layer_types") or []
    if CONV not in kinds:
        return []
    return [i for i, k in enumerate(kinds) if k == kind]


def mixer_scope(blocks) -> str:
    """Regular expression for ``scope_reduce.scope_ms``: the ``attn`` scope
    (the mixer whole: its projections, gates and taps) of the decoder blocks
    numbered ``blocks``, as ``costs_window.core_scope`` names their core."""
    alt = "|".join(str(b + 1) for b in blocks)
    return rf"layer/({alt})_DecoderBlock\W(.*/)?attn(/|$)"
