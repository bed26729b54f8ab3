"""Operations and bytes of a causal attention core whose mask may be cut to a
window and whose key/value heads may be fewer than its query heads, from
shapes alone (``costs.py``'s rules: the products' 2 x multiply-accumulates,
forward and two gradient products; recomputed work is not counted).
``costs.least_seconds`` turns the pair into the roofline's time."""
from __future__ import annotations

SLIDING = "sliding_attention"


def visible_pairs(seq: int, window=None) -> int:
    """(query, key) pairs a causal mask leaves visible: query r sees its
    ``min(r + 1, window)`` last keys, itself counted."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def masked_core(batch: int, heads: int, kv_heads: int, seq: int, width: int,
                window=None, itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of the core for ``batch`` sequences, forward and
    backward: scores and values at the visible pairs of every query head,
    ``width`` wide each, and twice that for the gradients. Bytes: queries
    and outputs per query head, keys and values per key/value head (a group
    reads its head once), read or written once forward, and they and their
    gradients once backward."""
    flops = 3 * 2.0 * batch * heads * visible_pairs(seq, window) * 2 * width
    tensors = batch * seq * width * (2 * heads + 2 * kv_heads)
    return flops, 3 * tensors * itemsize


def block_windows(kwargs: dict) -> list:
    """The window of every decoder block the configuration holds, in order
    (None: full causal), from the builder's ``layer_types`` and
    ``sliding_window``; empty where the configuration names neither."""
    kinds = kwargs.get("layer_types")
    if not kinds or "sliding_window" not in kwargs:
        return []
    return [kwargs["sliding_window"] if k == SLIDING else None for k in kinds]


def core_scope(blocks) -> str:
    """Regular expression for ``scope_reduce.scope_ms``: the ``attn/core``
    scope of the decoder blocks numbered ``blocks`` (0-based; the network's
    layer 0 is the embedding, so block i is layer ``i + 1``). An ``op_name``
    holds the layer inside JAX's ``jvp(...)`` and ``transpose(jvp(...))``,
    with ``checkpoint/rematted_computation`` between it and the scope."""
    alt = "|".join(str(b + 1) for b in blocks)
    return rf"layer/({alt})_DecoderBlock\W(.*/)?attn/core(/|$)"
