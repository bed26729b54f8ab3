"""What a checkpointed layer keeps besides its input.

``gradient_checkpointing`` wraps each layer application in
:func:`checkpoint_layer`: backward recomputes the layer's forward instead of
holding its activations. The attention kernels' own outputs are the
exception: they are small beside what they cost to make again, so the
kernels tag them (``jax.ad_checkpoint.checkpoint_name``, the identity
anywhere else) and the wrapper's policy keeps every value under one of
``KEPT`` until the layer's backward. The recomputed forward then runs
neither the flash core nor the selection; everything else (projections,
norms, the index scores, the indexer's loss, the feed-forward part) runs
again as before. A layer that emits none of the names keeps exactly its
input.
"""
from __future__ import annotations

import jax

#: the flash core's output [B, T, H, Dv] in the operands' dtype and its
#: float32 log-sum-exp [B * H, T] (``pallas_kernels._flash_fwd_rule`` and
#: ``_selected_fwd_rule``, where the tiled backward engages: its residuals)
CORE_OUT, CORE_LSE = "attn_core_out", "attn_core_lse"
#: an indexer's int8 selection [B, T, T] and the float32 log-sum-exp of each
#: query's chosen scores [B, T] (``indexer.select_topk``'s results, tagged
#: where ``DecoderBlock`` receives them)
SELECT, SELECT_LSE = "attn_select", "attn_select_lse"
KEPT = (CORE_OUT, CORE_LSE, SELECT, SELECT_LSE)


def checkpoint_layer(f):
    """``jax.checkpoint(f)`` that keeps the values named in ``KEPT``."""
    return jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
