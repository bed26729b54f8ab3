"""``kernel.full_core_roofline`` for a configuration whose other mixers are
Mamba-2 mixers: the causal, grouped core of the blocks whose letter of
``pattern`` is ``*`` against the chip's roofline, scores and values at the
causal pairs of every query head, forward and two gradient products
(``costs_window.masked_core``), the larger of the two bounds, over the
device time of the events under those blocks' ``attn/core``
(``costs_window.core_scope``). The accepted reader finds its blocks by
``layer_types``, which this configuration has not."""
import costs
import costs_ssd
import costs_window
import scope_reduce


def read(ctx):
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    blocks = costs_ssd.blocks_of(kw, costs_ssd.ATTENTION)
    ms = blocks and scope_reduce.scope_ms(ctx, costs_window.core_scope(blocks))
    if not ms:
        return None
    batch = int(ctx["cell"]["traffic"]["batch"])
    least = len(blocks) * costs.least_seconds(
        *costs_window.masked_core(batch, kw["n_heads"], kw["n_kv_heads"],
                                  kw["seq_len"], kw["head_dim"]),
        ctx["peak"])
    return costs.Share(least_s=least, device_s=ms / 1e3)
