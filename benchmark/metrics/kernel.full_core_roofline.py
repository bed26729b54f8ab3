"""The causal, grouped attention core of the ``full_attention`` blocks of a
configuration whose other mixers are short convolutions, against the chip's
roofline: scores and values at the causal pairs of every query head,
forward and two gradient products (``costs_window.masked_core``), the
larger of the two bounds, over the device time of the events under those
blocks' ``attn/core`` (``costs_window.core_scope``). The flash backward's
recomputed scores and the tiles' masked entries are not work."""
import costs
import costs_conv
import costs_window
import scope_reduce


def read(ctx):
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    blocks = costs_conv.blocks_of(kw, costs_conv.FULL)
    ms = blocks and scope_reduce.scope_ms(ctx, costs_window.core_scope(blocks))
    if not ms:
        return None
    batch = int(ctx["cell"]["traffic"]["batch"])
    least = len(blocks) * costs.least_seconds(
        *costs_window.masked_core(batch, kw["n_heads"], kw["n_kv_heads"],
                                  kw["seq_len"], kw["head_dim"]),
        ctx["peak"])
    return costs.Share(least_s=least, device_s=ms / 1e3)
