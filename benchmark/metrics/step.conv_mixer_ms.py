"""Device time of one train step under ``attn`` of the decoder blocks
whose ``layer_types`` entry is ``conv``, forward and backward with the
recomputed forward (scope_reduce.py): the short-convolution mixers whole,
their two projections, gates and taps, beside the attention block's
``attn``."""
import costs_conv
import scope_reduce


def read(ctx):
    blocks = costs_conv.blocks_of(ctx["cell"]["config"]["builder"]["kwargs"],
                                  costs_conv.CONV)
    if not blocks:
        return None
    return scope_reduce.scope_ms(ctx, costs_conv.mixer_scope(blocks))
