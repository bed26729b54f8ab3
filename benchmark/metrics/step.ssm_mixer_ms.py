"""Device time of one train step under ``attn`` of the decoder blocks whose
letter of ``pattern`` is ``M``, forward and backward with the recomputed
forward (scope_reduce.py): the Mamba-2 mixers whole, their two projections,
taps, scan, gate and norm, beside the attention block's ``attn``."""
import costs_conv
import costs_ssd
import scope_reduce


def read(ctx):
    blocks = costs_ssd.blocks_of(ctx["cell"]["config"]["builder"]["kwargs"],
                                 costs_ssd.MAMBA)
    if not blocks:
        return None
    return scope_reduce.scope_ms(ctx, costs_conv.mixer_scope(blocks))
