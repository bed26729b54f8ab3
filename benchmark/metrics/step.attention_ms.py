"""Device time of one train step under the scope ``attn`` of every decoder
block, forward and backward: the norm before attention, the projections,
the rotary embedding, the core and the output projection
(scope_reduce.py)."""
import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms(ctx, scope_reduce.ATTENTION)
