"""Device time of one train step under ``attn/core`` of the decoder blocks
whose ``layer_types`` entry is sliding, forward and backward (the flash
kernels and the head transposes around them; scope_reduce.py). Divided by
the number of such blocks it is what one windowed core costs."""
import costs_window
import scope_reduce


def read(ctx):
    windows = costs_window.block_windows(
        ctx["cell"]["config"]["builder"]["kwargs"])
    blocks = [i for i, w in enumerate(windows) if w is not None]
    if not blocks:
        return None
    return scope_reduce.scope_ms(ctx, costs_window.core_scope(blocks))
