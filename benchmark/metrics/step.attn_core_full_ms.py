"""Device time of one train step under ``attn/core`` of the decoder blocks
whose ``layer_types`` entry is full attention, forward and backward
(scope_reduce.py): beside ``step.attn_core_window_ms`` it says what the
window saves a block (0.44 of the visible pairs at 8,192 under a 2,048
window)."""
import costs_window
import scope_reduce


def read(ctx):
    windows = costs_window.block_windows(
        ctx["cell"]["config"]["builder"]["kwargs"])
    blocks = [i for i, w in enumerate(windows) if w is None]
    if not blocks:
        return None
    return scope_reduce.scope_ms(ctx, costs_window.core_scope(blocks))
