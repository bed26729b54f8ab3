"""The masked, grouped attention core against the chip's roofline: scores
and values at each decoder block's own visible pairs (the causal half, or
the window's band on a sliding layer), 32 query heads over 4 key/value heads
in Trinity-Mini, forward and two gradient products (costs_window.py), over
the device time of the events under ``attn/core``. The larger of the two
bounds is taken. The flash backward's recomputed scores and the tiles'
masked entries are not counted as work."""
import costs
import costs_window
import scope_reduce


def read(ctx):
    ms = scope_reduce.scope_ms(ctx, scope_reduce.ATTENTION_CORE)
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    windows = costs_window.block_windows(kw)
    if not ms or not windows:
        return None
    batch = int(ctx["cell"]["traffic"]["batch"])
    least = sum(costs.least_seconds(
        *costs_window.masked_core(batch, kw["n_heads"], kw["n_kv_heads"],
                                  kw["seq_len"], kw["head_dim"], w),
        ctx["peak"]) for w in windows)
    return costs.Share(least_s=least, device_s=ms / 1e3)
