"""Latent attention's core against the chip's roofline: scores and values
at the causal half for every head of every decoder block, forward and two
gradient products (costs.py; 192-wide queries and keys, 128-wide values in
DeepSeek-V2-Lite), over the device time of the events under ``attn/core``.
Compute-bound at these shapes; the larger of the two bounds is taken. The
flash backward's recomputed scores are not counted as work."""
import costs
import scope_reduce


def read(ctx):
    ms = scope_reduce.scope_ms(ctx, scope_reduce.ATTENTION_CORE)
    if not ms:
        return None
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    tr = ctx["cell"]["traffic"]
    one = costs.least_seconds(
        *costs.attention_core(int(tr["batch"]), kw["n_heads"], kw["seq_len"],
                              kw["qk_nope_head_dim"] + kw["qk_rope_head_dim"],
                              kw["v_head_dim"]), ctx["peak"])
    return costs.Share(least_s=one * kw["n_layers"], device_s=ms / 1e3)
