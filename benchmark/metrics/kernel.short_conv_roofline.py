"""The gated short convolution against the chip's roofline: ``V = Bg * X``,
the causal depthwise taps and ``Cg * Z`` of every ``conv`` block, forward
and backward, for the tokens the program ran through each
(``dl4j_short_conv_tokens_total``, a step's mean over the window), the
larger of the two bounds (costs_conv.py: bandwidth-bound, 11 tensors of
tokens x width), over the device time of the events under ``attn/conv``.
The two projections beside it are dense products of ``attn``
(``kernel.dense_roofline``); a checkpointed layer's recomputed forward is
time, not work."""
import costs
import costs_conv
import scope_reduce


def read(ctx):
    ms = scope_reduce.scope_ms(ctx, costs_conv.SCOPE)
    tokens = scope_reduce.by_layer(ctx, costs_conv.TOKENS)
    steps = ctx["window"]["steps"]
    if not ms or not tokens or not steps:
        return None
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    least = sum(costs.least_seconds(
        *costs_conv.gated_conv(n / steps, kw["hidden_size"],
                               kw["conv_kernel"]), ctx["peak"])
        for n in tokens.values())
    return costs.Share(least_s=least, device_s=ms / 1e3)
