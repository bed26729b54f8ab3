"""The routed experts' grouped products against the chip's roofline: the
(token, choice) pairs the program counted as routed to the experts it holds
(``dl4j_moe_routed_rows_total``, a step's mean over the window, all expert
layers), through three products and their six gradients (costs.py), over
the device time of the events under ``moe/experts`` (the grouped products
and the gate's activation between them). The bound is the larger of
operations over peak and bytes over bandwidth. Rows of padding are not
work; what is recomputed is not counted."""
import costs
import scope_reduce


def read(ctx):
    ms = scope_reduce.scope_ms(ctx, scope_reduce.MOE_EXPERTS)
    routed = scope_reduce.by_layer(ctx, "dl4j_moe_routed_rows_total")
    steps = ctx["window"]["steps"]
    if not ms or not routed or not steps:
        return None
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    first, end = kw["experts_held"]
    least = sum(costs.least_seconds(
        *costs.grouped_ffn(rows / steps, kw["hidden_size"],
                           kw["moe_intermediate_size"], end - first),
        ctx["peak"]) for rows in routed.values())
    return costs.Share(least_s=least, device_s=ms / 1e3)
