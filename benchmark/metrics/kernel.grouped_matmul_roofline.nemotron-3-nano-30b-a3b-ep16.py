"""``kernel.grouped_matmul_roofline`` for experts of two matrices: the
(token, choice) pairs the program counted as routed to the experts it holds
(``dl4j_moe_routed_rows_total``, a step's mean over the window, all expert
layers), through the squared ReLU's two products and their four gradients
(``costs_ssd.relu2_experts``), over the device time of the events under
``moe/experts``. The accepted reader counts three matrices
(``costs.grouped_ffn``), half again this configuration's work."""
import costs
import costs_ssd
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
        *costs_ssd.relu2_experts(rows / steps, kw["hidden_size"],
                                 kw["moe_intermediate_size"], end - first),
        ctx["peak"]) for rows in routed.values())
    return costs.Share(least_s=least, device_s=ms / 1e3)
