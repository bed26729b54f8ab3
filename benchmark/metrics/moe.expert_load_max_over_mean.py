"""How uneven the routing was, worst expert layer: the busiest held expert's
rows, summed over the window's steps (``dl4j_moe_expert_rows_max_total``),
over the mean rows of a held expert in the same steps
(``dl4j_moe_routed_rows_total`` / experts held). 1 is even. Both are counters
booked step by step from the same groups, so the ratio is the steps' own
max over mean, weighted by their rows, whichever group ended the window."""
import scope_reduce


def read(ctx):
    routed = scope_reduce.by_layer(ctx, "dl4j_moe_routed_rows_total")
    largest = scope_reduce.by_layer(ctx, "dl4j_moe_expert_rows_max_total")
    if not routed or not largest:
        return None
    first, end = ctx["cell"]["config"]["builder"]["kwargs"]["experts_held"]
    ratios = [largest[layer] * (end - first) / rows
              for layer, rows in routed.items() if rows and layer in largest]
    return max(ratios) if ratios else None
