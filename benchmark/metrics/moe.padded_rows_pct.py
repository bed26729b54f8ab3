"""Share of the rows the grouped expert products ran over that carried no
token: 1 - ``dl4j_moe_routed_rows_total`` / ``dl4j_moe_computed_rows_total``
over the window, all expert layers (a group's rows are computed in whole
row tiles)."""
import scope_reduce


def read(ctx):
    routed = sum(scope_reduce.by_layer(
        ctx, "dl4j_moe_routed_rows_total").values())
    computed = sum(scope_reduce.by_layer(
        ctx, "dl4j_moe_computed_rows_total").values())
    if not routed or not computed:
        return None
    return 100.0 * (1.0 - routed / computed)
