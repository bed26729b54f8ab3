"""Share of the score entries the attention cores computed that their masks
hid: 1 - ``dl4j_attn_score_entries_visible_total`` /
``dl4j_attn_score_entries_computed_total`` over the window, all decoder
blocks (the forward's tiles x their area; the backward computes the same
tiles). What a tile on the diagonal or on the window's edge computes beyond
its visible half, and anything a plan fails to skip."""
import scope_reduce


def read(ctx):
    visible = sum(scope_reduce.by_layer(
        ctx, "dl4j_attn_score_entries_visible_total").values())
    computed = sum(scope_reduce.by_layer(
        ctx, "dl4j_attn_score_entries_computed_total").values())
    if not visible or not computed:
        return None
    return 100.0 * (1.0 - visible / computed)
