"""Share of the score entries the attention cores computed that the
selection did not keep: 1 - ``dl4j_attn_score_entries_visible_total`` (for a
block with an indexer the selected pairs x heads) /
``dl4j_attn_score_entries_computed_total`` (what the core's plan computes)
over the window, all decoder blocks. A masked-dense core over 2,048 of up to
16,384 keys reads near 77; a plan that skips the tiles a selection leaves
empty lowers it."""
import costs_sparse
import scope_reduce


def read(ctx):
    if not costs_sparse.indexer_kwargs(ctx):
        return None
    visible = sum(scope_reduce.by_layer(
        ctx, "dl4j_attn_score_entries_visible_total").values())
    computed = sum(scope_reduce.by_layer(
        ctx, "dl4j_attn_score_entries_computed_total").values())
    if not visible or not computed:
        return None
    return 100.0 * (1.0 - visible / computed)
