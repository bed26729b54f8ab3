"""The indexer's scores against the chip's roofline: 16 heads of 64 at
every causal pair forward and, for the two gradient products, at the selected
pairs alone (costs_sparse.py; Keye-VL-2.0-30B-A3B's widths), the larger of
the two bounds, over the device time of the events under ``attn/indexer``:
the score kernel, the selection (``attn/indexer/select``, which requires no
operation and is in the denominator: it is what the indexer costs), the
kernel that sums the core's probabilities for the indexer's loss, and the
scores' backward. The indexer's three projections are dense products of
``attn`` (``kernel.dense_roofline``)."""
import costs
import costs_sparse
import scope_reduce


def read(ctx):
    kw = costs_sparse.indexer_kwargs(ctx)
    ms = kw and scope_reduce.scope_ms(ctx, costs_sparse.INDEXER)
    if not ms:
        return None
    batch = int(ctx["cell"]["traffic"]["batch"])
    least = kw["n_layers"] * costs.least_seconds(
        *costs_sparse.index_scores(batch, kw["index_n_heads"],
                                   kw["index_head_dim"], kw["seq_len"],
                                   kw["index_topk"]), ctx["peak"])
    return costs.Share(least_s=least, device_s=ms / 1e3)
