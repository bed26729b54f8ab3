"""The attention core over a learned selection against the chip's roofline:
scores and values at the SELECTED pairs of every decoder block (``min(t + 1,
topk)`` keys a query), 32 query heads over 4 key/value heads in
Keye-VL-2.0-30B-A3B, forward and two gradient products (costs_sparse.py), the
larger of the two bounds, over the device time of the events under
``attn/core``. What a plan computes at pairs the selection hides (a
masked-dense core computes every causal tile) is not work, and neither are
the backward's recomputed scores: they are time, and lower the share."""
import costs
import costs_sparse
import scope_reduce


def read(ctx):
    kw = costs_sparse.indexer_kwargs(ctx)
    ms = kw and scope_reduce.scope_ms(ctx, scope_reduce.ATTENTION_CORE)
    if not ms:
        return None
    batch = int(ctx["cell"]["traffic"]["batch"])
    least = kw["n_layers"] * costs.least_seconds(
        *costs_sparse.selected_core(batch, kw["n_heads"], kw["n_kv_heads"],
                                    kw["seq_len"], kw["head_dim"],
                                    kw["index_topk"]), ctx["peak"])
    return costs.Share(least_s=least, device_s=ms / 1e3)
