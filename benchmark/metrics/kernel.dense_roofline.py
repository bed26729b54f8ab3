"""A language model's dense products against the chip's peak, read by scope
and not by who runs the operation: the required operations of the products
that no kernel metric of its own accounts for (projections, gates, the dense
feed-forward, the shared expert, the router, the head: the entries of the
reference's ``layers()`` without a ``scope``) over the device time under
``attn``, ``ffn``, ``moe/shared``, ``moe/router`` and ``loss`` less the tagged
scopes (scope_reduce.py). A product moved from XLA into a kernel under
``attn`` stays in the denominator; norms, rotary embeddings, gates and a
recomputed forward in those scopes count as time and lower the share, which
is what they cost. Compute-bound at these shapes: the bound is the FLOP
peak, times the chips."""
import scope_reduce


def read(ctx):
    return scope_reduce.dense_share(ctx)
