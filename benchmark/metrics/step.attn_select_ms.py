"""Device time of one train step under ``attn/indexer/select`` of every
decoder block: the exact choice of each query's ``topk`` keys from its index
scores (a bisection over the scores' bit patterns and the tie cut), forward
and in the rematerialised forward of the backward pass (scope_reduce.py). No
operation is required of it."""
import costs_sparse
import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms(ctx, costs_sparse.SELECT)
