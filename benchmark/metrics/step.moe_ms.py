"""Device time of one train step under the scopes ``moe/router``,
``moe/dispatch``, ``moe/experts`` and ``moe/shared`` of every expert layer,
forward and backward (scope_reduce.py)."""
import scope_reduce


def read(ctx):
    return scope_reduce.scope_ms(ctx, scope_reduce.MOE)
