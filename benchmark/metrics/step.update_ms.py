"""Device time of one train step in the updater's own operations: under the
``update`` scope and holding no convolution or dot (span_reduce.py)."""
import span_reduce


def read(ctx):
    return span_reduce.phase_ms(ctx, "update")
