"""Device time of one train step in forward operations: under a ``layer/*``
or ``loss`` scope and outside ``transpose(``, after the fusion rule
(span_reduce.py)."""
import span_reduce


def read(ctx):
    return span_reduce.phase_ms(ctx, "forward")
