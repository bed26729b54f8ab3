"""Device time of one train step in backward operations: under
``transpose(jvp(layer/*))`` or ``transpose(jvp(loss))``; a weight-gradient
convolution fused with the updater's subtraction counts here
(span_reduce.py)."""
import span_reduce


def read(ctx):
    return span_reduce.phase_ms(ctx, "backward")
