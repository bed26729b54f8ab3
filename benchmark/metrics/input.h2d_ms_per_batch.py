"""Producer-thread time to hand one batch to the device: the ``input.h2d``
spans (the ``jax.device_put`` calls: submission, not the copy's own duration)
of the window's groups in the program's ring / batches."""
import span_reduce


def read(ctx):
    return span_reduce.stage_ms_per_batch(ctx, "input.h2d")
