"""Producer-thread time to cast one batch to the staging dtype on the host:
the ``input.cast`` spans (``_stage_host``) of the window's groups in the
program's ring / batches."""
import span_reduce


def read(ctx):
    return span_reduce.stage_ms_per_batch(ctx, "input.cast")
