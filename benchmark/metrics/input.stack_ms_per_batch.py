"""Producer-thread time to stack one batch into its K-step group: the
``input.stack`` spans (``np.stack`` of features and labels) of the window's
groups in the program's ring / batches."""
import span_reduce


def read(ctx):
    return span_reduce.stage_ms_per_batch(ctx, "input.stack")
