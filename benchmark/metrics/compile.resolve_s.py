"""Seconds set-up spent resolving programs (the spans ``compile.resolve``
that ended before the window, summed): fingerprint, then the store's read
and ``deserialize_and_load``, or lowering, the backend's compile and the
store's write."""
import startup_reduce


def read(ctx):
    return startup_reduce.span_seconds(ctx, "compile.resolve")
