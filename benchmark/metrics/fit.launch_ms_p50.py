"""Median time from the host's dispatch of a K-step group to the step
program's start on the device: per group of the traced slice, the start of
the execution minus the later of its ``fit.dispatch`` span's start and the
previous execution's end. Reads the program's span on the trace's clock; not
reported where any group reads negative (the clocks then are not shared to
that precision; the gap is logged)."""
import span_reduce


def read(ctx):
    return span_reduce.launch_ms_p50(ctx)
