"""Share of the device's idle time in the traced slice that the program's
spans own: a producer span (``input.pull/stack/cast/h2d``) of the group the
fit loop was waiting for, ``fit.dispatch`` / ``fit.listeners``, or the launch
that follows a dispatch (from the call's return to the execution's start on
the device). Idle time under a bare ``fit.wait`` or under no span is not
attributed."""
import span_reduce


def read(ctx):
    return span_reduce.idle_attributed_pct(ctx)
