"""Seconds in the networks' ``init()`` during set-up (the spans
``startup.init`` that ended before the window; under the harness's
``jax.jit`` an ``init`` is a trace)."""
import startup_reduce


def read(ctx):
    return startup_reduce.span_seconds(ctx, "startup.init")
