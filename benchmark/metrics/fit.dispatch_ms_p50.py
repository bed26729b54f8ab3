"""Median time between the starts of successive K-step dispatches, read where
they land: executions of the step program on the device plane's module line
(the maximum is logged by the harness)."""


def read(ctx):
    return ctx["trace"]["dispatch_gap_ms_p50"]
