"""Seconds the program's package takes to import, its first line to its
last (the span ``startup.import``)."""
import startup_reduce


def read(ctx):
    return startup_reduce.span_seconds(ctx, "startup.import", first_only=True)
