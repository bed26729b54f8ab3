"""Seconds from the OS's start of the process to the first line of the
program's package: the interpreter, ``import jax``, the device runtime's
start and whatever the harness does before it needs the program (the span
``startup.before_import``). No change to the program moves it."""
import startup_reduce


def read(ctx):
    return startup_reduce.span_seconds(ctx, "startup.before_import",
                                       first_only=True)
