"""A throw-away metric for the tests."""


def read(ctx):
    return ctx["window"]["steps"] / ctx["window"]["dispatches"]
