"""Producer-thread time to pull, stack, cast and hand one batch to the
device: dl4j_prefetch_staging_seconds_total over the window / batches."""


def read(ctx):
    total = sum(v for k, v in ctx["counters"].items()
                if k.startswith("dl4j_prefetch_staging_seconds_total"))
    steps = ctx["window"]["steps"]
    if not total or not steps:
        return None
    return 1e3 * total / steps
