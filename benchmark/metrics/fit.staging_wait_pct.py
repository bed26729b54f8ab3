"""Share of the window the dispatch loop spent waiting for a staged group:
the sum of dl4j_fit_phase_seconds{phase="staging"} over the window / window."""


def read(ctx):
    key = "dl4j_fit_phase_seconds{phase=staging}"
    if not ctx["counters"].get(key + "_count"):
        return None
    return 100.0 * ctx["counters"][key + "_sum"] / ctx["window"]["elapsed_s"]
