"""Device busy time of one train step: the busy union over the reduced slice
(whole dispatches of the step program) / the steps they held."""


def read(ctx):
    t = ctx["trace"]
    steps = t["dispatches"] * int(ctx["cell"]["traffic"]["dispatch_ksteps"])
    return 1e3 * t["busy_s"] / steps if steps else None
