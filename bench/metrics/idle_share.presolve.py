"""Share of the traced window in which no operation ran on the device,
in percent, in a presolve cell."""


def read(ctx):
    if "presolve_rounds" not in ctx.counters or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
