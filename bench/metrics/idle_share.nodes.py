"""Share of the traced window in which no operation ran on the device,
in percent, in a node cell."""


def read(ctx):
    if "node_rounds" not in ctx.counters or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
