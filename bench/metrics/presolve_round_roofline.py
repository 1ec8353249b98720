"""Share of the roofline that the presolve rounds reach, in percent: the
least time of the rounds the timed calls ran (``work.py``, from the
instance alone) over the device's busy time in the traced window."""


def read(ctx):
    least = ctx.counters.get("presolve_least_s")
    if least is None or ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * least / ctx.trace["busy_s"]
