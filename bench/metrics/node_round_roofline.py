"""Share of the roofline that the node rounds reach, in percent: the
least time of the rounds the timed frontiers ran (``work.py``, from the
instance and the nodes still running in each round) over the device's
busy time in the traced window."""


def read(ctx):
    least = ctx.counters.get("node_least_s")
    if least is None or ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    return 100.0 * least / ctx.trace["busy_s"]
