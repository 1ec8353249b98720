"""Median host milliseconds of the window's ``propagate_block_ell``
calls, entry to return, from the program's ``prop.presolve`` spans
(entry points).  The launch is asynchronous: the device's work is not in
it."""
import statistics

from bench import program_reads


def read(ctx):
    rounds = ctx.counters.get("presolve_rounds")
    found = program_reads.window_calls("prop.presolve", len(rounds or ()))
    if found is None:
        return None
    return 1e3 * statistics.median(s.t_end - s.t_start for s in found[0])
