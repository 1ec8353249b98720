"""Median host milliseconds of the window's ``propagate_nodes`` calls
(one frontier each), entry to return, from the program's ``prop.nodes``
spans (entry points): cast, padding, upload and launch of a frontier."""
import statistics

from bench import program_reads


def read(ctx):
    nodes = ctx.counters.get("node_rounds")
    found = program_reads.window_calls("prop.nodes", len(nodes or ()),
                                       size=lambda s: s.attrs.get("nodes", 0))
    if found is None:
        return None
    return 1e3 * statistics.median(s.t_end - s.t_start for s in found[0])
