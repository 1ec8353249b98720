"""Host-to-device bytes per node of the window's ``propagate_nodes``
calls: the ``bytes`` of the program's ``prop.upload`` spans under their
``prop.nodes`` spans, over their nodes (entry points)."""
from bench import program_reads


def read(ctx):
    nodes = ctx.counters.get("node_rounds")
    found = program_reads.window_calls("prop.nodes", len(nodes or ()),
                                       size=lambda s: s.attrs.get("nodes", 0))
    if found is None:
        return None
    calls, spans = found
    uploads = program_reads.under(spans, calls, "prop.upload")
    return sum(s.attrs.get("bytes", 0) for s in uploads) / len(nodes)
