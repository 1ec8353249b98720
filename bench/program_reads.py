"""What the per-layer metrics read of the program's own instrumentation,
in the run's process after the window: the device names of its kernels
(``repro.kernels.prop_round.KERNEL_NAMES``) and the spans of its tracer
(``repro.obs.trace.PROGRAM``).  A program without them gives ``None``,
and the metric is left out of the result line.
"""
from __future__ import annotations

import re


def kernel_seconds(trace):
    """Seconds of the trace's listed device ops that are the program's
    named kernels (``%<name>.N`` with ``name`` in ``KERNEL_NAMES``), or
    ``None`` where the program names none.  The trace lists its 10 longest
    ops, so a kernel below the tenth is not counted."""
    try:
        from repro.kernels.prop_round import KERNEL_NAMES
    except ImportError:
        return None
    names = set(KERNEL_NAMES.values())
    return sum(t for op, t in trace["device_ops"]
               if re.sub(r"\.\d+$", "", op.lstrip("%_")) in names)


def window_calls(entry, units, size=lambda span: 1):
    """The last ``entry`` spans of the program tracer that are calls of
    their own (no parent), as many as make up ``units`` by ``size``:
    the calls of the window, which the program made last.  ``None`` where
    the program has no tracer or its ring holds fewer."""
    try:
        from repro.obs.trace import PROGRAM
    except ImportError:
        return None
    spans = PROGRAM.spans()
    calls, total = [], 0
    for span in reversed(spans):
        if total >= units:
            break
        if span.name == entry and span.parent_id is None:
            calls.append(span)
            total += size(span)
    return (calls[::-1], spans) if units and total == units else None


def under(spans, roots, name):
    """The ``name`` spans of ``spans`` that descend from one of ``roots``."""
    parent = {s.span_id: s.parent_id for s in spans}
    ids = {r.span_id for r in roots}

    def inside(sid):
        while sid is not None:
            if sid in ids:
                return True
            sid = parent.get(sid)
        return False

    return [s for s in spans if s.name == name and inside(s.parent_id)]
