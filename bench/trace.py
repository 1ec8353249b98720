"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy intervals inside the measured window,
the time per device operation, and the idle gaps named by what the host
was doing in them.

The window and the host phases are ``jax.profiler.TraceAnnotation``
spans that the harness writes around its own calls (``WINDOW`` and the
names in ``HOST_PHASES``).  Device operations are the events of the
device planes' op lines; busy time is the union of their intervals,
averaged over the devices that ran any.
"""
from __future__ import annotations

import glob
import itertools
import os
from collections import defaultdict

WINDOW = "bench.window"
HOST_PHASES = ("dispatch", "readback")
IDLE_OTHER = "host_other"
TOP = 10
# Control-flow ops span the ops they run; per-op times leave them out.
CONTAINERS = ("%while", "%conditional", "%call")

# (plane name prefix, op line name prefix) of device operations.
TPU_OPS = (("/device:TPU:", "XLA Ops"),)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return files[0]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(profile):
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                yield plane.name, line.name, ev


def _union(intervals):
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def _name_gaps(gaps, spans):
    """``[(seconds_ns, phase)]`` for time-ordered ``gaps``: each is named by
    the host span that overlaps it most, or ``IDLE_OTHER``."""
    spans = sorted(spans)
    reach = list(itertools.accumulate((e for _, e, _ in spans), max))
    out, lo = [], 0
    for g0, g1 in gaps:
        while lo < len(spans) and reach[lo] <= g0:
            lo += 1
        best, name = 0.0, IDLE_OTHER
        for s, e, nm in spans[lo:]:
            if s >= g1:
                break
            ov = _overlap(g0, g1, s, e)
            if ov > best:
                best, name = ov, nm
        out.append((g1 - g0, name))
    return out


def reduce(profile, device_ops=TPU_OPS, window=WINDOW, phases=HOST_PHASES):
    """Busy and idle time of the device inside the ``window`` span.

    Returns a dict with ``window_s``, ``busy_s`` (mean over devices with
    any operation), ``n_devices``, ``device_ops`` (``[[name, seconds]]``,
    the ``TOP`` largest by HLO instruction name, control-flow containers
    left out, summed over devices and divided by their number)
    and ``idle_gaps`` (``[[phase, seconds]]``, the ``TOP`` longest gaps,
    each named by the host phase that overlaps it most).  Raises
    ``ValueError`` when the window span is missing or no device
    operation ran inside it."""
    spans, ops = [], defaultdict(list)
    win = None
    for plane, line, ev in _events(profile):
        start, dur = float(ev.start_ns), float(ev.duration_ns)
        if any(plane.startswith(p) and line.startswith(q) for p, q in device_ops):
            if dur > 0:
                ops[plane].append((start, start + dur, ev.name))
        elif ev.name == window:
            win = (start, start + dur)
        elif ev.name in phases:
            spans.append((start, start + dur, ev.name))
    if win is None:
        raise ValueError(f"no {window!r} span in the trace")
    w0, w1 = win
    busy_total, per_op, gaps = 0.0, defaultdict(float), []
    used = 0
    for plane, evs in ops.items():
        inside = [(max(s, w0), min(e, w1), nm) for s, e, nm in evs if e > w0 and s < w1]
        if not inside:
            continue
        used += 1
        for s, e, nm in inside:
            name = nm.split(" = ", 1)[0]  # the HLO instruction, not its whole text
            if not name.startswith(CONTAINERS):
                per_op[name] += e - s
        busy = _union([(s, e) for s, e, _ in inside])
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps += _name_gaps([(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2]) if g1 > g0],
                           spans)
    if not used:
        raise ValueError("no device operation ran inside the window")
    ns = 1e-9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy_total / used * ns,
        "n_devices": used,
        "device_ops": [[nm, t / used * ns] for nm, t in top_ops],
        "idle_gaps": [[nm, t * ns] for t, nm in sorted(gaps, key=lambda g: -g[0])[:TOP]],
    }
