"""Host cost of one program-tracer span, with the profiler off and on.

    PYTHONPATH=src python tools/span_cost.py [--spans 100000]

Opens ``--spans`` empty spans on a fresh ``obs.trace.Tracer`` (the ring
wraps, as the program's does in a long run), first with no profiler
running and then inside ``jax.profiler.trace``, and prints one JSON line:
microseconds per span in each case, and the backend it ran on.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))


def per_span_us(spans: int) -> float:
    from repro.obs.trace import Tracer

    tracer = Tracer()
    t0 = time.perf_counter()
    for _ in range(spans):
        with tracer.span("prop.cost") as attrs:
            attrs["bytes"] = 0
    return (time.perf_counter() - t0) / spans * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spans", type=int, default=100_000)
    args = ap.parse_args(argv)
    import jax

    backend = jax.devices()[0]
    per_span_us(1000)  # warm the interpreter's paths
    off = per_span_us(args.spans)
    where = tempfile.mkdtemp(prefix="span_cost_")
    try:
        with jax.profiler.trace(where):
            on = per_span_us(args.spans)
    finally:
        shutil.rmtree(where, ignore_errors=True)
    print(json.dumps({"spans": args.spans, "off_us": off, "on_us": on,
                      "device": f"{backend.platform} {backend.device_kind}"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
