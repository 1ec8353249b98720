"""The benchmark: one cell of ``BENCHMARK.json``, run on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In one process it names the device (and fails, printing no result, off a
TPU, with fewer chips than the cell asks for, or on a device kind that
``peaks.py`` does not know), turns on JAX's persistent compilation cache,
builds the cell's data from ``--seed``, warms up the cell's own shapes,
runs the cell's closed loop for ``--seconds`` (the window ends when the
call that crosses it completes, or the loop's pass that holds that call;
a loop that keeps calls in flight then sends nothing more, and the window
ends when every call sent is answered),
checks that no compile happened inside the window, holds the answers of
the timed calls to the plain reference in ``reference.py``, and prints
one JSON line.  ``--trace 1`` records a
profiler trace of the window and reports the per-layer metrics instead of
the end-to-end ones.

Everything a cell is made of is found by name: the workload names a
configuration (``configs/<config>.json``: the instance family, its sizes
and the limits of the check) and a traffic mix (``traffic/<mix>.json``:
the loop in ``loops/<loop>.py`` and its parameters); the instance family
is ``families/<family>.py``; each per-layer metric is read by
``metrics/<metric>.py``.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import peaks as peak_table  # noqa: E402
from bench import trace as trace_mod  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Refused(Exception):
    """The run cannot measure this cell here; no result is printed."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by path (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} module {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_cell(name: str):
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json (known: {sorted(cells)})")
    cell = cells[name]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return spec, cell, config, traffic


def listed_for(metrics, cell_name):
    """The metrics of ``BENCHMARK.json`` that this cell reports."""
    return [m for m in metrics if cell_name in m.get("workloads", [cell_name])]


def load_program():
    """The system under test: its entry points and input types."""
    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        raise Refused("the program's sources (src/repro) are not in this checkout")
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.core import propagate_nodes
    from repro.core.sparse import CSR, Problem
    from repro.kernels import propagate_block_ell

    def problem(inst):
        import numpy as np

        csr = CSR(row_ptr=inst.row_ptr.astype(np.int32), col=inst.col.astype(np.int32),
                  val=inst.val, n_cols=np.int32(inst.n))
        return Problem(csr=csr, lhs=inst.lhs, rhs=inst.rhs, lb=inst.lb, ub=inst.ub,
                       is_int=inst.is_int)

    return SimpleNamespace(problem=problem, propagate_block_ell=propagate_block_ell,
                           propagate_nodes=propagate_nodes)


def enable_compile_cache(jax) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` if set (JAX
    reads it itself), else at the fixed ``<checkout>/.jax_cache``."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    where = os.environ.get(CACHE_ENV)
    if where:
        return where
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


class GcWatch:
    """Counts the collector's passes and their longest pause while ``active``."""

    def __init__(self):
        self.active, self.count, self.longest, self._t = False, 0, 0.0, 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if not self.active:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.count += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    def __init__(self, monitoring):
        self.active, self.count = False, 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self.active and event in COMPILE_EVENTS:
            self.count += 1


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(args, *, require_tpu=True, overrides=None, program=None, peaks=None,
            device_ops=trace_mod.TPU_OPS):
    """One run; returns the result object.  ``require_tpu``, ``overrides``
    (cell parameters), ``program``, ``peaks`` and ``device_ops`` exist for
    the benchmark's own tests, which run a cell at a tiny size on the CPU
    with the timed path swapped out; the command line sets none of them."""
    spec, cell, config, traffic = load_cell(args.workload)
    program = program or load_program()
    import jax
    from jax import monitoring

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise Refused(f"the first device is {dev.platform}, not a TPU")
    if len(devices) < cell["chips"]:
        raise Refused(f"the cell needs {cell['chips']} chips, found {len(devices)}")
    peaks = peaks or peak_table.lookup(dev.device_kind)
    cache = enable_compile_cache(jax)
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; jax {jax.__version__}; "
        f"compile cache {cache}")

    params = {**config["params"], **traffic["params"], **(overrides or {})}
    loop_mod = load_module("loops", traffic["loop"])
    family = load_module("families", config["family"])
    t_data = time.perf_counter()
    state = loop_mod.Loop(params, args.seed, program, family)
    t_warm = time.perf_counter()
    state.warm()
    # A full collection walks every object the process holds (~0.1 s after
    # set-up) and stalls the host inside the window; frozen, set-up's
    # objects are not walked again.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - _T_START
    log(f"set-up {setup_s:.3f} s: start {t_data - _T_START:.3f}, data {t_warm - t_data:.3f}, "
        f"warm-up {_T_START + setup_s - t_warm:.3f}")

    counter, collector = CompileCounter(monitoring), GcWatch()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    annotate = jax.profiler.TraceAnnotation
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        counter.active = collector.active = True
        steps = []
        with annotate(trace_mod.WINDOW):
            t0 = now = time.perf_counter()
            while True:
                state.step(annotate)
                steps.append(time.perf_counter() - now)
                now = time.perf_counter()
                elapsed = now - t0
                if elapsed >= args.seconds and state.at_boundary:
                    break
            # A loop that keeps work in flight sends nothing more now, and
            # the window closes once all of it has been answered.
            if hasattr(state, "finish"):
                state.finish(annotate)
                steps.append(time.perf_counter() - now)
                elapsed = time.perf_counter() - t0
        counter.active = collector.active = False
    finally:
        gc.callbacks.remove(collector._on_gc)
        if trace_dir:
            jax.profiler.stop_trace()
    if counter.count:
        raise Refused(f"{counter.count} compiles inside the measured window")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    log(f"window {elapsed:.3f} s, {state.attempted} answers, {len(steps)} calls; call s "
        f"min {min(steps):.4f} median {sorted(steps)[len(steps) // 2]:.4f} max {max(steps):.4f} "
        f"(call {steps.index(max(steps))}); {collector.count} collections, longest "
        f"{collector.longest:.4f} s")

    breakdown = None
    if trace_dir:
        try:
            red = trace_mod.reduce(trace_mod.load(trace_mod.find_xplane(trace_dir)),
                                   device_ops=device_ops)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        ctx = SimpleNamespace(trace=red, counters=state.counters(peaks))
        metrics = {}
        for m in listed_for(spec["per_layer"], cell["name"]):
            value = load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        produced = {**state.end_to_end(elapsed), "setup_s": setup_s}
        metrics = {}
        for m in listed_for(spec["end_to_end"], cell["name"]):
            if m["name"] not in produced:
                raise Refused(f"the {traffic['loop']} loop does not produce {m['name']}")
            metrics[m["name"]] = {"value": produced[m["name"]], "unit": m["unit"]}

    readings = state.check()
    limits = config["limits"]
    check = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in check.values())
    out = {"correct": correct, "attempted": state.attempted, "failed": 0,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return out


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = measure(args)
    except (Refused, peak_table.UnknownDevice) as e:
        log(f"bench: {e}")
        return 1
    print(json.dumps(out), flush=True)
    for k, c in out["check"].items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
