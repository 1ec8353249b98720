"""Compile each cell's timed entry at its real size for a described TPU
v5e, on a machine without one -- the rehearsal before a chip run.

    JAX_PLATFORMS=cpu python bench/compile_check.py [workload ...]

For each cell it builds the instance as the run does, builds the
program's compiled fixed point for float32 with Mosaic kernels and
donated bounds (what the defaults resolve to on a TPU), lowers it for one
chip of a described ``v5e:2x2`` and compiles it, which refuses what the
chip's compiler would refuse.  It prints the compiled program's memory
analysis.  Nothing runs, so it says nothing about time or results.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from run import ROOT, load_cell, load_module, load_program  # noqa: E402


def compile_cell(name, sharding):
    import jax
    import jax.numpy as jnp

    _, _, config, traffic = load_cell(name)
    params = {**config["params"], **traffic["params"]}
    program = load_program()
    from repro.kernels import ops

    family = load_module("families", config["family"])
    p = program.problem(family.make(params))
    f32 = jnp.float32
    prep = ops.prepare_block_ell(p, dtype=f32)
    if traffic["loop"] == "nodes":
        batch = int(params["batch"])
        runner = ops.node_batch_runner(prep, batch, interpret=False, donate=True)
        shapes = [(batch, prep.n_pad)] * 2
    else:
        try:  # builds and caches the runner; running it needs the chip
            ops.propagate_block_ell(p, dtype=f32, interpret=False, donate=True)
        except Exception:
            pass
        runner = list(ops._runner_cache._d.values())[-1][1]
        shapes = [(prep.n_pad,)] * 2
    args = [jax.ShapeDtypeStruct(s, f32, sharding=sharding) for s in shapes]
    t0 = time.perf_counter()
    compiled = runner.lower(*args).compile()
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    return {
        "workload": name, "m": p.m, "n": p.n, "nnz": p.nnz, "n_pad": prep.n_pad,
        "tiles": int(prep.d.val.shape[0]), "fits_one_chunk": bool(prep.fits_one_chunk),
        "engine": ops._resolve_scatter("auto", prep),
        "mosaic_kernels": text.count("tpu_custom_call"),
        "compile_s": round(time.perf_counter() - t0, 1),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", -1)),
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", -1)),
        "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", -1)),
    }


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = argv or [w["name"] for w in spec["workloads"]]
    for name in names:
        print(json.dumps(compile_cell(name, one_chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
