"""Benchmark harness: one module per paper table/figure (DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV per row.  Propagation runs in fp64
(the paper's default); the precision module covers fp32.  A module that
raises prints an ``ERROR`` row, the others still run, and the harness then
exits non-zero.
"""
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)


def main() -> int:
    from repro.compile_cache import enable_compile_cache

    from . import (
        baseline_validation,
        bench_prop,
        block_ell_engine,
        loop_variants,
        ordering,
        precision,
        price_of_parallelism,
        prop_roofline,
        speedup_sets,
    )

    modules = [
        ("§2.2 price of parallelism", price_of_parallelism),
        ("Table 1 speedups by size set", speedup_sets),
        ("Fig 2 precision (fp32 vs fp64)", precision),
        ("Fig 3 baseline validation", baseline_validation),
        ("App B ordering", ordering),
        ("App C loop variants", loop_variants),
        ("§4.4 propagation roofline", prop_roofline),
        ("beyond-paper: block-ELL engine", block_ell_engine),
        ("perf trajectory: BENCH_prop.json", bench_prop),
    ]
    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failed = 0
    print("name,us_per_call,derived")
    for title, mod in modules:
        if only and only not in mod.__name__:
            continue
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001 -- report and continue
            print(f"{mod.__name__},0,ERROR: {type(e).__name__}: {e}")
            failed += 1
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        print(f"# [{title}] done in {time.time()-t0:.1f}s", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
