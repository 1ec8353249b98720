"""Bring-up smoke test: the Pallas propagation engines on a TPU, driven
through the entry points users call.

  python chip_smoke.py             # one chip: root presolve, service, solve()
  python chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip runs three phases, each checked against the host-numpy f64
oracles of ``repro.core.seq_ref``:

  * presolve -- ``propagate_block_ell(p)`` with its defaults on two seeded
    instances of the ``mixed`` family at the paper's unscaled Set-5 size
    (10^5 rows, ~1.6M nonzeros): one whose padded width resolves to the
    ``fused`` engine, one wider than ``SCATTER_MAX_NPAD`` that resolves to
    ``partitioned``;
  * service -- ``PropagationService.from_problems`` serving 32 requests
    (four families, Set-2/Set-3 sizes) from its background loop, with no
    compile while serving;
  * solve -- ``solve()`` with its defaults on seeded pseudo-boolean and
    random-MIP instances, against ``brute_force_solve``.

Bounds must give the oracle's infeasibility verdict and keep the float32
never-tighter contract against it (``repro.core.overtightening``).  The
paper's §4.3 ``bounds_equal`` holds each result to a float32 reference
engine run on the same chip -- the jnp round (``use_pallas=False``), or
the one-chip engine for the sharded paths.  Against the f64 oracle §4.3
is a statistic at float32, not an invariant: merges widen accepted bounds
by ``outward_eps_f32`` (7.6e-6 relative) and the fixed point may stop one
``tighten_eps_f32`` (1e-5) early, together above §4.3's 1e-5, so the
number of bounds outside it is printed, not asserted.  ``--chips 4``
instead runs ``propagate_sharded`` (one instance split by nonzeros) and
``propagate_batch_sharded`` (the batch axis) over a 4-device mesh, each
against the one-chip engines on the same instances and the oracle.

Earlier lines report each phase with its sizes and wall time (set-up and
compilation included: information, not a benchmark).  The last line is
one JSON object naming the device.  Exits non-zero, printing no result,
when the first device is not a TPU, when the repository's sources are
missing, or when any check fails.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    INF,
    PropagationService,
    bounds_equal,
    brute_force_solve,
    overtightening,
    propagate_sequential,
    solve,
)
from repro.data import (  # noqa: E402
    instances_for_set,
    make_mixed,
    make_pseudo_boolean,
    make_random_mip,
)
from repro.kernels import ops  # noqa: E402

# (engine the defaults must resolve to, make_mixed arguments).  Mean row
# length ~8 plus the family's power-law tail and two dense connecting rows.
PRESOLVE = [
    ("fused", dict(m=100_000, n=20_000, seed=5)),
    ("partitioned", dict(m=100_000, n=120_000, seed=7)),
]
SERVICE_SETS = ("Set-2", "Set-3")
SOLVE_PB_SEEDS = (0, 1, 2, 3)
SOLVE_MIP_SEEDS = (0, 1)


def log(msg: str) -> None:
    print(msg, flush=True)


class CheckFailed(AssertionError):
    pass


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise CheckFailed(msg)


def outside_tolerance(r, seq, t_abs=1e-8, t_rel=1e-5, inf=INF) -> int:
    """How many of ``r``'s bounds miss the paper's §4.3 tolerance against
    the oracle ``seq`` (the per-bound count behind ``bounds_equal``)."""
    miss = 0
    for a, b in ((r.lb, seq.lb), (r.ub, seq.ub)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        ok = (np.abs(a - b) <= t_abs + t_rel * np.abs(b)) | (
            (np.abs(a) >= inf) & (np.abs(b) >= inf) & (np.sign(a) == np.sign(b))
        )
        miss += int(np.sum(~ok))
    return miss


def check(name, p, r, seq, ref) -> int:
    """Hold ``r`` to the sequential f64 oracle ``seq`` (same verdict, never
    tighter) and to the float32 reference run ``ref`` (same verdict, equal
    at §4.3).  Returns how many bounds miss §4.3 against the oracle."""
    require(bool(r.infeasible) == bool(seq.infeasible),
            f"{name}: infeasible={bool(r.infeasible)}, oracle says {seq.infeasible}")
    require(bool(ref.infeasible) == bool(r.infeasible),
            f"{name}: infeasible={bool(r.infeasible)}, reference says {bool(ref.infeasible)}")
    if seq.infeasible:
        return 0
    msg = overtightening(r.lb, r.ub, seq.lb, seq.ub, p.is_int)
    require(msg is None, f"{name}: {msg}")
    require(bounds_equal(r.lb, r.ub, ref.lb, ref.ub),
            f"{name}: limit point differs from the float32 reference beyond §4.3")
    return outside_tolerance(r, seq)


def mixed(kw) -> "object":
    return make_mixed(density=8.0 / kw["n"], dense_rows=2, **kw)


def phase_presolve(specs=PRESOLVE) -> None:
    from repro.kernels import prepare_block_ell, propagate_block_ell

    for engine, kw in specs:
        t0 = time.perf_counter()
        p = mixed(kw)
        prep = prepare_block_ell(p)
        t_prep = time.perf_counter() - t0
        require(prep.d.val.dtype == np.float32, f"prepared {prep.d.val.dtype}, not float32")
        got = ops._resolve_scatter("auto", prep)
        require(got == engine, f"n_pad={prep.n_pad} resolved to {got}, expected {engine}")
        t0 = time.perf_counter()
        r = propagate_block_ell(p)
        jax.block_until_ready(r.lb)
        t_first = time.perf_counter() - t0
        t0 = time.perf_counter()
        r2 = propagate_block_ell(p)
        jax.block_until_ready(r2.lb)
        t_warm = time.perf_counter() - t0
        require(np.array_equal(np.asarray(r.lb), np.asarray(r2.lb))
                and np.array_equal(np.asarray(r.ub), np.asarray(r2.ub)),
                f"presolve/{engine}: two runs disagree")
        ref = propagate_block_ell(p, use_pallas=False, scatter="segment")
        t0 = time.perf_counter()
        seq = propagate_sequential(p)
        t_seq = time.perf_counter() - t0
        miss = check(f"presolve/{engine}", p, r, seq, ref)
        log(f"[presolve/{engine}] ok: m={p.m} n={p.n} n_pad={prep.n_pad} "
            f"nnz={p.nnz} tiles={prep.d.val.shape[0]} one_chunk={prep.fits_one_chunk} "
            f"rounds={int(r.rounds)} (jnp reference {int(ref.rounds)}, oracle "
            f"{seq.rounds}) converged={bool(r.converged)} "
            f"infeasible={bool(r.infeasible)}, bounds outside §4.3 of the f64 "
            f"oracle: {miss} of {2 * p.n} | prepare {t_prep:.1f}s, "
            f"first call {t_first:.1f}s, warm call {t_warm:.2f}s, "
            f"oracle {t_seq:.1f}s")


def service_population(sets=SERVICE_SETS):
    return [p for s in sets for _, p in instances_for_set(s, per_family=4)]


def phase_service(sets=SERVICE_SETS) -> None:
    from repro.kernels import propagate_batch_block_ell

    pop = service_population(sets)
    t0 = time.perf_counter()
    svc = PropagationService.from_problems(pop)
    t_build = time.perf_counter() - t0
    before = svc.compile_counts()
    t0 = time.perf_counter()
    with svc:
        tickets = [svc.submit(p) for p in pop]
        results = [tk.result(timeout=600) for tk in tickets]
    t_serve = time.perf_counter() - t0
    after = svc.compile_counts()
    require(after == before, f"service compiled while serving: {before} -> {after}")
    refs = propagate_batch_block_ell(pop, use_pallas=False)
    miss = sum(
        check(f"service/request {i}", p, r, propagate_sequential(p), ref)
        for i, (p, r, ref) in enumerate(zip(pop, results, refs))
    )
    st = svc.stats()
    log(f"[service] ok: {len(pop)} requests, m {min(p.m for p in pop)}-"
        f"{max(p.m for p in pop)}, n {min(p.n for p in pop)}-"
        f"{max(p.n for p in pop)}, {len(st['buckets'])} buckets "
        f"(n_pad/tile_width {[(b['n_pad'], b['tile_width']) for b in st['buckets']]}), "
        f"retired {st['retired']}, infeasible "
        f"{sum(bool(r.infeasible) for r in results)}, no compile while serving, "
        f"bounds outside §4.3 of the f64 oracle: {miss} of "
        f"{sum(2 * p.n for p in pop)} | build+warm {t_build:.1f}s, "
        f"serve {t_serve:.2f}s")


def _objective(n):
    """The solver tests' deterministic integral objective."""
    sign = np.where(np.arange(n) % 3 == 0, -1.0, 1.0)
    return np.arange(1, n + 1, dtype=np.float64) * sign


def solve_instances(pb_seeds=SOLVE_PB_SEEDS, mip_seeds=SOLVE_MIP_SEEDS):
    return (
        [(f"pb/{s}", make_pseudo_boolean(n=12, m=16, seed=s)) for s in pb_seeds]
        + [(f"mip/{s}", make_random_mip(n=9, m=12, seed=s)) for s in mip_seeds]
    )


def phase_solve(instances=None) -> None:
    instances = solve_instances() if instances is None else instances
    t0 = time.perf_counter()
    statuses = []
    for name, p in instances:
        c = _objective(p.n)
        bf = brute_force_solve(p, c)
        res = solve(p, c)
        want = "optimal" if bf.feasible else "infeasible"
        require(res.status == want, f"solve/{name}: status {res.status}, oracle {want}")
        require(res.objective == bf.objective,
                f"solve/{name}: objective {res.objective}, oracle {bf.objective}")
        statuses.append(f"{name}:{res.status}/{res.objective:g}/{res.nodes_expanded}")
    log(f"[solve] ok: {len(instances)} instances (name:status/objective/expanded "
        f"{' '.join(statuses)}) | {time.perf_counter() - t0:.1f}s")


def phase_four_chips(devices, kw=dict(m=20_000, n=16_000, seed=11),
                     sets=SERVICE_SETS) -> None:
    from jax.sharding import Mesh

    from repro.core import propagate_batch_sharded, propagate_sharded
    from repro.core.sharded import run_batch_sharded
    from repro.kernels import propagate_batch_block_ell, propagate_block_ell

    mesh = Mesh(np.array(devices[:4]), ("chips",))
    want = {d.id for d in devices[:4]}

    p = mixed(kw)
    seq = propagate_sequential(p)
    one = propagate_block_ell(p)
    t0 = time.perf_counter()
    r = propagate_sharded(p, mesh)
    jax.block_until_ready(r.lb)
    t_sh = time.perf_counter() - t0
    got = {s.device.id for s in r.lb.addressable_shards}
    require(got == want, f"propagate_sharded output on devices {got}, mesh {want}")
    miss = check("sharded", p, r, seq, one)
    log(f"[4 chips/propagate_sharded] ok: m={p.m} n={p.n} nnz={p.nnz} "
        f"rounds={int(r.rounds)} (one chip {int(one.rounds)}), output on devices "
        f"{sorted(got)}, bounds outside §4.3 of the f64 oracle: {miss} of "
        f"{2 * p.n} | {t_sh:.1f}s")

    pop = service_population(sets)
    ones = propagate_batch_block_ell(pop)
    _, outputs = run_batch_sharded(pop, mesh)
    got = {s.device.id for s in outputs[0].addressable_shards}
    require(got == want, f"propagate_batch_sharded output on devices {got}, mesh {want}")
    t0 = time.perf_counter()
    res = propagate_batch_sharded(pop, mesh)
    t_b = time.perf_counter() - t0
    miss = sum(
        check(f"batch_sharded/{i}", q, rb, propagate_sequential(q), r1)
        for i, (q, rb, r1) in enumerate(zip(pop, res, ones))
    )
    log(f"[4 chips/propagate_batch_sharded] ok: {len(pop)} instances, stacked "
        f"output on devices {sorted(got)}, bounds outside §4.3 of the f64 "
        f"oracle: {miss} of {sum(2 * q.n for q in pop)} | {t_b:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths over four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first device is {dev.platform}, not a TPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} x64={jax.config.jax_enable_x64} "
        f"compile cache={cache}")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(devices)
    else:
        phase_presolve()
        phase_service()
        phase_solve()
    log(f"all phases ok in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
