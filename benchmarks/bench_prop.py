"""Perf trajectory for the propagation engines.

Times one round per engine (the jnp-oracle arithmetic of each dataflow --
on CPU that is the honest number; interpret-mode Pallas timings measure the
emulator) and measures bytes accessed per round via
``repro.kernels.round_cost_analysis``; additionally times full batched
propagation (one dispatch per bucket, ``propagate_batch``) against
sequential per-instance dispatches, and warm-start NODE batches (B nodes of
one instance over a shared resident matrix, ``propagate_nodes``) against
repacking each node as a fresh instance, reporting instances/sec and
nodes/sec throughput.

A ``service`` row measures the continuous-batching propagation service
(``repro.core.service``) at saturation: instances pre-packed to slot shape
outside the timer, a closed submit->pump->retire loop over resident
super-tiles, reporting instances/sec, admit->retire latency percentiles,
mean slot occupancy, and a zero-recompile assertion over the serve loop.

A ``solver`` row measures the device-resident branch-and-bound driver
(``repro.core.solver.solve``) against the level-by-level Python driver of
``examples/bnb_dive.py`` on deep SOS1-style dives, reporting nodes/sec for
both drivers, the speedup (asserted >= 3x in the full run), and host syncs
per node on each side.

A ``partitioned`` engine row records the column-slab engine on
VMEM-exceeding banded large-n instances (``n_pad > SCATTER_MAX_NPAD``),
with the segment engine measured on the same instances for comparison.
The row sweeps candidate SLAB_NPAD widths (``sweep_slab_widths``), reports
the tuned width's round time plus a fenced per-phase breakdown
(copy/reduce/combine/merge), and nests its population facts under
``population`` so the row's top level holds measurements only.  ``--smoke``
runs a scaled-down row through the same builder and asserts its schema
merges cleanly (the CI bench-smoke job).

Results are MERGED into ``BENCH_prop.json`` (engine rows are updated or
added, unknown keys from earlier PRs are preserved) so the perf trajectory
stays comparable across PRs.  See docs/BENCHMARKS.md for the JSON schema,
the paired-trials methodology, and the recipe for adding an engine row.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bounds as bnd
from repro.core.nodes import branch_children, pick_most_fractional, propagate_nodes
from repro.core.propagator import fresh_instance_runner, owned_copy, propagate
from repro.core.service import BucketSpec, PropagationService
from repro.core.solver import solve
from repro.core.sparse import Problem, batch_stats, csr_from_dense
from repro.core.types import DEFAULT_CONFIG, INF
from repro.data.instances import instances_for_set, make_banded, make_pseudo_boolean
from repro.kernels import (
    SCATTER_MAX_NPAD,
    SLAB_NPAD,
    batched_device_runner,
    legacy_round_fn_for,
    packed_problems,
    prepare_block_ell,
    prepare_problem_batch,
    propagate_block_ell,
    round_cost_analysis,
    round_fn_for,
)
from repro.kernels import ref as kref
from repro.kernels.ops import default_slab_width
from repro.obs.metrics import run_metadata
from repro.obs.timing import (
    median_of,
    median_ratio,
    paired_trials,
    time_fenced,
    time_phases,
)
from repro.obs.trace import SPAN_KEYS, Tracer

from .common import geomean

SET = "Set-2"
PER_FAMILY = 2
ENGINES = ("fused", "segment", "legacy")
OUT_PATH = "BENCH_prop.json"

# Large-n population for the partitioned engine row: banded instances whose
# n_pad exceeds the VMEM accumulator budget (the regime the fused engine
# used to abandon to the segment fallback).  Banded columns keep the slab
# copy duplication near 1; nnz >> n so the nnz-proportional byte model, not
# the O(n_pad) resident vectors, dominates the comparison.
LARGE_N = SCATTER_MAX_NPAD + 4000
LARGE_SPECS = (
    dict(m=12_000, row_nnz=32, band=1024, seed=0),
    dict(m=15_000, row_nnz=32, band=1024, seed=1),
)
LARGE_TILE = dict(tile_rows=8, tile_width=32)

# Batched-throughput population: >= 8 Set-2 instances of the quick-verdict
# serving shape (set-cover presolves converge in one round, so the batch has
# no stragglers and the comparison isolates dispatch amortization -- the
# thing batching is for; straggler behaviour is covered by the per-instance
# convergence-mask tests instead).
BATCH_FAMILIES = ("set_cover",)
BATCH_PER_FAMILY = 12
# Both sides use the SAME fill-tuned tile layout: set-cover rows carry ~10
# nonzeros, so the default tile_width=128 pads each chunk >90% empty and the
# batched row would mostly measure padding arithmetic, under-reporting real
# throughput.  tile_width=16 keeps every bucket's super-tile at least half
# full (asserted below, recorded as ``bucket_fill``).
BATCH_TILE = dict(tile_rows=8, tile_width=16)
BATCH_MIN_FILL = 0.5


def bytes_per_round(engine: str, per_family: int = PER_FAMILY):
    """Measured bytes/round of one engine over the benchmark set (shared by
    this module and the roofline table so they report the same population)."""
    return [
        round_cost_analysis(p, engine)["bytes_accessed"]
        for _, p in instances_for_set(SET, per_family=per_family)
    ]


def _single_dispatch_runner(prep, max_rounds: int = 100):
    """Per-instance jitted device-loop fixed point (the strongest sequential
    baseline: compile paid once outside the timer, one dispatch per call)."""
    round_fn = round_fn_for(prep, use_pallas=False)
    n = prep.n

    @jax.jit
    def run(lb0, ub0):
        def body(s):
            lb, ub, _, r = s
            lb, ub, ch = round_fn(lb, ub)
            return lb, ub, ch, r + 1

        def cond(s):
            return s[2] & (s[3] < max_rounds)

        lb, ub, ch, r = jax.lax.while_loop(
            cond, body, (lb0, ub0, jnp.asarray(True), jnp.int32(0))
        )
        return lb[:n], ub[:n], r

    return run


def batched_throughput():
    """Instances/sec: one batched dispatch per bucket vs sequential
    per-instance dispatches, over >= 8 Set-2 instances (both sides use
    precompiled runners and the identical fill-tuned ``BATCH_TILE`` layout;
    compile excluded; per-bucket super-tile occupancy recorded and required
    to stay >= ``BATCH_MIN_FILL``)."""
    problems = [
        p
        for _, p in instances_for_set(
            SET, per_family=BATCH_PER_FAMILY, families=BATCH_FAMILIES
        )
    ]

    seq_runners = [
        (_single_dispatch_runner(prep), prep)
        for prep in (prepare_block_ell(p, **BATCH_TILE) for p in problems)
    ]

    def run_sequential():
        for run, prep in seq_runners:
            lb, _, _ = run(owned_copy(prep.lb0), owned_copy(prep.ub0))
        lb.block_until_ready()

    batches = packed_problems(problems, **BATCH_TILE)
    fills = [b["fill"] for b in batch_stats(batches)["per_bucket"]]
    assert min(fills) >= BATCH_MIN_FILL, (
        f"batched-row population under-fills its super-tiles: {fills} "
        f"(grow BATCH_PER_FAMILY or retune BATCH_TILE)"
    )
    batch_runners = [
        (batched_device_runner(prep, use_pallas=False), prep)
        for prep in (prepare_problem_batch(b) for b in batches)
    ]

    def run_batched():
        for run, prep in batch_runners:
            lb, *_ = run(owned_copy(prep.d.lb0), owned_copy(prep.d.ub0))
        lb.block_until_ready()

    # Paired trials (sequential and batched alternate within each trial) with
    # a median-of-trials speedup: robust against the container's background
    # load drifting between the two measurements.
    trials = paired_trials([run_sequential, run_batched], trials=7, repeats=3)
    speedup = median_ratio(trials, num=0, den=1)
    t_seq = median_of(trials, 0)
    t_bat = median_of(trials, 1)
    n_inst = len(problems)
    return {
        "instances": n_inst,
        "buckets": len(batches),
        "bucket_shapes": [list(b.ell.val.shape) for b in batches],
        "bucket_fill": [float(f) for f in fills],
        "tile_width": BATCH_TILE["tile_width"],
        "sequential_instances_per_sec": n_inst / t_seq,
        "batched_instances_per_sec": n_inst / t_bat,
        "batched_speedup": speedup,
    }


# Node-batch population: one Set-2-sized pseudo-boolean instance (the paper's
# §1 target workload; rows carry <= 8 nonzeros so tile_width=8 keeps the
# block-ELL padding proportional to nnz) x NODE_BATCH warm-started nodes,
# each differing from the propagated root by a couple of branching fixings.
NODE_BATCH = 32
NODE_TILE = dict(tile_rows=8, tile_width=8)


def _node_population():
    root = make_pseudo_boolean(n=150, m=160, seed=1)  # seed 1: feasible root
    r0 = propagate(root)
    assert not bool(r0.infeasible)
    lb0, ub0 = np.asarray(r0.lb), np.asarray(r0.ub)
    rng = np.random.default_rng(0)
    lb_nodes = np.repeat(lb0[None, :], NODE_BATCH, axis=0)
    ub_nodes = np.repeat(ub0[None, :], NODE_BATCH, axis=0)
    for i in range(NODE_BATCH):
        lb, ub = lb_nodes[i], ub_nodes[i]
        for _ in range(2):
            free = np.flatnonzero(root.is_int & (lb < ub))
            var = int(rng.choice(free))
            (dlb, dub), (ulb, uub) = branch_children(lb, ub, var, lb[var])
            lb, ub = (dlb, dub) if rng.random() < 0.5 else (ulb, uub)
        lb_nodes[i], ub_nodes[i] = lb, ub
    return root, lb_nodes, ub_nodes


def node_throughput():
    """Nodes/sec: one warm-start node-batch dispatch over the shared
    resident matrix vs repacking-and-dispatching each node as a fresh
    instance (``core.fresh_instance_runner``: per-node host repack + full
    re-upload, compile excluded; paired median-of-trials as above)."""
    root, lb_nodes, ub_nodes = _node_population()

    def run_shared():
        res = propagate_nodes(
            root, lb_nodes, ub_nodes, use_pallas=False, **NODE_TILE
        )
        res.lb.block_until_ready()

    propagate_fresh = fresh_instance_runner(root)

    def run_repack():
        for i in range(NODE_BATCH):
            lb, *_ = propagate_fresh(lb_nodes[i], ub_nodes[i])
        lb.block_until_ready()

    propagate_fresh(lb_nodes[0], ub_nodes[0])[0].block_until_ready()  # compile
    trials = paired_trials([run_repack, run_shared], trials=7, repeats=3)
    speedup = median_ratio(trials, num=0, den=1)
    t_rep = median_of(trials, 0)
    t_sha = median_of(trials, 1)
    return {
        "instance": {"family": "pseudo_boolean", "m": root.m, "n": root.n,
                     "nnz": root.nnz},
        "nodes": NODE_BATCH,
        "repack_nodes_per_sec": NODE_BATCH / t_rep,
        "shared_nodes_per_sec": NODE_BATCH / t_sha,
        "shared_matrix_speedup": speedup,
    }


# Solver-row population: SOS1-style instances -- one ``sum x <= 1`` packing
# row over all n binaries plus a few redundant two-variable rows.  Branching
# a variable UP (to 1) propagates every other variable to 0 in one round, so
# the search is a width-2 dive of depth ~n: the host-overhead-dominated
# regime a device-resident search loop exists for.  Both drivers use the
# same rule, branch point and pruning test, so they visit the IDENTICAL
# (2n-3)-node tree and nodes/sec compares equal work -- on wide trees the
# two drivers saturate at parity on CPU (the pool sweep costs what the
# frontier stack costs), so this row isolates the loop-hosting cost the
# solver removes, not propagation arithmetic.
SOLVER_NS = (48, 64)
SOLVER_EXTRA_ROWS = 4
# Tuned on this population: the dive's frontier never exceeds 2 open nodes,
# so a tiny pool keeps the per-level sweep cheap, and sync_every=16
# amortizes the host readback over 16 levels per dispatch.
SOLVER_KW = dict(node_cap=8, max_levels=256, sync_every=16, use_pallas=False)

# Every key the ``solver`` row must carry (the smoke job and
# docs/BENCHMARKS.md read this set; population facts are NESTED under
# ``population`` like the partitioned/service rows).
SOLVER_ROW_KEYS = frozenset({
    "population",
    "device_nodes_per_sec",
    "python_nodes_per_sec",
    "speedup_vs_python_driver",
    "target_met",
    "host_syncs",
    "python_host_syncs",
    "host_syncs_per_node",
    "python_host_syncs_per_node",
    "nodes",
    "levels",
    "objective_match",
    "statuses",
})


def _sos1_problem(n: int, extra_rows: int = SOLVER_EXTRA_ROWS) -> Problem:
    """One ``sum x <= 1`` row over n binaries plus ``extra_rows`` redundant
    pair rows (``x_i + x_j <= 1``) so the matrix is not a single row."""
    dense = np.zeros((1 + extra_rows, n))
    dense[0] = 1.0
    for k in range(extra_rows):
        dense[1 + k, k % n] = 1.0
        dense[1 + k, (k * 7 + 3) % n] = 1.0
    m = 1 + extra_rows
    return Problem(
        csr=csr_from_dense(dense),
        lhs=np.full(m, -INF),
        rhs=np.ones(m),
        lb=np.zeros(n, np.float64),
        ub=np.ones(n, np.float64),
        is_int=np.ones(n, bool),
    )


def _sos1_objective(n: int) -> np.ndarray:
    """Integral mixed-sign costs (every third negative) -- same shape the
    differential tests use, so pruning does real work on the dive."""
    sign = np.where(np.arange(n) % 3 == 0, -1.0, 1.0)
    return np.arange(1, n + 1, dtype=np.float64) * sign


def _python_bnb(p, c, node_cap: int, max_levels: int):
    """The level-by-level Python driver of ``examples/bnb_dive.py``: one
    ``propagate_nodes`` dispatch per frontier level, ALL search bookkeeping
    (objective, branching, incumbent, pruning) in host numpy, one readback
    per level.  Returns ``(incumbent, created, levels, syncs)``."""
    frontier = [(np.asarray(p.lb, np.float64), np.asarray(p.ub, np.float64))]
    inc = INF
    created, levels, syncs = 1, 0, 0
    while frontier and levels < max_levels:
        levels += 1
        lbs = np.stack([nd[0] for nd in frontier])
        ubs = np.stack([nd[1] for nd in frontier])
        out = propagate_nodes(
            p, lbs, ubs, use_pallas=False, tile_rows=8, tile_width=8
        )
        lbs, ubs = np.asarray(out.lb), np.asarray(out.ub)
        infeas = np.asarray(out.infeasible)
        syncs += 1  # readback before ANY host-side search decision
        nxt = []
        for i in range(lbs.shape[0]):
            if infeas[i]:
                continue
            lb, ub = lbs[i], ubs[i]
            obj = float(np.sum(np.where(c > 0, c * lb, c * ub)))
            if obj >= inc:
                continue
            var = pick_most_fractional(lb, ub, p.is_int)
            if var is None:
                inc = obj
                continue
            bv = np.clip(
                np.floor(0.5 * (lb[var] + ub[var])), lb[var], ub[var] - 1.0
            )
            down, up = branch_children(lb, ub, var, float(bv))
            nxt += [down, up]
            created += 2
        frontier = nxt[:node_cap]
    return inc, created, levels, syncs


def solver_row(
    ns=SOLVER_NS,
    kw: dict | None = None,
    trials: int = 5,
    repeats: int = 3,
    assert_target: bool = True,
):
    """Device-resident ``solve()`` vs the hosted level-by-level driver.

    Per instance the row first proves the comparison is apples-to-apples --
    same optimum AND same node count (identical trees) -- then times both
    drivers with :func:`paired_trials` (warm-up outside the timer, paired
    median-of-trials; docs/BENCHMARKS.md).  Reported nodes/sec divides each
    search's created-node count by its median wall time; ``target_met``
    records the tentpole criterion -- geomean speedup >= 3x on the CPU
    backend -- and the full run asserts it (``assert_target=False`` in the
    single-repeat smoke, where the schema is the contract)."""
    kw = dict(SOLVER_KW, **(kw or {}))
    dev_rate, py_rate, ratios = [], [], []
    nodes_l, levels_l, syncs_l, py_syncs_l, statuses = [], [], [], [], []
    objective_match = True
    for n in ns:
        p = _sos1_problem(n)
        c = _sos1_objective(n)
        res = solve(p, c, **kw)  # warm-up: prepare tiles + compile runner
        py_inc, py_created, py_levels, py_syncs = _python_bnb(
            p, c, kw["node_cap"], kw["max_levels"]
        )
        objective_match &= py_inc == res.objective
        assert py_inc == res.objective, (n, py_inc, res.objective)
        assert py_created == res.nodes_created, (
            n, py_created, res.nodes_created,
        )
        trials_ = paired_trials(
            [
                lambda: _python_bnb(p, c, kw["node_cap"], kw["max_levels"]),
                lambda: solve(p, c, **kw),
            ],
            trials=trials,
            repeats=repeats,
        )
        t_py, t_dev = median_of(trials_, 0), median_of(trials_, 1)
        ratios.append(median_ratio(trials_, num=0, den=1))
        dev_rate.append(res.nodes_created / t_dev)
        py_rate.append(py_created / t_py)
        nodes_l.append(int(res.nodes_created))
        levels_l.append(int(res.levels))
        syncs_l.append(int(res.host_syncs))
        py_syncs_l.append(int(py_syncs))
        statuses.append(res.status)
    speedup = geomean(ratios)
    if assert_target:
        assert speedup >= 3.0, (speedup, ratios)
    return {
        "population": {
            "family": "sos1",
            "ns": list(ns),
            "extra_rows": SOLVER_EXTRA_ROWS,
            "node_cap": kw["node_cap"],
            "sync_every": kw["sync_every"],
        },
        "device_nodes_per_sec": geomean(dev_rate),
        "python_nodes_per_sec": geomean(py_rate),
        "speedup_vs_python_driver": speedup,
        "target_met": bool(speedup >= 3.0),
        "host_syncs": syncs_l,
        "python_host_syncs": py_syncs_l,
        "host_syncs_per_node": float(sum(syncs_l)) / sum(nodes_l),
        "python_host_syncs_per_node": float(sum(py_syncs_l)) / sum(nodes_l),
        "nodes": nodes_l,
        "levels": levels_l,
        "objective_match": bool(objective_match),
        "statuses": statuses,
    }


# Service-row population: the FULL Set-2 family mix (the same four families
# as the engine rows), sized to keep the slot pool saturated.  A mixed
# stream is the serving scenario the slot machinery exists for: instances
# converge at different round counts, so quick ones retire and backfill
# while stragglers keep their slots -- a single-family population would
# degenerate to lockstep waves and measure none of that.
SERVICE_PER_FAMILY = 6
SERVICE_SLOTS = 4
SERVICE_SIZE_CLASSES = 2

# Every key the ``service`` row must carry (the smoke job and
# docs/BENCHMARKS.md read this set; population facts are NESTED under
# ``population`` like the partitioned row).
SERVICE_ROW_KEYS = frozenset({
    "population",
    "instances_per_sec",
    "sequential_instances_per_sec",
    "tuned_sequential_instances_per_sec",
    "speedup_vs_sequential_dispatch",
    "speedup_vs_tuned_sequential",
    "latency_ms_p50",
    "latency_ms_p95",
    "latency_ms_p99",
    "queue_latency_ms_p50",
    "queue_latency_ms_p95",
    "queue_latency_ms_p99",
    "service_latency_ms_p50",
    "service_latency_ms_p95",
    "service_latency_ms_p99",
    "mean_slot_occupancy",
    "bucket_fill",
    "compiles_during_serve",
})


def service_row(
    per_family: int = SERVICE_PER_FAMILY,
    slots: int = SERVICE_SLOTS,
    size_classes: int = SERVICE_SIZE_CLASSES,
    rounds_per_step: int = 8,
    tile_width: int | None = None,
    trials: int = 5,
    repeats: int = 3,
):
    """Continuous-batching service throughput at saturation.

    Closed loop: every instance is pre-packed to its slot shape OUTSIDE the
    timer (the measured loop is submit -> pump -> retire, device-bound, not
    host packing), all submitted at once so the slot pool stays saturated,
    then drained.  Two sequential baselines, both per-instance jitted
    single-dispatch runners with compile excluded: the DEFAULT-layout one
    is the baseline of record (the same definition the batched row has
    carried since its 1.05x days, so the headline speedup is comparable
    across PRs), and the fill-tuned one (the service's own tile sizing
    applied per instance) is recorded alongside so the layout contribution
    to the headline is explicit rather than hidden.  Latency percentiles
    are per ticket from the last timed trial, split three ways:
    submit->retire (``latency_ms_*``), submit->admit queueing
    (``queue_latency_ms_*``) and admit->retire resident service time
    (``service_latency_ms_*``);
    ``compiles_during_serve`` asserts the AOT warmup covered every engine
    the loop dispatched (slot backfill never recompiles).

    ``tile_width`` pins the bucket tile width (``None`` = fill-tuned per
    bucket, the default the service ships with); :func:`service_sweep_row`
    sweeps it alongside ``slots``/``rounds_per_step``."""
    problems = [p for _, p in instances_for_set(SET, per_family=per_family)]
    n_inst = len(problems)

    seq_runners = [
        (_single_dispatch_runner(prep), prep)
        for prep in (prepare_block_ell(p) for p in problems)
    ]

    def run_sequential():
        for run, prep in seq_runners:
            lb, _, _ = run(owned_copy(prep.lb0), owned_copy(prep.ub0))
        lb.block_until_ready()

    specs = BucketSpec.for_problems(
        problems, slots=slots, tile_width=tile_width,
        size_classes=size_classes,
    )
    tuned_runners = [
        (_single_dispatch_runner(prep), prep)
        for prep in (
            prepare_block_ell(
                p,
                tile_width=next(
                    s for s in specs if s.fits_problem(p)
                ).tile_width,
            )
            for p in problems
        )
    ]

    def run_tuned_sequential():
        for run, prep in tuned_runners:
            lb, _, _ = run(owned_copy(prep.lb0), owned_copy(prep.ub0))
        lb.block_until_ready()

    svc = PropagationService(
        specs, rounds_per_step=rounds_per_step, use_pallas=False
    )
    payloads = []
    for p in problems:
        spec = next(s for s in specs if s.fits_problem(p))
        payloads.append(spec.pack(p, dtype=np.float64))
    fill_by_spec = {
        s: [pl.fill() for pl in payloads if s.admits(pl)] for s in specs
    }

    last_tickets: list = []

    def run_service():
        last_tickets[:] = [svc.submit(payload=pl) for pl in payloads]
        svc.drain()

    run_service()  # settle allocator/caches outside the timer (compile
    # already happened at service construction -- AOT warmup)
    counts_before = svc.compile_counts()

    trials_ = paired_trials(
        [run_sequential, run_tuned_sequential, run_service],
        trials=trials, repeats=repeats,
    )
    counts_after = svc.compile_counts()
    compiles = sum(
        (a["step"] or 0) - (b["step"] or 0)
        + sum((a["admit"][k] or 0) - (b["admit"][k] or 0) for k in a["admit"])
        for a, b in zip(counts_after.values(), counts_before.values())
    )
    assert compiles == 0, f"serve loop recompiled: {counts_after}"

    speedup = median_ratio(trials_, num=0, den=2)
    speedup_tuned = median_ratio(trials_, num=1, den=2)
    t_seq = median_of(trials_, 0)
    t_tun = median_of(trials_, 1)
    t_svc = median_of(trials_, 2)
    lat_ms = np.asarray([tk.latency() for tk in last_tickets]) * 1e3
    queue_ms = np.asarray([tk.queue_latency() for tk in last_tickets]) * 1e3
    svc_ms = np.asarray([tk.service_latency() for tk in last_tickets]) * 1e3
    st = svc.stats()
    # Already a fraction of the slot pool: the bucket accumulates
    # occupied/slots per pump.
    occ = float(np.mean([b["mean_occupancy"] for b in st["buckets"]]))
    return {
        "population": {
            "set": SET,
            "families": sorted({s.family for s, _ in
                                instances_for_set(SET, per_family=1)}),
            "instances": n_inst,
            "buckets": len(specs),
            "slots": slots,
            "size_classes": size_classes,
            "rounds_per_step": rounds_per_step,
            "tile_widths": sorted({s.tile_width for s in specs}),
            "payloads_prebuilt": True,
        },
        "instances_per_sec": n_inst / t_svc,
        "sequential_instances_per_sec": n_inst / t_seq,
        "tuned_sequential_instances_per_sec": n_inst / t_tun,
        "speedup_vs_sequential_dispatch": speedup,
        "speedup_vs_tuned_sequential": speedup_tuned,
        "latency_ms_p50": float(np.percentile(lat_ms, 50)),
        "latency_ms_p95": float(np.percentile(lat_ms, 95)),
        "latency_ms_p99": float(np.percentile(lat_ms, 99)),
        "queue_latency_ms_p50": float(np.percentile(queue_ms, 50)),
        "queue_latency_ms_p95": float(np.percentile(queue_ms, 95)),
        "queue_latency_ms_p99": float(np.percentile(queue_ms, 99)),
        "service_latency_ms_p50": float(np.percentile(svc_ms, 50)),
        "service_latency_ms_p95": float(np.percentile(svc_ms, 95)),
        "service_latency_ms_p99": float(np.percentile(svc_ms, 99)),
        "mean_slot_occupancy": occ,
        "bucket_fill": [
            float(np.mean(fill_by_spec[s])) for s in specs if fill_by_spec[s]
        ],
        "compiles_during_serve": int(compiles),
    }


# The service tuning sweep: the row of record's config first, then
# one-factor moves around it (more slots, one size class, shorter/longer
# pump quanta, a pinned narrow tile).  A full factorial would mostly time
# jit compilation of baselines; one-factor probes around the shipped point
# answer the question the row exists for -- is the 0.5x headline a tuning
# artifact or structural?
SERVICE_SWEEP_GRID = (
    dict(slots=4, size_classes=2, rounds_per_step=8, tile_width=None),
    dict(slots=8, size_classes=2, rounds_per_step=8, tile_width=None),
    dict(slots=4, size_classes=1, rounds_per_step=8, tile_width=None),
    dict(slots=4, size_classes=2, rounds_per_step=4, tile_width=None),
    dict(slots=4, size_classes=2, rounds_per_step=16, tile_width=None),
    dict(slots=4, size_classes=2, rounds_per_step=8, tile_width=32),
)

_SWEEP_CFG_KEYS = ("slots", "size_classes", "rounds_per_step", "tile_width")

# Every key the ``service_sweep`` row must carry (the smoke job and
# docs/BENCHMARKS.md read this set).
SERVICE_SWEEP_ROW_KEYS = frozenset({
    "grid",
    "tuned",
    "target_met",
})


def service_sweep_row(
    grid=SERVICE_SWEEP_GRID,
    per_family: int = SERVICE_PER_FAMILY,
    trials: int = 3,
    repeats: int = 2,
    final_trials: int = 5,
    final_repeats: int = 3,
):
    """Sweep the service's tuning knobs and re-measure the best point.

    Each grid point runs :func:`service_row` at reduced fidelity (the sweep
    ranks configs; it does not need publication-grade medians), the config
    maximizing ``speedup_vs_tuned_sequential`` is re-run at full fidelity,
    and ``target_met`` records whether the tuned point clears 1.0x against
    the fill-tuned sequential baseline.  When it does not, the grid is the
    evidence that the gap is structural (pump-quantum overshoot on a
    fast-converging population) rather than a mistuned default -- see
    docs/BENCHMARKS.md."""
    points = []
    for cfg in grid:
        row = service_row(
            per_family=per_family, trials=trials, repeats=repeats, **cfg
        )
        points.append({
            **{k: cfg[k] for k in _SWEEP_CFG_KEYS},
            "speedup_vs_tuned_sequential": row["speedup_vs_tuned_sequential"],
            "speedup_vs_sequential_dispatch":
                row["speedup_vs_sequential_dispatch"],
            "instances_per_sec": row["instances_per_sec"],
        })
    best = max(points, key=lambda r: r["speedup_vs_tuned_sequential"])
    best_cfg = {k: best[k] for k in _SWEEP_CFG_KEYS}
    tuned = service_row(
        per_family=per_family, trials=final_trials, repeats=final_repeats,
        **best_cfg,
    )
    return {
        "grid": points,
        "tuned": {"config": best_cfg, **tuned},
        "target_met": bool(tuned["speedup_vs_tuned_sequential"] >= 1.0),
    }


# Every key the ``partitioned`` engine row must carry (the smoke job and
# docs/BENCHMARKS.md read this set; population facts are NESTED so the row's
# top level holds only measurements, like every other engine row).
PARTITIONED_ROW_KEYS = frozenset({
    "population",
    "geomean_round_us",
    "geomean_bytes_per_round",
    "segment_geomean_round_us",
    "segment_geomean_bytes_per_round",
    "round_us_vs_segment",
    "bytes_vs_segment",
    "tuned_slab_npad",
    "slab_sweep_us",
    "phases_us",
})

PHASE_NAMES = ("copy", "reduce", "combine", "merge")


def _partitioned_phase_fns(prep, part):
    """The partitioned round's four phases as separately jitted closures
    (jnp-oracle arithmetic, matching the engine-row timings):

      * ``copy``    -- pad the bound plane to the slab grid and gather every
        main-stream and straddle-stream copy's slab-local bound windows;
      * ``reduce``  -- per-copy activity partials over both streams;
      * ``combine`` -- straddle-table segment sum, per-row aggregate
        selection, and the candidate arithmetic;
      * ``merge``   -- the column reduction (rectangle gather when
        scheduled) and the bound merge.

    Each returns concrete arrays so ``jax.block_until_ready`` fences the
    phase boundary; feeding phase N the MATERIALIZED outputs of phase N-1
    is exactly what the fused kernel avoids, so the per-phase sum runs
    above the fused round time -- the breakdown is for attribution, not a
    faster total."""
    cfg = DEFAULT_CONFIG
    dt = prep.d.val.dtype
    eps = cfg.eps_for(dt)
    int_eps, inf = cfg.int_eps, cfg.inf
    n_pad = prep.n_pad
    extra = part.n_pad_part - n_pad
    has_straddle = part.has_straddle

    @jax.jit
    def copy_phase(lb, ub):
        z = jnp.zeros((extra,), lb.dtype)
        lbf = jnp.concatenate([lb, z])
        ubf = jnp.concatenate([ub, z])
        lb_g, ub_g, col_g = kref._partitioned_gathered_bounds(
            part, lbf, ubf, part.val, part.col_s, part.tile_inst, part.tile_slab
        )
        if has_straddle:
            a_lb, a_ub, _ = kref._partitioned_gathered_bounds(
                part, lbf, ubf, part.a_val, part.a_col_s,
                part.a_tile_inst, part.a_tile_slab,
            )
        else:
            a_lb = a_ub = jnp.zeros((0,) + part.val.shape[1:], dt)
        return lb_g, ub_g, a_lb, a_ub, col_g

    @jax.jit
    def reduce_phase(lb_g, ub_g, a_lb, a_ub):
        main = kref.activities_tiles_ref(part.val, lb_g, ub_g, inf)
        if has_straddle:
            sub = kref.activities_tiles_ref(part.a_val, a_lb, a_ub, inf)
        else:
            sub = main
        return main, sub

    @jax.jit
    def combine_phase(main, sub, lb_g, ub_g):
        mf, mc, xf, xc = main
        if has_straddle:
            slot = part.a_slot.reshape(-1)
            nseg = part.n_straddle + 1
            tab = lambda x: jax.ops.segment_sum(
                x.reshape(-1), slot, num_segments=nseg
            )
            done = part.row_done != 0
            sel = lambda local, t: jnp.where(done, local, tab(t)[part.agg_slot])
            amf, amc, axf, axc = sub
            rmf, rmc, rxf, rxc = sel(mf, amf), sel(mc, amc), sel(xf, axf), sel(xc, axc)
        else:
            rmf, rmc, rxf, rxc = mf, mc, xf, xc
        return kref.candidates_tiles_ref(
            part.val, lb_g, ub_g, part.ii_g != 0, rmf, rmc, rxf, rxc,
            part.lhs_g, part.rhs_g, int_eps, inf,
        )

    @jax.jit
    def merge_phase(lcand, ucand, col_g, lb, ub):
        if part.col_slots is not None:
            fl = jnp.concatenate([lcand.reshape(-1), jnp.full((1,), -inf, dt)])
            fu = jnp.concatenate([ucand.reshape(-1), jnp.full((1,), inf, dt)])
            best_l = jnp.maximum(fl[part.col_slots].max(axis=1), -inf)
            best_u = jnp.minimum(fu[part.col_slots].min(axis=1), inf)
        else:
            best_l, best_u = kref.batched_scatter_round_ref(
                lcand, ucand, col_g, 1, part.n_pad_part, inf
            )
            best_l, best_u = best_l.reshape(-1), best_u.reshape(-1)
        return bnd.apply_updates(lb, ub, best_l[:n_pad], best_u[:n_pad], eps, inf)

    return copy_phase, reduce_phase, combine_phase, merge_phase


def _partitioned_phase_times(prep, part, repeats: int = 3) -> dict:
    """Per-phase wall times (us) of one partitioned round via
    ``obs.timing.time_phases``: each phase is fed the previous phase's
    ready outputs and fenced at its boundary."""
    copy_f, reduce_f, combine_f, merge_f = _partitioned_phase_fns(prep, part)
    g = jax.block_until_ready
    lb, ub = prep.lb0, prep.ub0
    gathered = g(copy_f(lb, ub))
    partials = g(reduce_f(*gathered[:4]))
    cands = g(combine_f(*partials, gathered[0], gathered[1]))
    g(merge_f(*cands, gathered[4], lb, ub))
    return time_phases(
        {
            "copy": lambda: copy_f(lb, ub),
            "reduce": lambda: reduce_f(*gathered[:4]),
            "combine": lambda: combine_f(*partials, gathered[0], gathered[1]),
            "merge": lambda: merge_f(*cands, gathered[4], lb, ub),
        },
        repeats=repeats,
    )


def sweep_slab_widths(n_pad: int) -> "list[int]":
    """The SLAB_NPAD autotune candidates for a padded domain: the balanced
    width at the VMEM cap (the fewest slabs) plus the balanced widths at
    one and two extra slabs -- narrower windows trade accumulator residency
    for more straddling copies, and which side wins is an empirical
    property of the instance family, hence the sweep."""
    base = max(1, -(-n_pad // SLAB_NPAD))
    widths = []
    for ns in (base, base + 1, base + 2):
        w = default_slab_width(n_pad, cap=-(-n_pad // ns))
        if w not in widths:
            widths.append(w)
    return widths


def partitioned_large_row(
    specs=LARGE_SPECS,
    n: int = LARGE_N,
    tile: dict = LARGE_TILE,
    widths=None,
    repeats: int = 3,
):
    """The ``partitioned`` engine row: SLAB_NPAD-swept round time, per-phase
    breakdown and measured bytes/round of the column-slab engine on banded
    instances, with the segment engine measured on the SAME instances for
    the comparison the partitioned engine exists to win (jnp-oracle
    arithmetic timings, like the other engine rows; bytes from
    ``round_cost_analysis``).  Population facts live under the nested
    ``population`` key so the row's top level is measurements only
    (see ``PARTITIONED_ROW_KEYS`` and docs/BENCHMARKS.md)."""
    pairs = []
    for spec in specs:
        p = make_banded(n=n, **spec)
        pairs.append((p, prepare_block_ell(p, **tile)))
    n_pad = pairs[0][1].n_pad
    if widths is None:
        widths = sweep_slab_widths(n_pad)

    sweep_raw = {}
    for w in widths:
        us = []
        for _, prep in pairs:
            fn = jax.jit(
                round_fn_for(prep, use_pallas=False, scatter="partitioned", slab=w)
            )
            lb, ub = prep.lb0, prep.ub0
            t = time_fenced(lambda: fn(lb, ub), repeats=repeats)
            us.append(t * 1e6)
        sweep_raw[w] = us
    tuned = min(sweep_raw, key=lambda w: geomean(sweep_raw[w]))

    seg_us, seg_b, part_b = [], [], []
    phase_acc = {k: [] for k in PHASE_NAMES}
    for p, prep in pairs:
        fn = jax.jit(round_fn_for(prep, use_pallas=False, scatter="segment"))
        lb, ub = prep.lb0, prep.ub0
        t = time_fenced(lambda: fn(lb, ub), repeats=repeats)
        seg_us.append(t * 1e6)
        seg_b.append(round_cost_analysis(p, "segment", **tile)["bytes_accessed"])
        part_b.append(
            round_cost_analysis(p, "partitioned", **tile)["bytes_accessed"]
        )
        times = _partitioned_phase_times(
            prep, prep.slab_partition(tuned), repeats=repeats
        )
        for k in PHASE_NAMES:
            phase_acc[k].append(times[k])

    return {
        "population": {
            "set": f"banded n={n}",
            "instances": len(pairs),
            "n_pad_over_budget": bool(n_pad > SCATTER_MAX_NPAD),
        },
        "geomean_round_us": geomean(sweep_raw[tuned]),
        "geomean_bytes_per_round": geomean(part_b),
        "segment_geomean_round_us": geomean(seg_us),
        "segment_geomean_bytes_per_round": geomean(seg_b),
        "round_us_vs_segment": geomean(
            [t / s for t, s in zip(sweep_raw[tuned], seg_us)]
        ),
        "bytes_vs_segment": geomean(
            [pb / sb for pb, sb in zip(part_b, seg_b)]
        ),
        "tuned_slab_npad": int(tuned),
        "slab_sweep_us": {str(w): geomean(us) for w, us in sweep_raw.items()},
        "phases_us": {k: geomean(v) for k, v in phase_acc.items()},
    }


# Every key the ``obs`` observability row must carry (the smoke job and
# docs/OBSERVABILITY.md read this set).
OBS_ROW_KEYS = frozenset({
    "population",
    "telemetry_capacity",
    "overhead_ratio",
    "overhead_bound",
    "bitwise_identical",
    "rounds_recorded",
    "ring_wrapped",
    "span_count",
    "span_schema_ok",
    "metrics_sources",
})

# Acceptance bars for the telemetry-on/off wall-clock ratio.  The full-row
# population amortizes the per-round record ops into the round arithmetic;
# the smoke population is tiny (fixed dispatch costs loom large), so its
# bar is looser.  Both are pinned: a regression that makes telemetry
# expensive fails the bench, not just a dashboard.
OBS_OVERHEAD_BOUND = 1.25
OBS_SMOKE_OVERHEAD_BOUND = 1.5


def obs_row(
    per_family: int = PER_FAMILY,
    capacity: int = 64,
    trials: int = 7,
    repeats: int = 3,
    overhead_bound: float = OBS_OVERHEAD_BOUND,
):
    """The ``obs`` row: what does device telemetry cost, and does the rest
    of the observability plane hold its contracts?

    Three measurements in one row: (1) paired-trials wall-clock ratio of
    full fixed points with the telemetry plane on vs off over the Set-2
    population plus one contraction chain (the 100-round worst case, where
    per-round recording has the most rounds to slow down), asserted under
    the pinned ``overhead_bound`` and required bitwise-identical; (2) a
    traced+telemetered service run whose exported spans are schema-checked
    against ``SPAN_KEYS``; (3) the service's metrics-registry source list,
    so a silently dropped gauge shows up as a row diff."""
    from .precision import _contraction_chain  # lazy: precision imports us

    problems = [p for _, p in instances_for_set(SET, per_family=per_family)]
    problems.append(_contraction_chain(48, rho=0.9))

    def run_off():
        return [propagate_block_ell(p, use_pallas=False) for p in problems]

    def run_on():
        return [
            propagate_block_ell(p, use_pallas=False, telemetry=capacity)
            for p in problems
        ]

    off, on = run_off(), run_on()
    bitwise = all(
        np.array_equal(np.asarray(a.lb), np.asarray(b.lb))
        and np.array_equal(np.asarray(a.ub), np.asarray(b.ub))
        and int(a.rounds) == int(b.rounds)
        for a, b in zip(off, on)
    )
    assert bitwise, "telemetry-on bounds diverged from telemetry-off"
    rounds_recorded = sum(r.telemetry.rounds_recorded for r in on)
    ring_wrapped = sum(r.telemetry.rounds_recorded > capacity for r in on)

    trials_ = paired_trials([run_off, run_on], trials=trials, repeats=repeats)
    ratio = median_ratio(trials_, num=1, den=0)
    assert ratio <= overhead_bound, (
        f"telemetry overhead {ratio:.3f}x exceeds the {overhead_bound}x bar"
    )

    svc_probs = [p for _, p in instances_for_set(SET, per_family=1)]
    specs = BucketSpec.for_problems(svc_probs, slots=2)
    tracer = Tracer()
    svc = PropagationService(
        specs, use_pallas=False, telemetry=capacity, tracer=tracer
    )
    svc_res = svc.serve(svc_probs)
    assert all(r.telemetry is not None for r in svc_res)
    lines = [json.loads(ln) for ln in tracer.export().strip().splitlines()]
    span_schema_ok = bool(lines) and all(set(d) == SPAN_KEYS for d in lines)
    assert span_schema_ok, "exported spans violate the pinned SPAN_KEYS schema"
    assert {"pump", "step", "ticket"} <= {d["name"] for d in lines}

    return {
        "population": {
            "set": SET,
            "instances": len(problems),
            "contraction_chains": 1,
        },
        "telemetry_capacity": capacity,
        "overhead_ratio": float(ratio),
        "overhead_bound": float(overhead_bound),
        "bitwise_identical": bool(bitwise),
        "rounds_recorded": int(rounds_recorded),
        "ring_wrapped": int(ring_wrapped),
        "span_count": len(lines),
        "span_schema_ok": bool(span_schema_ok),
        "metrics_sources": sorted(svc.stats()["metrics"]["sources"]),
    }


def obs_smoke(out_path: str = OUT_PATH):
    """CI schema smoke for ``--smoke --telemetry``: a scaled-down ``obs``
    row from the SAME builder, schema-asserted against ``OBS_ROW_KEYS``
    (with the smoke overhead bar) and merged into a THROWAWAY copy of
    ``BENCH_prop.json``, run-metadata stamp included."""
    row = obs_row(
        per_family=1, capacity=8, trials=2, repeats=1,
        overhead_bound=OBS_SMOKE_OVERHEAD_BOUND,
    )
    missing = OBS_ROW_KEYS - set(row)
    extra = set(row) - OBS_ROW_KEYS
    assert not missing and not extra, (sorted(missing), sorted(extra))
    assert row["bitwise_identical"] is True
    assert row["overhead_ratio"] <= row["overhead_bound"]
    assert row["span_schema_ok"] and row["span_count"] > 0
    # The contraction chain runs to the round cap, so a capacity-8 ring
    # must have wrapped -- truncation semantics exercised, not just spare
    # capacity.
    assert row["ring_wrapped"] >= 1
    assert {"compile_counts", "engine_cache", "kernel_caches", "service"} <= set(
        row["metrics_sources"]
    )
    merged = _merge_report({"obs": row}, out_path)
    assert merged["obs"] == row
    assert set(merged["run_meta"]) == {
        "git_commit", "timestamp", "jax_version", "x64", "backend",
    }
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump(merged, f, indent=2)
        tmp = f.name
    try:
        with open(tmp) as f:
            assert json.load(f)["obs"] == row
    finally:
        os.unlink(tmp)
    return [
        ("obs_smoke", 0.0,
         f"schema_ok overhead_ratio={row['overhead_ratio']:.3f} "
         f"(bar<={row['overhead_bound']}) spans={row['span_count']} "
         f"ring_wrapped={row['ring_wrapped']}")
    ]


def obs_run(out_path: str = OUT_PATH):
    """Record the full-fidelity ``obs`` row into ``BENCH_prop.json``."""
    row = obs_row()
    merged = _merge_report({"obs": row}, out_path)
    with open(out_path, "w") as f:
        json.dump(merged, f, indent=2)
    return [
        ("obs_telemetry", 0.0,
         f"overhead_ratio={row['overhead_ratio']:.3f} "
         f"(bar<={row['overhead_bound']}) "
         f"rounds_recorded={row['rounds_recorded']} "
         f"ring_wrapped={row['ring_wrapped']} spans={row['span_count']}")
    ]


def smoke(out_path: str = OUT_PATH):
    """CI schema smoke (``--smoke``): scaled-down partitioned AND service
    rows from the SAME row builders as the full run (small instances, single
    repeat), schema-asserted against ``PARTITIONED_ROW_KEYS`` /
    ``SERVICE_ROW_KEYS`` and merged into a THROWAWAY copy of
    ``BENCH_prop.json`` -- proving the rows the next full run writes merge
    cleanly without touching the committed trajectory."""
    row = partitioned_large_row(
        specs=(dict(m=400, row_nnz=8, band=256, seed=0),),
        n=1500,
        tile=dict(tile_rows=8, tile_width=8),
        widths=[128, 256],
        repeats=1,
    )
    missing = PARTITIONED_ROW_KEYS - set(row)
    extra = set(row) - PARTITIONED_ROW_KEYS
    assert not missing and not extra, (sorted(missing), sorted(extra))
    assert set(row["phases_us"]) == set(PHASE_NAMES)
    assert set(row["population"]) == {"set", "instances", "n_pad_over_budget"}
    assert str(row["tuned_slab_npad"]) in row["slab_sweep_us"]

    svc = service_row(
        per_family=2, slots=2, size_classes=1, trials=1, repeats=1
    )
    missing = SERVICE_ROW_KEYS - set(svc)
    extra = set(svc) - SERVICE_ROW_KEYS
    assert not missing and not extra, (sorted(missing), sorted(extra))
    assert svc["compiles_during_serve"] == 0
    assert svc["latency_ms_p50"] <= svc["latency_ms_p99"]
    assert 0.0 < svc["mean_slot_occupancy"] <= 1.0

    sol = solver_row(ns=(24,), trials=1, repeats=1, assert_target=False)
    missing = SOLVER_ROW_KEYS - set(sol)
    extra = set(sol) - SOLVER_ROW_KEYS
    assert not missing and not extra, (sorted(missing), sorted(extra))
    assert sol["objective_match"]
    assert all(s == "optimal" for s in sol["statuses"])
    assert sol["host_syncs_per_node"] <= sol["python_host_syncs_per_node"]

    sweep = service_sweep_row(
        grid=(
            dict(slots=2, size_classes=1, rounds_per_step=8, tile_width=None),
            dict(slots=2, size_classes=1, rounds_per_step=4, tile_width=None),
        ),
        per_family=2, trials=1, repeats=1, final_trials=1, final_repeats=1,
    )
    missing = SERVICE_SWEEP_ROW_KEYS - set(sweep)
    extra = set(sweep) - SERVICE_SWEEP_ROW_KEYS
    assert not missing and not extra, (sorted(missing), sorted(extra))
    assert len(sweep["grid"]) == 2
    assert set(sweep["tuned"]) == SERVICE_ROW_KEYS | {"config"}
    assert sweep["tuned"]["config"] in [
        {k: pt[k] for k in _SWEEP_CFG_KEYS} for pt in sweep["grid"]
    ]
    assert sweep["target_met"] == (
        sweep["tuned"]["speedup_vs_tuned_sequential"] >= 1.0
    )

    merged = _merge_report(
        {"engines": {
            "partitioned": row, "service": svc, "service_sweep": sweep,
            "solver": sol,
        }}, out_path
    )
    assert merged["engines"]["partitioned"] == row
    assert merged["engines"]["service"] == svc
    assert merged["engines"]["service_sweep"] == sweep
    assert merged["engines"]["solver"] == sol
    if os.path.exists(out_path):
        with open(out_path) as f:
            old = json.load(f)
        lost_engines = set(old.get("engines", {})) - set(merged["engines"])
        lost_keys = set(old) - set(merged)
        assert not lost_engines and not lost_keys, (lost_engines, lost_keys)
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump(merged, f, indent=2)
        tmp = f.name
    try:
        with open(tmp) as f:
            back = json.load(f)
        assert back["engines"]["partitioned"] == row
        assert back["engines"]["service"] == svc
    finally:
        os.unlink(tmp)
    return [
        ("bench_prop_smoke", row["geomean_round_us"],
         f"schema_ok tuned_slab_npad={row['tuned_slab_npad']} "
         f"phases={','.join(PHASE_NAMES)} "
         f"service_ips={svc['instances_per_sec']:.1f} "
         f"solver_nps={sol['device_nodes_per_sec']:.0f}")
    ]


def _merge_report(report: dict, out_path: str) -> dict:
    """Merge new engine rows into an existing BENCH_prop.json: engine rows
    are updated/added, any other keys from earlier PRs are preserved.
    Every merge re-stamps ``run_meta`` (git commit, timestamp, jax
    version, x64, backend -- ``obs.metrics.run_metadata``) so the
    trajectory file always attributes its newest rows."""
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                old = json.load(f)
        except (OSError, json.JSONDecodeError):
            old = {}
        engines = dict(old.get("engines", {}))
        engines.update(report.get("engines", {}))
        merged = {**old, **report}
        merged["engines"] = engines
    else:
        merged = dict(report)
    merged["run_meta"] = run_metadata()
    return merged


def run(out_path: str = OUT_PATH):
    insts = instances_for_set(SET, per_family=PER_FAMILY)
    acc = {e: {"round_us": [], "bytes": []} for e in ENGINES}
    for spec, p in insts:
        prep = prepare_block_ell(p)
        for engine in ENGINES:
            if engine == "legacy":
                fn = jax.jit(legacy_round_fn_for(prep, use_pallas=False))
                lb, ub = prep.d.lb0, prep.d.ub0
            else:
                fn = jax.jit(round_fn_for(prep, use_pallas=False, scatter=engine))
                lb, ub = prep.lb0, prep.ub0
            t = time_fenced(lambda: fn(lb, ub))  # warmup compiles off-timer
            acc[engine]["round_us"].append(t * 1e6)
            acc[engine]["bytes"].append(
                round_cost_analysis(p, engine)["bytes_accessed"]
            )

    thru = batched_throughput()
    nodes = node_throughput()
    large = partitioned_large_row()
    svc = service_row()
    sweep = service_sweep_row()
    solver = solver_row()
    report = {
        "set": SET,
        "instances": len(insts),
        # The engine-row population (PR 3 added pseudo_boolean to the
        # default families, growing it 6 -> 8 instances): recorded so the
        # cross-PR trajectory is read against its workload, not assumed
        # constant.
        "families": sorted({spec.family for spec, _ in insts}),
        "engines": {
            e: {
                "geomean_round_us": geomean(v["round_us"]),
                "geomean_bytes_per_round": geomean(v["bytes"]),
            }
            for e, v in acc.items()
        },
    }
    report["engines"]["batched"] = {
        "instances_per_sec": thru["batched_instances_per_sec"],
        "speedup_vs_sequential_dispatch": thru["batched_speedup"],
        "bucket_fill": thru["bucket_fill"],
    }
    report["engines"]["service"] = svc
    report["engines"]["service_sweep"] = sweep
    report["engines"]["nodes"] = {
        "nodes_per_sec": nodes["shared_nodes_per_sec"],
        "speedup_vs_repack_dispatch": nodes["shared_matrix_speedup"],
    }
    report["engines"]["partitioned"] = large
    report["engines"]["solver"] = solver
    report["bytes_reduction_fused_vs_legacy"] = geomean(
        [l / f for l, f in zip(acc["legacy"]["bytes"], acc["fused"]["bytes"])]
    )
    report["batched_throughput"] = thru
    report["node_throughput"] = nodes
    report = _merge_report(report, out_path)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)

    rows = [
        (
            f"bench_prop_{e}",
            report["engines"][e]["geomean_round_us"],
            f"geomean_bytes_per_round={report['engines'][e]['geomean_bytes_per_round']:.0f}",
        )
        for e in ENGINES
    ]
    rows.append(
        ("bench_prop_batched",
         1e6 / thru["batched_instances_per_sec"],
         f"instances_per_sec={thru['batched_instances_per_sec']:.1f} "
         f"speedup_vs_sequential={thru['batched_speedup']:.2f}x "
         f"buckets={thru['buckets']} instances={thru['instances']} "
         f"bucket_fill={','.join(f'{f:.2f}' for f in thru['bucket_fill'])}")
    )
    rows.append(
        ("bench_prop_service",
         1e6 / svc["instances_per_sec"],
         f"instances_per_sec={svc['instances_per_sec']:.1f} "
         f"speedup_vs_sequential={svc['speedup_vs_sequential_dispatch']:.2f}x "
         f"p50={svc['latency_ms_p50']:.1f}ms p95={svc['latency_ms_p95']:.1f}ms "
         f"p99={svc['latency_ms_p99']:.1f}ms "
         f"occupancy={svc['mean_slot_occupancy']:.2f} "
         f"compiles_during_serve={svc['compiles_during_serve']}")
    )
    tuned_cfg = sweep["tuned"]["config"]
    rows.append(
        ("bench_prop_service_sweep",
         1e6 / sweep["tuned"]["instances_per_sec"],
         f"tuned[slots={tuned_cfg['slots']} "
         f"size_classes={tuned_cfg['size_classes']} "
         f"rounds_per_step={tuned_cfg['rounds_per_step']} "
         f"tile_width={tuned_cfg['tile_width']}] "
         f"speedup_vs_tuned_sequential="
         f"{sweep['tuned']['speedup_vs_tuned_sequential']:.2f}x "
         f"grid_points={len(sweep['grid'])} "
         f"target_met={sweep['target_met']}")
    )
    rows.append(
        ("bench_prop_nodes",
         1e6 / nodes["shared_nodes_per_sec"],
         f"nodes_per_sec={nodes['shared_nodes_per_sec']:.1f} "
         f"speedup_vs_repack={nodes['shared_matrix_speedup']:.2f}x "
         f"nodes={nodes['nodes']}")
    )
    phases = " ".join(
        f"{k}={large['phases_us'][k]:.0f}us" for k in PHASE_NAMES
    )
    rows.append(
        ("bench_prop_partitioned",
         large["geomean_round_us"],
         f"large_set={large['population']['set']} "
         f"tuned_slab_npad={large['tuned_slab_npad']} "
         f"round_us_vs_segment={large['round_us_vs_segment']:.2f}x "
         f"bytes_per_round={large['geomean_bytes_per_round']:.0f} "
         f"segment_bytes={large['segment_geomean_bytes_per_round']:.0f} "
         f"bytes_vs_segment={large['bytes_vs_segment']:.2f}x "
         f"phases[{phases}]")
    )
    rows.append(
        ("bench_prop_solver",
         1e6 / solver["device_nodes_per_sec"],
         f"device_nodes_per_sec={solver['device_nodes_per_sec']:.0f} "
         f"python_nodes_per_sec={solver['python_nodes_per_sec']:.0f} "
         f"speedup_vs_python_driver="
         f"{solver['speedup_vs_python_driver']:.2f}x "
         f"host_syncs_per_node={solver['host_syncs_per_node']:.3f} "
         f"python_syncs_per_node={solver['python_host_syncs_per_node']:.3f} "
         f"target_met={solver['target_met']}")
    )
    rows.append(
        ("bench_prop_json", 0.0,
         f"written={out_path} "
         f"bytes_reduction_fused_vs_legacy={report['bytes_reduction_fused_vs_legacy']:.2f}x")
    )
    return rows


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="quick CI schema check: scaled-down partitioned row, merged "
        "into a throwaway copy of BENCH_prop.json (nothing written)",
    )
    parser.add_argument(
        "--telemetry",
        action="store_true",
        help="additionally build the obs row: telemetry on/off overhead "
        "ratio (asserted under its pinned bound), span schema check and "
        "metrics-registry sources (merged like the engine rows; with "
        "--smoke, asserted against a throwaway copy instead)",
    )
    ns = parser.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)  # match benchmarks.run
    rows = list(smoke() if ns.smoke else run())
    if ns.telemetry:
        rows += obs_smoke() if ns.smoke else obs_run()
    for r in rows:
        print(",".join(map(str, r)))
