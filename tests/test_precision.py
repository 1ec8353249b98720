"""Two-tier adaptive precision: the safety contract and its plumbing.

The fp32 tier is only admissible because three invariants hold (see
``core.bounds.widen_outward`` / ``core.types.int_round_slack`` and the
two-tier front ends in ``core.propagator`` / ``kernels.ops`` /
``core.nodes``):

  * **never tighter** -- outward-rounded fp32 bounds stay outside the f64
    fixed point up to an fp32-representation band (observed <= 6.4e-8
    relative on cancellation-heavy rows; asserted here at 1e-6, well under
    the paper's 1e-5 limit-point criterion), and integer bounds are never
    overtightened at all (the rounding slack absorbs the discontinuity);
  * **no false infeasibility** -- an fp32 infeasible verdict is never
    trusted: the two-tier front ends rerun the endgame from the ORIGINAL
    bounds in the final dtype, so the reported verdict is always f64's;
  * **same limit point** -- promotion is an exact cast of outward bounds
    (with re-canonicalized infinity sentinels), so the tiered run lands on
    the f64-only fixed point: bitwise for integer variables, within the
    same fp32 band for continuous ones (the endgame's monotone merge keeps
    a band-tighter fp32 bound rather than weakening it).

Tests marked ``f32native`` compare JAX fp32 engines against the HOST
numpy-f64 sequential oracle (``core.seq_ref``), so they stay meaningful
with ``jax_enable_x64`` off -- CI's fp32 leg runs exactly these (see
``conftest.pytest_collection_modifyitems``).
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    DEFAULT_CONFIG,
    INF,
    PropagationService,
    TierPolicy,
    F32_BAND,
    bounds_equal,
    branch_children,
    int_round_slack,
    overtightening,
    progress_measure,
    propagate,
    propagate_batch,
    propagate_nodes,
    propagate_sequential,
    widen_outward,
)
from repro.data import (
    make_banded,
    make_knapsack,
    make_mixed,
    make_pseudo_boolean,
    make_set_cover,
)
from repro.kernels import prepare_block_ell, propagate_block_ell, round_cost_analysis


def _population():
    """Small instances of every family (fast under interpret mode)."""
    return [
        ("knapsack", make_knapsack(n=50, m=10, seed=0)),
        ("knapsack1", make_knapsack(n=50, m=10, seed=1)),
        ("set_cover", make_set_cover(n=60, m=20, seed=0)),
        ("mixed", make_mixed(m=80, n=60, seed=0)),
        ("mixed1", make_mixed(m=80, n=60, seed=3)),
        ("banded", make_banded(n=384, m=64, row_nnz=8, band=48, seed=0)),
        ("pb", make_pseudo_boolean(n=60, m=40, seed=0)),
    ]


def _run_f32(engine, p):
    """One fp32-only fixed point on the given engine family."""
    if engine == "jnp":
        return propagate(p, dtype=np.float32)
    return propagate_block_ell(p, dtype=np.float32, scatter=engine)


def _assert_never_tighter(name, lb_t, ub_t, lb_o, ub_o, is_int, band):
    """Tier bounds must stay outside the oracle's, up to ``band`` relative
    for continuous variables and EXACTLY for integer ones (an
    oracle-infinite bound made finite is never allowed)."""
    msg = overtightening(lb_t, ub_t, lb_o, ub_o, is_int, band)
    assert msg is None, f"{name}: {msg}"


# ---------------------------------------------------------------------------
# fp32 tier vs the host numpy f64 oracle (runs on the x64-off CI leg)
# ---------------------------------------------------------------------------


@pytest.mark.f32native
@pytest.mark.parametrize("engine", ["jnp", "fused", "segment", "batch"])
def test_fp32_tier_never_tighter_than_f64_oracle(engine):
    """Outward-rounded fp32 fixed points stay outside the sequential f64
    oracle's on every family and engine, and an fp32 infeasible verdict
    implies the oracle agrees (no false positives on these families)."""
    pop = _population()
    if engine == "batch":
        batch = propagate_batch([p for _, p in pop], dtype=np.float32)
    for idx, (name, p) in enumerate(pop):
        seq = propagate_sequential(p)
        r = batch[idx] if engine == "batch" else _run_f32(engine, p)
        if bool(r.infeasible):
            assert seq.infeasible, f"{name}/{engine}: false fp32 infeasibility"
            continue
        if seq.infeasible:
            continue  # engine missed a detection the verdict test covers
        _assert_never_tighter(
            f"{name}/{engine}", r.lb, r.ub,
            np.asarray(seq.lb), np.asarray(seq.ub),
            np.asarray(p.is_int, bool), F32_BAND,
        )
        # NOTE: limit-point agreement at the paper's tolerance is a
        # STATISTIC (the fp32-only fixed point may stop epsilon-weaker --
        # the paper reports 842/987, and benchmarks/precision.py accounts
        # the rate); the invariant tested here is only never-tighter.


@pytest.mark.f32native
@pytest.mark.parametrize("engine", ["fused", "segment"])
def test_fp32_single_instance_merge_widens_outward(engine):
    """``propagate_block_ell`` at fp32 merges with the outward widening of
    its config: switching the widening off yields tighter bounds, so the
    fixed point did use it (the same merge every other engine applies)."""
    p = make_mixed(m=80, n=60, seed=0)
    exact = dataclasses.replace(DEFAULT_CONFIG, outward_eps_f32=0.0)
    wide = propagate_block_ell(p, dtype=np.float32, scatter=engine)
    tight = propagate_block_ell(p, cfg=exact, dtype=np.float32, scatter=engine)
    lw, uw = np.asarray(wide.lb), np.asarray(wide.ub)
    lt, ut = np.asarray(tight.lb), np.asarray(tight.ub)
    assert np.all(lw <= lt) and np.all(uw >= ut)
    assert np.any(lw < lt) or np.any(uw > ut)


@pytest.mark.f32native
def test_fp32_infeasibility_detected_on_infeasible_family():
    """The pb family's infeasible seeds ARE detected by the fp32 tier
    (outward rounding weakens bounds but not past a real conflict)."""
    p = make_pseudo_boolean(n=80, m=80, seed=0)
    seq = propagate_sequential(p)
    assert seq.infeasible  # seed pinned to an infeasible instance
    assert bool(propagate(p, dtype=np.float32).infeasible)


# ---------------------------------------------------------------------------
# Safety primitives (pure, dtype-explicit)
# ---------------------------------------------------------------------------


@pytest.mark.f32native
def test_widen_outward_semantics():
    l = jnp.asarray([-2.0, 0.5, 1000.0], jnp.float32)
    u = jnp.asarray([3.0, 0.75, -1000.0], jnp.float32)
    wl, wu = widen_outward(l, u, 0.0)
    assert np.array_equal(np.asarray(wl), np.asarray(l))  # exact identity
    assert np.array_equal(np.asarray(wu), np.asarray(u))
    out = 2.0**-17
    wl, wu = widen_outward(l, u, out)
    dl = np.asarray(l, np.float64) - np.asarray(wl, np.float64)
    du = np.asarray(wu, np.float64) - np.asarray(u, np.float64)
    scale_l = np.maximum(1.0, np.abs(np.asarray(l, np.float64)))
    scale_u = np.maximum(1.0, np.abs(np.asarray(u, np.float64)))
    assert np.all(dl > 0) and np.all(du > 0)            # strictly outward
    assert np.all(dl >= 0.9 * out * scale_l)            # scale-aware width
    assert np.all(du >= 0.9 * out * scale_u)


@pytest.mark.f32native
def test_int_round_slack_per_dtype():
    assert int_round_slack(jnp.float32) == 2.0**-17
    assert int_round_slack(jnp.bfloat16) == 2.0**-6
    assert int_round_slack(jnp.float64) == 0.0  # f64 rounding stays bitwise


@pytest.mark.f32native
def test_progress_measure_semantics():
    lb = jnp.asarray([-INF, 0.0, 2.0], jnp.float32)
    ub = jnp.asarray([INF, 10.0, 4.0], jnp.float32)
    # No movement -> exactly zero.
    assert float(progress_measure(lb, ub, lb, ub)) == 0.0
    # An infinite->finite jump contributes ~1 (sentinel dominates the
    # denominator); a finite tighten contributes ~|delta|/scale.
    lb2 = jnp.asarray([0.0, 0.0, 2.0], jnp.float32)
    ub2 = jnp.asarray([INF, 5.0, 4.0], jnp.float32)
    m = float(progress_measure(lb, ub, lb2, ub2))
    assert m == pytest.approx(1.0 + 5.0 / 11.0, rel=1e-3)
    # Batched planes reduce per instance (trailing axis).
    mb = progress_measure(
        jnp.stack([lb, lb]), jnp.stack([ub, ub]),
        jnp.stack([lb2, lb]), jnp.stack([ub2, ub]),
    )
    assert mb.shape == (2,) and float(mb[1]) == 0.0


def test_compact_index_streams_per_dtype():
    """Low-precision prep narrows the index streams (int16 cols, int8
    integrality marks) -- the other half of the fp32 byte saving; f64 prep
    keeps the original int32 streams bitwise."""
    p = make_set_cover(n=60, m=20, seed=0)
    prep32 = prepare_block_ell(p, dtype=np.float32)
    assert prep32.d.col.dtype == np.dtype(np.int16)
    assert prep32.ii_g.dtype == np.dtype(np.int8)
    prep64 = prepare_block_ell(p, dtype=np.float64)
    assert prep64.d.col.dtype == np.dtype(np.int32)
    assert prep64.ii_g.dtype == np.dtype(np.int32)


def test_fp32_fused_bytes_per_round_ratio():
    """The acceptance bar of the tier: fused-engine fp32 rounds move
    <= 0.6x the bytes of fp64 rounds (value planes halve, index streams
    quarter/halve via the compact dtypes)."""
    for name, p in [
        ("mixed", make_mixed(m=80, n=60, seed=0)),
        ("set_cover", make_set_cover(n=60, m=20, seed=0)),
    ]:
        b32 = round_cost_analysis(p, "fused", dtype=np.float32)["bytes_accessed"]
        b64 = round_cost_analysis(p, "fused", dtype=np.float64)["bytes_accessed"]
        assert b32 / b64 <= 0.6, f"{name}: {b32 / b64:.3f}"


# ---------------------------------------------------------------------------
# Two-tier runs land on the f64 fixed point
# ---------------------------------------------------------------------------


def _assert_same_fixed_point(name, lb_t, ub_t, r64, is_int):
    """Two-tier vs f64-only: bitwise for integer variables; continuous
    ones agree within the fp32 band (a cancellation-heavy row can carry
    an fp32-tier bound up to ~6.6e-8 relative INSIDE the f64 fixed point,
    and the monotone endgame keeps the tighter value -- the same band the
    never-tighter contract allows), plus the paper's limit-point
    criterion, which is 10x looser."""
    lb_t, ub_t = np.asarray(lb_t), np.asarray(ub_t)
    lb_r, ub_r = np.asarray(r64.lb), np.asarray(r64.ub)
    assert np.array_equal(lb_t[is_int], lb_r[is_int]), name
    assert np.array_equal(ub_t[is_int], ub_r[is_int]), name
    tol = F32_BAND * (1.0 + np.abs(lb_r))
    assert np.all(np.abs(lb_t - lb_r) <= tol), name
    tol = F32_BAND * (1.0 + np.abs(ub_r))
    assert np.all(np.abs(ub_t - ub_r) <= tol), name
    assert bool(bounds_equal(lb_t, ub_t, r64.lb, r64.ub)), name


@pytest.mark.parametrize("engine", ["jnp", "fused"])
def test_two_tier_lands_on_f64_fixed_point(engine):
    run = (
        (lambda p, **kw: propagate(p, **kw)) if engine == "jnp"
        else (lambda p, **kw: propagate_block_ell(p, scatter="fused", **kw))
    )
    for name, p in _population():
        r64 = run(p)
        rt = run(p, policy=TierPolicy())
        assert bool(rt.infeasible) == bool(r64.infeasible), f"{name}/{engine}"
        if bool(r64.infeasible):
            continue
        _assert_same_fixed_point(
            f"{name}/{engine}", rt.lb, rt.ub, r64, np.asarray(p.is_int, bool)
        )
        # The tier actually ran (feasible instances promote, not restart).
        assert int(rt.tier_rounds) >= 1


def test_two_tier_batch_lands_on_f64_fixed_point():
    pop = _population()
    base = propagate_batch([p for _, p in pop])
    tier = propagate_batch([p for _, p in pop], policy=TierPolicy())
    for (name, p), r64, rt in zip(pop, base, tier):
        assert bool(rt.infeasible) == bool(r64.infeasible), name
        if bool(r64.infeasible):
            continue
        _assert_same_fixed_point(
            f"{name}/batch", rt.lb, rt.ub, r64, np.asarray(p.is_int, bool)
        )


def test_two_tier_nodes_lands_on_f64_fixed_point():
    p = make_set_cover(n=60, m=20, seed=0)
    var = int(np.where(np.asarray(p.is_int, bool))[0][0])
    (dl, du), (ul, uu) = branch_children(p.lb, p.ub, var, 0.0)
    lb_nodes = np.stack([np.asarray(p.lb, np.float64), dl, ul])
    ub_nodes = np.stack([np.asarray(p.ub, np.float64), du, uu])
    base = propagate_nodes(p, lb_nodes, ub_nodes)
    tier = propagate_nodes(p, lb_nodes, ub_nodes, policy=TierPolicy())
    is_int = np.asarray(p.is_int, bool)
    for i in range(3):
        assert bool(tier.infeasible[i]) == bool(base.infeasible[i])
        if bool(base.infeasible[i]):
            continue
        _assert_same_fixed_point(
            f"node{i}", tier.lb[i], tier.ub[i], base.result(i), is_int
        )


def test_two_tier_guard_ignores_fp32_infeasible(monkeypatch):
    """An fp32 infeasible verdict is NEVER the result: force the tier to
    claim infeasibility on a feasible instance and check the endgame
    restarts from the original bounds, landing bitwise on the f64-only
    run with the correct (feasible) verdict."""
    import repro.core.propagator as prop_mod

    p = make_set_cover(n=60, m=20, seed=0)
    r_base = propagate(p)
    assert not bool(r_base.infeasible)

    real = prop_mod._propagate_single

    def lying_fp32(p_, cfg_, driver_, dtype_, lb0_, ub0_, **kw):
        r = real(p_, cfg_, driver_, dtype_, lb0_, ub0_, **kw)
        if dtype_ is not None and np.dtype(dtype_) == np.float32:
            return r._replace(infeasible=jnp.asarray(True))
        return r

    monkeypatch.setattr(prop_mod, "_propagate_single", lying_fp32)
    rt = propagate(p, policy=TierPolicy())
    assert not bool(rt.infeasible)
    assert int(rt.tier_rounds) >= 1  # the (discarded) tier is accounted
    assert np.array_equal(np.asarray(rt.lb), np.asarray(r_base.lb))
    assert np.array_equal(np.asarray(rt.ub), np.asarray(r_base.ub))
    assert int(rt.rounds) == int(r_base.rounds)


# ---------------------------------------------------------------------------
# Progress-based early stop
# ---------------------------------------------------------------------------


@pytest.mark.f32native
def test_early_stop_is_a_trajectory_prefix():
    """Stopping on flatlined progress can only truncate the monotone
    trajectory: stopped bounds sit between the root bounds and the full
    fixed point, rounds never increase, and a truncated run reports
    converged=False."""
    saved = 0
    for name, p in _population():
        full = propagate(p, dtype=np.float32)
        if bool(full.infeasible):
            continue
        stop = propagate(
            p, dtype=np.float32,
            policy=TierPolicy(two_tier=False, stop_progress=0.05, patience=1),
        )
        assert int(stop.rounds) <= int(full.rounds), name
        saved += int(full.rounds) - int(stop.rounds)
        # fp32's sentinel is 1.00000002e20; clamp before comparing against
        # the f64 root bounds (semantically both are "infinite").
        lb_s = np.maximum(np.asarray(stop.lb, np.float64), -INF)
        ub_s = np.minimum(np.asarray(stop.ub, np.float64), INF)
        lb_f = np.maximum(np.asarray(full.lb, np.float64), -INF)
        ub_f = np.minimum(np.asarray(full.ub, np.float64), INF)
        assert np.all(lb_s >= np.asarray(p.lb, np.float64)), name
        assert np.all(ub_s <= np.asarray(p.ub, np.float64)), name
        assert np.all(lb_s <= lb_f) and np.all(ub_s >= ub_f), name
        if int(stop.rounds) < int(full.rounds):
            assert not bool(stop.converged), name
            assert float(stop.progress) < 0.05, name
    assert saved > 0  # the threshold actually fires somewhere in the set


def test_service_early_retire_frees_slots():
    """A service armed with ``stop_progress`` retires flatlined slots
    early: the stats counter matches the per-result evidence, and every
    early result is a valid prefix of the corresponding exact-service
    trajectory (same slot geometry -> bitwise comparable)."""
    pop = [make_set_cover(n=60, m=20, seed=s) for s in range(3)] + [
        make_mixed(m=80, n=60, seed=s) for s in range(3)
    ]
    exact = PropagationService.from_problems(
        pop, slots=2, tile_width=8, use_pallas=False
    )
    ref = exact.serve(pop)
    assert exact.stats()["early_stopped"] == 0
    eager = PropagationService.from_problems(
        pop, slots=2, tile_width=8, use_pallas=False,
        stop_progress=1e6, patience=1,  # everything flatlines immediately
    )
    got = eager.serve(pop)
    n_early = sum(
        1 for r in got
        if not bool(r.converged) and int(r.rounds) < DEFAULT_CONFIG.max_rounds
    )
    assert eager.stats()["early_stopped"] == n_early
    assert n_early >= 1
    for r, rr in zip(got, ref):
        if bool(rr.infeasible):
            continue
        assert np.all(np.asarray(r.lb) <= np.asarray(rr.lb))
        assert np.all(np.asarray(r.ub) >= np.asarray(rr.ub))
        assert np.isfinite(float(r.progress)) or bool(r.converged)
