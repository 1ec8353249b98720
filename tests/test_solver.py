"""Differential + property tests for the device-resident B&B solver.

Four layers:

  * differential: ``solve()`` vs the exhaustive oracle
    (``brute_force_solve``) on >= 20 seeded pure-integer instances across
    two families (pseudo-boolean, random MIP) and both branching rules --
    BITWISE objective agreement and matching feasibility verdicts (the
    integral-data exactness contract of ``core.solver``), plus
    infeasible-at-root and optimal-at-root edge cases;
  * search properties (hypothesis): ``branch_children`` partitions the
    parent domain; ``_plan_expansion`` never double-allocates or leaks
    pool slots; pruning never changes the optimum, only the node count;
  * sync contract: the host is consulted at most ``ceil(levels /
    sync_every)`` times, counted through the ``on_sync`` hook, and the
    pool accounting balances at every sync;
  * determinism: two identical ``solve()`` calls produce identical
    incumbent trajectories, node counts and solutions, for both rules.
"""
import math

import numpy as np
import pytest

try:  # property tests run under hypothesis when present, seeded draws if not
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - container without hypothesis
    HAVE_HYPOTHESIS = False

import jax.numpy as jnp

from repro.core import (
    INF,
    BranchRule,
    Problem,
    branch_children,
    brute_force_solve,
    csr_from_dense,
    solve,
)
from repro.core.solver import FREE, OPEN, READY, _plan_expansion
from repro.data import make_pseudo_boolean, make_random_mip
from repro.kernels import node_objective_tiles
from repro.kernels.ref import node_objective_ref


def _objective(p):
    """Deterministic integral objective with mixed signs (exact in f64)."""
    n = p.lb.shape[0]
    sign = np.where(np.arange(n) % 3 == 0, -1.0, 1.0)
    return np.arange(1, n + 1, dtype=np.float64) * sign


def _assert_solution_feasible(p, x, tol=1e-8):
    m, n = p.csr.m, p.csr.n
    dense = np.zeros((m, n))
    dense[np.asarray(p.csr.row_ids()), np.asarray(p.csr.col)] = np.asarray(
        p.csr.val
    )
    ax = dense @ x
    lhs, rhs = np.asarray(p.lhs), np.asarray(p.rhs)
    assert np.all((lhs <= -INF) | (ax >= lhs - tol))
    assert np.all((rhs >= INF) | (ax <= rhs + tol))
    assert np.all(x >= np.asarray(p.lb) - tol)
    assert np.all(x <= np.asarray(p.ub) + tol)
    assert np.all(np.abs(x - np.round(x)) <= 1e-6)


def _check_accounting(res):
    assert res.nodes_created == 1 + 2 * res.nodes_expanded
    if res.status in ("optimal", "infeasible"):
        assert res.nodes_created == (
            res.leaves
            + res.pruned_bound
            + res.pruned_infeasible
            + res.nodes_expanded
        )


# ---------------------------------------------------------------------------
# Differential suite: 20 seeded instances, both families, both rules.
# ---------------------------------------------------------------------------

PB_SEEDS = list(range(12))
MIP_SEEDS = list(range(8))


@pytest.mark.parametrize("seed", PB_SEEDS)
def test_differential_pseudo_boolean(seed):
    p = make_pseudo_boolean(n=12, m=16, seed=seed)
    c = _objective(p)
    bf = brute_force_solve(p, c)
    rule = BranchRule.PSEUDO_COST if seed % 2 else BranchRule.MOST_FRACTIONAL
    res = solve(
        p, c, rule=rule, node_cap=64, max_levels=32, sync_every=8,
        use_pallas=False,
    )
    assert res.feasible == bf.feasible
    assert res.objective == bf.objective  # bitwise, per the module contract
    _check_accounting(res)
    if res.feasible:
        assert res.status == "optimal"
        assert float(c @ res.x) == bf.objective
        _assert_solution_feasible(p, res.x)
    else:
        assert res.status == "infeasible"
        assert res.x is None


@pytest.mark.parametrize("seed", MIP_SEEDS)
def test_differential_random_mip(seed):
    p = make_random_mip(n=9, m=12, seed=seed)
    c = _objective(p)
    bf = brute_force_solve(p, c)
    res = solve(
        p, c, node_cap=128, max_levels=48, sync_every=8, use_pallas=False,
    )
    assert res.feasible == bf.feasible
    if bf.feasible:
        assert res.objective == bf.objective
        assert float(c @ res.x) == bf.objective
        _assert_solution_feasible(p, res.x)
    _check_accounting(res)


@pytest.mark.parametrize(
    "family,seed", [("pb", 0), ("pb", 1), ("pb", 3), ("mip", 0), ("mip", 1)]
)
def test_differential_tpu_defaults(monkeypatch, family, seed):
    """``solve()`` as it resolves on a TPU -- Pallas kernels, float32 with
    outward-rounded merges -- still matches the exhaustive oracle bitwise
    (kernels in interpret mode here)."""
    import jax

    from repro.kernels import prop_round

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(prop_round, "_on_cpu", lambda: True)
    p = (make_pseudo_boolean(n=12, m=16, seed=seed) if family == "pb"
         else make_random_mip(n=9, m=12, seed=seed))
    c = _objective(p)
    bf = brute_force_solve(p, c)
    res = solve(p, c, node_cap=64, max_levels=48)
    assert res.feasible == bf.feasible
    assert res.objective == bf.objective
    if res.feasible:
        assert float(c @ res.x) == bf.objective
        _assert_solution_feasible(p, res.x)


def test_infeasible_at_root():
    # x0 >= 1 and x0 <= 0: root propagation crosses the bounds immediately.
    dense = np.array([[1.0, 0.0], [1.0, 0.0], [0.0, 1.0]])
    p = Problem(
        csr=csr_from_dense(dense),
        lhs=np.array([1.0, -INF, -INF]),
        rhs=np.array([INF, 0.0, 1.0]),
        lb=np.zeros(2),
        ub=np.ones(2),
        is_int=np.ones(2, bool),
    )
    c = np.array([1.0, 1.0])
    bf = brute_force_solve(p, c)
    assert not bf.feasible
    res = solve(p, c, node_cap=8, max_levels=8, use_pallas=False)
    assert res.status == "infeasible"
    assert not res.feasible
    assert res.x is None
    assert res.nodes_expanded == 0
    assert res.pruned_infeasible == 1
    assert res.levels == 1
    assert res.host_syncs == 1


def test_optimal_at_root():
    # Equality rows fix every variable at the root fixed point: the search
    # finds the incumbent at level 1 without expanding a single node.
    dense = np.array([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0], [0.0, 0.0, 1.0]])
    sides = np.array([1.0, 2.0, 0.0])
    p = Problem(
        csr=csr_from_dense(dense),
        lhs=sides,
        rhs=sides,
        lb=np.zeros(3),
        ub=np.full(3, 3.0),
        is_int=np.ones(3, bool),
    )
    c = np.array([1.0, -1.0, 2.0])
    bf = brute_force_solve(p, c)
    res = solve(p, c, node_cap=8, max_levels=8, use_pallas=False)
    assert res.status == "optimal"
    assert res.objective == bf.objective == -1.0
    np.testing.assert_array_equal(res.x, sides)
    assert res.nodes_expanded == 0
    assert res.leaves == 1
    assert res.levels == 1
    assert res.host_syncs == 1


# ---------------------------------------------------------------------------
# Host-sync contract + pool accounting at every sync.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sync_every", [2, 8])
def test_host_sync_contract(sync_every):
    p = make_pseudo_boolean(n=12, m=16, seed=0)
    c = _objective(p)
    calls = []
    res = solve(
        p, c, node_cap=64, max_levels=32, sync_every=sync_every,
        use_pallas=False, on_sync=calls.append,
    )
    # Every host consultation goes through on_sync: the dispatch count IS
    # the sync count, bounded by ceil(levels / sync_every).
    assert len(calls) == res.host_syncs
    assert res.host_syncs <= max(1, math.ceil(res.levels / sync_every))
    assert len(res.incumbent_trajectory) == res.host_syncs
    assert calls[-1]["done"]
    for snap in calls:
        # Statuses tile the pool: no slot leaks or double-allocations.
        assert snap["open"] + snap["ready"] + snap["free"] == 64
    # Fate partition at the final sync: every created node is alive or has
    # exactly one recorded fate.
    last = calls[-1]
    alive = last["open"] + last["ready"]
    assert alive == (
        res.nodes_created
        - res.nodes_expanded
        - res.leaves
        - res.pruned_bound
        - res.pruned_infeasible
    )


def test_sync_every_one_syncs_every_level():
    p = make_pseudo_boolean(n=12, m=16, seed=1)
    c = _objective(p)
    calls = []
    res = solve(
        p, c, node_cap=64, max_levels=32, sync_every=1, use_pallas=False,
        on_sync=calls.append,
    )
    assert res.host_syncs == res.levels == len(calls)
    levels = [snap["levels"] for snap in calls]
    assert levels == list(range(1, res.levels + 1))


# ---------------------------------------------------------------------------
# Determinism: bit-identical reruns, both rules.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "rule", [BranchRule.MOST_FRACTIONAL, BranchRule.PSEUDO_COST]
)
def test_determinism(rule):
    p = make_pseudo_boolean(n=12, m=16, seed=3)
    c = _objective(p)
    kw = dict(
        rule=rule, node_cap=64, max_levels=32, sync_every=2,
        use_pallas=False, telemetry=8,
    )
    r1 = solve(p, c, **kw)
    r2 = solve(p, c, **kw)
    assert r1.incumbent_trajectory == r2.incumbent_trajectory
    assert r1.objective == r2.objective
    assert (
        r1.nodes_expanded, r1.nodes_created, r1.leaves,
        r1.pruned_bound, r1.pruned_infeasible, r1.levels, r1.host_syncs,
    ) == (
        r2.nodes_expanded, r2.nodes_created, r2.leaves,
        r2.pruned_bound, r2.pruned_infeasible, r2.levels, r2.host_syncs,
    )
    if r1.feasible:
        np.testing.assert_array_equal(r1.x, r2.x)
    np.testing.assert_array_equal(
        r1.telemetry.progress_history(), r2.telemetry.progress_history()
    )


# ---------------------------------------------------------------------------
# Search properties: hypothesis when available, seeded draws always.
# ---------------------------------------------------------------------------

def _check_branch_children_partition(seed, n, value):
    """Down/up children tile the parent's integer domain on the branching
    variable exactly: disjoint, and their union is the parent domain."""
    rng = np.random.default_rng(seed)
    lb = rng.integers(-3, 1, n).astype(np.float64)
    ub = lb + rng.integers(1, 5, n)
    var = int(rng.integers(0, n))
    value = float(np.clip(lb[var] + value % (ub[var] - lb[var]), lb[var],
                          ub[var] - 1.0))
    (dlb, dub), (ulb, uub) = branch_children(lb, ub, var, value)
    f = math.floor(value)
    # Unbranched variables untouched.
    mask = np.arange(n) != var
    np.testing.assert_array_equal(dlb[mask], lb[mask])
    np.testing.assert_array_equal(uub[mask], ub[mask])
    np.testing.assert_array_equal(dub[mask], ub[mask])
    np.testing.assert_array_equal(ulb[mask], lb[mask])
    parent = set(range(int(lb[var]), int(ub[var]) + 1))
    down = set(range(int(dlb[var]), int(dub[var]) + 1))
    up = set(range(int(ulb[var]), int(uub[var]) + 1))
    assert down == {v for v in parent if v <= f}
    assert up == {v for v in parent if v >= f + 1}
    assert down | up == parent
    assert not (down & up)


def _check_plan_expansion(seed, cap):
    """Slot planning pairs distinct READY parents with distinct FREE
    children, exactly min(#READY, #FREE) of each, sentinel ``cap``
    beyond -- so ``mode='drop'`` scatters can neither leak a slot nor
    write one twice."""
    rng = np.random.default_rng(seed)
    status = rng.choice([FREE, OPEN, READY], size=cap).astype(np.int32)
    depth = rng.integers(0, 6, cap).astype(np.int32)
    nbound = rng.integers(-9, 9, cap).astype(np.float64)
    parent, child, k, n_ready, n_free = (
        np.asarray(a)
        for a in _plan_expansion(
            jnp.asarray(status), jnp.asarray(depth), jnp.asarray(nbound)
        )
    )
    k = int(k)
    assert int(n_ready) == int((status == READY).sum())
    assert int(n_free) == int((status == FREE).sum())
    assert k == min(int(n_ready), int(n_free))
    pk, ck = parent[:k], child[:k]
    assert len(set(pk.tolist())) == k  # no parent expanded twice
    assert len(set(ck.tolist())) == k  # no slot allocated twice
    assert all(status[i] == READY for i in pk)
    assert all(status[i] == FREE for i in ck)
    assert (parent[k:] == cap).all()  # unused ranks carry the drop sentinel
    assert (child[k:] == cap).all()
    # Deterministic priority: deepest-first, then best bound, then slot id.
    keys = [(-int(depth[i]), float(nbound[i]), int(i)) for i in pk]
    assert keys == sorted(keys)
    assert ck.tolist() == sorted(np.nonzero(status == FREE)[0][:k].tolist())


@pytest.mark.parametrize("seed", range(10))
def test_branch_children_partition_domain(seed):
    rng = np.random.default_rng(1000 + seed)
    _check_branch_children_partition(
        seed, int(rng.integers(2, 13)), float(rng.uniform(-3.0, 3.0))
    )


@pytest.mark.parametrize("seed", range(10))
def test_plan_expansion_never_leaks_or_double_allocates(seed):
    rng = np.random.default_rng(2000 + seed)
    _check_plan_expansion(seed, int(rng.integers(4, 33)))


def test_expand_width_clamps_plan():
    """``width`` caps the wave at the TOP of the deepest-first priority:
    the clamped plan is exactly the unlimited plan's prefix."""
    rng = np.random.default_rng(42)
    cap = 24
    status = jnp.asarray(
        rng.choice([FREE, OPEN, READY], size=cap).astype(np.int32)
    )
    depth = jnp.asarray(rng.integers(0, 6, cap).astype(np.int32))
    nbound = jnp.asarray(rng.integers(-9, 9, cap).astype(np.float64))
    full = [np.asarray(a) for a in _plan_expansion(status, depth, nbound)]
    for width in (1, 2, 3):
        parent, child, k, n_ready, n_free = (
            np.asarray(a)
            for a in _plan_expansion(status, depth, nbound, width=width)
        )
        k = int(k)
        assert k == min(int(n_ready), int(n_free), width)
        assert int(n_ready) == int(full[3]) and int(n_free) == int(full[4])
        np.testing.assert_array_equal(parent[:k], full[0][:k])
        np.testing.assert_array_equal(child[:k], full[1][:k])
        assert (parent[k:] == cap).all() and (child[k:] == cap).all()


@pytest.mark.parametrize("seed", [0, 3])
def test_expand_width_beam_is_exact(seed):
    """A narrow DFS beam (un-expanded READY nodes wait, nothing is
    dropped) reaches the SAME proven optimum as the unlimited frontier --
    the property the Python driver's frontier truncation does not have."""
    p = make_pseudo_boolean(n=12, m=16, seed=seed)
    c = _objective(p)
    wide = solve(p, c, node_cap=64, max_levels=256, sync_every=8,
                 use_pallas=False)
    beam = solve(p, c, node_cap=16, max_levels=256, sync_every=8,
                 expand_width=2, use_pallas=False)
    assert wide.status == "optimal"
    assert beam.status == "optimal"
    assert beam.objective == wide.objective
    _check_accounting(beam)
    if beam.feasible:
        _assert_solution_feasible(p, beam.x)


if HAVE_HYPOTHESIS:
    SETTINGS = dict(max_examples=20, deadline=None)

    @given(
        st.integers(0, 2**31 - 1), st.integers(2, 12), st.floats(-3.0, 3.0)
    )
    @settings(**SETTINGS)
    def test_branch_children_partition_domain_hyp(seed, n, value):
        _check_branch_children_partition(seed, n, value)

    @given(st.integers(0, 2**31 - 1), st.integers(4, 32))
    @settings(**SETTINGS)
    def test_plan_expansion_hyp(seed, cap):
        _check_plan_expansion(seed, cap)


@pytest.mark.parametrize("seed", [0, 2, 5])
def test_pruning_only_removes_suboptimal_subtrees(seed):
    """prune_gap=-INF disables bound pruning: the search expands at least
    as many nodes, finds the SAME optimum, and never bound-prunes --
    i.e. pruned subtrees provably contained no better incumbent."""
    p = make_pseudo_boolean(n=8, m=12, seed=seed)
    c = _objective(p)
    on = solve(
        p, c, node_cap=512, max_levels=32, use_pallas=False, prune_gap=0.0
    )
    off = solve(
        p, c, node_cap=512, max_levels=32, use_pallas=False, prune_gap=-INF
    )
    assert on.status == off.status == "optimal"
    assert on.objective == off.objective
    assert off.pruned_bound == 0
    assert on.nodes_expanded <= off.nodes_expanded
    assert on.nodes_created <= off.nodes_created
    _check_accounting(on)
    _check_accounting(off)


# ---------------------------------------------------------------------------
# Kernel vs reference: the node-objective oracle, bitwise.
# ---------------------------------------------------------------------------

def test_node_objective_kernel_matches_ref(rng):
    bsz, n_pad, n = 16, 24, 19
    lb = rng.integers(-4, 2, (bsz, n_pad)).astype(np.float64)
    ub = lb + rng.integers(0, 4, (bsz, n_pad))
    # A few unbounded and a few crossed rows exercise every predicate.
    lb[3, 2] = -INF
    ub[4, 5] = INF
    lb[6, 7] = ub[6, 7] + 1.0
    c = rng.integers(-5, 6, n_pad).astype(np.float64)
    valid = np.zeros(n_pad, bool)
    valid[:n] = True
    is_int = valid.copy()
    args = (
        jnp.asarray(lb), jnp.asarray(ub), jnp.asarray(c),
        jnp.asarray(is_int), jnp.asarray(valid), 1e-8,
    )
    obj_r, fix_r, cr_r = node_objective_ref(*args)
    obj_k, fix_k, cr_k = node_objective_tiles(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(obj_k), np.asarray(obj_r))
    np.testing.assert_array_equal(np.asarray(fix_k), np.asarray(fix_r))
    np.testing.assert_array_equal(np.asarray(cr_k), np.asarray(cr_r))
    assert bool(np.asarray(cr_r)[6])


# ---------------------------------------------------------------------------
# Deeper search: invariants at scale (marked `solver`).
# ---------------------------------------------------------------------------

@pytest.mark.solver
def test_deep_search_invariants():
    p = make_pseudo_boolean(n=40, m=56, seed=7)
    c = _objective(p)
    calls = []
    res = solve(
        p, c, node_cap=512, max_levels=64, sync_every=8,
        use_pallas=False, telemetry=64, on_sync=calls.append,
    )
    assert res.status in ("optimal", "infeasible", "pool_exhausted",
                          "level_limit")
    _check_accounting(res)
    assert res.host_syncs <= max(1, math.ceil(res.levels / 8))
    assert res.telemetry.rounds_recorded == res.levels
    if res.feasible:
        _assert_solution_feasible(p, res.x)
        assert float(c @ res.x) == res.objective
