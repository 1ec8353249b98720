"""Device-resident branch-and-bound: the search loop joins the bounds on
device.

Every engine before this one kept the paper's central property -- rounds run
on the accelerator with no host synchronization -- INSIDE one node's fixed
point, while the tree search around it still round-tripped to Python every
level: frontier bookkeeping, branching-variable selection and incumbent
tracking all lived on the host (``examples/bnb_dive.py``'s original shape).
:func:`solve` moves the search itself into device arrays, the
propagate-and-search architecture of Talbot et al.'s GPU constraint solving
(arXiv:2207.12116) on top of this repo's node-batch propagation engine:

  * a fixed-capacity **node pool**: ``(cap, n_pad)`` lower/upper bound
    planes plus per-node ``status`` / ``depth`` / branching / objective
    lanes, with freed slots recycled in place (the service's
    converged-mask-as-occupancy trick from ``core.service``, applied to
    tree nodes instead of serving slots);
  * one **level step** = one traced function: ``batched_fixed_point`` over
    the OPEN rows (frozen rows are in-kernel no-ops), the node-objective
    kernel (``kernels.prop_round.node_objective_tiles`` /
    ``kernels.ref.node_objective_ref``), incumbent update, bound +
    infeasibility pruning, on-device branching-variable selection
    (:class:`BranchRule`) and child expansion -- all inside the same
    dispatch;
  * a ``lax.while_loop`` **outer search loop** whose carry is the pool,
    the incumbent scalar/solution plane, the pseudo-cost statistics, the
    counters and a scalar ``obs.TelemetryPlane``; the host syncs only
    every ``sync_every`` levels, for logging and termination checks, so a
    depth-``d`` search costs at most ``ceil(d / sync_every)`` host syncs.

Exactness contract: :func:`solve` targets PURE-INTEGER instances with
integral matrix data (coefficients, sides, bounds and objective), the
regime of the pseudo-boolean / random-MIP differential-test families.
There, every activity, candidate and objective sum is an exact f64
integer, so (1) a propagation fixed point whose variables are all fixed
and whose domains never crossed is a FEASIBLE point (a violated row would
violate by >= 1 and force a crossing tightening), and (2) ``solve()``'s
optimal objective matches the brute-force oracle
(``core.seq_ref.brute_force_solve``) bitwise -- the property
``tests/test_solver.py`` pins across >= 20 seeded instances.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .sparse import Problem
from .types import DEFAULT_CONFIG, INF, PropagatorConfig

# Node-pool slot states.  FREE slots are recyclable; OPEN nodes propagate
# next level; READY nodes are propagated survivors awaiting expansion.
FREE, OPEN, READY = 0, 1, 2


class BranchRule(enum.Enum):
    """On-device branching-variable selection rule (see ``kernels.ref``).

    ``MOST_FRACTIONAL`` scores each unfixed integer column by its domain
    midpoint's distance to integrality (``most_fractional_ref``);
    ``PSEUDO_COST`` by the product of the average propagated bound gains
    its two child directions achieved so far (``pseudo_cost_select_ref``),
    accumulated on device as ``(2, n_pad)`` sum/count planes.  Both
    resolve ties to the lowest column index, so searches are deterministic.
    """

    MOST_FRACTIONAL = "most_fractional"
    PSEUDO_COST = "pseudo_cost"


class SearchCarry(NamedTuple):
    """The device-resident search state: the ``lax.while_loop`` carry.

    Pool planes are ``(cap, n_pad)``; per-node lanes ``(cap,)``; the
    pseudo-cost statistics ``(2, n_pad)`` (direction 0 = down child);
    everything else is scalar.  ``nbound`` is each node's objective lower
    bound (its pruning key), ``pbound`` its parent's -- their difference
    is the pseudo-cost gain.  The telemetry ``plane`` records one sample
    per LEVEL (see ``obs.telemetry``)."""

    lb: jnp.ndarray        # (cap, n_pad) per-node lower bounds
    ub: jnp.ndarray        # (cap, n_pad) per-node upper bounds
    status: jnp.ndarray    # (cap,) int32: FREE / OPEN / READY
    depth: jnp.ndarray     # (cap,) int32 node depth (root = 0)
    bvar: jnp.ndarray      # (cap,) int32 branching column (-1 at root)
    bdir: jnp.ndarray      # (cap,) int32 branch direction (0 down, 1 up)
    pbound: jnp.ndarray    # (cap,) parent objective bound
    nbound: jnp.ndarray    # (cap,) node objective bound
    pc_sum: jnp.ndarray    # (2, n_pad) pseudo-cost gain sums
    pc_cnt: jnp.ndarray    # (2, n_pad) pseudo-cost observation counts
    inc: jnp.ndarray       # () incumbent objective (INF = none yet)
    inc_x: jnp.ndarray     # (n_pad,) incumbent solution plane
    expanded: jnp.ndarray  # () int32 nodes branched
    created: jnp.ndarray   # () int32 nodes created (root + children)
    leaves: jnp.ndarray    # () int32 feasible all-fixed nodes reached
    pruned_bound: jnp.ndarray   # () int32 nodes pruned on bound
    pruned_infeas: jnp.ndarray  # () int32 nodes pruned infeasible
    levels: jnp.ndarray    # () int32 search levels executed
    done: jnp.ndarray      # () bool: nothing left to expand
    stuck: jnp.ndarray     # () bool: READY nodes but no FREE slots
    plane: object          # scalar obs.TelemetryPlane (per-level samples)


@dataclasses.dataclass
class SolveResult:
    """Outcome of one :func:`solve` search (host-side, built at the final
    sync).  ``status`` is ``'optimal'`` (search completed with an
    incumbent), ``'infeasible'`` (completed without one),
    ``'pool_exhausted'`` (READY nodes remained but no FREE slots -- raise
    ``node_cap``) or ``'level_limit'`` (hit ``max_levels``).  The node
    accounting satisfies ``created == 1 + 2 * expanded`` and, on a
    completed search, ``created == leaves + pruned_infeasible +
    pruned_bound + expanded``.  ``incumbent_trajectory`` holds the
    incumbent objective observed at each host sync (``host_syncs``
    entries, one per device dispatch); ``telemetry`` is an
    ``obs.TelemetrySnapshot`` of the per-level plane when requested."""

    status: str
    objective: float
    x: "np.ndarray | None"
    feasible: bool
    nodes_expanded: int
    nodes_created: int
    leaves: int
    pruned_bound: int
    pruned_infeasible: int
    levels: int
    host_syncs: int
    incumbent_trajectory: "list[float]"
    telemetry: object = None


def _plan_expansion(status, depth, nbound, width=None):
    """Pure slot planning for one expansion wave (unit-testable).

    Ranks READY nodes deepest-first (DFS keeps the pool small), then
    best-bound, then slot id -- three chained STABLE argsorts, least
    significant key first, so the order is deterministic.  FREE slots rank
    by slot id.  ``k = min(#READY, #FREE)`` pairs expand (further clamped
    to ``width`` when given -- the DFS beam: un-expanded READY nodes just
    wait, so a bounded wave never loses completeness, only defers): rank
    ``r``'s parent slot is ``parent[r]``, its up-child's slot ``child[r]``
    (the down child reuses the parent slot in place).  Ranks ``>= k``
    carry the out-of-range sentinel ``cap``, so ``mode='drop'`` scatters
    ignore them.  Returns ``(parent, child, k, n_ready, n_free)``."""
    cap = status.shape[0]
    ready = status == READY
    free = status == FREE
    order = jnp.argsort(nbound, stable=True)
    order = order[jnp.argsort(-depth[order], stable=True)]
    order = order[jnp.argsort((~ready[order]).astype(jnp.int32), stable=True)]
    slots = jnp.argsort((~free).astype(jnp.int32), stable=True)
    n_ready = jnp.sum(ready, dtype=jnp.int32)
    n_free = jnp.sum(free, dtype=jnp.int32)
    k = jnp.minimum(n_ready, n_free)
    if width is not None:
        k = jnp.minimum(k, jnp.int32(width))
    r = jnp.arange(cap)
    parent = jnp.where(r < k, order, cap)
    child = jnp.where(r < k, slots, cap)
    return parent, child, k, n_ready, n_free


def _make_level_step(prep, cfg, rule, use_pallas, interpret, prune_gap,
                     expand_width):
    """Build the traced level step ``(carry, c_pad) -> carry`` over one
    prepared instance: propagate OPEN rows to their fixed points, score
    them, update the incumbent, prune, select branching variables and
    expand -- one function, inlined into the search ``while_loop`` body."""
    from ..kernels import ref as kref  # lazy: kernels imports core
    from ..kernels.prop_round import node_objective_tiles
    from .propagator import batched_fixed_point
    from ..obs import telemetry as obs

    n_pad, n = prep.n_pad, prep.n
    col_valid = np.zeros(n_pad, dtype=bool)
    col_valid[:n] = True
    valid = jnp.asarray(col_valid)
    is_int = np.zeros(n_pad, dtype=bool)
    is_int[:n] = np.asarray(prep.d.is_int, bool)[:n]
    ii = jnp.asarray(is_int)
    from ..kernels.ops import node_round_fn_for

    round_fn = node_round_fn_for(prep, cfg, use_pallas, interpret)
    pallas_objective = bool(use_pallas) and n_pad <= 2**16
    widened = bool(cfg.outward_for(prep.d.val.dtype))

    def step(c: SearchCarry, c_pad) -> SearchCarry:
        cap = c.status.shape[0]
        open_m = c.status == OPEN

        # (1) All OPEN nodes to their propagation fixed points, one inner
        # loop; FREE/READY rows are frozen (active0 mask).
        lb, ub, _, _ = batched_fixed_point(
            round_fn, c.lb, c.ub, cfg.max_rounds, active0=open_m
        )
        if widened:
            # A float32 merge widens each rounded integer bound outward
            # off the integer; no integer point lies in between, so
            # ceil/floor restore it and leaves keep exact objectives.
            lb = jnp.where(ii, jnp.ceil(lb), lb)
            ub = jnp.where(ii, jnp.floor(ub), ub)

        # (2) Objective bound + leaf / infeasibility predicates.
        if pallas_objective:
            obj, fixed, crossed = node_objective_tiles(
                lb, ub, c_pad, ii, valid, cfg.feas_eps, cfg.inf, interpret
            )
        else:
            obj, fixed, crossed = kref.node_objective_ref(
                lb, ub, c_pad, ii, valid, cfg.feas_eps, cfg.inf
            )
        infeas = crossed & open_m
        # Monotone: a child's bound can only improve on its parent's.
        nb = jnp.where(open_m, jnp.maximum(obj, c.pbound), c.nbound)

        # (3) Pseudo-cost statistics: each propagated child credits its
        # branching (column, direction) with its bound gain.  Sentinel
        # column n_pad + mode='drop' masks non-contributors.
        contrib = open_m & (c.bvar >= 0) & ~infeas
        gain = jnp.where(contrib, jnp.maximum(nb - c.pbound, 0.0), 0.0)
        vidx = jnp.where(contrib, c.bvar, n_pad)
        didx = jnp.clip(c.bdir, 0, 1)
        pc_sum = c.pc_sum.at[didx, vidx].add(gain, mode="drop")
        pc_cnt = c.pc_cnt.at[didx, vidx].add(
            contrib.astype(c.pc_cnt.dtype), mode="drop"
        )

        # (4) Incumbent: best feasible all-fixed node this level (min +
        # first-index argmin -- deterministic reduction order).
        leaf = open_m & ~infeas & fixed
        inc, inc_x, improved = kref.incumbent_update_ref(
            leaf, obj, c.inc, c.inc_x, lb, cfg.inf
        )

        # (5) Pruning + status transitions.  OPEN survivors whose bound
        # cannot beat the incumbent are fathomed; existing READY nodes are
        # re-fathomed against the improved incumbent.
        survivor = open_m & ~infeas & ~leaf
        pruned_o = survivor & (nb >= inc - prune_gap)
        to_ready = survivor & ~pruned_o
        pruned_r = (c.status == READY) & (c.nbound >= inc - prune_gap)
        status = jnp.where(
            open_m,
            jnp.where(to_ready, READY, FREE).astype(jnp.int32),
            c.status,
        )
        status = jnp.where(pruned_r, FREE, status)

        # (6) Expansion: slot plan + on-device branching selection.
        parent, child, k, n_ready, n_free = _plan_expansion(
            status, c.depth, nb, expand_width
        )
        if rule is BranchRule.PSEUDO_COST:
            var_all, _ = kref.pseudo_cost_select_ref(
                lb, ub, ii, valid, pc_sum, pc_cnt
            )
        else:
            var_all, _ = kref.most_fractional_ref(lb, ub, ii, valid)
        pg = jnp.minimum(parent, cap - 1)  # clamped gather twin of parent
        r = jnp.arange(cap)
        pv = var_all[pg]
        plbv = lb[pg, pv]
        pubv = ub[pg, pv]
        bv = jnp.clip(jnp.floor(0.5 * (plbv + pubv)), plbv, pubv - 1.0)
        pdep = c.depth[pg]
        pnb = nb[pg]
        # Parent planes gathered BEFORE the in-place down-child scatter.
        plb_rows = lb[pg]
        pub_rows = ub[pg]
        up_lb = plb_rows.at[r, pv].set(bv + 1.0)
        # Down child reuses the parent slot: only ub[bvar] moves.
        ub = ub.at[parent, pv].set(bv, mode="drop")
        # Up child fills a FREE slot with the parent's planes + lb[bvar].
        lb = lb.at[child].set(up_lb, mode="drop")
        ub = ub.at[child].set(pub_rows, mode="drop")

        def stamp(lane, down_val, up_val):
            return lane.at[parent].set(down_val, mode="drop").at[child].set(
                up_val, mode="drop"
            )

        status = stamp(status, jnp.int32(OPEN), jnp.int32(OPEN))
        depth = stamp(c.depth, pdep + 1, pdep + 1)
        bvar = stamp(c.bvar, pv.astype(jnp.int32), pv.astype(jnp.int32))
        bdir = stamp(c.bdir, jnp.int32(0), jnp.int32(1))
        pbound = stamp(c.pbound, pnb, pnb)
        nbound = stamp(nb, pnb, pnb)

        # (7) Counters, termination, telemetry (one sample per level: the
        # next frontier's width, first-incumbent / first-fathom latches).
        levels = c.levels + 1
        done = n_ready == 0
        stuck = (n_ready > 0) & (k == 0)
        plane = obs.record_round(
            c.plane,
            progress=(2 * k).astype(c.lb.dtype),
            rounds=levels,
            infeasible=jnp.any(infeas),
            stopped=improved,
        )
        return SearchCarry(
            lb=lb, ub=ub, status=status, depth=depth, bvar=bvar, bdir=bdir,
            pbound=pbound, nbound=nbound, pc_sum=pc_sum, pc_cnt=pc_cnt,
            inc=inc, inc_x=inc_x,
            expanded=(c.expanded + k).astype(jnp.int32),
            created=(c.created + 2 * k).astype(jnp.int32),
            leaves=(c.leaves + jnp.sum(leaf, dtype=jnp.int32)).astype(jnp.int32),
            pruned_bound=(
                c.pruned_bound
                + jnp.sum(pruned_o, dtype=jnp.int32)
                + jnp.sum(pruned_r, dtype=jnp.int32)
            ).astype(jnp.int32),
            pruned_infeas=(
                c.pruned_infeas + jnp.sum(infeas, dtype=jnp.int32)
            ).astype(jnp.int32),
            levels=levels, done=done, stuck=stuck, plane=plane,
        )

    return step


@functools.lru_cache(maxsize=32)
def _init_carry(cap, n_pad, dt, tel_cap):
    """Jitted fresh-pool builder: ONE dispatch instead of ~20 small ones.

    Building the carry eagerly costs a host round-trip per array on CPU --
    milliseconds of fixed overhead that dominates short searches.  The
    shape key is tiny, so the compiled builders are cached for the life of
    the process."""
    from ..obs import telemetry as obs

    @jax.jit
    def init(lb0, ub0):
        return SearchCarry(
            lb=jnp.zeros((cap, n_pad), dt).at[0].set(lb0),
            ub=jnp.zeros((cap, n_pad), dt).at[0].set(ub0),
            status=jnp.zeros(cap, jnp.int32).at[0].set(OPEN),
            depth=jnp.zeros(cap, jnp.int32),
            bvar=jnp.full(cap, -1, jnp.int32),
            bdir=jnp.zeros(cap, jnp.int32),
            pbound=jnp.full(cap, -INF, dt),
            nbound=jnp.full(cap, -INF, dt),
            pc_sum=jnp.zeros((2, n_pad), dt),
            pc_cnt=jnp.zeros((2, n_pad), dt),
            inc=jnp.asarray(INF, dt),
            inc_x=jnp.zeros(n_pad, dt),
            expanded=jnp.int32(0),
            created=jnp.int32(1),
            leaves=jnp.int32(0),
            pruned_bound=jnp.int32(0),
            pruned_infeas=jnp.int32(0),
            levels=jnp.int32(0),
            done=jnp.asarray(False),
            stuck=jnp.asarray(False),
            plane=obs.device_plane(tel_cap, dtype=dt),
        )

    return init


# Compiled search runners, cached per matrix structure + pool capacity +
# search knobs (bounds and the objective are runtime arguments, so one
# resident runner serves every solve() of the same instance).  Lazily
# constructed so importing core never drags the kernels package in.
_solver_runner_cache = None


def _solver_runner(prep, cap, cfg, rule, use_pallas, interpret, prune_gap,
                   expand_width, tel_cap):
    from ..kernels.ops import LRU
    from .propagator import donate_kwargs, donate_supported

    global _solver_runner_cache
    if _solver_runner_cache is None:
        _solver_runner_cache = LRU(maxsize=16)
    do_donate = donate_supported()
    key = (
        id(prep.d.val), cap, cfg, rule, use_pallas, interpret, prune_gap,
        expand_width, tel_cap, do_donate,
    )
    anchors = (prep.d.val,)
    runner = _solver_runner_cache.get(key, anchors)
    if runner is not None:
        return runner

    step = _make_level_step(
        prep, cfg, rule, use_pallas, interpret, prune_gap, expand_width
    )

    @functools.partial(jax.jit, **donate_kwargs(argnums=(0,)))
    def run(carry: SearchCarry, c_pad, level_target) -> SearchCarry:
        def cond(c):
            return (~c.done) & (~c.stuck) & (c.levels < level_target)

        return jax.lax.while_loop(cond, lambda c: step(c, c_pad), carry)

    _solver_runner_cache.put(key, anchors, run)
    return run


def solve(
    p: Problem,
    c,
    *,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    rule: BranchRule = BranchRule.MOST_FRACTIONAL,
    node_cap: int = 256,
    max_levels: int = 64,
    sync_every: int = 8,
    prune_gap: float = 0.0,
    expand_width: int | None = None,
    tile_rows: int = 8,
    tile_width: int = 8,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    telemetry: int | None = None,
    on_sync: "Callable[[dict], None] | None" = None,
) -> SolveResult:
    """Branch-and-bound minimization of ``c @ x`` with device-resident
    search state.

    ``p`` must be pure-integer (``p.is_int`` all True); ``c`` is the
    ``(n,)`` minimization objective.  The search lives in a fixed
    ``node_cap``-slot device pool and advances one LEVEL at a time -- each
    level propagates every OPEN node to its fixed point, updates the
    incumbent from feasible fully-fixed nodes, prunes on bound and
    infeasibility, and expands the survivors depth-first (down child in
    the parent's slot, up child in a recycled FREE slot).  The host is
    consulted only every ``sync_every`` levels: one small ``device_get``
    per dispatch, so a depth-``d`` search syncs at most
    ``ceil(d / sync_every)`` times (``on_sync``, when given, is called
    with a progress dict at exactly those points -- the test hook for the
    sync-count contract).

    ``rule`` picks the on-device branching-variable selection
    (:class:`BranchRule`); ``prune_gap`` widens the fathoming test to
    ``bound >= incumbent - prune_gap`` (0.0 = exact; ``-INF`` disables
    bound pruning, the property-test lever).  ``expand_width`` clamps each
    expansion wave (default: every READY node with a FREE slot expands) --
    with the deepest-first priority a small width acts as a DFS beam, so
    searches whose early levels would otherwise exhaust the pool before
    any leaf seeds the incumbent dig deep first instead; un-expanded READY
    nodes simply wait, so completeness is preserved.  ``use_pallas``
    defaults to
    Pallas kernels on TPU and the jnp dataflow elsewhere (same policy as
    the benches); ``telemetry`` (a ring capacity) records one sample per
    level into a scalar ``obs.TelemetryPlane`` riding the search carry.
    See the module docstring for the integral-data exactness contract.
    """
    from ..kernels.ops import prepare_block_ell

    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not bool(np.all(np.asarray(p.is_int, bool))):
        raise ValueError("solve() requires a pure-integer problem (is_int all True)")
    c = np.asarray(c, np.float64)
    if c.shape != (p.n,):
        raise ValueError(f"objective has shape {c.shape}, expected {(p.n,)}")
    cap = int(node_cap)
    if cap < 2:
        raise ValueError("node_cap must be >= 2")
    sync_every = max(1, int(sync_every))
    if expand_width is not None:
        expand_width = int(expand_width)
        if expand_width < 1:
            raise ValueError("expand_width must be >= 1 (or None)")
    tel_cap = int(telemetry or 0)

    prep = prepare_block_ell(p, tile_rows, tile_width, None)
    dt = prep.d.val.dtype
    n_pad = prep.n_pad
    from ..obs import telemetry as obs

    c_pad = jnp.asarray(np.pad(c, (0, n_pad - p.n)), dt)
    carry = _init_carry(cap, n_pad, dt, max(tel_cap, 1))(prep.lb0, prep.ub0)
    run = _solver_runner(
        prep, cap, cfg, rule, use_pallas, interpret, float(prune_gap),
        expand_width, tel_cap,
    )

    syncs = 0
    traj: "list[float]" = []
    target = 0
    while True:
        target = min(target + sync_every, max_levels)
        carry = run(carry, c_pad, jnp.int32(target))
        # THE host sync: one device_get of the scalars + status lane.
        host = jax.device_get((
            carry.done, carry.stuck, carry.levels, carry.inc,
            carry.expanded, carry.created, carry.leaves,
            carry.pruned_bound, carry.pruned_infeas, carry.status,
        ))
        done, stuck, levels, inc = (
            bool(host[0]), bool(host[1]), int(host[2]), float(host[3])
        )
        syncs += 1
        traj.append(inc)
        if on_sync is not None:
            st = np.asarray(host[9])
            on_sync({
                "sync": syncs,
                "levels": levels,
                "incumbent": inc,
                "done": done,
                "stuck": stuck,
                "expanded": int(host[4]),
                "created": int(host[5]),
                "open": int((st == OPEN).sum()),
                "ready": int((st == READY).sum()),
                "free": int((st == FREE).sum()),
            })
        if done or stuck or levels >= max_levels:
            break

    feasible = inc < INF
    if stuck:
        status = "pool_exhausted"
    elif not done:
        status = "level_limit"
    elif feasible:
        status = "optimal"
    else:
        status = "infeasible"
    x = np.asarray(carry.inc_x)[: p.n].copy() if feasible else None
    snap = obs.TelemetrySnapshot(plane=carry.plane) if tel_cap else None
    assert syncs <= max(1, math.ceil(levels / sync_every))
    return SolveResult(
        status=status,
        objective=inc if feasible else INF,
        x=x,
        feasible=feasible,
        nodes_expanded=int(host[4]),
        nodes_created=int(host[5]),
        leaves=int(host[6]),
        pruned_bound=int(host[7]),
        pruned_infeasible=int(host[8]),
        levels=levels,
        host_syncs=syncs,
        incumbent_trajectory=traj,
        telemetry=snap,
    )


__all__ = [
    "BranchRule",
    "SearchCarry",
    "SolveResult",
    "solve",
]
