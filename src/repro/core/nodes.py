"""Warm-start node-batch propagation: the tree-search serving shape.

Domain propagation is called at every node of a branch-and-bound search --
millions of times per solve -- and a node differs from its parent by ONE
branching bound.  Repacking the instance per node (the one-shot presolver
dataflow) pays block-ELL conversion, device transfer and compilation for a
two-number change.  This module serves the tree instead:

  * the MATRIX is prepared once per instance (``prepare_block_ell``, keyed
    on structure) and stays device-resident;
  * a :class:`NodeBatch` carries B sibling/frontier nodes as ``(B, n)``
    bound planes -- the only per-node state;
  * :func:`propagate_nodes` runs all B fixed points in ONE dispatch over
    the shared tiles, with the per-instance convergence mask of the batched
    engine reused as a per-node mask (converged nodes become in-kernel
    no-ops) and per-node infeasibility reported for pruning.

``examples/bnb_dive.py`` drives this as a batched diving search;
``benchmarks/bench_prop.py`` reports nodes/sec against per-node repacking.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import numpy as np

from ..obs.trace import PROGRAM
from .sparse import Problem
from .types import (
    DEFAULT_CONFIG,
    INF,
    PropagationResult,
    PropagatorConfig,
    TierPolicy,
)


class NodeBatchResult(NamedTuple):
    """Per-node results of one node-batch propagation (node axis leading)."""

    lb: object          # (B, n) propagated lower bounds
    ub: object          # (B, n) propagated upper bounds
    rounds: object      # (B,) int32 rounds to each node's fixed point
    converged: object   # (B,) bool
    infeasible: object  # (B,) bool: domain emptied -> prune this node
    progress: object = None     # (B,) last-round progress measure (or None)
    tier_rounds: object = 0     # (B,) int32 fp32-tier rounds (two-tier runs)
    telemetry: object = None    # batched obs.TelemetryPlane (or None)
    fp32_telemetry: object = None  # fp32 tier's plane under a TierPolicy

    @property
    def size(self) -> int:
        return int(self.lb.shape[0])

    def node_telemetry(self, i: int):
        """Node ``i``'s ``obs.TelemetrySnapshot`` (None when telemetry off).

        Rows view the shared batched plane lazily -- no readback until a
        snapshot accessor is called.  Under a two-tier run the fp32 tier's
        snapshot hangs off ``.fp32`` and ``tier_switch_round`` is the
        node's fp32 round count (``-1`` if its fp32 tier was distrusted).
        """
        if self.telemetry is None:
            return None
        from ..obs.telemetry import TelemetrySnapshot  # lazy: keep import light

        snap = TelemetrySnapshot(plane=self.telemetry, index=i)
        if self.fp32_telemetry is not None:
            snap.fp32 = TelemetrySnapshot(plane=self.fp32_telemetry, index=i)
            # tier_rounds was zeroed for nodes whose fp32 verdict was
            # distrusted (no promotion happened for them).
            tr = int(np.asarray(self.tier_rounds)[i])
            snap.tier_switch_round = tr if tr > 0 else -1
        return snap

    def result(self, i: int) -> PropagationResult:
        """Node ``i``'s result in single-instance form."""
        return PropagationResult(
            self.lb[i], self.ub[i], self.rounds[i], self.converged[i],
            self.infeasible[i], telemetry=self.node_telemetry(i),
        )

    def results(self) -> "list[PropagationResult]":
        return [self.result(i) for i in range(self.size)]


class NodeBatch(NamedTuple):
    """B nodes of ONE instance: the shared problem + per-node bound planes.

    ``lb``/``ub`` are host ``(B, n)`` arrays (numpy -- node bookkeeping is
    host-side search logic; only propagation runs on device)."""

    problem: Problem
    lb: np.ndarray  # (B, n)
    ub: np.ndarray  # (B, n)

    @property
    def size(self) -> int:
        return int(self.lb.shape[0])

    @classmethod
    def from_root(cls, p: Problem, copies: int = 1) -> "NodeBatch":
        """``copies`` identical nodes at the problem's root bounds."""
        lb = np.repeat(np.asarray(p.lb, np.float64)[None, :], copies, axis=0)
        ub = np.repeat(np.asarray(p.ub, np.float64)[None, :], copies, axis=0)
        return cls(problem=p, lb=lb, ub=ub)

    @classmethod
    def from_nodes(cls, p: Problem, nodes: Sequence[tuple]) -> "NodeBatch":
        """Stack ``(lb_i, ub_i)`` pairs into one batch."""
        lb = np.stack([np.asarray(l, np.float64) for l, _ in nodes])
        ub = np.stack([np.asarray(u, np.float64) for _, u in nodes])
        return cls(problem=p, lb=lb, ub=ub)

    def select(self, mask) -> "NodeBatch":
        """Keep the nodes where ``mask`` is True (pruning survivors)."""
        mask = np.asarray(mask)
        return NodeBatch(self.problem, self.lb[mask], self.ub[mask])


def branch_children(lb, ub, var: int, value: float) -> "tuple[tuple, tuple]":
    """The two children of branching ``x[var]`` at ``value``: the *down*
    child gets ``ub[var] = floor(value)``, the *up* child ``lb[var] =
    floor(value) + 1`` (the standard integer dichotomy; for a binary
    variable at value 0 this is the x=0 / x=1 split).  Returns
    ``((lb_down, ub_down), (lb_up, ub_up))`` as fresh host arrays."""
    lb = np.asarray(lb, np.float64)
    ub = np.asarray(ub, np.float64)
    f = float(np.floor(value))
    down_lb, down_ub = lb.copy(), ub.copy()
    down_ub[var] = min(down_ub[var], f)
    up_lb, up_ub = lb.copy(), ub.copy()
    up_lb[var] = max(up_lb[var], f + 1.0)
    return (down_lb, down_ub), (up_lb, up_ub)


def pick_most_fractional(lb, ub, is_int) -> "int | None":
    """Deterministic host-side branching rule: the unfixed integer variable
    whose domain midpoint is most fractional, ties to the lowest index --
    the host-numpy twin of the solver's on-device
    ``kernels.ref.most_fractional_ref``.  Replaces the RNG-per-level pick
    the diving example used, so level-by-level Python drivers (the bench
    ``solver`` row's baseline) are reproducible run-to-run.  Returns the
    column index, or ``None`` when every integer variable is fixed."""
    lb = np.asarray(lb, np.float64)
    ub = np.asarray(ub, np.float64)
    cand = np.asarray(is_int, bool) & (ub - lb > 0.5)
    if not cand.any():
        return None
    mid = 0.5 * (lb + ub)
    frac = mid - np.floor(mid)
    score = np.where(cand, 0.5 - np.abs(frac - 0.5), -1.0)
    return int(np.argmax(score))


@PROGRAM.traced("prop.nodes")
def propagate_nodes(
    p: Problem,
    lb_nodes,
    ub_nodes,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    tile_rows: int = 8,
    tile_width: int = 128,
    dtype=None,
    use_pallas: bool = True,
    interpret: bool | None = None,
    donate: bool | None = None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    policy: TierPolicy | None = None,
    telemetry: int | None = None,
) -> NodeBatchResult:
    """Propagate B warm-started nodes of ONE instance in one dispatch.

    ``lb_nodes``/``ub_nodes`` are ``(B, n)`` per-node bound planes (or a
    :class:`NodeBatch`'s fields).  The instance's block-ELL tiles, hoisted
    gathers and the compiled fixed point are cached per matrix structure
    (``kernels.cache_info()`` reports hits), so successive frontiers of
    the same search pay only the two ``(B, n)`` uploads and one dispatch.
    VMEM-exceeding instances (``n_pad > SCATTER_MAX_NPAD``) ride the
    column-slab partitioned node kernels automatically.  Per-node
    ``rounds``/``converged`` match what each node would see in its own
    single-instance run; ``infeasible`` nodes are reported for pruning,
    and their bucket mates are unaffected.

    ``stop_progress``/``patience`` arm the per-node progress-based early
    stop (see ``bounds.progress_measure``); ``policy`` (a
    :class:`TierPolicy`) runs the frontier through the two-tier precision
    scheme: an fp32 dispatch (outward-rounded merges, own cached prep +
    runner) until per-node progress drops below ``policy.switch_progress``,
    then an exact-cast warm start of the requested-dtype engine.

    ``telemetry`` (a ring capacity) carries a per-node device telemetry
    plane through the dispatch; read node trajectories via
    ``result.node_telemetry(i)`` / ``result.result(i).telemetry``.

    Each call is one ``prop.nodes`` span of the program tracer
    (``obs.trace.PROGRAM``, attrs ``nodes`` and ``n_pad``), from entry to
    return, holding the ``prop.prepare``, ``prop.upload`` (site ``nodes``,
    the two planes) and ``prop.launch`` spans of each tier."""
    from ..kernels.ops import (  # lazy: kernels imports core at module scope
        prepare_block_ell,
        propagate_nodes_prepared,
    )
    from .propagator import two_tier_bounds_dtypes

    tel_cap = int(telemetry or 0)
    pair = two_tier_bounds_dtypes(policy, dtype) if policy is not None else None
    if pair is not None:
        dt32, final = pair
        cap32 = max(1, int(cfg.max_rounds * policy.fp32_round_frac))
        prep32 = prepare_block_ell(p, tile_rows, tile_width, dt32)
        PROGRAM.note(nodes=len(lb_nodes), n_pad=prep32.n_pad)
        out32 = propagate_nodes_prepared(
            prep32, lb_nodes, ub_nodes,
            dataclasses.replace(cfg, max_rounds=cap32),
            use_pallas=use_pallas, interpret=interpret, donate=donate,
            slab=slab, stop_progress=policy.switch_progress,
            patience=policy.patience, telemetry=tel_cap,
        )
        lb32, ub32, r32, _, inf32 = out32[:5]
        plane32 = out32[5] if tel_cap else None
        # Per-node promotion; a node whose fp32 tier declared infeasibility
        # restarts from its ORIGINAL bounds (fp32 verdicts are never
        # trusted -- see core.propagator's two-tier front end).
        bad = np.asarray(inf32)[:, None]
        warm_lb = np.where(bad, np.asarray(lb_nodes), np.asarray(lb32, np.float64))
        warm_ub = np.where(bad, np.asarray(ub_nodes), np.asarray(ub32, np.float64))
        # Canonicalize the cast sentinels (fp32's 1e20 rounds up; see
        # bounds.canonical_infinite) so untouched infinite bounds promote
        # bitwise.
        warm_lb = np.where(warm_lb <= -INF, -INF, warm_lb)
        warm_ub = np.where(warm_ub >= INF, INF, warm_ub)
        r32 = np.where(np.asarray(inf32), 0, np.asarray(r32)).astype(np.int32)
        rem = dataclasses.replace(cfg, max_rounds=max(1, cfg.max_rounds - cap32))
        prep = prepare_block_ell(p, tile_rows, tile_width, final)
        out = propagate_nodes_prepared(
            prep, warm_lb, warm_ub, rem,
            use_pallas=use_pallas, interpret=interpret, donate=donate,
            slab=slab, stop_progress=policy.stop_progress,
            patience=policy.patience, with_progress=True, telemetry=tel_cap,
        )
        lb, ub, rounds, converged, infeasible, progress = out[:6]
        return NodeBatchResult(
            lb, ub, rounds + r32, converged, infeasible,
            progress=progress, tier_rounds=r32,
            telemetry=out[6] if tel_cap else None, fp32_telemetry=plane32,
        )
    if policy is not None:
        stop_progress = policy.stop_progress
        patience = policy.patience
    prep = prepare_block_ell(p, tile_rows, tile_width, dtype)
    PROGRAM.note(nodes=len(lb_nodes), n_pad=prep.n_pad)
    out = propagate_nodes_prepared(
        prep, lb_nodes, ub_nodes, cfg,
        use_pallas=use_pallas, interpret=interpret, donate=donate, slab=slab,
        stop_progress=stop_progress, patience=patience, with_progress=True,
        telemetry=tel_cap,
    )
    lb, ub, rounds, converged, infeasible, progress = out[:6]
    return NodeBatchResult(
        lb, ub, rounds, converged, infeasible, progress=progress,
        telemetry=out[6] if tel_cap else None,
    )


def propagate_node_batch(
    batch: NodeBatch,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    **kwargs,
) -> NodeBatchResult:
    """:func:`propagate_nodes` over a :class:`NodeBatch`."""
    return propagate_nodes(batch.problem, batch.lb, batch.ub, cfg, **kwargs)
