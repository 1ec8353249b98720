"""Pure-jnp oracles for every Pallas kernel in this package.

The oracles define the *exact* semantics (sentinel-infinity handling, padding
masks, reduction order at tile granularity) the kernels must reproduce; the
test suite sweeps shapes/dtypes and asserts allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.types import INF, int_round_slack


# ---------------------------------------------------------------------------
# Tile-level activity partials (kernel A oracle)
# ---------------------------------------------------------------------------


def activities_tiles_ref(val, lb_g, ub_g, inf: float = INF):
    """Per-chunk activity partials over block-ELL tiles.

    Args:
      val:  (T, R, K) coefficients, 0 == padding.
      lb_g: (T, R, K) lower bounds gathered at each nonzero's column.
      ub_g: (T, R, K) upper bounds gathered at each nonzero's column.

    Returns:
      (min_fin, min_cnt, max_fin, max_cnt): each (T, R); finite partial sums
      and int32 infinity-contribution counts per chunk.
    """
    pos = val > 0
    pad = val == 0
    b_min = jnp.where(pos, lb_g, ub_g)
    b_max = jnp.where(pos, ub_g, lb_g)
    min_is_inf = (jnp.abs(b_min) >= inf) & ~pad
    max_is_inf = (jnp.abs(b_max) >= inf) & ~pad
    min_fin = jnp.where(min_is_inf | pad, 0.0, val * b_min).sum(axis=-1)
    max_fin = jnp.where(max_is_inf | pad, 0.0, val * b_max).sum(axis=-1)
    min_cnt = min_is_inf.astype(jnp.int32).sum(axis=-1)
    max_cnt = max_is_inf.astype(jnp.int32).sum(axis=-1)
    return min_fin, min_cnt, max_fin, max_cnt


# ---------------------------------------------------------------------------
# Tile-level candidate computation (kernel B oracle)
# ---------------------------------------------------------------------------


def candidates_tiles_ref(
    val,
    lb_g,
    ub_g,
    is_int_g,
    row_min_fin,
    row_min_cnt,
    row_max_fin,
    row_max_cnt,
    lhs_g,
    rhs_g,
    int_eps: float,
    inf: float = INF,
):
    """Per-nonzero bound candidates over block-ELL tiles.

    Args:
      val, lb_g, ub_g: (T, R, K) as above.
      is_int_g: (T, R, K) bool, integrality of each nonzero's column.
      row_*: (T, R) *completed* row aggregates gathered per chunk.
      lhs_g, rhs_g: (T, R) constraint sides gathered per chunk.

    Returns:
      (lcand, ucand): (T, R, K); invalid entries at -inf/+inf sentinels.

    Candidates use the same division-first form as the kernels --
    ``(side - row_sum) / a + bound`` instead of dividing the explicit
    residual -- so that no backend can contract a step into an FMA and
    kernel-vs-oracle comparisons stay bitwise in every compilation
    context (see ``prop_round.tile_candidates``).
    """
    pos = val > 0
    pad = val == 0
    b_min = jnp.where(pos, lb_g, ub_g)
    b_max = jnp.where(pos, ub_g, lb_g)
    min_is_inf = (jnp.abs(b_min) >= inf) & ~pad
    max_is_inf = (jnp.abs(b_max) >= inf) & ~pad

    rmf = row_min_fin[..., None]
    rmc = row_min_cnt[..., None]
    rxf = row_max_fin[..., None]
    rxc = row_max_cnt[..., None]

    # Residual usable at this entry (§3.4 single-infinity rule): all
    # contributions finite and the row sum complete, or exactly this
    # entry's bound infinite so the sum over the others IS the residual.
    ok_min = jnp.where(min_is_inf, rmc == 1, rmc == 0)
    ok_max = jnp.where(max_is_inf, rxc == 1, rxc == 0)
    inc_min = jnp.where(min_is_inf | pad, 0.0, b_min)
    inc_max = jnp.where(max_is_inf | pad, 0.0, b_max)

    lhs_b = lhs_g[..., None]
    rhs_b = rhs_g[..., None]
    safe_a = jnp.where(pad, 1.0, val)
    q_min = (rhs_b - rmf) / safe_a + inc_min
    q_max = (lhs_b - rxf) / safe_a + inc_max
    lcand = jnp.where(pos, q_max, q_min)
    ucand = jnp.where(pos, q_min, q_max)

    valid_l = (
        jnp.where(pos, (lhs_b > -inf) & ok_max, (rhs_b < inf) & ok_min)
        & ~pad
    )
    valid_u = (
        jnp.where(pos, (rhs_b < inf) & ok_min, (lhs_b > -inf) & ok_max)
        & ~pad
    )
    lcand = jnp.where(valid_l, jnp.clip(lcand, -inf, inf), -inf)
    ucand = jnp.where(valid_u, jnp.clip(ucand, -inf, inf), inf)

    # Integrality strengthening (same dtype-keyed low-precision slack as
    # the kernel, so kernel-vs-oracle comparisons stay bitwise per tier).
    do_l = is_int_g & (jnp.abs(lcand) < inf)
    do_u = is_int_g & (jnp.abs(ucand) < inf)
    slack = int_round_slack(jnp.result_type(lcand))
    sl = su = int_eps
    if slack:
        sl = int_eps + slack * jnp.maximum(1.0, jnp.abs(lcand))
        su = int_eps + slack * jnp.maximum(1.0, jnp.abs(ucand))
    lcand = jnp.where(do_l, jnp.ceil(lcand - sl), lcand)
    ucand = jnp.where(do_u, jnp.floor(ucand + su), ucand)
    return lcand, ucand


# ---------------------------------------------------------------------------
# Fused one-tile round (kernel C oracle): rows complete within their chunk
# ---------------------------------------------------------------------------


def fused_round_tiles_ref(
    val, lb_g, ub_g, is_int_g, lhs_g, rhs_g, int_eps: float, inf: float = INF
):
    """Activities + candidates in one pass; valid iff every row fits one chunk.

    This is the Alg.-3-faithful fusion: the chunk's activity lives in
    registers/VMEM and is immediately reused for the candidates -- the TPU
    analogue of the paper's shared-memory reuse (§3.5).
    """
    min_fin, min_cnt, max_fin, max_cnt = activities_tiles_ref(val, lb_g, ub_g, inf)
    return candidates_tiles_ref(
        val,
        lb_g,
        ub_g,
        is_int_g,
        min_fin,
        min_cnt,
        max_fin,
        max_cnt,
        lhs_g,
        rhs_g,
        int_eps,
        inf,
    )


# ---------------------------------------------------------------------------
# Fused-scatter oracles (kernels D/E): column-wise best-bound reduction
# ---------------------------------------------------------------------------


def scatter_round_ref(lcand, ucand, col, n_pad: int, inf: float = INF):
    """Column reduction oracle for the in-kernel scatter.

    Matches the kernels' sentinel semantics exactly: accumulators start at
    the -inf/+inf *sentinels*, so columns with no nonzeros come out at
    -inf/+inf sentinel (segment-op identities are clamped accordingly).
    """
    flat_col = col.reshape(-1)
    best_l = jax.ops.segment_max(lcand.reshape(-1), flat_col, num_segments=n_pad)
    best_u = jax.ops.segment_min(ucand.reshape(-1), flat_col, num_segments=n_pad)
    return jnp.maximum(best_l, -inf), jnp.minimum(best_u, inf)


def fused_scatter_round_tiles_ref(
    val, col, is_int_g, lhs_g, rhs_g, lb, ub, n_pad: int,
    int_eps: float, inf: float = INF,
):
    """Oracle for kernel D: in-kernel bound gather + fused round + column
    reduction.  (T,R,K) tiles + (n_pad,) bounds -> (n_pad,) x2."""
    lb_g = lb[col]
    ub_g = ub[col]
    lcand, ucand = fused_round_tiles_ref(
        val, lb_g, ub_g, is_int_g, lhs_g, rhs_g, int_eps, inf
    )
    return scatter_round_ref(lcand, ucand, col, n_pad, inf)


def activities_gather_tiles_ref(val, col, lb, ub, n_pad: int, inf: float = INF):
    """Oracle for kernel A': in-kernel bound gather + activity partials."""
    del n_pad  # shape bookkeeping only; the gather is by column id
    return activities_tiles_ref(val, lb[col], ub[col], inf)


def candidates_scatter_tiles_ref(
    val, col, is_int_g,
    row_min_fin, row_min_cnt, row_max_fin, row_max_cnt,
    lhs_g, rhs_g, lb, ub, n_pad: int, int_eps: float, inf: float = INF,
):
    """Oracle for kernel E: in-kernel bound gather + candidates from row
    aggregates + column scatter."""
    lcand, ucand = candidates_tiles_ref(
        val, lb[col], ub[col], is_int_g,
        row_min_fin, row_min_cnt, row_max_fin, row_max_cnt,
        lhs_g, rhs_g, int_eps, inf,
    )
    return scatter_round_ref(lcand, ucand, col, n_pad, inf)


def packed_round_ref(
    val, col, is_int_g, seg, lhs_s, rhs_s, lb, ub, n_pad: int,
    int_eps: float, inf: float = INF,
):
    """Oracle for the packed kernel: ``(T, R, K)`` packed tiles, whose
    chunk rows hold several rows as segments (``seg``, -1 on padding)
    with per-slot sides, + ``(n_pad,)`` bounds -> ``(n_pad,)`` x2.  Each
    slot is a row of width one whose aggregates are the segment sums of
    its row, so the per-row oracles apply unchanged."""
    t, r, k = val.shape
    chunk = jnp.arange(t * r).reshape(t, r, 1)
    gid = jnp.where(seg >= 0, chunk * k + seg, t * r * k).reshape(-1)
    one = lambda x: x[..., None]
    lb_g, ub_g = one(lb[col]), one(ub[col])
    parts = activities_tiles_ref(one(val), lb_g, ub_g, inf)
    tot = lambda x: jax.ops.segment_sum(
        x.reshape(-1), gid, num_segments=t * r * k + 1
    )[gid].reshape(t, r, k)
    lcand, ucand = candidates_tiles_ref(
        one(val), lb_g, ub_g, one(is_int_g != 0), *(tot(x) for x in parts),
        lhs_s, rhs_s, int_eps, inf,
    )
    return scatter_round_ref(lcand[..., 0], ucand[..., 0], col, n_pad, inf)


# ---------------------------------------------------------------------------
# Batched oracles: flat super-tile stream, per-instance column windows
# ---------------------------------------------------------------------------


def batched_scatter_round_ref(lcand, ucand, col_g, batch: int, n_pad: int, inf: float = INF):
    """Column reduction over the whole batch in ONE flat segment op.

    ``col_g`` carries global column ids (``col + tile_inst * n_pad``), so
    instance windows never alias; within each window the element order is
    the instance's own tile order, which keeps the per-instance reduction
    bit-identical to :func:`scatter_round_ref`."""
    flat_col = col_g.reshape(-1)
    best_l = jax.ops.segment_max(lcand.reshape(-1), flat_col, num_segments=batch * n_pad)
    best_u = jax.ops.segment_min(ucand.reshape(-1), flat_col, num_segments=batch * n_pad)
    best_l = jnp.maximum(best_l, -inf).reshape(batch, n_pad)
    best_u = jnp.minimum(best_u, inf).reshape(batch, n_pad)
    return best_l, best_u


def batched_fused_scatter_round_ref(
    val, col_g, is_int_g, lhs_g, rhs_g, lb, ub, n_pad: int,
    int_eps: float, inf: float = INF,
):
    """Oracle for the batched fused-scatter kernel: ``(T, R, K)`` flat tile
    stream + ``(B, n_pad)`` bound plane -> ``(B, n_pad)`` x2.  The bound
    gather indexes the flattened plane with global column ids; per instance
    the arithmetic is exactly the single-instance fused round."""
    batch = lb.shape[0]
    lbf, ubf = lb.reshape(-1), ub.reshape(-1)
    lcand, ucand = fused_round_tiles_ref(
        val, lbf[col_g], ubf[col_g], is_int_g, lhs_g, rhs_g, int_eps, inf
    )
    return batched_scatter_round_ref(lcand, ucand, col_g, batch, n_pad, inf)


def node_fused_scatter_round_ref(
    val, col, is_int_g, lhs_g, rhs_g, lb, ub, n_pad: int,
    int_eps: float, inf: float = INF,
):
    """Oracle for the node-batch fused-scatter kernel: ONE instance's
    ``(T, R, K)`` tiles broadcast over a ``(B, n_pad)`` bound plane.  Per
    node this is exactly :func:`fused_scatter_round_tiles_ref`, vmapped
    over the node axis -- the matrix operands are closed over, so only the
    bound planes carry the batch dimension."""
    fn = lambda l, u: fused_scatter_round_tiles_ref(
        val, col, is_int_g, lhs_g, rhs_g, l, u, n_pad, int_eps, inf
    )
    return jax.vmap(fn)(lb, ub)


def _partitioned_gathered_bounds(part, lbf, ubf, val, col_s, tile_inst, tile_slab):
    """Bounds of a slab-partitioned copy stream gathered from the flattened
    ``(B * n_pad_part,)`` plane via each copy's global window base."""
    base = tile_inst.astype(jnp.int32) * jnp.int32(part.n_pad_part) + (
        tile_slab.astype(jnp.int32) * jnp.int32(part.slab)
    )
    col_g = col_s + base[:, None, None]
    return lbf[col_g], ubf[col_g], col_g


def partitioned_round_ref(part, lb_p, ub_p, int_eps: float, inf: float = INF):
    """Slab oracle: one round over a chunk-granularity slab partition.

    Defines the exact semantics of the slab-parallel fused kernels
    (``*_slab_partials_tiles`` / ``*_slab_round_tiles`` in
    ``prop_round.py``) at the data level.  ``part`` is a
    ``SlabPartition``-shaped record (duck-typed); ``lb_p``/``ub_p`` are
    ``(B, n_pad)`` planes for any ``n_pad <= n_pad_part`` (padded to the
    slab grid here).  Per copy: local activity partials; straddle rows
    (``row_done == 0``) replace their local partial with the completed
    aggregate segment-summed over the sub-stream's ``a_slot`` table --
    exactly the summation grouping the engine's out-of-band combine
    commits to, so complete rows' aggregates are the untouched local sums
    and bitwise comparisons hold.  Candidates come from the selected
    aggregates; the column reduction runs over global padded ids, via the
    build-time rectangle-gather schedule (``col_slots``) when present.
    Returns ``(B, n_pad_part)`` best_l / best_u with sentinel identities."""
    bsz, n_pad = lb_p.shape
    dt = lb_p.dtype
    extra = part.n_pad_part - n_pad
    if extra:
        z = jnp.zeros((bsz, extra), dt)
        lb_p = jnp.concatenate([lb_p, z], axis=1)
        ub_p = jnp.concatenate([ub_p, z], axis=1)
    lbf, ubf = lb_p.reshape(-1), ub_p.reshape(-1)

    lb_g, ub_g, col_g = _partitioned_gathered_bounds(
        part, lbf, ubf, part.val, part.col_s, part.tile_inst, part.tile_slab
    )
    mf, mc, xf, xc = activities_tiles_ref(part.val, lb_g, ub_g, inf)

    if int(part.a_val.shape[0]):
        a_lb, a_ub, _ = _partitioned_gathered_bounds(
            part, lbf, ubf, part.a_val, part.a_col_s,
            part.a_tile_inst, part.a_tile_slab,
        )
        amf, amc, axf, axc = activities_tiles_ref(part.a_val, a_lb, a_ub, inf)
        slot = part.a_slot.reshape(-1)
        nseg = part.n_straddle + 1
        tab = lambda x: jax.ops.segment_sum(x.reshape(-1), slot, num_segments=nseg)
        done = part.row_done != 0
        sel = lambda local, t: jnp.where(done, local, tab(t)[part.agg_slot])
        rmf, rmc = sel(mf, amf), sel(mc, amc)
        rxf, rxc = sel(xf, axf), sel(xc, axc)
    else:
        rmf, rmc, rxf, rxc = mf, mc, xf, xc

    lcand, ucand = candidates_tiles_ref(
        part.val, lb_g, ub_g, part.ii_g != 0, rmf, rmc, rxf, rxc,
        part.lhs_g, part.rhs_g, int_eps, inf,
    )
    if part.col_slots is not None:
        # Rectangle-gather reduction: one gather + row-wise max/min over the
        # build-time per-column slot lists (sentinel slot -> the appended
        # -inf/+inf identity element).  Bitwise-equal to the segment ops --
        # min/max are grouping-independent.
        fl = jnp.concatenate([lcand.reshape(-1), jnp.full((1,), -inf, dt)])
        fu = jnp.concatenate([ucand.reshape(-1), jnp.full((1,), inf, dt)])
        best_l = fl[part.col_slots].max(axis=1)
        best_u = fu[part.col_slots].min(axis=1)
        best_l = jnp.maximum(best_l, -inf).reshape(bsz, part.n_pad_part)
        best_u = jnp.minimum(best_u, inf).reshape(bsz, part.n_pad_part)
        return best_l, best_u
    return batched_scatter_round_ref(
        lcand, ucand, col_g, bsz, part.n_pad_part, inf
    )


def node_partitioned_round_ref(part, lb_p, ub_p, int_eps: float, inf: float = INF):
    """Node-batch slab oracle: ONE instance's slab partition broadcast over
    ``(B, n_pad)`` per-node bound planes.  Per node this is exactly
    :func:`partitioned_round_ref` at ``B == 1``, vmapped over the node
    axis; returns ``(B, n_pad_part)`` best_l / best_u."""
    fn = lambda l, u: partitioned_round_ref(part, l[None], u[None], int_eps, inf)
    bl, bu = jax.vmap(fn)(lb_p, ub_p)
    return bl[:, 0], bu[:, 0]


# ---------------------------------------------------------------------------
# Solver oracles: node objective bound, branch selection, incumbent update
# ---------------------------------------------------------------------------


def node_objective_ref(lb, ub, c, is_int, valid, feas_eps: float, inf: float = INF):
    """Per-node objective lower bound + leaf/prune predicates (solver oracle).

    Args:
      lb, ub: (B, n_pad) propagated per-node bound planes (sentinel-infinite).
      c:      (n_pad,) minimization objective (0 on padded columns).
      is_int: (n_pad,) bool integrality marks.
      valid:  (n_pad,) bool, True on real (non-padded) columns.

    Returns ``(obj, fixed, crossed)``, each ``(B,)``:

      * ``obj`` -- the domain-relaxation bound ``sum_j min(c_j lb_j, c_j
        ub_j)`` (i.e. ``c_j lb_j`` for ``c_j > 0``, ``c_j ub_j`` for
        ``c_j < 0``), a valid lower bound on every feasible point in the
        node's box; ``-inf`` sentinel if any contributing bound is
        infinite.  For a node whose variables are all fixed this IS the
        point's objective, and over integral data the f64 sum is exact --
        the bitwise anchor of the differential tests.
      * ``fixed`` -- every valid integer column has ``ub - lb <= 0.5``
        (an integral domain of width 0: the node is a candidate leaf).
      * ``crossed`` -- some valid column's domain emptied
        (``lb > ub + feas_eps``): prune the node as infeasible.
    """
    v = valid[None, :]
    cb = c[None, :]
    contrib = jnp.where(cb > 0, cb * lb, cb * ub)
    contrib = jnp.where(v & (cb != 0), contrib, 0.0)
    unbounded = v & (((cb > 0) & (lb <= -inf)) | ((cb < 0) & (ub >= inf)))
    obj = jnp.where(
        jnp.any(unbounded, axis=-1), -inf, jnp.sum(contrib, axis=-1)
    )
    fixed = jnp.all(~(v & is_int[None, :]) | (ub - lb <= 0.5), axis=-1)
    crossed = jnp.any((lb > ub + feas_eps) & v, axis=-1)
    return obj, fixed, crossed


def most_fractional_ref(lb, ub, is_int, valid):
    """Most-fractional branching selection over ``(B, n_pad)`` bound planes.

    Candidate columns are valid unfixed integers (``ub - lb > 0.5``); the
    score is the domain midpoint's distance-to-integrality
    ``0.5 - |frac(mid) - 0.5|`` and ties resolve to the LOWEST column index
    (``argmax`` first-hit), so selection is deterministic.  Returns
    ``(var, has)``: per-node selected column and whether any candidate
    existed (``var`` is 0 and meaningless when ``has`` is False)."""
    cand = valid[None, :] & is_int[None, :] & (ub - lb > 0.5)
    mid = 0.5 * (lb + ub)
    frac = mid - jnp.floor(mid)
    score = jnp.where(cand, 0.5 - jnp.abs(frac - 0.5), -1.0)
    return jnp.argmax(score, axis=-1), jnp.any(cand, axis=-1)


def pseudo_cost_select_ref(
    lb, ub, is_int, valid, pc_sum, pc_cnt, prior: float = 1e-4
):
    """Pseudo-cost branching selection over ``(B, n_pad)`` bound planes.

    ``pc_sum``/``pc_cnt`` are the search's ``(2, n_pad)`` accumulated
    bound-gain statistics (direction 0 = down child, 1 = up child): each
    propagated child adds ``max(child_bound - parent_bound, 0)`` for its
    branching column and direction.  The score is the product of the two
    directions' average gains (plus a small ``prior`` so unseen columns
    stay comparable), the standard product rule; candidates and
    tie-breaking are exactly :func:`most_fractional_ref`'s.  Returns
    ``(var, has)``."""
    cand = valid[None, :] & is_int[None, :] & (ub - lb > 0.5)
    avg_d = pc_sum[0] / jnp.maximum(pc_cnt[0], 1.0)
    avg_u = pc_sum[1] / jnp.maximum(pc_cnt[1], 1.0)
    score = (avg_d + prior) * (avg_u + prior)
    score = jnp.where(cand, score[None, :], -1.0)
    return jnp.argmax(score, axis=-1), jnp.any(cand, axis=-1)


def incumbent_update_ref(leaf, obj, inc, inc_x, lb, inf: float = INF):
    """Device-resident incumbent update (solver oracle).

    ``leaf`` masks the ``(B,)`` nodes whose propagated domains are feasible
    candidate solutions this level, ``obj`` their objectives, ``inc`` /
    ``inc_x`` the running incumbent scalar and ``(n_pad,)`` solution plane,
    ``lb`` the ``(B, n_pad)`` bound planes (a leaf's solution is its
    ``lb`` row -- all variables fixed).  The best leaf is selected with
    ``min`` + first-index ``argmin``, so reduction order is deterministic;
    the incumbent moves only on STRICT improvement.  Returns
    ``(inc, inc_x, improved)``."""
    leaf_obj = jnp.where(leaf, obj, inf)
    best = jnp.min(leaf_obj)
    improved = best < inc
    inc_new = jnp.where(improved, best, inc)
    x_new = jnp.where(improved, lb[jnp.argmin(leaf_obj)], inc_x)
    return inc_new, x_new, improved


def batched_candidates_scatter_round_ref(
    val, col_g, is_int_g, chunk_row, lhs_g, rhs_g, lb, ub,
    m_total: int, n_pad: int, int_eps: float, inf: float = INF,
):
    """Batched round for rows spanning several chunks: one flat activity
    segment-combine over GLOBAL row ids (instance ``i``'s padding chunks
    target its own dummy row, so segments never alias across instances),
    then candidates + the flat column reduction."""
    batch = lb.shape[0]
    lbf, ubf = lb.reshape(-1), ub.reshape(-1)
    lb_t, ub_t = lbf[col_g], ubf[col_g]
    mf, mc, xf, xc = activities_tiles_ref(val, lb_t, ub_t, inf)
    flat = chunk_row.reshape(-1)
    seg = lambda x: jax.ops.segment_sum(x.reshape(-1), flat, num_segments=m_total + 1)
    g = lambda x: seg(x)[chunk_row]
    lcand, ucand = candidates_tiles_ref(
        val, lb_t, ub_t, is_int_g, g(mf), g(mc), g(xf), g(xc),
        lhs_g, rhs_g, int_eps, inf,
    )
    return batched_scatter_round_ref(lcand, ucand, col_g, batch, n_pad, inf)
