"""Pallas TPU kernels for the fused propagation round (paper Alg. 3).

TPU adaptation of CSR-adaptive (DESIGN.md §2): the matrix is stored as
length-bucketed block-ELL tiles of shape (R, K) = (tile_rows, tile_width).
On the target (TPU v5e) K=128 matches the lane width and R=8 the sublane
count, so a tile is exactly one VREG-aligned VMEM block; grid steps pipeline
HBM->VMEM DMAs of consecutive tiles.

Kernel inventory
----------------

Split-phase kernels (general case, long rows span chunks):

  * ``_activities_kernel``  -- per-chunk activity partials + inf counters
                               (CSR-stream/CSR-vector unified: long rows span
                               chunks, partials are segment-combined outside).
  * ``_candidates_kernel``  -- residual activities (§3.4 single-infinity
                               rule) + bound candidates (Eqs. 4/5) +
                               integrality rounding, given completed row
                               aggregates gathered per chunk.
  * ``_fused_round_kernel`` -- Alg.-3-faithful fusion of both phases for the
                               common case where every row fits in one chunk
                               (activities stay in VMEM and are reused
                               immediately -- the shared-memory trick).

Fully fused scatter kernels (the zero-HBM-tensor round engine):

  * ``_fused_scatter_kernel``      -- bound gather + activities + candidates
        + column-wise best-bound reduction in ONE kernel.  The bound vectors
        and the ``(2, n_pad)`` best-bound accumulators live in VMEM and are
        revisited by every grid step (the TPU grid is sequential, so a block
        whose index map is constant acts as an on-chip reduction buffer);
        neither the gathered bounds nor the candidates EVER touch HBM.  The
        column scatter is the atomic-free replacement for the paper's
        atomicMax/atomicMin: exact one-hot matmuls on the MXU place each
        tile row's candidates in an ``(n_pad // 128, 128)`` plane, combined
        by max/min (see ``_scatter_tile``); the gather is its exact dual
        (see ``_gather_bounds_tile``).
  * ``_activities_gather_kernel``  -- activity partials with the in-kernel
        bound gather, for rows spanning several chunks (partials are
        segment-combined outside, they are only (T, R)-sized).
  * ``_candidates_scatter_kernel`` -- same fused gather+scatter, but
        candidates are computed from completed row aggregates gathered per
        chunk (rows that span several chunks; the CSR-vector analogue).
  * ``_packed_round_kernel``       -- kernel D over PACKED tiles: each
        chunk row holds several whole short rows as contiguous segments of
        slots (column-disjoint within the chunk row), so a chunk row's
        gather and scatter serve all its rows; per-slot row aggregates come
        from a segmented reduction within the chunk row (two one-hot
        products on the MXU, see ``_segment_totals``).
  * ``_apply_updates_kernel``      -- the small merge kernel: folds the
        accumulated best bounds into (lb, ub) with the shared
        ``bounds.apply_updates`` semantics.  ``input_output_aliases`` donates
        the bound buffers so the fixed-point loop updates bounds in place.

Slab-parallel partitioned kernels (``n_pad > SCATTER_MAX_NPAD``):

  * ``_batched_slab_round_kernel`` / ``_node_slab_round_kernel`` -- the
        fused round over a column-slab partition (``ops.build_slab_partition``)
        on a 2D ``(run, tile)`` grid: one run per ``(instance, slab)``
        window, best-bound accumulators in per-run VMEM scratch, and the
        bound merge folded into the run's last step so no partial plane
        round-trips through HBM.  The run axis is declared ``parallel``.
  * ``_batched_slab_partials_kernel`` / ``_node_slab_partials_kernel`` --
        activity partials for the few STRADDLE rows whose nonzeros are
        split across slab copies (completed by a tiny segment sum outside).
  * ``_apply_updates_gated_kernel`` -- standalone slab-windowed or
        batched merge (kept for callers composing their own partitioned
        pipelines; the round kernels above merge in place themselves).

In the fused engine the irregular gather itself moves into the kernels
(``_gather_bounds_tile``): the bound vectors ride along as VMEM-resident
``(1, n_pad)`` blocks, so no nnz-proportional tensor exists in HBM at all
during a round -- per grid step HBM only streams the tile's static matrix
data.  Kernels are validated on CPU via ``interpret=True`` against
``ref.py``.

Every ``pallas_call`` is named from :data:`KERNEL_NAMES` (``prop_<kernel>``),
so each kernel keeps its name on the device and in a profiler trace.

Mosaic layout rules (what makes every kernel compile for a TPU)
----------------------------------------------------------------

  * The last two dims of every block equal the array's or are multiples of
    (8, 128).  Per-row data (sides, aggregates, partials) travels as
    ``(T, 1, R)`` arrays with squeezed ``(None, 1, R)`` blocks; flags as
    ``(.., 1, 1)``.  Bound vectors and planes travel lane-dense as
    ``(.., n // LANE, LANE)`` (:func:`_lane_rows`), so a ``LANE``-wide
    column window is one row of the block; slab windows add a slab axis,
    ``(B, n_slabs, S // LANE, LANE)``.
  * No dynamic slicing of values, which Mosaic cannot lower: tile rows are
    walked by a static unroll over the ``R`` sublanes, and the column
    gather and scatter are one-hot matmuls over whole blocks.
  * Index maps are explicitly int32 (``_I0``), so kernels compile with
    ``jax_enable_x64`` on or off.
  * Mosaic takes no 64-bit operand: a compile for the TPU (``interpret``
    False) refuses float64 with a clear error (:func:`resolve_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import bounds as bnd
# col_pad moved to core.sparse with the batch packing; re-exported here (the
# redundant alias marks the intentional re-export) for kernel-level callers.
from ..core.sparse import LANE as LANE, col_pad as col_pad
from ..core.types import (
    INF, any_true, clamp_to_sentinel, int_round_slack, scalar_like,
)

# Index-map constant: a Python ``0`` would trace as int64 under x64, which
# Mosaic cannot lower.
_I0 = np.int32(0)
# One-hot constants: Python floats would trace as float64 under x64.
_ONE, _ZERO = np.float32(1.0), np.float32(0.0)

#: The device name of each kernel, keyed by the kernel body it runs
#: (``_<key>_kernel``; the two batched slab kernels drop ``batched_``).
#: Every ``pallas_call`` passes its kernel's name as ``name=``, so the HLO
#: custom call, and the op a profiler trace lists, is ``%<name>.N`` rather
#: than the name of the enclosing scope.  Two call sites of one kernel
#: share its name.
KERNEL_NAMES = {
    "activities": "prop_activities",
    "activities_gather": "prop_activities_gather",
    "candidates": "prop_candidates",
    "fused_round": "prop_fused_round",
    "fused_scatter": "prop_fused_scatter",
    "candidates_scatter": "prop_candidates_scatter",
    "packed_round": "prop_packed_round",
    "apply_updates": "prop_apply_updates",
    "batched_fused_scatter": "prop_batched_fused_scatter",
    "node_fused_scatter": "prop_node_fused_scatter",
    "slab_partials": "prop_slab_partials",
    "slab_round": "prop_slab_round",
    "node_slab_partials": "prop_node_slab_partials",
    "node_slab_round": "prop_node_slab_round",
    "apply_updates_gated": "prop_apply_updates_gated",
    "node_objective": "prop_node_objective",
}


def _on_cpu() -> bool:
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None, dtype) -> bool:
    """``interpret=None`` -> interpret mode off the TPU, a Mosaic compile on
    it.  A Mosaic compile of a 64-bit operand raises instead of falling
    back to interpret mode or to the jnp round."""
    if interpret is None:
        interpret = _on_cpu()
    if not interpret and jnp.dtype(dtype).itemsize == 8:
        raise TypeError(
            f"{jnp.dtype(dtype).name} cannot run in a Pallas kernel on the "
            "TPU (Mosaic has no 64-bit types); use float32 -- dtype=None "
            "resolves to it on the TPU -- or use_pallas=False"
        )
    return interpret


def _lane_rows(x, block: int = LANE):
    """``(.., n)`` -> ``(.., n // block, block)`` lane-dense rows (``(.., 1,
    n)`` when ``block`` does not divide ``n``): the VMEM layout of bound
    vectors and planes."""
    n = x.shape[-1]
    w = block if n % block == 0 else n
    return x.reshape(*x.shape[:-1], n // w, w)


def _rows3(x):
    """``(T, R)`` per-row data -> ``(T, 1, R)``: the unit sublane axis makes
    a ``(None, 1, R)`` block legal for any ``T``."""
    return x.reshape(*x.shape[:-1], 1, x.shape[-1])


def _check_windows(n: int, block: int, name: str = "n_pad") -> None:
    if n % block:
        raise ValueError(f"{name}={n} must be a multiple of block={block}")


def _vec_spec(n_pad: int, block: int):
    """A whole lane-dense ``(n_pad // block, block)`` bound vector, resident
    across every grid step (its index map is constant)."""
    return pl.BlockSpec((n_pad // block, block), lambda *_: (_I0, _I0))


def _int_operand(x):
    """Integer pallas_call operand: bools widen to int32, integer dtypes
    pass through unchanged -- compact low-precision index streams (int16
    cols / int8 integrality marks) must reach the kernel narrow, since an
    entry-point widening would materialize an int32 copy at the HBM
    boundary and forfeit the tier's byte savings."""
    x = jnp.asarray(x)
    return x.astype(jnp.int32) if x.dtype == jnp.bool_ else x


# ---------------------------------------------------------------------------
# Shared tile math (used by every kernel AND by the jnp oracles in ref.py)
# ---------------------------------------------------------------------------


def _select(c, a, b):
    """``jnp.where`` over bools, as logic ops (Mosaic cannot select
    between boolean vectors)."""
    return (c & a) | (~c & b)


def tile_contributions(val, lb_g, ub_g, inf):
    """Per-nonzero activity contributions of one (or many) (.., R, K) tiles.

    Returns (pos, pad, min_is_inf, max_is_inf, c_min, c_max)."""
    pos = val > 0
    pad = val == 0
    b_min = jnp.where(pos, lb_g, ub_g)
    b_max = jnp.where(pos, ub_g, lb_g)
    min_is_inf = (jnp.abs(b_min) >= inf) & ~pad
    max_is_inf = (jnp.abs(b_max) >= inf) & ~pad
    zero = scalar_like(0.0, val)
    c_min = jnp.where(min_is_inf | pad, zero, val * b_min)
    c_max = jnp.where(max_is_inf | pad, zero, val * b_max)
    return pos, pad, min_is_inf, max_is_inf, c_min, c_max


def _per_slot(x, val):
    """Per-row data ``(.., R)`` broadcast over the ``K`` slots; data that is
    already per slot (the packed stream's) passes as it is."""
    return x if x.ndim == val.ndim else x[..., None]


def tile_candidates(
    val,
    lb_g,
    ub_g,
    is_int_g,
    row_min_fin,
    row_min_cnt,
    row_max_fin,
    row_max_cnt,
    lhs,
    rhs,
    int_eps,
    inf,
):
    """Residual activities (§3.4 single-infinity rule) + bound candidates
    (Eqs. 4/5) + integrality rounding.  Row aggregates / sides are (.., R)
    and broadcast over the K axis, or (.., R, K) per slot (packed tiles,
    where a chunk row holds several rows).  Pure jnp: callable inside
    kernels.

    Candidates use the division-first form ``(side - row_sum) / a + bound``
    rather than dividing the residual ``row_sum - a * bound``: the two are
    algebraically equal, but the residual form multiplies into a
    subtraction, which CPU/LLVM backends contract into an FMA in some
    compilation contexts (inside a fused Pallas kernel) and not others
    (the op-by-op oracle), breaking bitwise kernel-vs-oracle equality in
    the last mantissa bit.  The division-first chain (sub, div, add) has
    no contractible pattern, so every context rounds identically."""
    pos, pad, min_is_inf, max_is_inf, _, _ = tile_contributions(
        val, lb_g, ub_g, inf
    )
    rmf, rmc, rxf, rxc, lhs_b, rhs_b = (
        _per_slot(x, val)
        for x in (row_min_fin, row_min_cnt, row_max_fin, row_max_cnt, lhs, rhs)
    )

    # Residual usable at this entry (§3.4): all contributions finite and
    # the row sum complete (cnt == 0), or exactly this entry's bound
    # infinite so the sum over the others IS the residual (cnt == 1).
    ok_min = _select(min_is_inf, rmc == 1, rmc == 0)
    ok_max = _select(max_is_inf, rxc == 1, rxc == 0)
    # This entry's own bound, folded back in candidate space (0 when the
    # entry's contribution was never part of the finite sum).
    b_min = jnp.where(pos, lb_g, ub_g)
    b_max = jnp.where(pos, ub_g, lb_g)
    zero = scalar_like(0.0, val)
    inc_min = jnp.where(min_is_inf | pad, zero, b_min)
    inc_max = jnp.where(max_is_inf | pad, zero, b_max)

    safe_a = jnp.where(pad, scalar_like(1.0, val), val)
    q_min = (rhs_b - rmf) / safe_a + inc_min
    q_max = (lhs_b - rxf) / safe_a + inc_max
    lcand = jnp.where(pos, q_max, q_min)
    ucand = jnp.where(pos, q_min, q_max)

    valid_l = (
        _select(pos, (lhs_b > -inf) & ok_max, (rhs_b < inf) & ok_min)
        & ~pad
    )
    valid_u = (
        _select(pos, (rhs_b < inf) & ok_min, (lhs_b > -inf) & ok_max)
        & ~pad
    )
    lcand = jnp.where(valid_l, clamp_to_sentinel(lcand, inf), scalar_like(-inf, val))
    ucand = jnp.where(valid_u, clamp_to_sentinel(ucand, inf), scalar_like(inf, val))

    do_l = is_int_g & (jnp.abs(lcand) < inf)
    do_u = is_int_g & (jnp.abs(ucand) < inf)
    # Low-precision tiers widen the integrality rounding by the dtype's
    # scale-aware slack (see core.types.int_round_slack): ceil/floor are
    # discontinuous, so tier arithmetic error must not cross an integer.
    slack = int_round_slack(jnp.result_type(lcand))
    sl = su = int_eps
    if slack:  # static per dtype: fp64 keeps the exact scalar subtraction
        sl = int_eps + slack * jnp.maximum(1.0, jnp.abs(lcand))
        su = int_eps + slack * jnp.maximum(1.0, jnp.abs(ucand))
    lcand = jnp.where(do_l, jnp.ceil(lcand - sl), lcand)
    ucand = jnp.where(do_u, jnp.floor(ucand + su), ucand)
    return lcand, ucand


def tile_row_aggregates(val, lb_g, ub_g, inf):
    """In-register row aggregates of a chunk-complete tile (.., R)."""
    _, _, min_is_inf, max_is_inf, c_min, c_max = tile_contributions(
        val, lb_g, ub_g, inf
    )
    rmf = c_min.sum(axis=-1)
    rxf = c_max.sum(axis=-1)
    rmc = min_is_inf.sum(axis=-1, dtype=jnp.int32)
    rxc = max_is_inf.sum(axis=-1, dtype=jnp.int32)
    return rmf, rmc, rxf, rxc


# ---------------------------------------------------------------------------
# Kernel A: activity partials
# ---------------------------------------------------------------------------


def _tile_spec(r, k):
    """One ``(R, K)`` tile per step of a 1D tile grid."""
    return pl.BlockSpec((None, r, k), lambda i: (i, _I0, _I0))


def _row_spec(r):
    """One tile's ``(1, R)`` per-row data per step of a 1D tile grid."""
    return pl.BlockSpec((None, 1, r), lambda i: (i, _I0, _I0))


def _partials_shapes(lead, r, dtype):
    """Out shapes of the four activity partials: ``lead + (1, R)`` each."""
    return [
        jax.ShapeDtypeStruct(lead + (1, r), dt)
        for dt in (dtype, jnp.int32, dtype, jnp.int32)
    ]


def _write_partials(refs, aggs):
    """Store ``(R,)`` row aggregates into ``(1, R)`` output blocks."""
    for ref, a in zip(refs, aggs):
        ref[...] = a.reshape(1, -1)


def _activities_kernel(val_ref, lb_ref, ub_ref, mf_ref, mc_ref, xf_ref, xc_ref, *, inf):
    # (R, K) VMEM blocks -> (1, R) per-chunk partials.
    aggs = tile_row_aggregates(val_ref[...], lb_ref[...], ub_ref[...], inf)
    _write_partials((mf_ref, mc_ref, xf_ref, xc_ref), aggs)


def activities_tiles(val, lb_g, ub_g, inf: float = INF, interpret: bool | None = None):
    """Pallas-backed per-chunk activity partials. Shapes: (T, R, K) -> (T, R)."""
    interpret = resolve_interpret(interpret, val.dtype)
    t, r, k = val.shape
    tile = _tile_spec(r, k)
    fn = pl.pallas_call(
        functools.partial(_activities_kernel, inf=inf),
        grid=(t,),
        in_specs=[tile, tile, tile],
        out_specs=[_row_spec(r)] * 4,
        out_shape=_partials_shapes((t,), r, val.dtype),
        name=KERNEL_NAMES["activities"],
        interpret=interpret,
    )
    return tuple(x.reshape(t, r) for x in fn(val, lb_g, ub_g))


def _activities_gather_kernel(
    val_ref, col_ref, lb_ref, ub_ref, mf_ref, mc_ref, xf_ref, xc_ref, *, inf, block
):
    """Kernel A': activity partials with the bound gather done in-kernel
    from the VMEM-resident lane-dense bound vectors (no HBM-side gather)."""
    val = val_ref[...]
    col = col_ref[...].astype(jnp.int32)
    lb_g, ub_g = _gather_bounds_tile(col, lb_ref, ub_ref, inf=inf, block=block)
    aggs = tile_row_aggregates(val, lb_g, ub_g, inf)
    _write_partials((mf_ref, mc_ref, xf_ref, xc_ref), aggs)


def activities_gather_tiles(
    val,
    col,
    lb,
    ub,
    n_pad: int,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Per-chunk activity partials with in-kernel bound gather.

    (T, R, K) tiles + (n_pad,) bounds -> 4 x (T, R); the gathered-bound
    tensors never exist in HBM."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(n_pad, block)
    t, r, k = val.shape
    tile = _tile_spec(r, k)
    vec = _vec_spec(n_pad, block)
    fn = pl.pallas_call(
        functools.partial(_activities_gather_kernel, inf=inf, block=block),
        grid=(t,),
        in_specs=[tile, tile, vec, vec],
        out_specs=[_row_spec(r)] * 4,
        out_shape=_partials_shapes((t,), r, val.dtype),
        name=KERNEL_NAMES["activities_gather"],
        interpret=interpret,
    )
    out = fn(val, col, _lane_rows(lb, block), _lane_rows(ub, block))
    return tuple(x.reshape(t, r) for x in out)


# ---------------------------------------------------------------------------
# Kernel B: candidates from completed row aggregates
# ---------------------------------------------------------------------------


def _row(ref):
    """A ``(1, R)`` per-row block as an ``(R,)`` vector."""
    return ref[...].reshape(-1)


def _candidates_kernel(
    val_ref, lb_ref, ub_ref, ii_ref, rmf_ref, rmc_ref, rxf_ref, rxc_ref,
    lhs_ref, rhs_ref, lc_ref, uc_ref, *, int_eps, inf,
):
    lc_ref[...], uc_ref[...] = tile_candidates(
        val_ref[...], lb_ref[...], ub_ref[...], ii_ref[...] != 0,
        _row(rmf_ref), _row(rmc_ref), _row(rxf_ref), _row(rxc_ref),
        _row(lhs_ref), _row(rhs_ref), int_eps, inf,
    )


def candidates_tiles(
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
    interpret: bool | None = None,
):
    """Pallas-backed candidates. (T,R,K) tiles + (T,R) row data -> (T,R,K) x2."""
    interpret = resolve_interpret(interpret, val.dtype)
    t, r, k = val.shape
    tile = _tile_spec(r, k)
    out_shape = [jax.ShapeDtypeStruct((t, r, k), val.dtype)] * 2
    fn = pl.pallas_call(
        functools.partial(_candidates_kernel, int_eps=int_eps, inf=inf),
        grid=(t,),
        in_specs=[tile] * 4 + [_row_spec(r)] * 6,
        out_specs=[tile, tile],
        out_shape=out_shape,
        name=KERNEL_NAMES["candidates"],
        interpret=interpret,
    )
    rows = (row_min_fin, row_min_cnt, row_max_fin, row_max_cnt, lhs_g, rhs_g)
    return fn(
        val, lb_g, ub_g, _int_operand(is_int_g), *(_rows3(x) for x in rows)
    )


# ---------------------------------------------------------------------------
# Kernel C: fused round (rows complete within one chunk)
# ---------------------------------------------------------------------------


def _fused_round_kernel(
    val_ref, lb_ref, ub_ref, ii_ref, lhs_ref, rhs_ref, lc_ref, uc_ref, *, int_eps, inf
):
    val = val_ref[...]
    lb_g = lb_ref[...]
    ub_g = ub_ref[...]
    # Row aggregates entirely in VMEM (the paper's shared-memory reuse).
    rmf, rmc, rxf, rxc = tile_row_aggregates(val, lb_g, ub_g, inf)
    lc_ref[...], uc_ref[...] = tile_candidates(
        val, lb_g, ub_g, ii_ref[...] != 0,
        rmf, rmc, rxf, rxc, _row(lhs_ref), _row(rhs_ref), int_eps, inf,
    )


def fused_round_tiles(
    val,
    lb_g,
    ub_g,
    is_int_g,
    lhs_g,
    rhs_g,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
):
    """Alg.-3-faithful fused tile round. Requires max row length <= K."""
    interpret = resolve_interpret(interpret, val.dtype)
    t, r, k = val.shape
    tile = _tile_spec(r, k)
    out_shape = [jax.ShapeDtypeStruct((t, r, k), val.dtype)] * 2
    fn = pl.pallas_call(
        functools.partial(_fused_round_kernel, int_eps=int_eps, inf=inf),
        grid=(t,),
        in_specs=[tile] * 4 + [_row_spec(r)] * 2,
        out_specs=[tile, tile],
        out_shape=out_shape,
        name=KERNEL_NAMES["fused_round"],
        interpret=interpret,
    )
    return fn(
        val, lb_g, ub_g, _int_operand(is_int_g), _rows3(lhs_g), _rows3(rhs_g)
    )


# ---------------------------------------------------------------------------
# Kernels D/E: fused column scatter -- candidates never leave VMEM
# ---------------------------------------------------------------------------


# The gather and the scatter run on the MXU as one-hot matmuls.  A column id
# ``c`` splits into its window ``c // block`` (a row of the lane-dense bound
# layout) and its lane ``c % block``; per tile row a ``(block, K)`` one-hot
# of the lanes turns a product with the ``(n_win, block)`` bound rows into
# every window's value at each slot's lane, and a sublane mask picks the
# slot's own window.  Each product has one nonzero term, so it is exact as
# long as the operands are: float32 values go through the MXU as three
# bfloat16 pieces whose float32 sum is the value again (:func:`_pieces`).


def _pieces(x):
    """``(pieces, acc)``: ``x`` as MXU operands whose sum in ``acc`` is
    exactly ``x`` -- three bfloat16 pieces of a float32 (8 + 8 + 8
    significand bits), ``x`` itself otherwise (bfloat16 is an MXU type;
    float64 only runs in interpret mode)."""
    if x.dtype != jnp.float32:
        return (x,), jnp.float32 if x.dtype == jnp.bfloat16 else x.dtype
    hi = x.astype(jnp.bfloat16)
    r1 = x - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return (hi, mid, lo), jnp.float32


def _window_lane(c, block):
    """Column ids -> (window, lane) in the lane-dense layout."""
    if block & (block - 1):
        raise ValueError(f"block={block} must be a power of two")
    return c >> (block.bit_length() - 1), c & (block - 1)


def _lane_onehot(lane, block, dtype):
    """``(1, K)`` lane ids -> ``(block, K)`` one-hot ``[l, k] = lane[k] == l``."""
    hit = jax.lax.broadcasted_iota(jnp.int32, (block, lane.shape[-1]), 0) == lane
    return jnp.where(hit, _ONE, _ZERO).astype(dtype)


def _onehot_dot(pieces, onehot, contract, acc):
    """``sum(p @ onehot)`` (``contract`` 0) or ``sum(p @ onehot.T)``
    (``contract`` 1) over ``pieces``, accumulated in ``acc``: exact, since
    every output element takes one term per piece."""
    out = None
    for p in pieces:
        d = jax.lax.dot_general(
            p, onehot, (((1,), (contract,)), ((), ())), preferred_element_type=acc
        )
        out = d if out is None else out + d
    return out


def _scatter_tile(lcand, ucand, col, bl_ref, bu_ref, *, inf, block):
    """Column-wise max/min merge of one ``(R, K)`` candidate tile into the
    lane-dense ``(n_pad // block, block)`` best-bound accumulators resident
    in VMEM.

    Per tile row, a one-hot matmul places each slot's candidate at its
    ``(window, lane)`` cell of an ``(n_win, block)`` plane, and a second
    one of the same masks counts the hits per cell.  A matrix row holds
    each column once, so no cell sums two candidates and the placement is
    exact; rows are then combined by max/min, which is associative and
    commutative, so the result is bit-identical to a global segment
    reduction regardless of tile or visit order.  Slots with no candidate
    on either side (padding included) carry the -inf/+inf identity and are
    left out of the masks.  A tile whose row repeats a column (a cell hit
    twice) takes the compare-based merge instead, which is exact for any
    column ids."""
    r, k = lcand.shape
    n_win = bl_ref.shape[-2]
    dtype = lcand.dtype
    neg, pos = scalar_like(-inf, lcand), scalar_like(inf, lcand)
    win_ids = jax.lax.broadcasted_iota(jnp.int32, (n_win, k), 0)
    best_l = jnp.full((n_win, block), -inf, dtype)
    best_u = jnp.full((n_win, block), inf, dtype)
    hits = None
    for i in range(r):
        lc, uc = lcand[i:i + 1], ucand[i:i + 1]
        w, lane = _window_lane(col[i:i + 1], block)
        sel = (win_ids == w) & ((lc > neg) | (uc < pos))
        pl_, acc = _pieces(lc)
        pu_, _ = _pieces(uc)
        onehot = _lane_onehot(lane, block, pl_[0].dtype)

        def place(pieces):
            masked = [jnp.where(sel, p.astype(acc), 0.0).astype(p.dtype) for p in pieces]
            return _onehot_dot(masked, onehot, 1, acc).astype(dtype)

        count = _onehot_dot([jnp.where(sel, _ONE, _ZERO).astype(onehot.dtype)], onehot, 1, acc)
        hits = count if hits is None else jnp.maximum(hits, count)
        hit = count > 0.5
        best_l = jnp.maximum(best_l, jnp.where(hit, place(pl_), neg))
        best_u = jnp.minimum(best_u, jnp.where(hit, place(pu_), pos))
    distinct = jnp.max(hits) < 1.5

    @pl.when(distinct)
    def _():
        bl_ref[...] = jnp.maximum(bl_ref[...], best_l)
        bu_ref[...] = jnp.minimum(bu_ref[...], best_u)

    @pl.when(~distinct)
    def _():
        _scatter_tile_compare(lcand, ucand, col, bl_ref, bu_ref, inf=inf, block=block)


def _scatter_tile_compare(lcand, ucand, col, bl_ref, bu_ref, *, inf, block):
    """:func:`_scatter_tile` for rows that repeat a column: for each
    ``block``-wide column window, compare the slots' column ids against
    the window's lanes and reduce the hits by max/min."""
    r, k = lcand.shape
    dtype = lcand.dtype
    neg, pos = scalar_like(-inf, lcand), scalar_like(inf, lcand)
    lanes0 = jax.lax.broadcasted_iota(jnp.int32, (k, block), 1)

    def col_block(j, carry):
        lanes = j * block + lanes0
        best_l = jnp.full((1, block), -inf, dtype)
        best_u = jnp.full((1, block), inf, dtype)
        for i in range(r):
            hit = col[i][:, None] == lanes
            best_l = jnp.maximum(
                best_l, jnp.where(hit, lcand[i][:, None], neg).max(axis=0, keepdims=True)
            )
            best_u = jnp.minimum(
                best_u, jnp.where(hit, ucand[i][:, None], pos).min(axis=0, keepdims=True)
            )
        win = pl.ds(j, 1)
        bl_ref[win, :] = jnp.maximum(bl_ref[win, :], best_l)
        bu_ref[win, :] = jnp.minimum(bu_ref[win, :], best_u)
        return (j + 1, carry)

    # A scan with an explicit int32 counter: ``fori_loop`` with static
    # bounds counts in a Python int, which traces as int64 under x64.
    jax.lax.scan(lambda c, _: (col_block(*c), None), (np.int32(0), ()), None,
                 length=bl_ref.shape[-2])


def _init_accumulators(bl_ref, bu_ref, inf):
    @pl.when(pl.program_id(0) == 0)
    def _():
        bl_ref[...] = jnp.full_like(bl_ref[...], -inf)
        bu_ref[...] = jnp.full_like(bu_ref[...], inf)


def _gather_bounds_tile(col, lb_ref, ub_ref, *, inf, block):
    """In-kernel bound gather: reconstruct (lb, ub) at each ``(R, K)`` tile
    slot from the lane-dense bound vectors resident in VMEM.

    Dual of ``_scatter_tile``: per tile row, the lane one-hot times the
    ``(n_win, block)`` bound rows gives every window's bound at each
    slot's lane, and a sublane mask sums out the slot's own window -- one
    nonzero term, so the gather is exact.  Bounds are clamped to the
    ``inf`` sentinel first (beyond it the engines only test ``|b| >=
    inf``), which keeps the bfloat16 pieces finite.  This removes the
    per-round XLA gather entirely: the (T, R, K) gathered-bound tensors
    never exist in HBM."""
    r, k = col.shape
    n_win = lb_ref.shape[-2]
    dtype = lb_ref.dtype
    win_ids = jax.lax.broadcasted_iota(jnp.int32, (n_win, k), 0)
    sides = [_pieces(clamp_to_sentinel(ref[...], inf)) for ref in (lb_ref, ub_ref)]
    rows_l, rows_u = [], []
    for i in range(r):
        w, lane = _window_lane(col[i:i + 1], block)
        sel = win_ids == w
        onehot = _lane_onehot(lane, block, sides[0][0][0].dtype)
        for (pieces, acc), rows in zip(sides, (rows_l, rows_u)):
            g = _onehot_dot(pieces, onehot, 0, acc)
            rows.append(jnp.where(sel, g, 0.0).sum(axis=0, keepdims=True).astype(dtype))
    return jnp.concatenate(rows_l, axis=0), jnp.concatenate(rows_u, axis=0)


def _tile_round(val, col, ii, lb_ref, ub_ref, lhs, rhs, *, int_eps, inf, block,
                straddle=None):
    """One tile's whole round up to the candidates, in VMEM: in-kernel
    bound gather, row aggregates, candidates.  ``straddle`` is an optional
    ``(done, (mf, mc, xf, xc))`` pair: rows with ``done == 0`` take their
    aggregates from the given completed values instead of the tile's own
    (rows split across chunks or slab copies)."""
    lb_g, ub_g = _gather_bounds_tile(col, lb_ref, ub_ref, inf=inf, block=block)
    aggs = tile_row_aggregates(val, lb_g, ub_g, inf)
    if straddle is not None:
        done, given = straddle
        aggs = tuple(jnp.where(done, a, g) for a, g in zip(aggs, given))
    return tile_candidates(
        val, lb_g, ub_g, ii != 0, *aggs, lhs, rhs, int_eps, inf,
    )


def _fused_scatter_kernel(
    val_ref, col_ref, ii_ref, lhs_ref, rhs_ref, lb_ref, ub_ref,
    bl_ref, bu_ref, *, int_eps, inf, block,
):
    """Kernel D: the whole round for chunk-complete rows.  Bound gather,
    activities, residuals, candidates AND the column-wise best-bound
    reduction happen in VMEM; per grid step HBM only streams the tile's
    matrix data (val, col, is_int) -- the bound vectors and the (2, n_pad)
    accumulators stay resident across all steps."""
    _init_accumulators(bl_ref, bu_ref, inf)
    col = col_ref[...].astype(jnp.int32)
    lcand, ucand = _tile_round(
        val_ref[...], col, ii_ref[...].astype(jnp.int32), lb_ref, ub_ref,
        _row(lhs_ref), _row(rhs_ref), int_eps=int_eps, inf=inf, block=block,
    )
    _scatter_tile(lcand, ucand, col, bl_ref, bu_ref, inf=inf, block=block)


def fused_scatter_round_tiles(
    val,
    col,
    is_int_g,
    lhs_g,
    rhs_g,
    lb,
    ub,
    n_pad: int,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Fully fused round: (T, R, K) tiles + (n_pad,) bounds -> (n_pad,)
    best_l / best_u.

    Neither the gathered-bound nor the candidate tensors ever materialize
    in HBM.  Requires max row length <= K (rows complete within their
    chunk) and n_pad % block == 0."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(n_pad, block)
    t, r, k = val.shape
    tile = _tile_spec(r, k)
    row = _row_spec(r)
    vec = _vec_spec(n_pad, block)  # resident every step
    out_shape = [jax.ShapeDtypeStruct((n_pad // block, block), val.dtype)] * 2
    fn = pl.pallas_call(
        functools.partial(_fused_scatter_kernel, int_eps=int_eps, inf=inf, block=block),
        grid=(t,),
        in_specs=[tile, tile, tile, row, row, vec, vec],
        out_specs=[vec, vec],
        out_shape=out_shape,
        name=KERNEL_NAMES["fused_scatter"],
        interpret=interpret,
    )
    best_l, best_u = fn(
        val, col, _int_operand(is_int_g), _rows3(lhs_g), _rows3(rhs_g),
        _lane_rows(lb, block), _lane_rows(ub, block),
    )
    return best_l.reshape(n_pad), best_u.reshape(n_pad)


def _candidates_scatter_kernel(
    val_ref, col_ref, ii_ref,
    rmf_ref, rmc_ref, rxf_ref, rxc_ref, lhs_ref, rhs_ref,
    lb_ref, ub_ref, bl_ref, bu_ref, *, int_eps, inf, block,
):
    """Kernel E: in-kernel bound gather + candidates from completed row
    aggregates + in-VMEM column scatter (rows spanning several chunks;
    aggregates combined outside)."""
    _init_accumulators(bl_ref, bu_ref, inf)
    val = val_ref[...]
    col = col_ref[...].astype(jnp.int32)
    lb_g, ub_g = _gather_bounds_tile(col, lb_ref, ub_ref, inf=inf, block=block)
    lcand, ucand = tile_candidates(
        val, lb_g, ub_g, ii_ref[...].astype(jnp.int32) != 0,
        _row(rmf_ref), _row(rmc_ref), _row(rxf_ref), _row(rxc_ref),
        _row(lhs_ref), _row(rhs_ref), int_eps, inf,
    )
    _scatter_tile(lcand, ucand, col, bl_ref, bu_ref, inf=inf, block=block)


def candidates_scatter_tiles(
    val,
    col,
    is_int_g,
    row_min_fin,
    row_min_cnt,
    row_max_fin,
    row_max_cnt,
    lhs_g,
    rhs_g,
    lb,
    ub,
    n_pad: int,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Candidates + fused column reduction: (T, R, K) tiles + (T, R) row
    aggregates + (n_pad,) bounds -> (n_pad,) x2.  Neither the gathered
    bounds nor the candidates ever materialize in HBM."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(n_pad, block)
    t, r, k = val.shape
    tile = _tile_spec(r, k)
    vec = _vec_spec(n_pad, block)
    out_shape = [jax.ShapeDtypeStruct((n_pad // block, block), val.dtype)] * 2
    fn = pl.pallas_call(
        functools.partial(
            _candidates_scatter_kernel, int_eps=int_eps, inf=inf, block=block
        ),
        grid=(t,),
        in_specs=[tile] * 3 + [_row_spec(r)] * 6 + [vec, vec],
        out_specs=[vec, vec],
        out_shape=out_shape,
        name=KERNEL_NAMES["candidates_scatter"],
        interpret=interpret,
    )
    rows = (row_min_fin, row_min_cnt, row_max_fin, row_max_cnt, lhs_g, rhs_g)
    best_l, best_u = fn(
        val, col, _int_operand(is_int_g), *(_rows3(x) for x in rows),
        _lane_rows(lb, block), _lane_rows(ub, block),
    )
    return best_l.reshape(n_pad), best_u.reshape(n_pad)


# ---------------------------------------------------------------------------
# Kernel P: the packed round -- several short rows share one chunk row
# ---------------------------------------------------------------------------


def _segment_totals(seg, c_min, c_max, min_inf, max_inf):
    """Per-slot row aggregates of one packed ``(1, K)`` chunk row: every
    slot gets the sums over the slots of its own segment (its row).

    Two one-hot products on the MXU with the segment one-hot ``[s, k] =
    seg[k] == s``: the first sums each segment's terms (the float32
    contributions as bfloat16 pieces, the infinity flags as 0/1), the
    second hands each segment's totals back to its slots.  The flags sum
    exactly and the hand-back takes one term per slot, so counts are
    exact and each finite sum is a float32 sum of the row's own terms in
    another order.  Slots of no segment (``seg < 0``, padding) read 0."""
    k = seg.shape[-1]
    p_min, acc = _pieces(c_min)
    p_max, _ = _pieces(c_max)
    dt = p_min[0].dtype
    onehot = _lane_onehot(seg, k, dt)
    flag = lambda b: jnp.where(b, _ONE, _ZERO).astype(dt)
    terms = jnp.concatenate([*p_min, *p_max, flag(min_inf), flag(max_inf)], axis=0)
    tot = _onehot_dot([terms], onehot, 1, acc)
    n = len(p_min)
    # The pieces of one value, summed hi + mid + lo in rows at..at+n-1.
    value = lambda x, at: sum((x[i:i + 1] for i in range(at + 1, at + n)), x[at:at + 1])
    q_min, _ = _pieces(value(tot, 0).astype(c_min.dtype))
    q_max, _ = _pieces(value(tot, n).astype(c_min.dtype))
    back = jnp.concatenate([*q_min, *q_max, tot[2 * n:].astype(dt)], axis=0)
    per = _onehot_dot([back], onehot, 0, acc)
    mf, xf = (value(per, at).astype(c_min.dtype) for at in (0, n))
    mc, xc = (per[i:i + 1].astype(jnp.int32) for i in (2 * n, 2 * n + 1))
    return mf, mc, xf, xc


def tile_segment_aggregates(val, lb_g, ub_g, seg, inf):
    """Per-slot row aggregates ``(mf, mc, xf, xc)`` of a packed ``(R, K)``
    tile, each ``(R, K)``: slot ``k`` of chunk row ``i`` holds the
    aggregates of the row whose segment ``seg[i, k]`` it belongs to."""
    _, _, min_inf, max_inf, c_min, c_max = tile_contributions(val, lb_g, ub_g, inf)
    rows = [
        _segment_totals(seg[i:i + 1], c_min[i:i + 1], c_max[i:i + 1],
                        min_inf[i:i + 1], max_inf[i:i + 1])
        for i in range(val.shape[0])
    ]
    return tuple(jnp.concatenate(parts, axis=0) for parts in zip(*rows))


def _packed_round_kernel(
    val_ref, col_ref, ii_ref, seg_ref, lhs_ref, rhs_ref, lb_ref, ub_ref,
    bl_ref, bu_ref, *, int_eps, inf, block,
):
    """Kernel P: the whole round of a packed tile, whose chunk rows each
    hold several whole rows (contiguous segments of slots, column-
    disjoint within a chunk row).  One in-kernel gather, per-slot row
    aggregates by a segmented reduction, candidates with per-slot sides,
    and the one-hot scatter into the resident accumulators."""
    _init_accumulators(bl_ref, bu_ref, inf)
    val = val_ref[...]
    col = col_ref[...].astype(jnp.int32)
    lb_g, ub_g = _gather_bounds_tile(col, lb_ref, ub_ref, inf=inf, block=block)
    aggs = tile_segment_aggregates(
        val, lb_g, ub_g, seg_ref[...].astype(jnp.int32), inf
    )
    lcand, ucand = tile_candidates(
        val, lb_g, ub_g, ii_ref[...].astype(jnp.int32) != 0, *aggs,
        lhs_ref[...], rhs_ref[...], int_eps, inf,
    )
    _scatter_tile(lcand, ucand, col, bl_ref, bu_ref, inf=inf, block=block)


def packed_round_tiles(
    val,
    col,
    is_int_g,
    seg,
    lhs_s,
    rhs_s,
    lb,
    ub,
    n_pad: int,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Packed round: ``(T, R, K)`` packed tiles (``seg`` the slot's
    segment within its chunk row, -1 on padding; ``lhs_s``/``rhs_s`` its
    row's sides) + ``(n_pad,)`` bounds -> ``(n_pad,)`` best_l / best_u.
    The rows of one chunk row must not share a column (the scatter's
    one-hot placement then holds)."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(n_pad, block)
    t, r, k = val.shape
    tile = _tile_spec(r, k)
    vec = _vec_spec(n_pad, block)
    out_shape = [jax.ShapeDtypeStruct((n_pad // block, block), val.dtype)] * 2
    fn = pl.pallas_call(
        functools.partial(_packed_round_kernel, int_eps=int_eps, inf=inf, block=block),
        grid=(t,),
        in_specs=[tile] * 6 + [vec, vec],
        out_specs=[vec, vec],
        out_shape=out_shape,
        name=KERNEL_NAMES["packed_round"],
        interpret=interpret,
    )
    best_l, best_u = fn(
        val, col, _int_operand(is_int_g), _int_operand(seg), lhs_s, rhs_s,
        _lane_rows(lb, block), _lane_rows(ub, block),
    )
    return best_l.reshape(n_pad), best_u.reshape(n_pad)


# ---------------------------------------------------------------------------
# Kernel F: merge -- fold best bounds into (lb, ub) in place
# ---------------------------------------------------------------------------


def _merge(lb_ref, ub_ref, bl_ref, bu_ref, active, nlb_ref, nub_ref, ch_ref,
           *, eps, inf, outward):
    """The shared ``bounds.apply_updates`` merge of one bound block, gated
    by ``active`` (a scalar or ``(1, 1)`` bool, or None for always):
    inactive blocks pass through untouched and report no change."""
    lb, ub = lb_ref[...], ub_ref[...]
    new_lb, new_ub, changed = bnd.apply_updates(
        lb, ub, bl_ref[...], bu_ref[...], eps, inf, outward
    )
    if active is not None:
        new_lb = jnp.where(active, new_lb, lb)
        new_ub = jnp.where(active, new_ub, ub)
        changed = changed & active
    nlb_ref[...] = new_lb
    nub_ref[...] = new_ub
    ch_ref[...] = changed.astype(jnp.int32).reshape(1, 1)


def _apply_updates_kernel(
    lb_ref, ub_ref, bl_ref, bu_ref, nlb_ref, nub_ref, ch_ref, *, eps, inf, outward
):
    _merge(lb_ref, ub_ref, bl_ref, bu_ref, None, nlb_ref, nub_ref, ch_ref,
           eps=eps, inf=inf, outward=outward)


def apply_updates_tiles(
    lb,
    ub,
    best_l,
    best_u,
    eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    outward: float = 0.0,
):
    """Pallas merge kernel: (n_pad,) bounds x best candidates -> updated
    bounds + changed flag.  The bound buffers are donated
    (``input_output_aliases``) so the update is in place on device.

    Shares ``bounds.apply_updates`` with every other engine, so all paths
    converge to identical fixed points by construction; ``outward`` is the
    fp32-tier safety widening (0.0 = exact fp64 merge)."""
    interpret = resolve_interpret(interpret, lb.dtype)
    (n_pad,) = lb.shape
    planes = [_lane_rows(x) for x in (lb, ub, best_l, best_u)]
    vec = pl.BlockSpec(planes[0].shape, lambda: (_I0, _I0))
    out_shape = [
        jax.ShapeDtypeStruct(planes[0].shape, lb.dtype),
        jax.ShapeDtypeStruct(planes[0].shape, lb.dtype),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
    ]
    fn = pl.pallas_call(
        functools.partial(_apply_updates_kernel, eps=eps, inf=inf, outward=outward),
        in_specs=[vec, vec, vec, vec],
        out_specs=[vec, vec, pl.BlockSpec((1, 1), lambda: (_I0, _I0))],
        out_shape=out_shape,
        input_output_aliases={0: 0, 1: 1},
        name=KERNEL_NAMES["apply_updates"],
        interpret=interpret,
    )
    new_lb, new_ub, changed = fn(*planes)
    return new_lb.reshape(n_pad), new_ub.reshape(n_pad), changed.reshape(()) != 0


# ---------------------------------------------------------------------------
# Batched kernels: flat super-tile grid + per-instance convergence mask
# ---------------------------------------------------------------------------


def _scatter_round_step(
    val_ref, col_ref, ii_ref, lhs_ref, rhs_ref, lb_ref, ub_ref, bl_ref, bu_ref,
    *, int_eps, inf, block, straddle=None,
):
    """Kernel D's per-tile body, shared by every fused-scatter kernel:
    gather, aggregates, candidates and the column scatter into the
    resident accumulators (see :func:`_tile_round` for ``straddle``)."""
    col = col_ref[...].astype(jnp.int32)
    lcand, ucand = _tile_round(
        val_ref[...], col, ii_ref[...].astype(jnp.int32), lb_ref, ub_ref,
        _row(lhs_ref), _row(rhs_ref), int_eps=int_eps, inf=inf, block=block,
        straddle=straddle,
    )
    _scatter_tile(lcand, ucand, col, bl_ref, bu_ref, inf=inf, block=block)


def _batched_fused_scatter_kernel(
    inst_ref, act_ref,
    val_ref, col_ref, ii_ref, lhs_ref, rhs_ref, lb_ref, ub_ref,
    bl_ref, bu_ref, *, int_eps, inf, block,
):
    """Kernel D over a packed batch: the grid walks the flat tile stream
    and the scalar-prefetched ``tile_inst`` map routes every block.

    The batch lives in the leading dimension of the ``(B, n_pad)`` bound
    plane and accumulators; each tile's blocks are selected by its
    instance id (``inst_ref``), so instance boundaries are where the
    resident accumulator block is flushed/reloaded -- tiles of one
    instance are contiguous by construction, giving each instance exactly
    one flush, like the single-instance kernel.  ``act_ref`` is the
    per-instance convergence mask: a converged instance's tiles skip
    gather/compute/scatter entirely (their accumulators stay at the
    reduction identity, so the merge kernel reports them unchanged) --
    finished instances become no-ops instead of blocking the batch.

    The continuous-batching service (``repro.core.service``) reuses this
    same mask as its SLOT-OCCUPANCY mask: an empty or retired slot is
    simply an inactive instance, so its tiles skip all compute and its
    stale accumulator rows stay at the identity.  No separate "empty
    slot" machinery exists in the kernel.
    """
    i = pl.program_id(0)
    inst = inst_ref[i]
    first = (i == 0) | (inst_ref[jnp.maximum(i - 1, 0)] != inst)

    @pl.when(first)
    def _():
        bl_ref[...] = jnp.full_like(bl_ref[...], -inf)
        bu_ref[...] = jnp.full_like(bu_ref[...], inf)

    @pl.when(act_ref[inst] != 0)
    def _():
        _scatter_round_step(
            val_ref, col_ref, ii_ref, lhs_ref, rhs_ref, lb_ref, ub_ref,
            bl_ref, bu_ref, int_eps=int_eps, inf=inf, block=block,
        )


def _plane_shape(bsz, n_pad, block):
    return (bsz, n_pad // block, block)


def batched_fused_scatter_round_tiles(
    val,
    col,
    is_int_g,
    lhs_g,
    rhs_g,
    lb,
    ub,
    tile_inst,
    active,
    n_pad: int,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Fully fused round over a packed batch: ``(T, R, K)`` flat tile
    stream (instance-local columns) + ``(B, n_pad)`` bound plane + ``(T,)``
    tile->instance map + ``(B,)`` active mask -> ``(B, n_pad)`` best_l /
    best_u.

    Same per-instance semantics as :func:`fused_scatter_round_tiles`
    (requires every row of every instance to fit one chunk); inactive
    instances produce identity accumulator rows.  ``active`` doubles as
    the propagation service's slot-occupancy mask -- see
    :func:`batched_occupancy_round_tiles`."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(n_pad, block)
    t, r, k = val.shape
    bsz = lb.shape[0]
    tile = pl.BlockSpec((None, r, k), lambda i, inst, act: (i, _I0, _I0))
    row = pl.BlockSpec((None, 1, r), lambda i, inst, act: (i, _I0, _I0))
    vec = pl.BlockSpec(
        (None, n_pad // block, block), lambda i, inst, act: (inst[i], _I0, _I0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t,),
        in_specs=[tile, tile, tile, row, row, vec, vec],
        out_specs=[vec, vec],
    )
    out_shape = [jax.ShapeDtypeStruct(_plane_shape(bsz, n_pad, block), val.dtype)] * 2
    fn = pl.pallas_call(
        functools.partial(
            _batched_fused_scatter_kernel, int_eps=int_eps, inf=inf, block=block
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=KERNEL_NAMES["batched_fused_scatter"],
        interpret=interpret,
    )
    best_l, best_u = fn(
        tile_inst.astype(jnp.int32), active.astype(jnp.int32),
        val, col, _int_operand(is_int_g), _rows3(lhs_g), _rows3(rhs_g),
        _lane_rows(lb, block), _lane_rows(ub, block),
    )
    return best_l.reshape(bsz, n_pad), best_u.reshape(bsz, n_pad)


def batched_occupancy_round_tiles(
    val,
    col,
    is_int_g,
    lhs_g,
    rhs_g,
    lb,
    ub,
    tile_inst,
    occupied,
    n_pad: int,
    eps: float,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
    outward: float = 0.0,
):
    """One full occupancy-masked round (candidates + scatter + merge) over a
    slot-resident super-tile: ``(S*T, R, K)`` tile stream, ``(S, n_pad)``
    bound plane, ``(S,)`` ``occupied`` mask -> updated bounds + per-slot
    ``changed`` flags.

    This is the round the continuous-batching service runs on its kernel
    path.  ``occupied`` is the per-slot occupancy mask (an alias of the
    batched kernels' ``active`` mask): free or retired slots cost no
    gather/compute/scatter in the round kernel and pass through the merge
    untouched, so admission and retirement never have to compact or
    re-shape the resident state.  Requires the fused-path contract (every
    row fits one chunk of width ``block``); multichunk buckets use the jnp
    reference round instead."""
    best_l, best_u = batched_fused_scatter_round_tiles(
        val, col, is_int_g, lhs_g, rhs_g, lb, ub, tile_inst, occupied,
        n_pad, int_eps, inf, interpret, block,
    )
    return apply_updates_batch_tiles(
        lb, ub, best_l, best_u, occupied, eps, inf, interpret, outward
    )


# ---------------------------------------------------------------------------
# Node-batch kernel: one matrix, many bound planes (tree-search shape)
# ---------------------------------------------------------------------------


def _node_fused_scatter_kernel(
    act_ref,
    val_ref, col_ref, ii_ref, lhs_ref, rhs_ref, lb_ref, ub_ref,
    bl_ref, bu_ref, *, int_eps, inf, block,
):
    """Kernel D over a node batch: B bound planes of ONE instance share the
    matrix tiles.

    The grid is ``(B, T)`` with the tile axis minor, so for each node the
    matrix tiles stream once while that node's lane-dense bound block and
    accumulator rows stay VMEM-resident across its whole tile sweep --
    the matrix is revisited per node from on-device HBM, never re-packed or
    re-uploaded from the host.  ``act_ref`` is the per-node convergence
    mask: a converged (or pruned-infeasible) node's grid steps skip
    gather/compute/scatter entirely, leaving its accumulators at the
    reduction identity so the batched merge reports it unchanged.
    """
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        bl_ref[...] = jnp.full_like(bl_ref[...], -inf)
        bu_ref[...] = jnp.full_like(bu_ref[...], inf)

    @pl.when(act_ref[b] != 0)
    def _():
        _scatter_round_step(
            val_ref, col_ref, ii_ref, lhs_ref, rhs_ref, lb_ref, ub_ref,
            bl_ref, bu_ref, int_eps=int_eps, inf=inf, block=block,
        )


def node_fused_scatter_round_tiles(
    val,
    col,
    is_int_g,
    lhs_g,
    rhs_g,
    lb,
    ub,
    active,
    n_pad: int,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Fully fused round over a node batch: ``(T, R, K)`` tiles of ONE
    instance, broadcast across the node axis, + ``(B, n_pad)`` per-node
    bound planes + ``(B,)`` active mask -> ``(B, n_pad)`` best_l / best_u.

    Per node the arithmetic is exactly :func:`fused_scatter_round_tiles`
    (requires every row to fit one chunk and ``n_pad % block == 0``);
    inactive nodes produce identity accumulator rows."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(n_pad, block)
    t, r, k = val.shape
    bsz = lb.shape[0]
    tile = pl.BlockSpec((None, r, k), lambda b, i, act: (i, _I0, _I0))
    row = pl.BlockSpec((None, 1, r), lambda b, i, act: (i, _I0, _I0))
    vec = pl.BlockSpec((None, n_pad // block, block), lambda b, i, act: (b, _I0, _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz, t),
        in_specs=[tile, tile, tile, row, row, vec, vec],
        out_specs=[vec, vec],
    )
    out_shape = [jax.ShapeDtypeStruct(_plane_shape(bsz, n_pad, block), val.dtype)] * 2
    fn = pl.pallas_call(
        functools.partial(
            _node_fused_scatter_kernel, int_eps=int_eps, inf=inf, block=block
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=KERNEL_NAMES["node_fused_scatter"],
        interpret=interpret,
    )
    best_l, best_u = fn(
        active.astype(jnp.int32),
        val, col, _int_operand(is_int_g), _rows3(lhs_g), _rows3(rhs_g),
        _lane_rows(lb, block), _lane_rows(ub, block),
    )
    return best_l.reshape(bsz, n_pad), best_u.reshape(bsz, n_pad)


# ---------------------------------------------------------------------------
# Column-slab partitioned kernels: VMEM-exceeding column spaces
# ---------------------------------------------------------------------------
#
# When ``n_pad`` outgrows the VMEM accumulator budget (``SCATTER_MAX_NPAD``)
# the resident bound/accumulator blocks of the fused kernels no longer fit
# on chip.  The partitioned engine keeps the fused dataflow by splitting
# the padded column space into ``slab``-wide windows and the CHUNK stream
# into per-slab copies grouped by ``(instance, slab)`` window
# (``ops.build_slab_partition``): a copy keeps only the nonzeros whose
# columns fall in its slab, so its in-kernel gather and scatter touch
# exactly one ``S``-column bound window -- VMEM-resident across the
# window's whole tile run.  Bound planes travel as ``(B, n_slabs, S //
# LANE, LANE)`` (:func:`_slab_planes`), one block per window.
#
# The round kernels walk a 2D ``(run, tile)`` grid: the major axis is one
# step per ``(instance, slab)`` window (``run_*`` scalar-prefetch maps from
# the partition), the minor axis sweeps the window's copy tiles, padded to
# the longest run with idempotent revisits of the run's last tile.  The run
# axis carries no cross-step state -- the best-bound accumulators live in
# VMEM *scratch* re-initialized at each run's first step -- so it is
# declared ``parallel``: independent windows' reductions may run
# concurrently (on multiple cores) while each window's sweep stays ordered.
# Because every copy tile (including the duplicated straddling-tile copies)
# enters through BlockSpec index maps, Mosaic's grid pipeline
# double-buffers the HBM->VMEM copy stream automatically: step ``j+1``'s
# tile DMAs while step ``j`` computes, so duplication overlaps the
# reduction instead of preceding it.
#
# Rows whose nonzeros are split across copies cannot finish their activity
# aggregate inside any one copy.  Those STRADDLE rows ride a small
# sub-stream (``a_*``): ``*_slab_partials_tiles`` emits their per-copy
# partials, a tiny XLA segment sum completes them into a table, and the
# round kernel selects per row between its local in-register aggregate
# (``row_done == 1``, the vast majority) and the table value.  The round
# kernel then computes candidates, scatters them into the scratch
# accumulators, AND merges the window's bounds in place at the run's last
# step -- no partial best-bound plane ever round-trips through HBM.  The
# jnp oracle is ``ref.partitioned_round_ref`` over the SAME partition
# arrays, which the kernels match bitwise.


def _dims(*semantics: str):
    """Mosaic ``compiler_params`` declaring the grid's dimension semantics
    (the run/window axes ``parallel``, sweep axes ``arbitrary``)."""
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _slab_planes(x, slab: int, block: int):
    """``(B, n_slabs * S)`` -> ``(B, n_slabs, S // block, block)``: one
    block per slab window, legal for any lane-multiple ``S``."""
    bsz, n = x.shape
    return x.reshape(bsz, n // slab, slab // block, block)


def _run_tile_index(j, st, ln, rr):
    """Copy-tile index of run ``rr`` at sweep step ``j``, clamped to the
    run's last tile: steps padding a short run to ``max_run_len`` revisit
    that tile (idempotent recompute) instead of reading out of range."""
    return st[rr] + jnp.minimum(j, ln[rr] - 1)


def _write_zero_partials(refs):
    for ref in refs:
        ref[...] = jnp.zeros_like(ref[...])


def _slab_partials_body(act, val_ref, col_ref, lb_ref, ub_ref, out_refs, *, inf, block):
    """One copy tile's activity partials from its window's resident bounds
    (zeros for inactive instances / nodes)."""

    @pl.when(act)
    def _():
        col = col_ref[...].astype(jnp.int32)
        lb_g, ub_g = _gather_bounds_tile(col, lb_ref, ub_ref, inf=inf, block=block)
        _write_partials(out_refs, tile_row_aggregates(val_ref[...], lb_g, ub_g, inf))

    @pl.when(~act)
    def _():
        _write_zero_partials(out_refs)


def _batched_slab_partials_kernel(
    st_ref, ln_ref, ri_ref, rs_ref, act_ref,
    val_ref, col_ref, lb_ref, ub_ref,
    mf_ref, mc_ref, xf_ref, xc_ref, *, inf, block,
):
    """Straddle-partials kernel over a slab-partitioned (optionally
    batched) sub-stream on the 2D ``(run, tile)`` grid.

    Each grid step computes ONE copy tile's per-row activity partials with
    the in-kernel gather from its window's resident bound block (routed
    by the prefetched run maps).  Padded steps of short runs recompute the
    run's last tile -- same inputs, same outputs, harmless.  Copies of
    converged instances write zero partials and skip the gather.
    """
    act = act_ref[ri_ref[pl.program_id(0)]] != 0
    _slab_partials_body(
        act, val_ref, col_ref, lb_ref, ub_ref, (mf_ref, mc_ref, xf_ref, xc_ref),
        inf=inf, block=block,
    )


def batched_slab_partials_tiles(
    val,
    col_s,
    run_start,
    run_len,
    run_inst,
    run_slab,
    active,
    lb,
    ub,
    slab: int,
    max_run_len: int,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Per-copy activity partials of a slab-partitioned sub-stream on the
    slab-parallel 2D grid.

    ``(Ta, R, K)`` slab-masked copies (slab-local columns) + the run maps
    (one entry per populated ``(instance, slab)`` window) + ``(B,
    n_pad_part)`` bound planes + ``(B,)`` active mask -> 4 x ``(Ta, R)``
    partials.  Single-instance callers pass ``B == 1`` planes with
    ``run_inst == 0``.  The gathered bounds never exist in HBM; each window
    reads only its resident bound block, and independent windows are
    declared parallel."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(slab, block, "slab")
    t, r, k = val.shape
    n_runs = run_start.shape[0]

    def copy(rr, j, st, ln, *_):
        return _run_tile_index(j, st, ln, rr)

    tile = pl.BlockSpec((None, r, k), lambda *a: (copy(*a), _I0, _I0))
    out_tile = pl.BlockSpec((None, 1, r), lambda *a: (copy(*a), _I0, _I0))
    vec = pl.BlockSpec(
        (None, None, slab // block, block),
        lambda rr, j, st, ln, ri, rs, act: (ri[rr], rs[rr], _I0, _I0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_runs, max_run_len),
        in_specs=[tile, tile, vec, vec],
        out_specs=[out_tile] * 4,
    )
    fn = pl.pallas_call(
        functools.partial(_batched_slab_partials_kernel, inf=inf, block=block),
        grid_spec=grid_spec,
        out_shape=_partials_shapes((t,), r, val.dtype),
        name=KERNEL_NAMES["slab_partials"],
        interpret=interpret,
        compiler_params=_dims("parallel", "arbitrary"),
    )
    out = fn(
        run_start.astype(jnp.int32), run_len.astype(jnp.int32),
        run_inst.astype(jnp.int32), run_slab.astype(jnp.int32),
        active.astype(jnp.int32),
        val, col_s, _slab_planes(lb, slab, block), _slab_planes(ub, slab, block),
    )
    return tuple(x.reshape(t, r) for x in out)


def _slab_round_body(
    act, ln, j,
    val_ref, col_ref, ii_ref, done_ref, s_refs, lhs_ref, rhs_ref,
    lb_ref, ub_ref, nlb_ref, nub_ref, ch_ref, acc_l, acc_u,
    *, eps, int_eps, inf, outward, block,
):
    """One step of a run's sweep in the slab round kernels: (1) the first
    step initializes the window's best-bound accumulators, held in VMEM
    *scratch* so no partial plane exists in HBM; (2) every real step of an
    active run gathers, aggregates -- taking the straddle rows'
    (``row_done == 0``) aggregates from the prefetched table values --
    computes candidates and scatters them into the scratch; (3) the run's
    LAST real step merges the accumulators into the window's bounds
    (``bounds.apply_updates`` semantics) and emits the run's changed flag.
    Padded steps leave the merged block untouched; inactive runs pass
    bounds through unchanged."""

    @pl.when(j == 0)
    def _():
        acc_l[...] = jnp.full_like(acc_l[...], -inf)
        acc_u[...] = jnp.full_like(acc_u[...], inf)

    @pl.when((j < ln) & act)
    def _():
        straddle = (_row(done_ref) != 0, tuple(_row(s) for s in s_refs))
        _scatter_round_step(
            val_ref, col_ref, ii_ref, lhs_ref, rhs_ref, lb_ref, ub_ref,
            acc_l, acc_u, int_eps=int_eps, inf=inf, block=block,
            straddle=straddle,
        )

    @pl.when(j == ln - 1)
    def _():
        _merge(lb_ref, ub_ref, acc_l, acc_u, act, nlb_ref, nub_ref, ch_ref,
               eps=eps, inf=inf, outward=outward)


def _batched_slab_round_kernel(
    st_ref, ln_ref, ri_ref, rs_ref, act_ref,
    val_ref, col_ref, ii_ref, done_ref,
    smf_ref, smc_ref, sxf_ref, sxc_ref,
    lhs_ref, rhs_ref, lb_ref, ub_ref,
    nlb_ref, nub_ref, ch_ref,
    acc_l, acc_u, *, eps, int_eps, inf, outward, block,
):
    """The fused slab-parallel round kernel over a partitioned (optionally
    batched) stream on the 2D ``(run, tile)`` grid; one run == one
    ``(instance, slab)`` window (sweep protocol: :func:`_slab_round_body`)."""
    rr = pl.program_id(0)
    _slab_round_body(
        act_ref[ri_ref[rr]] != 0, ln_ref[rr], pl.program_id(1),
        val_ref, col_ref, ii_ref, done_ref, (smf_ref, smc_ref, sxf_ref, sxc_ref),
        lhs_ref, rhs_ref, lb_ref, ub_ref, nlb_ref, nub_ref, ch_ref, acc_l, acc_u,
        eps=eps, int_eps=int_eps, inf=inf, outward=outward, block=block,
    )


def batched_slab_round_tiles(
    val,
    col_s,
    is_int_g,
    row_done,
    str_min_fin,
    str_min_cnt,
    str_max_fin,
    str_max_cnt,
    lhs_g,
    rhs_g,
    run_start,
    run_len,
    run_inst,
    run_slab,
    active,
    lb,
    ub,
    slab: int,
    max_run_len: int,
    eps: float,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
    outward: float = 0.0,
):
    """The fused slab-parallel round over a partitioned stream: candidates,
    per-slab scatter AND the bound merge in ONE kernel on the 2D ``(run,
    tile)`` grid.

    ``(T'', R, K)`` slab-masked copies + ``(T'', R)`` ``row_done`` select
    mask and gathered straddle aggregates (``str_*``; any values where
    ``row_done == 1``) + the run maps (exactly one run per ``(instance,
    slab)`` window) + ``(B, n_pad_part)`` bound planes + ``(B,)`` active
    mask -> updated ``(B, n_pad_part)`` bounds and ``(n_runs,)`` per-run
    changed flags (OR-combine per instance outside).  Best-bound
    accumulators live in VMEM scratch re-initialized per run, so the run
    axis is parallel and no partial bound plane round-trips through HBM.
    The bound buffers are NOT aliased in place (the window merge writes a
    fresh plane); single-instance callers pass ``B == 1`` with
    ``run_inst == 0``.  Shares ``bounds.apply_updates`` semantics with
    every other engine."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(slab, block, "slab")
    t, r, k = val.shape
    bsz, n_pad_part = lb.shape
    n_runs = run_start.shape[0]
    dtype = val.dtype

    def copy(rr, j, st, ln, *_):
        return _run_tile_index(j, st, ln, rr)

    tile = pl.BlockSpec((None, r, k), lambda *a: (copy(*a), _I0, _I0))
    row = pl.BlockSpec((None, 1, r), lambda *a: (copy(*a), _I0, _I0))
    vec = pl.BlockSpec(
        (None, None, slab // block, block),
        lambda rr, j, st, ln, ri, rs, act: (ri[rr], rs[rr], _I0, _I0),
    )
    flag = pl.BlockSpec((None, 1, 1), lambda rr, *_: (rr, _I0, _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_runs, max_run_len),
        in_specs=[tile] * 3 + [row] * 7 + [vec, vec],
        out_specs=[vec, vec, flag],
        scratch_shapes=[pltpu.VMEM((slab // block, block), dtype)] * 2,
    )
    planes = _slab_planes(lb, slab, block).shape
    out_shape = [
        jax.ShapeDtypeStruct(planes, dtype),
        jax.ShapeDtypeStruct(planes, dtype),
        jax.ShapeDtypeStruct((n_runs, 1, 1), jnp.int32),
    ]
    fn = pl.pallas_call(
        functools.partial(
            _batched_slab_round_kernel, eps=eps, int_eps=int_eps, inf=inf,
            outward=outward, block=block,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=KERNEL_NAMES["slab_round"],
        interpret=interpret,
        compiler_params=_dims("parallel", "arbitrary"),
    )
    rows = (row_done, str_min_fin, str_min_cnt, str_max_fin, str_max_cnt, lhs_g, rhs_g)
    new_lb, new_ub, ch = fn(
        run_start.astype(jnp.int32), run_len.astype(jnp.int32),
        run_inst.astype(jnp.int32), run_slab.astype(jnp.int32),
        active.astype(jnp.int32),
        val, col_s, _int_operand(is_int_g), *(_rows3(x) for x in rows),
        _slab_planes(lb, slab, block), _slab_planes(ub, slab, block),
    )
    return (
        new_lb.reshape(bsz, n_pad_part), new_ub.reshape(bsz, n_pad_part),
        ch.reshape(n_runs),
    )


def _node_slab_partials_kernel(
    st_ref, ln_ref, rs_ref, act_ref,
    val_ref, col_ref, lb_ref, ub_ref,
    mf_ref, mc_ref, xf_ref, xc_ref, *, inf, block,
):
    """Straddle-partials kernel over a node batch: ONE instance's
    sub-stream swept per node on a ``(B, run, tile)`` grid with per-node
    slab windows.  Inactive nodes write zero partials."""
    act = act_ref[pl.program_id(0)] != 0
    _slab_partials_body(
        act, val_ref, col_ref, lb_ref, ub_ref, (mf_ref, mc_ref, xf_ref, xc_ref),
        inf=inf, block=block,
    )


def node_slab_partials_tiles(
    val,
    col_s,
    run_start,
    run_len,
    run_slab,
    active,
    lb,
    ub,
    slab: int,
    max_run_len: int,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
):
    """Per-copy, per-node activity partials of ONE instance's straddle
    sub-stream: ``(Ta, R, K)`` slab-masked copies broadcast across the node
    axis + ``(B, n_pad_part)`` per-node bound planes -> 4 x ``(B, Ta, R)``
    partials (completed outside by a per-node segment sum over
    ``a_slot``)."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(slab, block, "slab")
    t, r, k = val.shape
    bsz = lb.shape[0]
    n_runs = run_start.shape[0]

    def copy(b, rr, j, st, ln, *_):
        return _run_tile_index(j, st, ln, rr)

    tile = pl.BlockSpec((None, r, k), lambda *a: (copy(*a), _I0, _I0))
    out_tile = pl.BlockSpec(
        (None, None, 1, r), lambda b, *a: (b, copy(b, *a), _I0, _I0)
    )
    vec = pl.BlockSpec(
        (None, None, slab // block, block),
        lambda b, rr, j, st, ln, rs, act: (b, rs[rr], _I0, _I0),
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bsz, n_runs, max_run_len),
        in_specs=[tile, tile, vec, vec],
        out_specs=[out_tile] * 4,
    )
    fn = pl.pallas_call(
        functools.partial(_node_slab_partials_kernel, inf=inf, block=block),
        grid_spec=grid_spec,
        out_shape=_partials_shapes((bsz, t), r, val.dtype),
        name=KERNEL_NAMES["node_slab_partials"],
        interpret=interpret,
        compiler_params=_dims("parallel", "parallel", "arbitrary"),
    )
    out = fn(
        run_start.astype(jnp.int32), run_len.astype(jnp.int32),
        run_slab.astype(jnp.int32), active.astype(jnp.int32),
        val, col_s, _slab_planes(lb, slab, block), _slab_planes(ub, slab, block),
    )
    return tuple(x.reshape(bsz, t, r) for x in out)


def _node_slab_round_kernel(
    st_ref, ln_ref, rs_ref, act_ref,
    val_ref, col_ref, ii_ref, done_ref,
    smf_ref, smc_ref, sxf_ref, sxc_ref,
    lhs_ref, rhs_ref, lb_ref, ub_ref,
    nlb_ref, nub_ref, ch_ref,
    acc_l, acc_u, *, eps, int_eps, inf, outward, block,
):
    """The fused slab-parallel round kernel over a node batch: ONE
    instance's copies against B bound planes on a ``(B, run, tile)`` grid.
    Same sweep protocol as the batched variant (:func:`_slab_round_body`),
    with per-node bound windows, per-node straddle aggregates and per-node
    changed flags."""
    rr = pl.program_id(1)
    _slab_round_body(
        act_ref[pl.program_id(0)] != 0, ln_ref[rr], pl.program_id(2),
        val_ref, col_ref, ii_ref, done_ref, (smf_ref, smc_ref, sxf_ref, sxc_ref),
        lhs_ref, rhs_ref, lb_ref, ub_ref, nlb_ref, nub_ref, ch_ref, acc_l, acc_u,
        eps=eps, int_eps=int_eps, inf=inf, outward=outward, block=block,
    )


def node_slab_round_tiles(
    val,
    col_s,
    is_int_g,
    row_done,
    str_min_fin,
    str_min_cnt,
    str_max_fin,
    str_max_cnt,
    lhs_g,
    rhs_g,
    run_start,
    run_len,
    run_slab,
    active,
    lb,
    ub,
    slab: int,
    max_run_len: int,
    eps: float,
    int_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    block: int = LANE,
    outward: float = 0.0,
):
    """The fused slab-parallel round over a node batch: ``(T'', R, K)``
    slab-masked copies of ONE instance + ``(B, T'', R)`` per-node gathered
    straddle aggregates (``str_*``) + shared ``(T'', R)`` ``row_done`` /
    sides + ``(B, n_pad_part)`` per-node bound planes + ``(B,)`` active
    mask -> updated ``(B, n_pad_part)`` bounds and ``(B, n_runs)`` changed
    flags (OR-combine per node outside).  Per node the arithmetic is
    exactly the batched variant at ``B == 1``; inactive nodes pass their
    bounds through unchanged."""
    interpret = resolve_interpret(interpret, val.dtype)
    _check_windows(slab, block, "slab")
    t, r, k = val.shape
    bsz, n_pad_part = lb.shape
    n_runs = run_start.shape[0]
    dtype = val.dtype

    def copy(b, rr, j, st, ln, *_):
        return _run_tile_index(j, st, ln, rr)

    tile = pl.BlockSpec((None, r, k), lambda *a: (copy(*a), _I0, _I0))
    row = pl.BlockSpec((None, 1, r), lambda *a: (copy(*a), _I0, _I0))
    node_row = pl.BlockSpec(
        (None, None, 1, r), lambda b, *a: (b, copy(b, *a), _I0, _I0)
    )
    vec = pl.BlockSpec(
        (None, None, slab // block, block),
        lambda b, rr, j, st, ln, rs, act: (b, rs[rr], _I0, _I0),
    )
    flag = pl.BlockSpec((None, None, 1, 1), lambda b, rr, *_: (b, rr, _I0, _I0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bsz, n_runs, max_run_len),
        in_specs=[tile] * 3 + [row] + [node_row] * 4 + [row, row, vec, vec],
        out_specs=[vec, vec, flag],
        scratch_shapes=[pltpu.VMEM((slab // block, block), dtype)] * 2,
    )
    planes = _slab_planes(lb, slab, block).shape
    out_shape = [
        jax.ShapeDtypeStruct(planes, dtype),
        jax.ShapeDtypeStruct(planes, dtype),
        jax.ShapeDtypeStruct((bsz, n_runs, 1, 1), jnp.int32),
    ]
    fn = pl.pallas_call(
        functools.partial(
            _node_slab_round_kernel, eps=eps, int_eps=int_eps, inf=inf,
            outward=outward, block=block,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        name=KERNEL_NAMES["node_slab_round"],
        interpret=interpret,
        compiler_params=_dims("parallel", "parallel", "arbitrary"),
    )
    strad = (str_min_fin, str_min_cnt, str_max_fin, str_max_cnt)
    new_lb, new_ub, ch = fn(
        run_start.astype(jnp.int32), run_len.astype(jnp.int32),
        run_slab.astype(jnp.int32), active.astype(jnp.int32),
        val, col_s, _int_operand(is_int_g), _rows3(row_done),
        *(_rows3(x) for x in strad), _rows3(lhs_g), _rows3(rhs_g),
        _slab_planes(lb, slab, block), _slab_planes(ub, slab, block),
    )
    return (
        new_lb.reshape(bsz, n_pad_part), new_ub.reshape(bsz, n_pad_part),
        ch.reshape(bsz, n_runs),
    )


def _apply_updates_gated_kernel(
    lb_ref, ub_ref, bl_ref, bu_ref, act_ref, nlb_ref, nub_ref, ch_ref,
    *, eps, inf, outward
):
    _merge(lb_ref, ub_ref, bl_ref, bu_ref, act_ref[...] != 0,
           nlb_ref, nub_ref, ch_ref, eps=eps, inf=inf, outward=outward)


def apply_updates_slab_tiles(
    lb,
    ub,
    best_l,
    best_u,
    active,
    slab: int,
    eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    outward: float = 0.0,
):
    """Slab-gridded merge kernel for VMEM-exceeding column spaces:
    ``(B, n_pad_part)`` bounds x best candidates -> updated bounds +
    ``(B,)`` per-instance changed flags.

    The grid walks ``(instance, slab)`` so only one slab window is ever
    VMEM-resident; per-window changed flags are OR-combined outside (the
    cheap cross-slab combine).  Every grid step touches a DISJOINT window
    of the planes (no carried accumulator), so both axes are declared
    ``parallel`` like the slab round kernel -- Mosaic may run the window
    merges in any order or concurrently.  The bound buffers are donated
    (``input_output_aliases``); inactive instances pass through untouched.
    Shares ``bounds.apply_updates`` semantics with every other engine."""
    interpret = resolve_interpret(interpret, lb.dtype)
    bsz, n_pad_part = lb.shape
    if n_pad_part % slab:
        raise ValueError(f"n_pad_part={n_pad_part} must be a multiple of slab={slab}")
    _check_windows(slab, LANE, "slab")
    n_slabs = n_pad_part // slab
    planes = [_slab_planes(x, slab, LANE) for x in (lb, ub, best_l, best_u)]
    vec = pl.BlockSpec((None, None, slab // LANE, LANE), lambda b, s: (b, s, _I0, _I0))
    flag_in = pl.BlockSpec((None, 1, 1), lambda b, s: (b, _I0, _I0))
    flag_out = pl.BlockSpec((None, None, 1, 1), lambda b, s: (b, s, _I0, _I0))
    out_shape = [
        jax.ShapeDtypeStruct(planes[0].shape, lb.dtype),
        jax.ShapeDtypeStruct(planes[0].shape, lb.dtype),
        jax.ShapeDtypeStruct((bsz, n_slabs, 1, 1), jnp.int32),
    ]
    fn = pl.pallas_call(
        functools.partial(
            _apply_updates_gated_kernel, eps=eps, inf=inf, outward=outward
        ),
        grid=(bsz, n_slabs),
        in_specs=[vec, vec, vec, vec, flag_in],
        out_specs=[vec, vec, flag_out],
        out_shape=out_shape,
        input_output_aliases={0: 0, 1: 1},
        name=KERNEL_NAMES["apply_updates_gated"],
        interpret=interpret,
        compiler_params=_dims("parallel", "parallel"),
    )
    new_lb, new_ub, changed = fn(
        *planes, active.astype(jnp.int32).reshape(bsz, 1, 1)
    )
    return (
        new_lb.reshape(bsz, n_pad_part), new_ub.reshape(bsz, n_pad_part),
        jnp.any(changed.reshape(bsz, n_slabs) != 0, axis=1),
    )


def apply_updates_batch_tiles(
    lb,
    ub,
    best_l,
    best_u,
    active,
    eps: float,
    inf: float = INF,
    interpret: bool | None = None,
    outward: float = 0.0,
):
    """Batched merge kernel: ``(B, n_pad)`` bounds x best candidates ->
    updated bounds + ``(B,)`` per-instance changed flags.  The bound buffers
    are donated (``input_output_aliases``); inactive instances pass through
    untouched and report unchanged.  Like the round kernel, the ``active``
    gate doubles as the service's slot-occupancy mask: retired/empty slots
    keep their last bounds bit-for-bit and never flag a change."""
    interpret = resolve_interpret(interpret, lb.dtype)
    bsz, n_pad = lb.shape
    planes = [_lane_rows(x) for x in (lb, ub, best_l, best_u)]
    vec = pl.BlockSpec((None,) + planes[0].shape[1:], lambda b: (b, _I0, _I0))
    flag = pl.BlockSpec((None, 1, 1), lambda b: (b, _I0, _I0))
    out_shape = [
        jax.ShapeDtypeStruct(planes[0].shape, lb.dtype),
        jax.ShapeDtypeStruct(planes[0].shape, lb.dtype),
        jax.ShapeDtypeStruct((bsz, 1, 1), jnp.int32),
    ]
    fn = pl.pallas_call(
        functools.partial(
            _apply_updates_gated_kernel, eps=eps, inf=inf, outward=outward
        ),
        grid=(bsz,),
        in_specs=[vec, vec, vec, vec, flag],
        out_specs=[vec, vec, flag],
        out_shape=out_shape,
        input_output_aliases={0: 0, 1: 1},
        name=KERNEL_NAMES["apply_updates_gated"],
        interpret=interpret,
    )
    new_lb, new_ub, changed = fn(
        *planes, active.astype(jnp.int32).reshape(bsz, 1, 1)
    )
    return (
        new_lb.reshape(bsz, n_pad), new_ub.reshape(bsz, n_pad),
        changed.reshape(bsz) != 0,
    )


def _node_objective_kernel(
    lb_ref, ub_ref, c_ref, ii_ref, valid_ref, obj_ref, fix_ref, cr_ref,
    *, feas_eps, inf
):
    lb, ub = lb_ref[...], ub_ref[...]
    c = c_ref[...]
    ii = ii_ref[...] != 0
    valid = valid_ref[...] != 0
    contrib = jnp.where(c > 0, c * lb, c * ub)
    contrib = jnp.where(valid & (c != 0), contrib, scalar_like(0.0, c))
    unbounded = valid & (((c > 0) & (lb <= -inf)) | ((c < 0) & (ub >= inf)))
    obj = jnp.where(any_true(unbounded), scalar_like(-inf, c), jnp.sum(contrib))
    fixed = ~any_true(valid & ii & ~(ub - lb <= 0.5))
    crossed = any_true((lb > ub + feas_eps) & valid)
    obj_ref[...] = obj.reshape(1, 1)
    fix_ref[...] = fixed.astype(jnp.int32).reshape(1, 1)
    cr_ref[...] = crossed.astype(jnp.int32).reshape(1, 1)


def node_objective_tiles(
    lb,
    ub,
    c,
    is_int,
    valid,
    feas_eps: float,
    inf: float = INF,
    interpret: bool | None = None,
):
    """Per-node objective bound + leaf/prune predicates, one kernel pass.

    The solver's post-propagation scan: grid ``(B,)``, each step reads one
    node's lane-dense bound rows plus the shared objective / integrality /
    validity vectors (their blocks constant across the grid, so the
    ``(n_pad,)`` constants stay VMEM-resident across the sweep) and writes
    three ``(1, 1)`` scalars -- the domain-relaxation objective bound, the
    all-integers-fixed flag and the crossed-domain flag.  Exact semantics
    (sentinel handling, tie behaviour) are defined by
    ``ref.node_objective_ref``; returns ``(obj, fixed, crossed)`` as
    ``(B,)`` arrays with the flags as bools."""
    interpret = resolve_interpret(interpret, lb.dtype)
    bsz, n_pad = lb.shape
    dtype = lb.dtype
    planes = [_lane_rows(x) for x in (lb, ub)]
    shared = [
        _lane_rows(x) for x in
        (jnp.asarray(c, dtype), _int_operand(is_int), _int_operand(valid))
    ]
    rows = planes[0].shape[1:]
    vec = pl.BlockSpec((None,) + rows, lambda b: (b, _I0, _I0))
    const = pl.BlockSpec(rows, lambda b: (_I0, _I0))
    flag = pl.BlockSpec((None, 1, 1), lambda b: (b, _I0, _I0))
    out_shape = [
        jax.ShapeDtypeStruct((bsz, 1, 1), dtype),
        jax.ShapeDtypeStruct((bsz, 1, 1), jnp.int32),
        jax.ShapeDtypeStruct((bsz, 1, 1), jnp.int32),
    ]
    fn = pl.pallas_call(
        functools.partial(_node_objective_kernel, feas_eps=feas_eps, inf=inf),
        grid=(bsz,),
        in_specs=[vec, vec, const, const, const],
        out_specs=[flag, flag, flag],
        out_shape=out_shape,
        name=KERNEL_NAMES["node_objective"],
        interpret=interpret,
    )
    obj, fixed, crossed = fn(*planes, *shared)
    return obj.reshape(bsz), fixed.reshape(bsz) != 0, crossed.reshape(bsz) != 0
