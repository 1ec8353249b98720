"""Jit'd public wrappers around the Pallas kernels: a complete block-ELL
propagation engine (kernels + column reduction + bound update).

This is the kernel-backed sibling of ``core.propagator``; both share the
bound-update logic so they converge to identical fixed points.

Engine anatomy (see README "fused-scatter dataflow"):

  * ``prepare_block_ell`` -- one-time, cached per instance: block-ELL
    conversion, device transfer, and the *round-constant* gathers
    (``is_int[col]``, ``lhs1[chunk_row]``, ``rhs1[chunk_row]``) that the seed
    engine recomputed every round.
  * ``scatter="fused"`` -- the fully fused round: one Pallas kernel gathers
    the bounds in-kernel from the VMEM-resident (n_pad,) vectors, computes
    activities and candidates, AND does the column-wise best-bound
    reduction into ``(2, n_pad)`` accumulators that stay in VMEM across all
    grid steps; a small merge kernel then folds them into (lb, ub) in place
    (``input_output_aliases``).  NO nnz-shaped tensor -- neither gathered
    bounds nor candidates -- is produced in HBM during a round.  Under
    ``fused="auto"`` short rows are packed into shared chunk rows first
    (:class:`PackedStream`): the kernel's gather and scatter cost the same
    per chunk row however few of its slots are used.
  * ``scatter="partitioned"`` -- the column-slab engine for instances whose
    ``n_pad`` exceeds the VMEM accumulator budget: the padded column space
    is split into balanced slabs (``default_slab_width``, capped at
    ``SLAB_NPAD``, overridable per call via ``slab=``), the CHUNK stream
    into per-slab masked copies grouped by ``(instance, slab)`` window
    (``build_slab_partition``, cached on the prep per width), and the round
    is ONE fused slab-parallel kernel on a 2D ``(run, tile)`` grid --
    gather, activities, candidates, per-slab scatter into VMEM scratch
    accumulators AND the bound merge, with the window (slab) axis parallel.
    Only rows whose nonzeros straddle copies detour through a tiny
    out-of-band partials kernel + XLA segment combine first.  Only
    ``(1, S)`` bound/accumulator windows are ever VMEM-resident, no partial
    bound plane round-trips through HBM, and the fused byte model holds at
    any instance size.  ``scatter="auto"`` selects it beyond
    ``SCATTER_MAX_NPAD`` (override: ``REPRO_AUTO_LARGE_SCATTER=segment``).
  * ``scatter="segment"`` -- the materializing oracle: XLA bound gathers,
    candidates written to HBM, column reduction via XLA segment ops (the
    seed dataflow, kept for cross-validation).
  * Zero-copy fixed point: every jitted driver donates the (lb, ub) buffers
    (``donate_argnums``) so XLA updates bounds in place round over round.
    Donation is requested only on backends that implement it (TPU/GPU); the
    drivers hand the loop *private copies* of the cached initial bounds so
    donation can never invalidate the prepare() cache.

Per-round HBM-traffic model (8-byte fp, 4-byte ints, nnz_pad = T*R*K):

  segment (seed): gather writes+reads 2x lb/ub + is_int (~40 B/nnz), tile
    reads val+col (~12 B/nnz), candidate writes (~16 B/nnz), segment-op
    candidate+col reads (~24 B/nnz)   => ~92 B/nnz + O(m + n)
  fused:          tile reads val+col+is_int (~16 B/nnz) + O(m + n_pad)
    for the resident bound/accumulator vectors and row aggregates

``round_cost_analysis`` measures this at the HBM boundary of the actual
lowered round instead of asserting it.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
from collections import OrderedDict
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bounds as bnd
from ..core.propagator import (
    batched_fixed_point,
    donate_kwargs,
    donate_supported,
    owned_copy,
    two_tier_bounds_dtypes,
)
from ..core.sparse import (
    BlockEll,
    Problem,
    ProblemBatch,
    chunk_stream,
    csr_to_block_ell,
    pack_problems,
)
from ..core.types import (
    DEFAULT_CONFIG,
    INF,
    PropagationResult,
    PropagatorConfig,
    TierPolicy,
    _is_low_precision,
    resolve_dtype,
)
from ..obs import telemetry as obs
from ..obs.trace import PROGRAM
from . import prop_round as kern
from . import ref as kref


# Compact index streams: a low-precision tier whose padded column space fits
# int16 narrows its per-nonzero index streams (col -> int16, the is_int
# gather -> int8), shrinking the round's dominant HBM traffic beyond the
# value-dtype halving alone (the fp32 fused round streams 7 B per padded
# nonzero instead of 12).  Kernels compare/gather with the narrow ids
# directly -- widening happens in registers, never at the HBM boundary.
_COMPACT_COL_MAX_NPAD = 1 << 15


class DeviceBlockEll(NamedTuple):
    """Device-resident block-ELL instance (pytree)."""

    val: jnp.ndarray        # (T, R, K)
    col: jnp.ndarray        # (T, R, K) int32 (int16 on compact low-precision tiers)
    chunk_row: jnp.ndarray  # (T, R) int32 in [0, m]; m == padding
    lhs1: jnp.ndarray       # (m+1,) sides padded with one dummy slot at index m
    rhs1: jnp.ndarray       # (m+1,)
    is_int: jnp.ndarray     # (n,) bool
    lb0: jnp.ndarray        # (n,)
    ub0: jnp.ndarray        # (n,)


def _upload(site: str, *pairs) -> tuple:
    """``jnp.asarray(a, dtype)`` for each ``(a, dtype)`` of ``pairs``.
    The host arrays among them are copied to the device under one
    ``prop.upload`` span whose ``bytes`` (what the device receives) also
    count under the program's ``h2d_bytes`` counter at ``site``.  ``None``
    stays ``None``, a device array is only cast, and with no host array
    there is no span."""
    host = [a is not None and not isinstance(a, jax.Array) for a, _ in pairs]
    if not any(host):
        return tuple(a if a is None else jnp.asarray(a, dt) for a, dt in pairs)
    with PROGRAM.span("prop.upload", site=site) as attrs:
        out = tuple(a if a is None else jnp.asarray(a, dt) for a, dt in pairs)
        nbytes = sum(int(o.nbytes) for o, h in zip(out, host) if h)
        attrs["bytes"] = nbytes
        PROGRAM.add("h2d_bytes", site, nbytes)
    return out


def device_block_ell(p: Problem, tile_rows: int = 8, tile_width: int = 128, dtype=None) -> DeviceBlockEll:
    """Convert + upload one instance: block-ELL tiles of shape
    ``(tile_rows, tile_width)``, sides padded with a dummy slot for the
    padding row, bounds and integrality marks as ``(n,)`` device arrays
    (one ``prop.upload`` span, site ``prepare``).
    Prefer :func:`prepare_block_ell`, which caches this and hoists the
    round-constant gathers."""
    dtype = resolve_dtype(dtype, p.csr.val.dtype)
    b = csr_to_block_ell(p.csr, tile_rows=tile_rows, tile_width=tile_width)
    pad1 = lambda x: np.concatenate([x, np.zeros(1, dtype=x.dtype)])
    return DeviceBlockEll(*_upload(
        "prepare",
        (b.val, dtype), (b.col, None), (b.chunk_row, None),
        (pad1(p.lhs), dtype), (pad1(p.rhs), dtype), (p.is_int, None),
        (p.lb, dtype), (p.ub, dtype),
    ))


def rows_fit_one_chunk(p: Problem, tile_width: int) -> bool:
    """True iff every row's nonzeros fit one ``tile_width``-wide chunk --
    the condition for the single-kernel fused round (no cross-chunk
    activity combine needed)."""
    return int(np.diff(p.csr.row_ptr).max(initial=0)) <= tile_width


# ---------------------------------------------------------------------------
# Column-slab partitioning: the tile stream re-bucketed per VMEM-sized slab
# ---------------------------------------------------------------------------


class SlabPartition(NamedTuple):
    """A block-ELL stream re-bucketed by column slabs at CHUNK granularity,
    carrying everything the slab-parallel fused round consumes.

    The padded column space is split into ``n_slabs`` windows of ``slab``
    columns.  The source tiles are flattened to chunks (one matrix-row
    slice each, see ``core.sparse.chunk_stream``); each chunk becomes one
    COPY per slab its nonzeros touch, keeping only the in-slab nonzeros
    (``val == 0`` elsewhere, the block-ELL padding convention) with
    slab-LOCAL columns.  Chunk granularity is what keeps the duplication
    near 1: a whole-tile copy would inherit the unrelated rows sharing the
    tile, duplicating nearly every tile once per slab on column-scattered
    instances.

    The MAIN stream packs every copy into ``(T'', R, K)`` tiles grouped by
    ``(instance, slab)`` window, each group padded to whole tiles with
    dummy-row chunks.  ``run_*`` describe the groups: run ``r`` covers
    copies ``run_start[r] : run_start[r] + run_len[r]`` of window
    ``(run_inst[r], run_slab[r])`` -- the scalar-prefetch map that routes
    the 2D ``(run, tile)`` grid of the slab-parallel round kernel.  Every
    window has exactly one run (empty windows get one all-padding tile),
    so per-window outputs are always written.

    A row whose nonzeros are split across copies (several slabs and/or
    several chunks) is a STRADDLE row; its activity aggregate cannot
    complete inside any one copy.  The sub-stream ``a_*`` repacks exactly
    those rows' copies; the engine computes per-copy partials over it,
    segment-sums them into a table of ``n_straddle`` completed aggregates
    (slot 0 is a dummy), and the round kernel selects per main-stream row
    between its local aggregate (``row_done == 1``) and the table value
    gathered at ``agg_slot``.  Complete rows -- the vast majority --
    never leave the kernel.

    ``col_slots`` is the build-time rectangle-gather schedule of the jnp
    oracle's column reduction: row ``c`` lists the flat main-stream
    candidate slots of column ``c`` (sentinel ``T''*R*K`` elsewhere), so
    the best-bound reduction is one gather + row-wise max/min instead of a
    segment op over the copy stream.  ``None`` when the rectangle would be
    too large (see ``RECT_SLOTS_MAX_RATIO``).

    Built once per prepared instance/bucket and slab width by
    :func:`build_slab_partition` and cached (see
    ``PreparedBlockEll.slab_partition``)."""

    # Main stream: every chunk copy, (instance, slab)-grouped and padded.
    val: jnp.ndarray        # (T'', R, K) slab-masked copies; 0 == padding
    col_s: jnp.ndarray      # (T'', R, K) int32 slab-LOCAL columns
    chunk_row: jnp.ndarray  # (T'', R) int32 rows (global ids in batched use)
    tile_inst: jnp.ndarray  # (T'',) int32 instance of each copy tile
    tile_slab: jnp.ndarray  # (T'',) int32 slab of each copy tile
    ii_g: jnp.ndarray       # (T'', R, K) int32 is_int at each kept nonzero
    lhs_g: jnp.ndarray      # (T'', R) sides gathered per chunk row
    rhs_g: jnp.ndarray      # (T'', R)
    row_done: jnp.ndarray   # (T'', R) int32: 1 iff copy holds its whole row
    agg_slot: jnp.ndarray   # (T'', R) int32 straddle-table slot (0 = dummy)
    run_start: jnp.ndarray  # (B*n_slabs,) int32 first copy tile of each run
    run_len: jnp.ndarray    # (B*n_slabs,) int32 copy tiles per run (>= 1)
    run_inst: jnp.ndarray   # (B*n_slabs,) int32 window instance per run
    run_slab: jnp.ndarray   # (B*n_slabs,) int32 window slab per run
    # Straddle sub-stream: the copies of split rows, packed the same way
    # (phase-A partials only; empty when nothing straddles).
    a_val: jnp.ndarray        # (Ta, R, K)
    a_col_s: jnp.ndarray      # (Ta, R, K) int32 slab-local
    a_slot: jnp.ndarray       # (Ta, R) int32 straddle-table slot (0 = dummy)
    a_tile_inst: jnp.ndarray  # (Ta,) int32
    a_tile_slab: jnp.ndarray  # (Ta,) int32
    a_run_start: jnp.ndarray  # (n_aruns,) int32
    a_run_len: jnp.ndarray    # (n_aruns,) int32
    a_run_inst: jnp.ndarray   # (n_aruns,) int32
    a_run_slab: jnp.ndarray   # (n_aruns,) int32
    # Rectangle-gather schedule of the oracle reduction (or None).
    col_slots: jnp.ndarray | None  # (B*n_pad_part, C) int32
    # Static layout facts.
    slab: int               # S: columns per slab (multiple of LANE)
    n_slabs: int            # windows per instance
    n_pad_part: int         # n_slabs * slab >= n_pad
    batch: int              # B: instances sharing the stream (1 if single)
    n_straddle: int         # straddle rows (table has n_straddle + 1 slots)
    max_run_len: int        # max(run_len) -- the round grid's minor extent
    a_max_run_len: int      # max(a_run_len), 0 when no straddle copies
    source_tiles: int       # T of the unpartitioned stream
    source_chunks: int      # nonzero-carrying chunks of the source stream
    num_chunk_copies: int   # chunk copies before window padding

    @property
    def num_copies(self) -> int:
        """Main-stream copy tiles (T'')."""
        return int(self.val.shape[0])

    @property
    def has_straddle(self) -> bool:
        """True iff any row's nonzeros are split across copies."""
        return int(self.a_val.shape[0]) > 0

    @property
    def duplication(self) -> float:
        """Chunk-copy blowup vs the source chunks (1.0 == no straddling)."""
        return self.num_chunk_copies / max(1, self.source_chunks)


# Size guard for the oracle's rectangle-gather reduction schedule: the
# (B*n_pad_part, C) slot matrix may use at most this many int32 entries per
# candidate-stream element before the builder falls back to segment ops.
RECT_SLOTS_MAX_RATIO = 8


def _pack_copy_windows(
    sel, cp_inst, cp_slab, cp_val, cp_col, cp_ii, cp_row, cp_done, cp_slot,
    bsz, n_slabs, r, k, dummy_rows, cover,
):
    """Pack the selected chunk copies into per-``(instance, slab)`` window
    groups of whole ``(R, K)`` tiles, plus the run maps describing each
    group.  ``cover=True`` materializes one all-padding tile for windows
    with no copies (the main stream: every window's outputs must be
    written); ``cover=False`` keeps only populated windows (the straddle
    sub-stream).  Window-padding rows are dummy-row chunks: ``val == 0``
    everywhere, ``done = 1``, ``slot = 0``."""
    idx = np.flatnonzero(sel)
    inst_g = cp_inst[idx]
    slab_g = cp_slab[idx]
    order = np.lexsort((idx, slab_g, inst_g))  # stable: stream order in-window
    idx, inst_g, slab_g = idx[order], inst_g[order], slab_g[order]
    win = inst_g * n_slabs + slab_g

    if cover:
        win_ids = np.arange(bsz * n_slabs, dtype=np.int64)
        counts = np.bincount(win, minlength=bsz * n_slabs)
        rows_per_win = np.maximum(-(-counts // r), 1) * r
    else:
        win_ids, counts = np.unique(win, return_counts=True)
        rows_per_win = -(-counts // r) * r
    n_runs = int(win_ids.size)
    offs = np.zeros(n_runs + 1, dtype=np.int64)
    np.cumsum(rows_per_win, out=offs[1:])
    total_rows = int(offs[-1])
    n_tiles = total_rows // r

    if idx.size:
        uw, uc = np.unique(win, return_counts=True)
        starts = np.concatenate([[0], np.cumsum(uc)[:-1]])
        rank = np.arange(win.size) - np.repeat(starts, uc)
        pos = win if cover else np.searchsorted(win_ids, win)
        dst = offs[pos] + rank
    else:
        dst = np.zeros(0, dtype=np.int64)

    row_win = np.repeat(win_ids, rows_per_win)
    w_inst = (row_win // n_slabs).astype(np.int64)
    p_val = np.zeros((total_rows, k), cp_val.dtype)
    p_col = np.zeros((total_rows, k), np.int32)
    p_ii = np.zeros((total_rows, k), bool)
    p_row = dummy_rows[w_inst].astype(np.int32)
    p_done = np.ones(total_rows, dtype=np.int32)
    p_slot = np.zeros(total_rows, dtype=np.int64)
    p_val[dst] = cp_val[idx]
    p_col[dst] = cp_col[idx]
    p_ii[dst] = cp_ii[idx]
    p_row[dst] = cp_row[idx]
    p_done[dst] = cp_done[idx]
    p_slot[dst] = cp_slot[idx]

    run_len = (rows_per_win // r).astype(np.int32)
    run_start = (offs[:-1] // r).astype(np.int32)
    run_inst = (win_ids // n_slabs).astype(np.int32)
    run_slab = (win_ids % n_slabs).astype(np.int32)
    tiles = {
        "val": p_val.reshape(n_tiles, r, k),
        "col": p_col.reshape(n_tiles, r, k),
        "ii": p_ii.reshape(n_tiles, r, k),
        "row": p_row.reshape(n_tiles, r),
        "done": p_done.reshape(n_tiles, r),
        "slot": p_slot.reshape(n_tiles, r).astype(np.int32),
        "tile_inst": np.repeat(run_inst, run_len),
        "tile_slab": np.repeat(run_slab, run_len),
    }
    return tiles, run_start, run_len, run_inst, run_slab


def _rect_gather_schedule(m_val, m_col, tile_inst, tile_slab, slab, bsz, n_pad_part):
    """Build-time per-column slot matrix for the oracle's best-bound
    reduction: row ``c`` holds the flat candidate-stream indices of column
    ``c``'s nonzeros, padded with the sentinel index ``stream_len`` (the
    oracle appends one sentinel candidate there).  Returns ``None`` when
    the rectangle would exceed ``RECT_SLOTS_MAX_RATIO`` int32 entries per
    stream element -- pathological column skew -- and the oracle falls
    back to segment ops."""
    n_tiles, r, k = m_val.shape
    stream_len = n_tiles * r * k
    gbase = tile_inst.astype(np.int64) * n_pad_part + tile_slab.astype(np.int64) * slab
    gcol = gbase[:, None, None] + m_col
    flat_nz = (m_val != 0).reshape(-1)
    cols_nz = gcol.reshape(-1)[flat_nz]
    slots_nz = np.flatnonzero(flat_nz)
    counts = np.bincount(cols_nz, minlength=bsz * n_pad_part)
    width = max(1, int(counts.max(initial=0)))
    if bsz * n_pad_part * width > RECT_SLOTS_MAX_RATIO * max(1, stream_len):
        return None
    rect = np.full((bsz * n_pad_part, width), stream_len, dtype=np.int64)
    if cols_nz.size:
        order = np.argsort(cols_nz, kind="stable")
        cs, ss = cols_nz[order], slots_nz[order]
        uc, cnt = np.unique(cs, return_counts=True)
        starts = np.concatenate([[0], np.cumsum(cnt)[:-1]])
        rank = np.arange(cs.size) - np.repeat(starts, cnt)
        rect[cs, rank] = ss
    return rect.astype(np.int32)


def build_slab_partition(
    val: np.ndarray,
    col: np.ndarray,
    chunk_row: np.ndarray,
    tile_inst: np.ndarray,
    lhs1: np.ndarray,
    rhs1: np.ndarray,
    is_int_rows: np.ndarray,
    n_pad: int,
    slab: int,
    dummy_rows: np.ndarray,
) -> SlabPartition:
    """Host-side slab bucketing of a (possibly batched) block-ELL stream
    at chunk granularity (see :class:`SlabPartition` for the layout).

    ``val``/``col`` are ``(T, R, K)`` tiles with instance-local columns;
    ``chunk_row`` carries the row ids (global across instances in batched
    use); ``lhs1``/``rhs1`` are the side vectors those ids index;
    ``is_int_rows`` is the ``(B, n_pad)`` integrality plane and
    ``dummy_rows`` each instance's padding row.

    Each nonzero-carrying chunk becomes one copy per slab its columns
    touch, so every matrix nonzero lands in exactly one copy.  Rows whose
    nonzeros split across copies are diverted to the straddle sub-stream
    for the out-of-kernel aggregate combine; everything else completes
    in-kernel.  ``SlabPartition.duplication`` reports the chunk-copy
    blowup (near 1 unless single rows genuinely span many slabs)."""
    val = np.asarray(val)
    # Compact (int16) tier streams widen here: slab arithmetic below mixes
    # columns with slab offsets that overflow narrow index types.
    col = np.asarray(col, dtype=np.int32)
    chunk_row = np.asarray(chunk_row)
    tile_inst = np.asarray(tile_inst, dtype=np.int64)
    is_int_rows = np.asarray(is_int_rows)
    dummy_rows = np.asarray(dummy_rows, dtype=np.int64)
    t, r, k = val.shape
    dt = val.dtype
    if slab % kern.LANE:
        raise ValueError(f"slab={slab} must be a multiple of LANE={kern.LANE}")
    n_slabs = -(-n_pad // slab)
    n_pad_part = n_slabs * slab
    bsz = int(dummy_rows.shape[0])

    cval, ccol, crow, cinst, src = chunk_stream(val, col, chunk_row, tile_inst)
    nc = t * r
    nz = cval != 0

    # Copy list: one (chunk, slab) pair per touched slab, chunk-major.
    slab_of = np.where(nz, ccol // slab, 0)
    touched = np.zeros((nc, n_slabs), dtype=bool)
    c_idx = np.broadcast_to(np.arange(nc)[:, None], (nc, k))
    touched[c_idx[nz], slab_of[nz]] = True
    ch_ids, s_ids = np.nonzero(touched)
    cp_inst = cinst[ch_ids]

    keep = nz[ch_ids] & (slab_of[ch_ids] == s_ids[:, None])
    cp_nnz = keep.sum(axis=1)

    # Straddle detection: a copy is complete iff it holds ALL of its row's
    # nonzeros; rows with any incomplete copy get a table slot (>= 1).
    n_rows_all = int(np.asarray(lhs1).shape[0])
    row_nnz = np.zeros(n_rows_all, dtype=np.int64)
    np.add.at(row_nnz, crow, nz.sum(axis=1))
    cp_row = crow[ch_ids].astype(np.int64)
    complete = cp_nnz == row_nnz[cp_row]
    srows = np.unique(cp_row[~complete])
    n_straddle = int(srows.size)
    slot_of_row = np.zeros(n_rows_all, dtype=np.int64)
    slot_of_row[srows] = 1 + np.arange(n_straddle)

    cp_val = np.where(keep, cval[ch_ids], 0).astype(dt)
    cp_col = np.where(keep, ccol[ch_ids] - s_ids[:, None] * slab, 0).astype(np.int32)
    cp_ii = np.where(keep, is_int_rows[cp_inst[:, None], ccol[ch_ids]], False)
    cp_slot = slot_of_row[cp_row]

    main, run_start, run_len, run_inst, run_slab = _pack_copy_windows(
        np.ones(ch_ids.size, dtype=bool), cp_inst, s_ids,
        cp_val, cp_col, cp_ii, cp_row, complete, cp_slot,
        bsz, n_slabs, r, k, dummy_rows, cover=True,
    )
    sub, a_run_start, a_run_len, a_run_inst, a_run_slab = _pack_copy_windows(
        ~complete, cp_inst, s_ids,
        cp_val, cp_col, cp_ii, cp_row, complete, cp_slot,
        bsz, n_slabs, r, k, dummy_rows, cover=False,
    )

    col_slots = _rect_gather_schedule(
        main["val"], main["col"], main["tile_inst"], main["tile_slab"],
        slab, bsz, n_pad_part,
    )

    lhs1 = np.asarray(lhs1, dtype=dt)
    rhs1 = np.asarray(rhs1, dtype=dt)
    # The partition may be built lazily inside a jit trace (the first round
    # closure that needs it); materialize concrete device constants there
    # instead of leaking trace-scoped tracers into the prep cache.
    with jax.ensure_compile_time_eval():
        return SlabPartition(
            val=jnp.asarray(main["val"]),
            col_s=jnp.asarray(main["col"]),
            chunk_row=jnp.asarray(main["row"]),
            tile_inst=jnp.asarray(main["tile_inst"].astype(np.int32)),
            tile_slab=jnp.asarray(main["tile_slab"].astype(np.int32)),
            ii_g=jnp.asarray(main["ii"].astype(np.int32)),
            lhs_g=jnp.asarray(lhs1[main["row"]]),
            rhs_g=jnp.asarray(rhs1[main["row"]]),
            row_done=jnp.asarray(main["done"]),
            agg_slot=jnp.asarray(main["slot"]),
            run_start=jnp.asarray(run_start),
            run_len=jnp.asarray(run_len),
            run_inst=jnp.asarray(run_inst),
            run_slab=jnp.asarray(run_slab),
            a_val=jnp.asarray(sub["val"]),
            a_col_s=jnp.asarray(sub["col"]),
            a_slot=jnp.asarray(sub["slot"]),
            a_tile_inst=jnp.asarray(sub["tile_inst"].astype(np.int32)),
            a_tile_slab=jnp.asarray(sub["tile_slab"].astype(np.int32)),
            a_run_start=jnp.asarray(a_run_start),
            a_run_len=jnp.asarray(a_run_len),
            a_run_inst=jnp.asarray(a_run_inst),
            a_run_slab=jnp.asarray(a_run_slab),
            col_slots=None if col_slots is None else jnp.asarray(col_slots),
            slab=int(slab),
            n_slabs=int(n_slabs),
            n_pad_part=int(n_pad_part),
            batch=bsz,
            n_straddle=n_straddle,
            max_run_len=int(run_len.max(initial=1)),
            a_max_run_len=int(a_run_len.max(initial=0)),
            source_tiles=t,
            source_chunks=int(src.sum()),
            num_chunk_copies=int(ch_ids.size),
        )


# ---------------------------------------------------------------------------
# Packed tile stream: short rows share chunk rows (the fused engine's input)
# ---------------------------------------------------------------------------


class PackedStream(NamedTuple):
    """The fused engine's tile stream with short rows packed together.

    Block-ELL gives every row whole ``K``-slot chunk rows, and the fused
    round's in-kernel gather and scatter cost the same per chunk row
    however few slots hold a nonzero.  Here each row of at most ``K``
    nonzeros that repeats no column is packed WHOLE into a shared chunk
    row, first fit over :data:`PACK_OPEN_ROWS` open chunk rows, as a
    contiguous segment of slots; the rows of one chunk row never share a
    column, so the one-hot scatter stays exact on every packed tile.
    Every slot carries its row's local segment id (``seg``, -1 on
    padding) and sides.  Empty rows take no slot.

    The other rows (longer than ``K``, or repeating a column) keep their
    block-ELL chunks as the LONG sub-stream ``l_*``, with rows renumbered
    ``0 .. n_long - 1`` (``n_long`` on padding chunks), for the split
    pair of kernels.  Built once per prepared instance
    (``PreparedBlockEll.packed_stream``)."""

    val: jnp.ndarray      # (Tp, R, K) packed short rows, 0 == padding
    col: jnp.ndarray      # (Tp, R, K) columns (the prep's index dtype)
    ii_g: jnp.ndarray     # (Tp, R, K) is_int at each slot
    seg: jnp.ndarray      # (Tp, R, K) segment within the chunk row, -1 padding
    lhs_s: jnp.ndarray    # (Tp, R, K) the slot's row sides
    rhs_s: jnp.ndarray    # (Tp, R, K)
    l_val: jnp.ndarray    # (Tl, R, K) chunks of the long rows
    l_col: jnp.ndarray    # (Tl, R, K)
    l_ii: jnp.ndarray     # (Tl, R, K)
    l_row: jnp.ndarray    # (Tl, R) int32 long-row id, n_long == padding
    l_lhs: jnp.ndarray    # (Tl, R)
    l_rhs: jnp.ndarray    # (Tl, R)
    n_long: int           # rows in the long sub-stream
    long_chunks: int      # chunks of the long rows (before tile fill)

    @property
    def slots(self) -> int:
        """Slots a round runs over: both streams' tiles x R x K."""
        return int(self.val.size + self.l_val.size)


#: Chunk rows the packer keeps open for first fit: a row that fits none
#: opens a new one and the oldest closes.
PACK_OPEN_ROWS = 4


def _first_fit_rows(starts, lengths, cols, k, open_rows=PACK_OPEN_ROWS):
    """Place rows (columns ``cols[starts[i] : starts[i] + lengths[i]]``)
    whole into ``k``-slot chunk rows, first fit over ``open_rows`` open
    ones, never two rows that share a column in one chunk row.  Returns
    per row its chunk row, first slot and segment (chunk row -1 for a row
    that repeats a column, which is not placed) and the chunk rows used."""
    cols = cols.tolist()
    bins, offs, segs = [], [], []
    open_ = []  # [chunk row, slots used, rows placed, columns used]
    used = 0
    for a, n in zip(starts.tolist(), lengths.tolist()):
        c = set(cols[a:a + n])
        if len(c) != n:
            bins.append(-1)
            offs.append(0)
            segs.append(-1)
            continue
        for b in open_:
            if b[1] + n <= k and b[3].isdisjoint(c):
                break
        else:
            b = [used, 0, 0, set()]
            used += 1
            open_.append(b)
            if len(open_) > open_rows:
                del open_[0]
        bins.append(b[0])
        offs.append(b[1])
        segs.append(b[2])
        b[1] += n
        b[2] += 1
        b[3] |= c
        if b[1] == k:
            open_.remove(b)
    as_ = lambda x: np.asarray(x, np.int64)
    return as_(bins), as_(offs), as_(segs), used


def build_packed_stream(val, col, chunk_row, lhs1, rhs1, is_int, ii_dtype):
    """Pack a single instance's ``(T, R, K)`` block-ELL stream (host
    arrays; ``lhs1``/``rhs1`` the ``(m + 1,)`` padded sides) into a
    :class:`PackedStream`.  An instance with no short rows gets only the
    long sub-stream, which is the block-ELL split pair over its chunks."""
    val = np.asarray(val)
    t, r, k = val.shape
    m = len(lhs1) - 1
    cval, ccol, crow, _, _ = chunk_stream(val, col, chunk_row)
    held = crow < m
    nz = cval != 0
    cnt = nz.sum(axis=1)
    chunks = np.bincount(crow[held], minlength=m + 1)
    short = held & (chunks[crow] == 1) & (cnt > 0)
    sc = np.flatnonzero(short)
    s_nz = nz[sc]
    s_len = cnt[sc]
    s_starts = np.concatenate([[0], np.cumsum(s_len)[:-1]]).astype(np.int64)
    s_col = ccol[sc][s_nz].astype(np.int64)
    bins, offs, segs, used = _first_fit_rows(s_starts, s_len, s_col, k)
    placed = bins >= 0
    long_ = held & (cnt > 0) & ~short
    long_[sc[~placed]] = True
    lc = np.flatnonzero(long_)

    # Packed stream: every placed row's nonzeros at consecutive slots.
    tp = -(-used // r)
    size = tp * r * k
    p_len = np.where(placed, s_len, 0)
    first = np.repeat(bins * k + offs, p_len)
    at = np.arange(p_len.sum()) - np.repeat(np.cumsum(p_len) - p_len, p_len)
    slot = first + at
    take = np.repeat(placed, s_len)
    p_col = s_col[take]
    # The one-hot scatter is exact only where no chunk row holds a column
    # twice; a packed tile must never need the compare-based fallback.
    key = (slot // k) * (int(p_col.max(initial=0)) + 1) + p_col
    assert np.unique(key).size == key.size, "packed rows share a column"
    rows = np.repeat(crow[sc], p_len)
    lay = lambda fill, dt, x: _scatter_slots(size, fill, dt, slot, x).reshape(tp, r, k)
    packed = (
        lay(0, val.dtype, cval[sc][s_nz][take]),
        lay(0, np.asarray(col).dtype, p_col),
        lay(0, ii_dtype, np.asarray(is_int)[p_col]),
        lay(-1, np.int8 if k <= 128 else np.int32, np.repeat(segs, p_len)),
        lay(0, val.dtype, lhs1[rows]),
        lay(0, val.dtype, rhs1[rows]),
    )

    # Long sub-stream: the remaining rows' own chunks, rows renumbered.
    long_rows, l_ids = np.unique(crow[lc], return_inverse=True)
    tl = -(-lc.size // r)
    fill = tl * r - lc.size
    padc = lambda x, v: np.concatenate(
        [x, np.full((fill,) + x.shape[1:], v, x.dtype)]
    ).reshape((tl, r) + x.shape[1:])
    l_val = padc(cval[lc], 0)
    l_col = padc(ccol[lc], 0)
    l_row = padc(l_ids.astype(np.int32), long_rows.size)
    l_src = padc(crow[lc], m)
    longs = (
        l_val, l_col, np.asarray(is_int)[l_col].astype(ii_dtype), l_row,
        lhs1[l_src], rhs1[l_src],
    )
    arrays = _upload("prepare", *((x, None) for x in packed + longs))
    return PackedStream(
        *arrays, n_long=int(long_rows.size), long_chunks=int(lc.size),
    )


def _scatter_slots(size, fill, dtype, slot, x):
    out = np.full(size, fill, dtype)
    out[slot] = x
    return out


# ---------------------------------------------------------------------------
# Prepared instances: one-time setup, hoisted round constants, LRU-cached
# ---------------------------------------------------------------------------

# Largest column-padded width the fused scatter keeps resident in VMEM
# (2 accumulators x n_pad x 8 B = 1 MiB at the cap; ~6% of a v5e core's VMEM).
SCATTER_MAX_NPAD = 1 << 16

# Cap on the partitioned engine's column-slab width: one slab's resident
# state is at most what the fused engine keeps at its cap, so any instance
# the fused engine could hold is one slab of the partitioned one.  The
# default width is BALANCED below the cap (``default_slab_width``) so the
# slab grid overhangs the padded domain by less than one lane row per slab
# instead of up to a whole slab.
SLAB_NPAD = SCATTER_MAX_NPAD


def default_slab_width(n_pad: int, cap: int | None = None) -> int:
    """Balanced column-slab width for a padded domain: the fewest slabs
    whose width stays within the VMEM cap (:data:`SLAB_NPAD`), each width a
    LANE multiple, so ``n_pad_part - n_pad < LANE * n_slabs`` -- the
    per-round pad/slice of the partitioned dataflow stays negligible."""
    cap = SLAB_NPAD if cap is None else int(cap)
    n_slabs = max(1, -(-n_pad // cap))
    return -(-n_pad // (n_slabs * kern.LANE)) * kern.LANE


class LRU:
    """Bounded LRU keyed by tuples that embed ``id()`` of host objects.

    Every entry pins its ``anchors`` (the objects whose ids appear in the
    key) so an id cannot be recycled while the entry is live, and a hit is
    honoured only if every anchor is still the identical object.  Counts
    hits/misses for ``cache_info()``; ``on_evict`` lets dependent caches
    (compiled runners pinning a prep's device tiles) be purged with it.

    THREAD-SAFE: every operation (including the hit/miss counters and the
    eviction walk) holds one re-entrant lock, so the serving loop's
    background admission worker and the device-loop thread can hit the
    runner/pack caches concurrently (``core.service``).  ``on_evict`` hooks
    run under the lock -- they only touch other LRUs, whose own re-entrant
    locks keep the nesting safe.
    """

    def __init__(self, maxsize: int, on_evict=None):
        self.maxsize = maxsize
        self._d: "OrderedDict[tuple, tuple[tuple, object]]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self._on_evict = on_evict
        self._lock = threading.RLock()

    def get(self, key, anchors: tuple):
        with self._lock:
            hit = self._d.get(key)
            if hit is not None and all(a is b for a, b in zip(hit[0], anchors)):
                self._d.move_to_end(key)
                self.hits += 1
                return hit[1]
            self.misses += 1
            return None

    def put(self, key, anchors: tuple, value) -> None:
        with self._lock:
            self._d[key] = (anchors, value)
            while len(self._d) > self.maxsize:
                _, (anchors_e, value_e) = self._d.popitem(last=False)
                if self._on_evict is not None:
                    self._on_evict(anchors_e, value_e)

    def drop_where(self, pred) -> None:
        """Remove every entry whose ``(anchors, value)`` satisfies ``pred``."""
        with self._lock:
            for key in [k for k, v in self._d.items() if pred(*v)]:
                del self._d[key]

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)

    def info(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "size": len(self._d),
                "maxsize": self.maxsize,
            }


@dataclasses.dataclass(frozen=True)
class PreparedBlockEll:
    """Device tiles + everything about a round that does not change across
    rounds: the constant gathers the seed engine recomputed per round, the
    column-padded initial bounds, and static layout facts.

    Not a pytree on purpose -- drivers close over it, so its arrays become
    jit constants and its ints/bools stay static.  The round closures read
    only MATRIX STRUCTURE from it (``d``, the hoisted gathers, the layout
    ints); ``lb0``/``ub0`` are per-problem defaults that every driver
    accepts as runtime overrides, so one prepared engine serves any bounds
    (the warm-start / tree-search contract).
    """

    d: DeviceBlockEll
    ii_g: jnp.ndarray    # (T, R, K) int32: is_int[col], hoisted
    lhs_g: jnp.ndarray   # (T, R): lhs1[chunk_row], hoisted
    rhs_g: jnp.ndarray   # (T, R): rhs1[chunk_row], hoisted
    lb0: jnp.ndarray     # (n_pad,) default initial bounds (column-padded)
    ub0: jnp.ndarray     # (n_pad,)
    m: int
    n: int
    n_pad: int
    fits_one_chunk: bool
    # Slab partitions derived from the (immutable) tiles, built lazily and
    # keyed by slab width; shared by bounds-swapped views of this prep.
    _slabs: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False
    )
    # The packed stream of the fused engine, built lazily.
    _packed: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False
    )

    def slab_partition(self, slab: int | None = None) -> SlabPartition:
        """This instance's tile stream re-bucketed into ``slab``-wide column
        windows (default: :func:`default_slab_width`, balanced below the
        :data:`SLAB_NPAD` cap), for the ``partitioned`` engine.

        Built once per slab width from the resident tiles (a host-side
        pass over the block-ELL arrays) and cached on the prep, so rounds
        and recompilations never pay it again."""
        s = default_slab_width(self.n_pad) if slab is None else int(slab)
        part = self._slabs.get(s)
        if part is None:
            d = self.d
            is_int_rows = np.zeros((1, self.n_pad), dtype=bool)
            is_int_rows[0, : self.n] = np.asarray(d.is_int)
            part = build_slab_partition(
                np.asarray(d.val),
                np.asarray(d.col),
                np.asarray(d.chunk_row),
                np.zeros(d.val.shape[0], dtype=np.int32),
                np.asarray(d.lhs1),
                np.asarray(d.rhs1),
                is_int_rows,
                self.n_pad,
                s,
                np.array([self.m], dtype=np.int32),
            )
            self._slabs[s] = part
        return part

    def packed_stream(self) -> PackedStream:
        """This instance's short rows packed into shared chunk rows, for
        the fused engine (:class:`PackedStream`).  Built once from the
        resident tiles (a host-side pass) and cached on the prep, like
        :meth:`slab_partition`."""
        if "stream" not in self._packed:
            d = self.d
            self._packed["stream"] = build_packed_stream(
                np.asarray(d.val), np.asarray(d.col), np.asarray(d.chunk_row),
                np.asarray(d.lhs1), np.asarray(d.rhs1), np.asarray(d.is_int),
                self.ii_g.dtype,
            )
        return self._packed["stream"]

    def pad_bound(self, arr):
        """One caller bound vector -> the column-padded ``(n_pad,)`` domain
        (padded columns sit at 0, the same trivially-converged fill prepare
        uses)."""
        dt = self.d.val.dtype
        a = jnp.asarray(arr, dt)
        if a.shape != (self.n,):
            raise ValueError(f"bounds have shape {a.shape}, expected {(self.n,)}")
        if self.n_pad > self.n:
            a = jnp.concatenate([a, jnp.zeros((self.n_pad - self.n,), dt)])
        return a

    def pad_bounds(self, lb, ub):
        return self.pad_bound(lb), self.pad_bound(ub)


# Structure anchors: a prepared engine depends on the matrix, the sides and
# the integrality marks -- NOT on the bounds.  Keying prepare on these means
# a B&B node built as ``root._replace(lb=..., ub=...)`` (same csr/lhs/rhs/
# is_int objects) hits the cache and reuses the resident tiles.
def _structure_anchors(p: Problem) -> tuple:
    return (p.csr, p.lhs, p.rhs, p.is_int)


def _drop_runners_for(anchors, value) -> None:
    """Prep-cache eviction hook: compiled runners close over the evicted
    prep's device tiles, so dropping them alongside keeps device memory
    bounded by the prepare LRU, not by the (larger) runner LRUs."""
    _, prep = value
    tiles = prep.d.val
    dead = lambda runner_anchors, _runner: runner_anchors[0] is tiles
    _runner_cache.drop_where(dead)
    _node_runner_cache.drop_where(dead)


_prep_cache = LRU(maxsize=32, on_evict=_drop_runners_for)


@PROGRAM.traced("prop.prepare")
def prepare_block_ell(
    p: Problem, tile_rows: int = 8, tile_width: int = 128, dtype=None
) -> PreparedBlockEll:
    """One-time setup for kernel-backed propagation, LRU-cached per matrix
    STRUCTURE (``csr``/``lhs``/``rhs``/``is_int`` identity -- maxsize 32,
    see ``cache_info()``).

    Repeated propagations of the same ``Problem`` -- or of a bounds-only
    variant like a tree-search node (``p._replace(lb=..., ub=...)``) --
    reuse the block-ELL tiles, device buffers and hoisted gathers instead
    of rebuilding and re-transferring them.  The cache pins the keyed
    structure arrays so ``id()`` keys cannot be recycled while an entry is
    live; a hit from a problem whose bounds differ from the cached defaults
    returns a cheap bounds-swapped view sharing every device tile.
    ``dtype=None`` is the instance's own dtype, float32 on a TPU
    (:func:`~repro.core.types.resolve_dtype`).  Each call is one
    ``prop.prepare`` span of the program tracer, ``hit`` telling a cache
    hit from a build.
    """
    dt = resolve_dtype(dtype, p.csr.val.dtype)
    anchors = _structure_anchors(p)
    key = tuple(id(a) for a in anchors) + (tile_rows, tile_width, dt.str)
    hit = _prep_cache.get(key, anchors)
    PROGRAM.note(hit=hit is not None)
    if hit is not None:
        creator, prep = hit
        if creator.lb is p.lb and creator.ub is p.ub:
            return prep
        # Bounds-swapped view: every heavy array (tiles, hoisted gathers) is
        # shared with the cached prep, and BOTH bound carriers -- the padded
        # prep.lb0/ub0 and the unpadded d.lb0/ub0 -- reflect p's bounds, so
        # legacy readers of d.lb0 cannot silently see the creator's domain.
        # Runner caches key on id(d.val) (stable across _replace), so the
        # view reuses the creator's compiled fixed points.
        lb, ub = _upload("prepare", (p.lb, dt), (p.ub, dt))
        lb0, ub0 = prep.pad_bounds(lb, ub)
        d = prep.d._replace(lb0=lb, ub0=ub)
        return dataclasses.replace(prep, d=d, lb0=lb0, ub0=ub0)

    d = device_block_ell(p, tile_rows, tile_width, dt)
    n_pad = kern.col_pad(p.n)
    compact = _is_low_precision(dt) and n_pad <= _COMPACT_COL_MAX_NPAD
    ii_g = d.is_int[d.col].astype(jnp.int8 if compact else jnp.int32)
    if compact:
        d = d._replace(col=d.col.astype(jnp.int16))
    padn = lambda x: jnp.concatenate([x, jnp.zeros((n_pad - p.n,), x.dtype)])
    prep = PreparedBlockEll(
        d=d,
        ii_g=ii_g,
        lhs_g=d.lhs1[d.chunk_row],
        rhs_g=d.rhs1[d.chunk_row],
        lb0=padn(d.lb0) if n_pad > p.n else d.lb0,
        ub0=padn(d.ub0) if n_pad > p.n else d.ub0,
        m=p.m,
        n=p.n,
        n_pad=n_pad,
        fits_one_chunk=rows_fit_one_chunk(p, tile_width),
    )
    _prep_cache.put(key, anchors, (p, prep))
    return prep


def clear_prepare_cache() -> None:
    """Drop all cached prepared instances and their compiled single-instance
    / node-batch runners (frees device buffers)."""
    _prep_cache.clear()
    _runner_cache.clear()
    _node_runner_cache.clear()


# ---------------------------------------------------------------------------
# One block-ELL round
# ---------------------------------------------------------------------------


def block_ell_round(
    d: DeviceBlockEll,
    lb,
    ub,
    m: int,
    n: int,
    eps: float,
    int_eps: float,
    inf: float = INF,
    use_pallas: bool = True,
    fused: bool = False,
    interpret: bool | None = None,
    outward: float = 0.0,
):
    """One propagation round over block-ELL tiles (seed dataflow, kept as the
    legacy baseline: per-round constant gathers, candidates materialized in
    HBM, XLA segment reduction).  Returns (lb, ub, changed)."""
    lb_g = lb[d.col]
    ub_g = ub[d.col]
    ii_g = d.is_int[d.col]
    lhs_g = d.lhs1[d.chunk_row]
    rhs_g = d.rhs1[d.chunk_row]

    if fused:
        # Alg.-3-faithful: activities live in VMEM, reused for candidates.
        if use_pallas:
            lcand, ucand = kern.fused_round_tiles(
                d.val, lb_g, ub_g, ii_g, lhs_g, rhs_g, int_eps, inf, interpret
            )
        else:
            lcand, ucand = kref.fused_round_tiles_ref(
                d.val, lb_g, ub_g, ii_g, lhs_g, rhs_g, int_eps, inf
            )
    else:
        if use_pallas:
            mf, mc, xf, xc = kern.activities_tiles(d.val, lb_g, ub_g, inf, interpret)
        else:
            mf, mc, xf, xc = kref.activities_tiles_ref(d.val, lb_g, ub_g, inf)
        # Combine chunk partials into completed row aggregates (long rows).
        crow = d.chunk_row.reshape(-1)
        seg = lambda x: jax.ops.segment_sum(x.reshape(-1), crow, num_segments=m + 1)
        row_mf, row_mc = seg(mf), seg(mc)
        row_xf, row_xc = seg(xf), seg(xc)
        # Gather completed aggregates back per chunk.
        g = lambda x: x[d.chunk_row]
        if use_pallas:
            lcand, ucand = kern.candidates_tiles(
                d.val, lb_g, ub_g, ii_g,
                g(row_mf), g(row_mc), g(row_xf), g(row_xc),
                lhs_g, rhs_g, int_eps, inf, interpret,
            )
        else:
            lcand, ucand = kref.candidates_tiles_ref(
                d.val, lb_g, ub_g, ii_g,
                g(row_mf), g(row_mc), g(row_xf), g(row_xc),
                lhs_g, rhs_g, int_eps, inf,
            )

    flat_col = d.col.reshape(-1)
    best_l = jax.ops.segment_max(lcand.reshape(-1), flat_col, num_segments=n)
    best_u = jax.ops.segment_min(ucand.reshape(-1), flat_col, num_segments=n)
    return bnd.apply_updates(lb, ub, best_l, best_u, eps, inf, outward)


def _combine_chunk_partials(chunk_row, rows: int, mf, mc, xf, xc):
    """Chunk partials -> completed per-chunk row aggregates (long rows);
    ``chunk_row`` numbers the chunks' rows ``0 .. rows`` (``rows`` the
    padding)."""
    crow = chunk_row.reshape(-1)
    seg = lambda x: jax.ops.segment_sum(x.reshape(-1), crow, num_segments=rows + 1)
    g = lambda x: seg(x)[chunk_row]
    return g(mf), g(mc), g(xf), g(xc)


def _packed_best_bounds(packed: PackedStream, n_pad: int, lb, ub, *, int_eps, inf, interpret):
    """The fused engine's column-wise best bounds over a packed stream:
    the packed kernel over the packed rows, the split pair over the long
    rows' own chunks, combined by max/min."""
    best = []
    if packed.val.shape[0]:
        best.append(kern.packed_round_tiles(
            packed.val, packed.col, packed.ii_g, packed.seg, packed.lhs_s,
            packed.rhs_s, lb, ub, n_pad, int_eps, inf, interpret,
        ))
    if packed.l_val.shape[0]:
        parts = kern.activities_gather_tiles(
            packed.l_val, packed.l_col, lb, ub, n_pad, inf, interpret
        )
        aggs = _combine_chunk_partials(packed.l_row, packed.n_long, *parts)
        best.append(kern.candidates_scatter_tiles(
            packed.l_val, packed.l_col, packed.l_ii, *aggs, packed.l_lhs,
            packed.l_rhs, lb, ub, n_pad, int_eps, inf, interpret,
        ))
    if not best:
        return jnp.full_like(lb, -inf), jnp.full_like(ub, inf)
    return functools.reduce(
        lambda a, b: (jnp.maximum(a[0], b[0]), jnp.minimum(a[1], b[1])), best
    )


def _straddle_aggregates(part: SlabPartition, lb, ub, active, *, node, inf, interpret):
    """Completed activity aggregates of the straddle rows, as a
    ``(n_straddle + 1,)`` table per aggregate kind (slot 0 is the dummy the
    main stream's complete rows point at) -- ``(B, n_straddle + 1)`` under
    ``node=True``.

    Phase A of a partitioned round: the straddle sub-stream's copies
    produce per-copy partials in a slab-parallel kernel, and a tiny XLA
    segment sum over ``a_slot`` completes them.  Everything row-sized here
    is ``O(straddle copies)``, not ``O(nnz)``; with no straddle rows the
    engine skips this entirely."""
    nseg = part.n_straddle + 1
    if node:
        mf, mc, xf, xc = kern.node_slab_partials_tiles(
            part.a_val, part.a_col_s, part.a_run_start, part.a_run_len,
            part.a_run_slab, active, lb, ub, part.slab, part.a_max_run_len,
            inf, interpret,
        )
        slot = part.a_slot.reshape(-1)
        seg1 = lambda x: jax.ops.segment_sum(x, slot, num_segments=nseg)
        g = lambda x: jax.vmap(seg1)(x.reshape(x.shape[0], -1))
    else:
        mf, mc, xf, xc = kern.batched_slab_partials_tiles(
            part.a_val, part.a_col_s, part.a_run_start, part.a_run_len,
            part.a_run_inst, part.a_run_slab, active, lb, ub, part.slab,
            part.a_max_run_len, inf, interpret,
        )
        slot = part.a_slot.reshape(-1)
        g = lambda x: jax.ops.segment_sum(x.reshape(-1), slot, num_segments=nseg)
    return g(mf), g(mc), g(xf), g(xc)


def _partitioned_pallas_round(
    part: SlabPartition, lb, ub, active,
    *, node: bool, eps: float, int_eps: float, inf: float,
    interpret: bool | None, outward: float = 0.0,
):
    """The one slab-round dataflow every partitioned engine shares, over
    ``(B, n_pad)`` bound planes: pad to the slab grid -> straddle-row
    aggregate tables (phase A, skipped when nothing straddles) -> ONE fused
    slab-parallel kernel per plane set (activities, candidates, per-slab
    scatter into VMEM accumulators, AND the bound merge, on the 2D
    ``(run, tile)`` grid) -> slice back.

    ``node=True`` runs every node's plane against the shared copies on a
    ``(B, run, tile)`` grid (per-node straddle tables, per-node windows);
    otherwise copies route to their own instance's plane rows via the run
    maps (single-instance callers pass ``B == 1``).  Returns the updated
    ``(B, n_pad)`` planes and the ``(B,)`` changed flags."""
    bsz, n_pad = lb.shape
    extra = part.n_pad_part - n_pad
    if extra:
        z = jnp.zeros((bsz, extra), lb.dtype)
        lbp = jnp.concatenate([lb, z], axis=1)
        ubp = jnp.concatenate([ub, z], axis=1)
    else:
        lbp, ubp = lb, ub
    if part.has_straddle:
        smf, smc, sxf, sxc = _straddle_aggregates(
            part, lbp, ubp, active, node=node, inf=inf, interpret=interpret
        )
        tab = lambda t: t[..., part.agg_slot]
        smf, smc, sxf, sxc = tab(smf), tab(smc), tab(sxf), tab(sxc)
    else:
        shape = ((bsz,) if node else ()) + tuple(part.chunk_row.shape)
        smf = jnp.zeros(shape, lbp.dtype)
        smc = jnp.zeros(shape, jnp.int32)
        sxf, sxc = smf, smc
    if node:
        new_lb, new_ub, ch = kern.node_slab_round_tiles(
            part.val, part.col_s, part.ii_g, part.row_done, smf, smc, sxf, sxc,
            part.lhs_g, part.rhs_g, part.run_start, part.run_len,
            part.run_slab, active, lbp, ubp, part.slab, part.max_run_len,
            eps, int_eps, inf, interpret, outward=outward,
        )
        changed = jnp.any(ch != 0, axis=1)
    else:
        new_lb, new_ub, ch = kern.batched_slab_round_tiles(
            part.val, part.col_s, part.ii_g, part.row_done, smf, smc, sxf, sxc,
            part.lhs_g, part.rhs_g, part.run_start, part.run_len,
            part.run_inst, part.run_slab, active, lbp, ubp, part.slab,
            part.max_run_len, eps, int_eps, inf, interpret, outward=outward,
        )
        changed = jax.ops.segment_max(ch, part.run_inst, num_segments=bsz) != 0
    if extra:
        new_lb, new_ub = new_lb[:, :n_pad], new_ub[:, :n_pad]
    return new_lb, new_ub, changed


def _prepared_round(
    prep: PreparedBlockEll,
    lb,
    ub,
    *,
    eps: float,
    int_eps: float,
    inf: float,
    use_pallas: bool,
    fused: bool,
    scatter: str,
    interpret: bool | None,
    slab: int | None = None,
    outward: float = 0.0,
    packed: PackedStream | None = None,
):
    """One round over hoisted constants.  (lb, ub) live in the column-padded
    ``(n_pad,)`` domain end to end; only the bound gathers run in XLA.
    ``packed`` (Pallas, ``scatter="fused"``) runs the fused round over the
    packed stream instead of the block-ELL tiles."""
    d = prep.d
    if use_pallas:
        interpret = kern.resolve_interpret(interpret, lb.dtype)

    if scatter == "partitioned":
        # Column-slab partitioned round (VMEM-exceeding n_pad): chunk-copy
        # slab partition, straddle aggregates out of band, then ONE fused
        # slab-parallel kernel (candidates + scatter + merge) on the 2D
        # (run, tile) grid.  Only (1, S) windows are ever VMEM-resident;
        # no nnz-shaped tensor touches HBM.
        part = prep.slab_partition(slab)
        if use_pallas:
            new_lb, new_ub, ch = _partitioned_pallas_round(
                part, lb[None, :], ub[None, :], jnp.ones((1,), jnp.int32),
                node=False, eps=eps, int_eps=int_eps, inf=inf,
                interpret=interpret, outward=outward,
            )
            return new_lb[0], new_ub[0], ch[0]
        best_l, best_u = kref.partitioned_round_ref(
            part, lb[None, :], ub[None, :], int_eps, inf
        )
        return bnd.apply_updates(
            lb, ub, best_l[0, : prep.n_pad], best_u[0, : prep.n_pad], eps, inf,
            outward,
        )

    if scatter == "fused":
        if packed is not None:
            best_l, best_u = _packed_best_bounds(
                packed, prep.n_pad, lb, ub, int_eps=int_eps, inf=inf,
                interpret=interpret,
            )
        elif fused:
            # Fully fused: even the bound gather happens in the kernel, so
            # no nnz-shaped tensor is produced in HBM at all this round.
            if use_pallas:
                best_l, best_u = kern.fused_scatter_round_tiles(
                    d.val, d.col, prep.ii_g, prep.lhs_g, prep.rhs_g,
                    lb, ub, prep.n_pad, int_eps, inf, interpret,
                )
            else:
                best_l, best_u = kref.fused_scatter_round_tiles_ref(
                    d.val, d.col, prep.ii_g, prep.lhs_g, prep.rhs_g,
                    lb, ub, prep.n_pad, int_eps, inf,
                )
        else:
            # Long rows: chunk partials (in-kernel gather) -> XLA segment
            # combine of the tiny (T, R) aggregates -> fused scatter round.
            if use_pallas:
                mf, mc, xf, xc = kern.activities_gather_tiles(
                    d.val, d.col, lb, ub, prep.n_pad, inf, interpret
                )
            else:
                mf, mc, xf, xc = kref.activities_gather_tiles_ref(
                    d.val, d.col, lb, ub, prep.n_pad, inf
                )
            rmf, rmc, rxf, rxc = _combine_chunk_partials(
                d.chunk_row, prep.m, mf, mc, xf, xc
            )
            if use_pallas:
                best_l, best_u = kern.candidates_scatter_tiles(
                    d.val, d.col, prep.ii_g, rmf, rmc, rxf, rxc,
                    prep.lhs_g, prep.rhs_g, lb, ub, prep.n_pad, int_eps, inf,
                    interpret,
                )
            else:
                best_l, best_u = kref.candidates_scatter_tiles_ref(
                    d.val, d.col, prep.ii_g, rmf, rmc, rxf, rxc,
                    prep.lhs_g, prep.rhs_g, lb, ub, prep.n_pad, int_eps, inf,
                )
        if use_pallas:
            return kern.apply_updates_tiles(
                lb, ub, best_l, best_u, eps, inf, interpret, outward
            )
        return bnd.apply_updates(lb, ub, best_l, best_u, eps, inf, outward)

    # scatter == "segment": the materializing oracle path (hoisted gathers).
    lb_g = lb[d.col]
    ub_g = ub[d.col]
    if fused:
        if use_pallas:
            lcand, ucand = kern.fused_round_tiles(
                d.val, lb_g, ub_g, prep.ii_g, prep.lhs_g, prep.rhs_g,
                int_eps, inf, interpret,
            )
        else:
            lcand, ucand = kref.fused_round_tiles_ref(
                d.val, lb_g, ub_g, prep.ii_g, prep.lhs_g, prep.rhs_g, int_eps, inf
            )
    else:
        if use_pallas:
            mf, mc, xf, xc = kern.activities_tiles(d.val, lb_g, ub_g, inf, interpret)
        else:
            mf, mc, xf, xc = kref.activities_tiles_ref(d.val, lb_g, ub_g, inf)
        rmf, rmc, rxf, rxc = _combine_chunk_partials(
            d.chunk_row, prep.m, mf, mc, xf, xc
        )
        if use_pallas:
            lcand, ucand = kern.candidates_tiles(
                d.val, lb_g, ub_g, prep.ii_g, rmf, rmc, rxf, rxc,
                prep.lhs_g, prep.rhs_g, int_eps, inf, interpret,
            )
        else:
            lcand, ucand = kref.candidates_tiles_ref(
                d.val, lb_g, ub_g, prep.ii_g, rmf, rmc, rxf, rxc,
                prep.lhs_g, prep.rhs_g, int_eps, inf,
            )
    flat_col = d.col.reshape(-1)
    best_l = jax.ops.segment_max(lcand.reshape(-1), flat_col, num_segments=prep.n_pad)
    best_u = jax.ops.segment_min(ucand.reshape(-1), flat_col, num_segments=prep.n_pad)
    return bnd.apply_updates(lb, ub, best_l, best_u, eps, inf, outward)


def legacy_round_fn_for(
    prep: PreparedBlockEll,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    interpret: bool | None = None,
):
    """The seed round (``block_ell_round``) as a jit-able ``(lb, ub) ->
    (lb, ub, changed)`` closure over a prepared instance -- bounds in the
    unpadded ``(n,)`` domain.  Kept as the measured baseline."""
    eps = cfg.eps_for(prep.d.val.dtype)
    return functools.partial(
        block_ell_round,
        prep.d,
        m=prep.m,
        n=prep.n,
        eps=eps,
        int_eps=cfg.int_eps,
        inf=cfg.inf,
        use_pallas=use_pallas,
        fused=prep.fits_one_chunk,
        interpret=interpret,
        outward=cfg.outward_for(prep.d.val.dtype),
    )


def round_fn_for(
    prep: PreparedBlockEll,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    scatter: str = "fused",
    fused: bool | None = None,
    interpret: bool | None = None,
    slab: int | None = None,
):
    """A jit-able ``(lb, ub) -> (lb, ub, changed)`` round closure over a
    prepared instance (bounds in the ``(n_pad,)`` domain).  ``slab``
    overrides the partitioned engine's column-slab width (default
    :data:`SLAB_NPAD`; ignored by the other scatter modes).  ``fused=None``
    lets the instance choose: with Pallas and the fused engine, the packed
    stream (:meth:`PreparedBlockEll.packed_stream`), else the single
    kernel when every row fits one chunk."""
    scatter = _resolve_scatter(scatter, prep)
    do_fuse = prep.fits_one_chunk if fused is None else bool(fused)
    packed = _packed_for(prep, use_pallas, scatter, fused is None)
    return _round_fn(prep, cfg, use_pallas, scatter, do_fuse, interpret, slab, packed)


def _round_fn(prep, cfg, use_pallas, scatter, do_fuse, interpret, slab, packed):
    return functools.partial(
        _prepared_round,
        prep,
        eps=cfg.eps_for(prep.d.val.dtype),
        int_eps=cfg.int_eps,
        inf=cfg.inf,
        use_pallas=use_pallas,
        fused=do_fuse,
        scatter=scatter,
        interpret=interpret,
        slab=slab,
        outward=cfg.outward_for(prep.d.val.dtype),
        packed=packed,
    )


def _packed_for(prep: PreparedBlockEll, use_pallas, scatter, auto):
    """The packed stream serves the Pallas fused engine whenever the
    caller leaves the tile layout to the instance (``fused`` auto)."""
    return prep.packed_stream() if use_pallas and scatter == "fused" and auto else None


# ---------------------------------------------------------------------------
# Full propagation drivers over block-ELL
# ---------------------------------------------------------------------------


# Escape hatch for the large-instance leg of ``scatter="auto"``: set
# REPRO_AUTO_LARGE_SCATTER=segment to route VMEM-exceeding instances to the
# materializing segment engine instead of the partitioned one (e.g. while
# re-validating a slab-width regression on new hardware).  The default is
# the slab-parallel partitioned engine, which wins on both bytes and wall
# clock on the benchmarked large-instance families (see BENCH_prop.json).
AUTO_LARGE_SCATTER_ENV = "REPRO_AUTO_LARGE_SCATTER"


def _auto_large_scatter() -> str:
    mode = os.environ.get(AUTO_LARGE_SCATTER_ENV, "partitioned")
    if mode not in ("partitioned", "segment"):
        raise ValueError(
            f"{AUTO_LARGE_SCATTER_ENV}={mode!r}: expected 'partitioned' or 'segment'"
        )
    return mode


def _resolve_scatter(scatter: str, prep: PreparedBlockEll) -> str:
    """The engine decision (see docs/ARCHITECTURE.md): ``auto`` keeps the
    fully fused round while the ``(2, n_pad)`` accumulators fit the VMEM
    budget and moves to the column-slab partitioned round beyond it
    (overridable via :data:`AUTO_LARGE_SCATTER_ENV`), so the fused
    ~16 B/nnz dataflow holds at every instance size; ``segment`` (the
    materializing oracle) is otherwise only ever explicit."""
    return _scatter_for(scatter, prep.n_pad)


def _scatter_for(scatter: str, n_pad: int) -> str:
    if scatter == "auto":
        return "fused" if n_pad <= SCATTER_MAX_NPAD else _auto_large_scatter()
    if scatter not in ("fused", "segment", "partitioned"):
        raise ValueError(f"unknown scatter mode: {scatter!r}")
    return scatter


def _round_slots(prep: PreparedBlockEll, scatter: str, packed, slab) -> int:
    """Slots a round runs over: the packed stream's, the slab copies'
    (main and straddle streams), or the block-ELL tiles'."""
    if packed is not None:
        return packed.slots
    if scatter == "partitioned":
        part = prep.slab_partition(slab)
        return int(part.val.size + part.a_val.size)
    return int(prep.d.val.size)


# Jitted single-instance fixed points, cached per matrix structure + config:
# the tree-search pattern re-propagates the same prepared engine with fresh
# bounds thousands of times, and rebuilding the jit closure per call would
# recompile every time.  Keyed on id(prep.d.val) -- the tile array shared by
# every bounds-swapped prepare() view of one structure -- so ONE compiled
# engine serves any bounds (the round closures read only structure from the
# prep they were built over, never its bound defaults).
_runner_cache = LRU(maxsize=64)


def _initial_padded_bounds(prep: PreparedBlockEll, lb0, ub0):
    """Per-call bound overrides -> private, donated-safe (n_pad,) buffers
    (host overrides uploaded under site ``bounds``)."""
    dt = prep.d.val.dtype
    lb0, ub0 = _upload("bounds", (lb0, dt), (ub0, dt))
    lb = owned_copy(prep.lb0 if lb0 is None else prep.pad_bound(lb0))
    ub = owned_copy(prep.ub0 if ub0 is None else prep.pad_bound(ub0))
    return lb, ub


@PROGRAM.traced("prop.presolve")
def propagate_block_ell(
    p: Problem,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    tile_rows: int = 8,
    tile_width: int = 128,
    dtype=None,
    use_pallas: bool = True,
    fused: str = "auto",
    driver: str = "device_loop",
    interpret: bool | None = None,
    scatter: str = "auto",
    donate: bool | None = None,
    lb0=None,
    ub0=None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    policy: TierPolicy | None = None,
    telemetry: int | None = None,
) -> PropagationResult:
    """Kernel-backed propagation.

    ``fused='auto'`` lets the instance pick its tiles: with Pallas and the
    fused engine, the packed stream (short rows share chunk rows,
    :class:`PackedStream`), else the Alg.-3 fusion whenever every row fits
    in one chunk (the paper's common case).
    ``scatter='auto'`` picks the fully fused in-VMEM column reduction while the padded column count fits the
    accumulator budget and the column-slab ``partitioned`` engine beyond it
    (``slab`` overrides its window width); ``scatter='segment'`` forces the
    materializing oracle.  ``donate=None`` donates the bound buffers
    wherever the backend implements donation (zero-copy fixed point).

    ``lb0``/``ub0`` warm-start the fixed point from caller-supplied bounds:
    the prepared tiles, hoisted gathers AND the compiled fixed point are all
    cached per matrix structure, so propagating a B&B node costs one
    dispatch with two (n,) uploads -- no repacking, no recompilation.

    ``stop_progress``/``patience`` arm the progress-based early stop (see
    ``bounds.progress_measure``); ``policy`` (a :class:`TierPolicy`) runs
    the two-tier precision scheme -- an fp32 tier with outward-rounded
    merges until per-round progress drops below ``policy.switch_progress``,
    then an exact-cast promotion into the requested dtype for the endgame.
    Both tiers reuse their own dtype-keyed prepared engines and compiled
    runners, so tiered tree search stays recompile-free.

    ``telemetry`` (a ring capacity) carries an ``obs.TelemetryPlane``
    through the while_loop and attaches its snapshot to the result --
    per-round progress ring, early-stop and first-infeasible rounds, read
    back only at exit.  Recording reuses the progress scalar the carry
    already computes, so bounds stay bitwise identical and the fixed point
    remains one dispatch; the telemetry capacity is part of the runner
    cache key (on/off are distinct compiled runners, each cached once).

    Each call is one ``prop.presolve`` span of the program tracer
    (``obs.trace.PROGRAM``, attrs ``engine``, ``n_pad``, ``packed`` (the
    round runs the packed stream), and ``slots`` and ``nnz``: the slots of
    the tile streams a round runs and the nonzeros they hold), from entry
    to return: the launch is asynchronous and nothing waits for the device.
    Inside it are the ``prop.prepare``, ``prop.upload`` and ``prop.launch``
    spans (``built`` on a runner-cache miss); a two-tier call nests one
    ``prop.presolve`` span per tier."""
    if driver not in ("host_loop", "device_loop"):
        raise ValueError(f"unknown driver: {driver!r}")
    tel_cap = int(telemetry or 0)
    pair = two_tier_bounds_dtypes(policy, dtype) if policy is not None else None
    if pair is not None:
        dt32, final = pair
        kw = dict(
            tile_rows=tile_rows, tile_width=tile_width, use_pallas=use_pallas,
            fused=fused, driver=driver, interpret=interpret, scatter=scatter,
            donate=donate, slab=slab, patience=policy.patience,
            telemetry=telemetry,
        )
        cap32 = max(1, int(cfg.max_rounds * policy.fp32_round_frac))
        r32 = propagate_block_ell(
            p, dataclasses.replace(cfg, max_rounds=cap32), dtype=dt32,
            lb0=lb0, ub0=ub0, stop_progress=policy.switch_progress, **kw,
        )
        if bool(r32.infeasible):
            # fp32 infeasibility is never trusted (see core.propagator):
            # re-derive the verdict in the final dtype from scratch.
            r = propagate_block_ell(
                p, cfg, dtype=final, lb0=lb0, ub0=ub0,
                stop_progress=policy.stop_progress, **kw,
            )
            if r.telemetry is not None:
                r = r._replace(
                    telemetry=dataclasses.replace(r.telemetry, fp32=r32.telemetry)
                )
            return r._replace(tier_rounds=r32.rounds)
        tier_rounds = int(r32.rounds)
        rem = dataclasses.replace(
            cfg, max_rounds=max(1, cfg.max_rounds - tier_rounds)
        )
        warm_lb, warm_ub = bnd.canonical_infinite(
            jnp.asarray(r32.lb, final), jnp.asarray(r32.ub, final)
        )
        r = propagate_block_ell(
            p, rem, dtype=final, lb0=warm_lb, ub0=warm_ub,
            stop_progress=policy.stop_progress, **kw,
        )
        if r.telemetry is not None:
            r = r._replace(
                telemetry=dataclasses.replace(
                    r.telemetry, tier_switch_round=tier_rounds,
                    fp32=r32.telemetry,
                )
            )
        return r._replace(rounds=r.rounds + r32.rounds, tier_rounds=r32.rounds)
    if policy is not None:
        stop_progress = policy.stop_progress
        patience = policy.patience
    scatter = _scatter_for(scatter, kern.col_pad(p.n))
    auto = fused == "auto"
    prep = prepare_block_ell(p, tile_rows, tile_width, dtype)
    do_fuse = prep.fits_one_chunk if auto else bool(fused == "yes" or fused is True)
    packed = _packed_for(prep, use_pallas, scatter, auto)
    PROGRAM.note(
        engine=scatter, n_pad=prep.n_pad, packed=packed is not None,
        slots=_round_slots(prep, scatter, packed, slab), nnz=int(p.nnz),
    )
    do_donate = donate_supported() if donate is None else bool(donate)
    n = prep.n

    key = (
        id(prep.d.val), cfg, use_pallas, do_fuse, scatter, interpret, do_donate,
        driver, slab, stop_progress, patience, tel_cap, packed is not None,
    )
    anchors = (prep.d.val,)

    def build():
        donate_kw = {"donate_argnums": (0, 1)} if do_donate else {}
        round_fn = _round_fn(
            prep, cfg, use_pallas, scatter, do_fuse, interpret, slab, packed
        )
        if driver == "host_loop":
            # Progress is computed INSIDE the jit, where the pre-round
            # bounds are still live (they are donated away by the call).
            def round_presolve(lb, ub):
                with jax.named_scope("prop_round"):
                    nlb, nub, ch = round_fn(lb, ub)
                with jax.named_scope("fixed_point"):
                    out = nlb, nub, ch, bnd.progress_measure(lb, ub, nlb, nub)
                    if tel_cap:
                        out = out + (jnp.any(nlb > nub + cfg.feas_eps),)
                return out

            return jax.jit(round_presolve, **donate_kw)

        @functools.partial(jax.jit, **donate_kw)
        def fixed_point_presolve(lb0, ub0):
            def body(state):
                lb, ub, _, r, _, flat = state[:6]
                with jax.named_scope("prop_round"):
                    nlb, nub, ch = round_fn(lb, ub)
                with jax.named_scope("fixed_point"):
                    prog = bnd.progress_measure(lb, ub, nlb, nub)
                    if stop_progress is not None:
                        flat = jnp.where(prog < stop_progress, flat + 1, jnp.int32(0))
                    out = (nlb, nub, ch, r + 1, prog, flat)
                    if tel_cap:
                        stopped = (
                            (flat >= patience) if stop_progress is not None else None
                        )
                        out = out + (obs.record_round(
                            state[6], prog, r + 1,
                            jnp.any(nlb > nub + cfg.feas_eps), stopped,
                        ),)
                return out

            @jax.named_scope("fixed_point")
            def cond(state):
                ch, r, flat = state[2], state[3], state[5]
                go = ch & (r < cfg.max_rounds)
                if stop_progress is not None:
                    go = go & (flat < patience)
                return go

            init = (
                lb0, ub0, jnp.asarray(True), jnp.int32(0),
                jnp.asarray(jnp.nan, lb0.dtype), jnp.int32(0),
            )
            if tel_cap:
                init = init + (obs.device_plane(tel_cap, dtype=lb0.dtype),)
            final = jax.lax.while_loop(cond, body, init)
            lb, ub, ch, r, prog = final[:5]
            lb, ub = lb[:n], ub[:n]
            res = (lb, ub, r, ~ch, jnp.any(lb > ub + cfg.feas_eps), prog)
            return res + ((final[6],) if tel_cap else ())

        return fixed_point_presolve

    lb, ub = _initial_padded_bounds(prep, lb0, ub0)
    with PROGRAM.span("prop.launch") as launch:
        runner = _runner_cache.get(key, anchors)
        launch["built"] = runner is None
        if runner is None:
            runner = build()
            _runner_cache.put(key, anchors, runner)
        if driver == "device_loop":
            out = runner(lb, ub)
            lb, ub, rounds, converged, infeasible, prog = out[:6]
            snap = obs.TelemetrySnapshot(plane=out[6]) if tel_cap else None
            return PropagationResult(
                lb, ub, rounds, converged, infeasible, progress=prog, telemetry=snap
            )

        rounds, changed, flat = 0, True, 0
        prog = jnp.asarray(jnp.nan, lb.dtype)
        history: list[float] = []
        stop_round = infeas_round = -1
        while changed and rounds < cfg.max_rounds:
            # Donated in, fresh buffers out: the loop owns its bounds, so XLA
            # reuses the same two (n_pad,) buffers round over round.
            lb, ub, cdev, prog, *infeas_dev = runner(lb, ub)
            changed = bool(cdev)
            rounds += 1
            if tel_cap:
                history.append(float(prog))
                if infeas_round < 0 and bool(infeas_dev[0]):
                    infeas_round = rounds
            if stop_progress is not None:
                flat = flat + 1 if float(prog) < stop_progress else 0
                if flat >= patience:
                    stop_round = rounds
                    break
        infeas = bool(jnp.any(lb[:n] > ub[:n] + cfg.feas_eps))
        snap = obs.host_snapshot(
            history, tel_cap, stop_round=stop_round, infeas_round=infeas_round
        ) if tel_cap else None
        return PropagationResult(
            lb[:n], ub[:n], jnp.int32(rounds), jnp.asarray(not changed),
            jnp.asarray(infeas), progress=prog, telemetry=snap,
        )


# ---------------------------------------------------------------------------
# Batched engine: a whole ProblemBatch per dispatch
# ---------------------------------------------------------------------------


class DeviceProblemBatch(NamedTuple):
    """Device-resident packed batch (pytree): the flat tile stream, hoisted
    round-constant gathers/offsets, initial bounds and the real-column
    mask.  ``col`` keeps instance-local columns (the kernel routes blocks
    by ``tile_inst``); ``col_g`` carries the precomputed global ids
    ``col + tile_inst * n_pad`` for the flat XLA dataflow."""

    val: jnp.ndarray        # (T, R, K)
    col: jnp.ndarray        # (T, R, K) int32 instance-local
    col_g: jnp.ndarray      # (T, R, K) int32 global (bound-plane) columns
    chunk_row: jnp.ndarray  # (T, R) int32 global row ids
    tile_inst: jnp.ndarray  # (T,) int32 instance of each tile
    ii_g: jnp.ndarray       # (T, R, K) int32: is_int[col], hoisted
    lhs_g: jnp.ndarray      # (T, R): lhs1[chunk_row], hoisted
    rhs_g: jnp.ndarray      # (T, R)
    lb0: jnp.ndarray        # (B, n_pad)
    ub0: jnp.ndarray        # (B, n_pad)
    col_valid: jnp.ndarray  # (B, n_pad) bool: j < n_i (real columns)


@dataclasses.dataclass(frozen=True)
class PreparedBatch:
    """One bucket, device-ready.  Like :class:`PreparedBlockEll`, not a
    pytree: drivers close over it so arrays become jit constants."""

    batch: ProblemBatch
    d: DeviceProblemBatch
    size: int
    m_total: int
    n_pad: int
    fits_one_chunk: bool
    # Lazy slab partitions of the packed stream, keyed by slab width.
    _slabs: dict = dataclasses.field(
        default_factory=dict, compare=False, repr=False
    )

    def slab_partition(self, slab: int | None = None) -> SlabPartition:
        """The bucket's flat super-tile stream re-bucketed into per-instance
        ``slab``-wide column windows (default :func:`default_slab_width`), copies
        sorted ``(instance, slab, tile)``; built once per slab width from
        the host-side packed arrays and cached on the prep."""
        s = default_slab_width(self.n_pad) if slab is None else int(slab)
        part = self._slabs.get(s)
        if part is None:
            ell = self.batch.ell
            dt = np.dtype(self.d.val.dtype)
            # Instance i's padding chunks target its dummy row, the last of
            # its row range.
            dummy_rows = (ell.row_offset[1:] - 1).astype(np.int32)
            part = build_slab_partition(
                np.asarray(ell.val, dtype=dt),
                ell.col,
                ell.chunk_row,
                ell.tile_inst,
                self.batch.lhs1,
                self.batch.rhs1,
                self.batch.is_int,
                self.n_pad,
                s,
                dummy_rows,
            )
            self._slabs[s] = part
        return part


_batch_prep_cache = LRU(maxsize=16)


def prepare_problem_batch(batch: ProblemBatch, dtype=None) -> PreparedBatch:
    """Device transfer + hoisted constant gathers for one packed bucket,
    LRU-cached per ``ProblemBatch`` (maxsize 16, see ``cache_info()``; the
    serving pattern re-propagates the same packed batch with fresh
    bounds -- ``propagate_batch_prepared`` takes them as per-call
    arguments)."""
    ell = batch.ell
    dt = resolve_dtype(dtype, ell.val.dtype)
    key = (id(batch), dt.str)
    hit = _batch_prep_cache.get(key, (batch,))
    if hit is not None:
        return hit

    n_pad = batch.n_pad
    col_g = ell.col + ell.tile_inst[:, None, None] * np.int32(n_pad)
    ii_g = batch.is_int.reshape(-1)[col_g]
    lhs_g = batch.lhs1[ell.chunk_row]
    rhs_g = batch.rhs1[ell.chunk_row]
    col_valid = np.arange(n_pad)[None, :] < ell.n[:, None]
    d = DeviceProblemBatch(
        val=jnp.asarray(ell.val, dtype=dt),
        col=jnp.asarray(ell.col),
        col_g=jnp.asarray(col_g),
        chunk_row=jnp.asarray(ell.chunk_row),
        tile_inst=jnp.asarray(ell.tile_inst),
        ii_g=jnp.asarray(ii_g.astype(np.int32)),
        lhs_g=jnp.asarray(lhs_g.astype(dt)),
        rhs_g=jnp.asarray(rhs_g.astype(dt)),
        lb0=jnp.asarray(batch.lb, dtype=dt),
        ub0=jnp.asarray(batch.ub, dtype=dt),
        col_valid=jnp.asarray(col_valid),
    )
    prep = PreparedBatch(
        batch=batch,
        d=d,
        size=batch.size,
        m_total=batch.m_total,
        n_pad=n_pad,
        fits_one_chunk=all(
            rows_fit_one_chunk(p, ell.tile_width) for p in batch.problems
        ),
    )
    _batch_prep_cache.put(key, (batch,), prep)
    return prep


def batched_reference_round(
    val, col_g, ii_g, chunk_row, lhs_g, rhs_g, lb, ub, active,
    *, m_total: int, n_pad: int, fits_one_chunk: bool,
    eps: float, int_eps: float, inf: float, outward: float = 0.0,
):
    """One batched round at the data level (jnp oracle arithmetic), usable
    under ``shard_map``/``jit`` with the batch axis as a plain leading dim
    of the bound plane.  The whole batch is ONE flat dataflow -- one
    gather, one candidate sweep, one column segment reduction -- so the
    per-op dispatch overhead is paid once per round, not once per instance.
    Inactive instances' candidates are forced to the reduction identity, so
    their bounds pass through unchanged and report no change."""
    if fits_one_chunk:
        best_l, best_u = kref.batched_fused_scatter_round_ref(
            val, col_g, ii_g, lhs_g, rhs_g, lb, ub, n_pad, int_eps, inf
        )
    else:
        best_l, best_u = kref.batched_candidates_scatter_round_ref(
            val, col_g, ii_g, chunk_row, lhs_g, rhs_g, lb, ub,
            m_total, n_pad, int_eps, inf,
        )
    best_l = jnp.where(active[:, None], best_l, -inf)
    best_u = jnp.where(active[:, None], best_u, inf)
    return bnd.apply_updates_batch(lb, ub, best_l, best_u, eps, inf, outward)


def _batched_prepared_round(
    prep: PreparedBatch, lb, ub, active,
    *, eps: float, int_eps: float, inf: float,
    use_pallas: bool, interpret: bool | None, slab: int | None = None,
    outward: float = 0.0,
):
    """One round over a prepared bucket: ``(B, n_pad)`` bounds + ``(B,)``
    active mask -> updated bounds + per-instance changed flags.

    The Pallas path (chunk-complete rows, the paper's common case) runs the
    batched kernel D -- the grid walks the flat tile stream, the
    scalar-prefetched instance map routes each tile to its bound-plane and
    accumulator rows, converged instances are gated off in-kernel -- then
    the batched merge kernel.  Buckets whose ``n_pad`` exceeds the VMEM
    accumulator budget run the slab-partitioned kernels instead (copies
    routed by ``(instance, slab)``, same gating); only buckets with rows
    spanning chunks at small ``n_pad`` use the batched jnp dataflow."""
    d = prep.d
    if use_pallas:
        interpret = kern.resolve_interpret(interpret, lb.dtype)
    if use_pallas and prep.fits_one_chunk and prep.n_pad <= SCATTER_MAX_NPAD:
        best_l, best_u = kern.batched_fused_scatter_round_tiles(
            d.val, d.col, d.ii_g, d.lhs_g, d.rhs_g, lb, ub,
            d.tile_inst, active, prep.n_pad, int_eps, inf, interpret,
        )
        return kern.apply_updates_batch_tiles(
            lb, ub, best_l, best_u, active, eps, inf, interpret, outward
        )
    if use_pallas and prep.n_pad > SCATTER_MAX_NPAD:
        return _partitioned_pallas_round(
            prep.slab_partition(slab), lb, ub, active,
            node=False, eps=eps, int_eps=int_eps, inf=inf, interpret=interpret,
            outward=outward,
        )
    return batched_reference_round(
        d.val, d.col_g, d.ii_g, d.chunk_row, d.lhs_g, d.rhs_g, lb, ub, active,
        m_total=prep.m_total, n_pad=prep.n_pad,
        fits_one_chunk=prep.fits_one_chunk,
        eps=eps, int_eps=int_eps, inf=inf, outward=outward,
    )


def batched_round_fn_for(
    prep: PreparedBatch,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    interpret: bool | None = None,
    slab: int | None = None,
):
    """A jit-able ``(lb, ub, active) -> (lb, ub, changed)`` batched round
    closure over a prepared bucket.  ``slab`` overrides the partitioned
    engine's column-slab width for VMEM-exceeding buckets (ignored
    otherwise)."""
    eps = cfg.eps_for(prep.d.val.dtype)
    return functools.partial(
        _batched_prepared_round,
        prep,
        eps=eps,
        int_eps=cfg.int_eps,
        inf=cfg.inf,
        use_pallas=use_pallas,
        interpret=interpret,
        slab=slab,
        outward=cfg.outward_for(prep.d.val.dtype),
    )


def _unpack_batch_results(
    prep, lb, ub, rounds, converged, infeasible, progress=None, plane=None
):
    out = []
    for i, p in enumerate(prep.batch.problems):
        # Per-instance snapshots share ONE underlying batched plane (row
        # selected lazily by index) -- attaching them costs no readback.
        snap = (
            obs.TelemetrySnapshot(plane=plane, index=i)
            if plane is not None else None
        )
        out.append(
            PropagationResult(
                lb[i, : p.n], ub[i, : p.n], rounds[i], converged[i], infeasible[i],
                progress=jnp.nan if progress is None else progress[i],
                telemetry=snap,
            )
        )
    return out


# Jitted fixed-point runners, cached per prepared bucket + config (maxsize
# 64, see ``cache_info()``): the serving loop re-propagates the same packed
# batches, and rebuilding the jit closure per request would recompile every
# time.  Bounds are runtime arguments of every runner, so one compiled
# fixed point serves any warm-start bound plane.
_batch_runner_cache = LRU(maxsize=64)


def _cached_batch_runner(prep, key, build):
    runner = _batch_runner_cache.get(key, (prep,))
    if runner is None:
        runner = build()
        _batch_runner_cache.put(key, (prep,), runner)
    return runner


def batched_device_runner(
    prep: PreparedBatch,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    interpret: bool | None = None,
    donate: bool | None = None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    telemetry: int | None = None,
):
    """The bucket's whole fixed point as ONE jitted dispatch, cached:
    ``run(lb0, ub0) -> (lb, ub, rounds, converged, infeasible, progress)``
    (all per-instance; ``lb0``/``ub0`` donated where supported).
    ``stop_progress``/``patience`` arm the per-instance progress-based
    early stop inside the dispatch; ``telemetry`` (a ring capacity)
    appends the batched ``obs.TelemetryPlane`` to the return."""
    tel_cap = int(telemetry or 0)
    key = (
        id(prep), cfg, use_pallas, interpret, donate, slab,
        stop_progress, patience, tel_cap, "device",
    )

    def build():
        round_fn = batched_round_fn_for(prep, cfg, use_pallas, interpret, slab)
        if donate is None:
            donate_kw = donate_kwargs(argnums=(0, 1))
        else:
            donate_kw = {"donate_argnums": (0, 1)} if donate else {}
        col_valid = prep.d.col_valid

        @functools.partial(jax.jit, **donate_kw)
        def run(lb0, ub0):
            plane = (
                obs.device_plane(tel_cap, batch=lb0.shape[0], dtype=lb0.dtype)
                if tel_cap else None
            )
            out = batched_fixed_point(
                round_fn, lb0, ub0, cfg.max_rounds,
                stop_progress=stop_progress, patience=patience,
                with_progress=True, plane=plane, feas_eps=cfg.feas_eps,
            )
            lb, ub, rounds, converged, progress = out[:5]
            infeasible = jnp.any((lb > ub + cfg.feas_eps) & col_valid, axis=-1)
            res = (lb, ub, rounds, converged, infeasible, progress)
            return res + ((out[5],) if tel_cap else ())

        return run

    return _cached_batch_runner(prep, key, build)


def _batch_initial_bounds(prep: PreparedBatch, lb0, ub0):
    """Per-call bound planes -> private, donated-safe (B, n_pad) buffers."""
    d = prep.d
    out = []
    for override, default in ((lb0, d.lb0), (ub0, d.ub0)):
        if override is None:
            out.append(owned_copy(default))
            continue
        arr = jnp.asarray(override, d.val.dtype)
        if arr.shape != default.shape:
            raise ValueError(
                f"bound plane has shape {arr.shape}, expected {default.shape}"
            )
        out.append(owned_copy(arr))
    return tuple(out)


def propagate_batch_prepared(
    prep: PreparedBatch,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    driver: str = "device_loop",
    interpret: bool | None = None,
    donate: bool | None = None,
    lb0=None,
    ub0=None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    telemetry: int | None = None,
):
    """Run one prepared bucket to its per-instance fixed points.

    ``device_loop``: the entire batched fixed point is ONE dispatch
    (``batched_fixed_point`` under jit, bounds donated).  ``host_loop``:
    host syncs the per-instance changed flags each round and retires
    converged instances from the active mask.  ``lb0``/``ub0`` warm-start
    the bucket from a caller-supplied ``(B, n_pad)`` bound plane (default:
    the packed instances' root bounds) -- the prepared tiles and the cached
    runner serve any plane.  Returns one ``PropagationResult`` per
    instance, bucket order.  ``telemetry`` (a ring capacity) attaches
    per-instance ``obs.TelemetrySnapshot``s -- device-accumulated on the
    device loop, host-accumulated (this driver syncs every round anyway)
    on the host loop."""
    d = prep.d
    bsz = prep.size
    tel_cap = int(telemetry or 0)

    if driver == "host_loop":
        key = (id(prep), cfg, use_pallas, interpret, donate, slab, tel_cap, "host")

        def build():
            round_fn = batched_round_fn_for(prep, cfg, use_pallas, interpret, slab)
            if donate is None:
                donate_kw = donate_kwargs(argnums=(0, 1))
            else:
                donate_kw = {"donate_argnums": (0, 1)} if donate else {}
            col_valid = prep.d.col_valid

            # Progress is computed INSIDE the jit, where the pre-round
            # bounds are still live (they are donated away by the call).
            def step(lb, ub, active):
                nlb, nub, ch = round_fn(lb, ub, active)
                out = nlb, nub, ch, bnd.progress_measure(lb, ub, nlb, nub)
                if tel_cap:
                    out = out + (
                        jnp.any((nlb > nub + cfg.feas_eps) & col_valid, axis=-1),
                    )
                return out

            return jax.jit(step, **donate_kw)

        jit_round = _cached_batch_runner(prep, key, build)
        lb, ub = _batch_initial_bounds(prep, lb0, ub0)
        active = np.ones(bsz, dtype=bool)
        last_changed = np.ones(bsz, dtype=bool)
        rounds = np.zeros(bsz, dtype=np.int32)
        flat = np.zeros(bsz, dtype=np.int32)
        progress = np.full(bsz, np.nan)
        histories: list[list[float]] = [[] for _ in range(bsz)]
        stop_round = np.full(bsz, -1, np.int32)
        infeas_round = np.full(bsz, -1, np.int32)
        while active.any():
            ran = active
            lb, ub, ch, prog, *inf_dev = jit_round(lb, ub, jnp.asarray(active))
            ch = np.asarray(ch)  # the per-round host<->device sync point
            prog = np.asarray(prog)
            rounds += active
            last_changed = np.where(active, ch, last_changed)
            progress = np.where(active, prog, progress)
            active = active & ch & (rounds < cfg.max_rounds)
            if stop_progress is not None:
                flat = np.where(ran & (prog < stop_progress), flat + 1, 0)
                stopped = ran & (flat >= patience)
                stop_round = np.where(
                    stopped & (stop_round < 0), rounds, stop_round
                )
                active = active & (flat < patience)
            if tel_cap:
                inf_now = np.asarray(inf_dev[0])
                infeas_round = np.where(
                    ran & inf_now & (infeas_round < 0), rounds, infeas_round
                )
                for i in np.flatnonzero(ran):
                    histories[i].append(float(prog[i]))
        infeasible = np.asarray(
            jnp.any((lb > ub + cfg.feas_eps) & d.col_valid, axis=-1)
        )
        results = _unpack_batch_results(
            prep, lb, ub, rounds, ~last_changed, infeasible, progress
        )
        if tel_cap:
            results = [
                r._replace(telemetry=obs.host_snapshot(
                    histories[i], tel_cap,
                    stop_round=int(stop_round[i]),
                    infeas_round=int(infeas_round[i]),
                ))
                for i, r in enumerate(results)
            ]
        return results

    if driver != "device_loop":
        raise ValueError(f"unknown driver: {driver!r}")

    run = batched_device_runner(
        prep, cfg, use_pallas, interpret, donate, slab, stop_progress, patience,
        telemetry=tel_cap,
    )
    lb_init, ub_init = _batch_initial_bounds(prep, lb0, ub0)
    out = run(lb_init, ub_init)
    lb, ub, rounds, converged, infeasible, progress = out[:6]
    plane = out[6] if tel_cap else None
    return _unpack_batch_results(
        prep, lb, ub, rounds, converged, infeasible, progress, plane=plane
    )


# Packed-batch cache (maxsize 8, see ``cache_info()``): serving
# re-propagates the same request list, and repacking would defeat both the
# prepare() and the runner caches (both key on object identity).
_pack_cache = LRU(maxsize=8)


def packed_problems(problems, tile_rows: int = 8, tile_width: int = 128):
    """LRU-cached ``pack_problems``: the same problem list (by identity)
    packs once and reuses its ``ProblemBatch`` objects across calls."""
    problems = list(problems)
    anchors = tuple(problems)
    key = (tuple(id(p) for p in problems), tile_rows, tile_width)
    hit = _pack_cache.get(key, anchors)
    if hit is not None:
        return hit
    batches = pack_problems(problems, tile_rows=tile_rows, tile_width=tile_width)
    _pack_cache.put(key, anchors, batches)
    return batches


def clear_batch_caches() -> None:
    """Drop packed batches, prepared buckets and jitted runners."""
    _pack_cache.clear()
    _batch_prep_cache.clear()
    _batch_runner_cache.clear()


def cache_info() -> dict:
    """Hit/miss/size/maxsize counters of every engine-level LRU cache
    (prepared instances, compiled single-instance runners, packed batches,
    prepared buckets, batched runners, node-batch runners).  Complements
    the ``clear_*`` helpers; sizes are entry counts, not bytes."""
    return {
        "prepare_block_ell": _prep_cache.info(),
        "block_ell_runner": _runner_cache.info(),
        "packed_problems": _pack_cache.info(),
        "prepare_problem_batch": _batch_prep_cache.info(),
        "batch_runner": _batch_runner_cache.info(),
        "node_runner": _node_runner_cache.info(),
    }


def _bound_planes_for_batch(batch: ProblemBatch, bounds):
    """Per-problem ``(lb, ub)`` overrides -> this bucket's (B, n_pad) planes.

    ``bounds[i]`` (input order) is either ``None`` (use problem ``i``'s own
    bounds) or a ``(lb, ub)`` pair of ``(n_i,)`` arrays."""
    lb_plane = np.array(batch.lb, copy=True)
    ub_plane = np.array(batch.ub, copy=True)
    touched = False
    for row, (idx, p) in enumerate(zip(batch.indices, batch.problems)):
        pair = bounds[idx]
        if pair is None:
            continue
        lb_i, ub_i = pair
        lb_i = np.asarray(lb_i, lb_plane.dtype)
        ub_i = np.asarray(ub_i, ub_plane.dtype)
        if lb_i.shape != (p.n,) or ub_i.shape != (p.n,):
            raise ValueError(
                f"bounds for instance {idx} have shapes {lb_i.shape}/{ub_i.shape}, "
                f"expected {(p.n,)}"
            )
        lb_plane[row, : p.n] = lb_i
        ub_plane[row, : p.n] = ub_i
        touched = True
    if not touched:
        return None, None
    return lb_plane, ub_plane


def propagate_batch_block_ell(
    problems,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    tile_rows: int = 8,
    tile_width: int = 128,
    dtype=None,
    use_pallas: bool = True,
    driver: str = "device_loop",
    interpret: bool | None = None,
    donate: bool | None = None,
    bounds=None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    policy: TierPolicy | None = None,
    telemetry: int | None = None,
):
    """Batched kernel-backed propagation: pack -> per-bucket dispatch ->
    per-instance results in input order.  Packing, device transfer and the
    jitted fixed-point runners are all LRU-cached, so a serving loop that
    re-propagates the same instances pays them once.  ``bounds`` (one
    ``(lb, ub)`` pair or ``None`` per problem, input order) warm-starts
    instances from caller bounds through the SAME packed tiles and compiled
    runners -- nothing is repacked or recompiled.  The public front end is
    ``repro.core.propagate_batch``.

    ``stop_progress``/``patience`` arm the per-instance progress-based
    early stop; ``policy`` (a :class:`TierPolicy`) runs the whole batch
    through the two-tier precision scheme -- an fp32 pass (outward-rounded
    merges) until each instance's progress drops below
    ``policy.switch_progress``, then an exact-cast warm start of the
    requested-dtype engine through the same packed batches.  ``telemetry``
    (a ring capacity) attaches per-instance device telemetry snapshots;
    each bucket's instances share one batched plane (zero extra
    readbacks), and under ``policy`` the fp32 tier's snapshot hangs off
    the endgame snapshot's ``.fp32``."""
    problems = list(problems)
    pair = two_tier_bounds_dtypes(policy, dtype) if policy is not None else None
    if pair is not None:
        dt32, final = pair
        kw = dict(
            tile_rows=tile_rows, tile_width=tile_width, use_pallas=use_pallas,
            driver=driver, interpret=interpret, donate=donate, slab=slab,
            patience=policy.patience, telemetry=telemetry,
        )
        cap32 = max(1, int(cfg.max_rounds * policy.fp32_round_frac))
        r32 = propagate_batch_block_ell(
            problems, dataclasses.replace(cfg, max_rounds=cap32),
            dtype=dt32, bounds=bounds,
            stop_progress=policy.switch_progress, **kw,
        )
        # Per-instance promotion, except that an instance whose fp32 tier
        # declared infeasibility restarts from its ORIGINAL bounds (fp32
        # verdicts are never trusted -- see core.propagator).
        orig = bounds if bounds is not None else [None] * len(problems)
        warm = [
            None if bool(t.infeasible) else bnd.canonical_infinite(
                jnp.asarray(t.lb, final), jnp.asarray(t.ub, final)
            )
            for t in r32
        ]
        warm = [w if w is not None else o for w, o in zip(warm, orig)]
        rem = dataclasses.replace(cfg, max_rounds=max(1, cfg.max_rounds - cap32))
        res = propagate_batch_block_ell(
            problems, rem, dtype=final, bounds=warm,
            stop_progress=policy.stop_progress, **kw,
        )
        def _combine_tel(r, t):
            if r.telemetry is None:
                return None
            return dataclasses.replace(
                r.telemetry,
                tier_switch_round=(
                    -1 if bool(t.infeasible) else int(t.rounds)
                ),
                fp32=t.telemetry,
            )
        return [
            r._replace(
                rounds=r.rounds + (0 if bool(t.infeasible) else t.rounds),
                tier_rounds=t.rounds,
                telemetry=_combine_tel(r, t),
            )
            for r, t in zip(res, r32)
        ]
    if policy is not None:
        stop_progress = policy.stop_progress
        patience = policy.patience
    if bounds is not None:
        bounds = list(bounds)
        if len(bounds) != len(problems):
            raise ValueError(
                f"bounds has {len(bounds)} entries for {len(problems)} problems"
            )
    batches = packed_problems(problems, tile_rows=tile_rows, tile_width=tile_width)
    out = [None] * len(problems)
    for batch in batches:
        prep = prepare_problem_batch(batch, dtype)
        lb0 = ub0 = None
        if bounds is not None:
            lb0, ub0 = _bound_planes_for_batch(batch, bounds)
        results = propagate_batch_prepared(
            prep, cfg, use_pallas=use_pallas, driver=driver,
            interpret=interpret, donate=donate, lb0=lb0, ub0=ub0, slab=slab,
            stop_progress=stop_progress, patience=patience,
            telemetry=telemetry,
        )
        for idx, res in zip(batch.indices, results):
            out[idx] = res
    return out


# ---------------------------------------------------------------------------
# Node-batch engine: one shared matrix, many bound planes (tree search)
# ---------------------------------------------------------------------------


def _node_round(
    prep: PreparedBlockEll, lb, ub, active,
    *, eps: float, int_eps: float, inf: float,
    use_pallas: bool, interpret: bool | None, slab: int | None = None,
    outward: float = 0.0,
):
    """One round over a node batch: ``(B, n_pad)`` per-node bounds +
    ``(B,)`` active mask -> updated bounds + per-node changed flags, with
    the instance's matrix tiles shared by every node.

    The Pallas path (chunk-complete rows, accumulator budget respected)
    runs the node kernel -- the grid walks ``(B, T)`` with the tile axis
    minor, converged nodes gated off in-kernel -- then the batched merge
    kernel.  Nodes of a VMEM-exceeding instance (``n_pad`` beyond the
    accumulator budget) run the slab-partitioned node kernels on a
    ``(B, T')`` grid over the per-slab copies, same gating.  Otherwise the
    single-instance jnp round is vmapped over the node axis (multichunk
    rows at small ``n_pad``, or ``use_pallas=False``), with inactive
    nodes' bounds frozen outside."""
    if use_pallas:
        interpret = kern.resolve_interpret(interpret, lb.dtype)
    if use_pallas and prep.fits_one_chunk and prep.n_pad <= SCATTER_MAX_NPAD:
        d = prep.d
        best_l, best_u = kern.node_fused_scatter_round_tiles(
            d.val, d.col, prep.ii_g, prep.lhs_g, prep.rhs_g, lb, ub,
            active, prep.n_pad, int_eps, inf, interpret,
        )
        return kern.apply_updates_batch_tiles(
            lb, ub, best_l, best_u, active, eps, inf, interpret, outward
        )
    if use_pallas and prep.n_pad > SCATTER_MAX_NPAD:
        return _partitioned_pallas_round(
            prep.slab_partition(slab), lb, ub, active,
            node=True, eps=eps, int_eps=int_eps, inf=inf, interpret=interpret,
            outward=outward,
        )
    single = functools.partial(
        _prepared_round,
        prep,
        eps=eps,
        int_eps=int_eps,
        inf=inf,
        use_pallas=False,
        fused=prep.fits_one_chunk,
        scatter=_resolve_scatter("auto", prep),
        interpret=interpret,
        outward=outward,
    )
    new_lb, new_ub, changed = jax.vmap(single)(lb, ub)
    lb = jnp.where(active[:, None], new_lb, lb)
    ub = jnp.where(active[:, None], new_ub, ub)
    return lb, ub, changed & active


def node_round_fn_for(
    prep: PreparedBlockEll,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    interpret: bool | None = None,
    slab: int | None = None,
):
    """A jit-able ``(lb, ub, active) -> (lb, ub, changed)`` node-batch
    round closure over a prepared instance (bounds ``(B, n_pad)``).
    ``slab`` overrides the partitioned engine's column-slab width for
    VMEM-exceeding instances (ignored otherwise)."""
    eps = cfg.eps_for(prep.d.val.dtype)
    return functools.partial(
        _node_round,
        prep,
        eps=eps,
        int_eps=cfg.int_eps,
        inf=cfg.inf,
        use_pallas=use_pallas,
        interpret=interpret,
        slab=slab,
        outward=cfg.outward_for(prep.d.val.dtype),
    )


# Node-batch fixed-point runners, cached per matrix structure + node count +
# config (maxsize 32, see ``cache_info()``): a tree search re-propagates the
# same instance with fresh node bounds every dive, and the bounds are
# runtime arguments, so each (structure, B) pair compiles exactly once.
_node_runner_cache = LRU(maxsize=32)


def node_batch_runner(
    prep: PreparedBlockEll,
    batch_size: int,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    interpret: bool | None = None,
    donate: bool | None = None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    telemetry: int | None = None,
):
    """The node batch's whole fixed point as ONE jitted dispatch, cached:
    ``fixed_point_nodes(lb0, ub0) -> (lb, ub, rounds, converged,
    infeasible, progress)`` with the node axis leading everywhere
    (``lb0``/``ub0`` donated where supported).  ``stop_progress``/
    ``patience`` arm the per-node progress-based early stop inside the
    dispatch; ``telemetry`` (a ring capacity) appends the per-node
    ``obs.TelemetryPlane`` to the return.  Notes ``built`` (a cache miss)
    on the program tracer's open span."""
    do_donate = donate_supported() if donate is None else bool(donate)
    tel_cap = int(telemetry or 0)
    key = (
        id(prep.d.val), batch_size, cfg, use_pallas, interpret, do_donate, slab,
        stop_progress, patience, tel_cap,
    )
    anchors = (prep.d.val,)
    runner = _node_runner_cache.get(key, anchors)
    PROGRAM.note(built=runner is None)
    if runner is not None:
        return runner

    round_fn = node_round_fn_for(prep, cfg, use_pallas, interpret, slab)
    donate_kw = {"donate_argnums": (0, 1)} if do_donate else {}
    col_valid = jnp.arange(prep.n_pad) < prep.n

    @functools.partial(jax.jit, **donate_kw)
    def fixed_point_nodes(lb0, ub0):
        plane = (
            obs.device_plane(tel_cap, batch=lb0.shape[0], dtype=lb0.dtype)
            if tel_cap else None
        )
        out = batched_fixed_point(
            round_fn, lb0, ub0, cfg.max_rounds,
            stop_progress=stop_progress, patience=patience, with_progress=True,
            plane=plane, feas_eps=cfg.feas_eps,
        )
        lb, ub, rounds, converged, progress = out[:5]
        with jax.named_scope("fixed_point"):
            infeasible = jnp.any((lb > ub + cfg.feas_eps) & col_valid[None, :], axis=-1)
        res = (lb, ub, rounds, converged, infeasible, progress)
        return res + ((out[5],) if tel_cap else ())

    _node_runner_cache.put(key, anchors, fixed_point_nodes)
    return fixed_point_nodes


def propagate_nodes_prepared(
    prep: PreparedBlockEll,
    lb_nodes,
    ub_nodes,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    use_pallas: bool = True,
    interpret: bool | None = None,
    donate: bool | None = None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    with_progress: bool = False,
    telemetry: int | None = None,
):
    """Run B warm-started nodes of one prepared instance to their fixed
    points in ONE dispatch.

    ``lb_nodes``/``ub_nodes`` are ``(B, n)`` per-node bound planes (the
    only per-node state -- the matrix tiles are resident once).  Returns
    ``(lb, ub, rounds, converged, infeasible)`` with the node axis leading
    (``with_progress=True`` appends the ``(B,)`` last-round progress
    measure); ``infeasible`` marks nodes whose domain emptied (prune
    them).  ``stop_progress``/``patience`` arm the per-node progress-based
    early stop.  Each node's result is exactly what its own
    single-instance warm-started ``propagate_block_ell`` run would
    produce, including round counts.  ``telemetry`` (a ring capacity)
    appends the per-node batched ``obs.TelemetryPlane`` to either return
    shape -- wrap rows in ``obs.TelemetrySnapshot(plane, index=i)`` to
    read one node's trajectory.

    The two planes go up under one ``prop.upload`` span (site ``nodes``);
    runner lookup and dispatch are one ``prop.launch`` span."""
    lb_nodes = np.asarray(lb_nodes)
    ub_nodes = np.asarray(ub_nodes)
    if lb_nodes.ndim != 2 or lb_nodes.shape != ub_nodes.shape:
        raise ValueError(
            f"node bound planes must share a (B, n) shape, got "
            f"{lb_nodes.shape} / {ub_nodes.shape}"
        )
    bsz, n = lb_nodes.shape
    if n != prep.n:
        raise ValueError(f"node bounds have n={n}, instance has n={prep.n}")
    dt = prep.d.val.dtype
    pad = prep.n_pad - prep.n
    planes = []
    for plane in (lb_nodes, ub_nodes):
        plane = np.asarray(plane, dt)
        if pad:
            plane = np.concatenate([plane, np.zeros((bsz, pad), dt)], axis=1)
        planes.append((plane, None))
    planes = _upload("nodes", *planes)
    tel_cap = int(telemetry or 0)
    with PROGRAM.span("prop.launch"):
        run = node_batch_runner(
            prep, bsz, cfg, use_pallas, interpret, donate, slab,
            stop_progress, patience, telemetry=tel_cap,
        )
        res = run(*planes)
    lb, ub, rounds, converged, infeasible, progress = res[:6]
    out = (lb[:, : prep.n], ub[:, : prep.n], rounds, converged, infeasible)
    if with_progress:
        out = out + (progress,)
    if tel_cap:
        out = out + (res[6],)
    return out


# ---------------------------------------------------------------------------
# Measured bytes-per-round (XLA cost analysis, not assertions)
# ---------------------------------------------------------------------------


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    size = 1
    for s in shape:
        size *= int(s)
    return size * np.dtype(aval.dtype).itemsize


# Structural primitives whose own operands are pass-through loop/call state:
# recurse into their bodies (counted once, as HloCostAnalysis does for while
# bodies) instead of counting the carried tuple.
_RECURSE_PRIMS = frozenset(
    {"pjit", "closed_call", "custom_jvp_call", "custom_vjp_call", "while", "cond", "scan"}
)
_INNER_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr", "branches")


def _inner_jaxprs(eqn):
    out = []
    for name in _INNER_JAXPR_PARAMS:
        v = eqn.params.get(name)
        if v is None:
            continue
        for j in v if isinstance(v, (list, tuple)) else [v]:
            out.append(j.jaxpr if hasattr(j, "jaxpr") else j)
    return out


def hbm_bytes_of(fn, *args) -> float:
    """HBM-boundary bytes-accessed of ``fn``, measured from its traced jaxpr.

    Every XLA op counts operand + result bytes -- the same per-instruction
    definition XLA's ``HloCostAnalysis`` uses.  A ``pallas_call`` counts its
    operands + results only: that is exactly the traffic the kernel DMAs
    between HBM and VMEM, while kernel-internal values are VMEM/register
    resident by construction (the interpret-mode emulation would otherwise
    misattribute them as memory traffic).
    """
    closed = jax.make_jaxpr(fn)(*args)

    def walk(jaxpr) -> float:
        total = 0.0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in _RECURSE_PRIMS:
                for inner in _inner_jaxprs(eqn):
                    total += walk(inner)
                continue
            total += sum(
                _aval_bytes(v.aval)
                for v in list(eqn.invars) + list(eqn.outvars)
                if hasattr(v, "aval")
            )
        return total

    return walk(closed.jaxpr)


def round_cost_analysis(
    p: Problem,
    scatter: str = "fused",
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    tile_rows: int = 8,
    tile_width: int = 128,
    dtype=None,
    interpret: bool | None = None,
    include_compiled: bool = False,
) -> dict:
    """Measure ONE propagation round's memory traffic.

    ``scatter`` selects the dataflow being measured:
      * ``"fused"``       -- the fully fused in-VMEM gather+round+reduction;
      * ``"partitioned"`` -- the column-slab engine (per-slab tile copies,
        two-phase, slab-windowed scatter) that replaces ``fused`` beyond
        the VMEM accumulator budget;
      * ``"segment"``     -- candidates materialized + XLA segment
        reduction, with hoisted constant gathers;
      * ``"legacy"``      -- the seed round verbatim (``block_ell_round``):
        per-round constant gathers + materialized candidates.

    Returns a dict with
      * ``bytes_accessed``: HBM-boundary bytes (see ``hbm_bytes_of``) -- the
        number the fused engine is designed to shrink;
      * with ``include_compiled=True``, also ``bytes_accessed_compiled`` /
        ``flops``: the raw aggregate from ``Compiled.cost_analysis()`` on
        this backend's lowering, reported for transparency (on CPU it
        includes interpret-mode emulation buffers that a TPU kernel keeps in
        VMEM; computing it pays a full XLA compile, hence opt-in).
    """
    prep = prepare_block_ell(p, tile_rows, tile_width, dtype)
    val_dtype = prep.d.val.dtype
    if scatter == "legacy":
        fn = legacy_round_fn_for(prep, cfg, use_pallas=True, interpret=interpret)
        shape = (prep.n,)
    else:
        fn = round_fn_for(prep, cfg, use_pallas=True, scatter=scatter, interpret=interpret)
        shape = (prep.n_pad,)
    sds = jax.ShapeDtypeStruct(shape, val_dtype)
    out = {"bytes_accessed": hbm_bytes_of(fn, sds, sds)}
    if include_compiled:
        compiled = jax.jit(fn).lower(sds, sds).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        out["bytes_accessed_compiled"] = float(ca.get("bytes accessed", 0.0))
        out["flops"] = float(ca.get("flops", 0.0))
    return out
