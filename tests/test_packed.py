"""The packed tile stream of the fused engine: short rows share chunk rows.

Three layers, on the CPU in interpret mode:
  * packing invariants: every nonzero lands once, the rows of a packed
    chunk row are contiguous segments that share no column, rows longer
    than ``K`` (or repeating a column) keep their own chunks in the long
    sub-stream, and ``fused="auto"`` packs whatever the rows' lengths;
  * the ``prop_packed_round`` kernel against ``ref.packed_round_ref``;
  * the engine against the float64 sequential oracle, in float64 and
    within the float32 band, on random ``mixed`` instances and on edge
    rows (length 1, exactly ``K``, ``K + 1``, empty, all-infinite bounds,
    rows that share columns).
"""
import collections

import numpy as np
import pytest

from repro.core import (
    F32_BAND,
    INF,
    Problem,
    bounds_equal,
    csr_from_coo,
    overtightening,
    propagate_sequential,
)
from repro.core.nodes import propagate_nodes
from repro.data import make_mixed
from repro.kernels import (
    col_pad,
    packed_round_tiles,
    prepare_block_ell,
    propagate_block_ell,
)
from repro.kernels import ref as kref
from repro.obs.trace import PROGRAM

K = 16


def _problem(rows, n, seed=0, all_inf=False):
    """A problem whose row ``i`` has the columns ``rows[i]`` (repeats
    allowed), coefficients ``±{1, 2, 3}`` and sides scaled to the row."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(len(rows)), [len(c) for c in rows]).astype(np.int32)
    c = np.concatenate([np.asarray(x, np.int32) for x in rows] + [np.zeros(0, np.int32)])
    v = rng.choice([-3.0, -2.0, -1.0, 1.0, 2.0, 3.0], size=r.size)
    csr = csr_from_coo(r, c, v, len(rows), n)
    if all_inf:
        lb, ub = np.full(n, -INF), np.full(n, INF)
    else:
        lb = -rng.integers(0, 3, size=n).astype(np.float64)
        ub = rng.integers(1, 8, size=n).astype(np.float64)
        lb[rng.random(n) < 0.1] = -INF
        ub[rng.random(n) < 0.1] = INF
    size = np.bincount(r, np.abs(v), minlength=len(rows)) * 2.0
    lhs = np.where(rng.random(len(rows)) < 0.4, -INF, -size * rng.uniform(0.1, 0.5))
    rhs = np.where(rng.random(len(rows)) < 0.2, INF, size * rng.uniform(0.1, 0.5))
    return Problem(csr=csr, lhs=lhs, rhs=rhs, lb=lb, ub=ub, is_int=rng.random(n) < 0.5)


def _edge_rows(n=40):
    """Length 1, exactly K, K + 1, empty rows, a row that repeats a column,
    rows that share columns, then short random rows."""
    rng = np.random.default_rng(3)
    rows = [[5], list(range(K)), list(range(3, 3 + K + 1)), [], [7, 7, 9], []]
    rows += [list(range(20, 25))] * 6  # share every column
    rows += [sorted(rng.choice(n, size=int(rng.integers(1, 6)), replace=False))
             for _ in range(60)]
    return rows, n


def _packing_instances():
    rows, n = _edge_rows()
    return [
        ("edge", _problem(rows, n)),
        ("mixed", make_mixed(m=80, n=60, seed=0)),
        ("mixed1", make_mixed(m=120, n=70, seed=4)),
    ]


def _stream(p, k=K):
    return prepare_block_ell(p, 4, k).packed_stream()


@pytest.mark.parametrize("name,p", _packing_instances())
def test_every_nonzero_lands_once(name, p):
    s = _stream(p)
    row = np.repeat(np.arange(p.m), np.diff(p.csr.row_ptr))
    want = collections.Counter(zip(p.csr.col.tolist(), p.csr.val.tolist(),
                                   p.lhs[row].tolist(), p.rhs[row].tolist()))
    got = collections.Counter()
    v, c = np.asarray(s.val), np.asarray(s.col)
    nz = v != 0
    got.update(zip(c[nz].tolist(), v[nz].tolist(), np.asarray(s.lhs_s)[nz].tolist(),
                   np.asarray(s.rhs_s)[nz].tolist()))
    lv, lc = np.asarray(s.l_val), np.asarray(s.l_col)
    lnz = lv != 0
    side = lambda x: np.broadcast_to(np.asarray(x)[..., None], lv.shape)[lnz].tolist()
    got.update(zip(lc[lnz].tolist(), lv[lnz].tolist(), side(s.l_lhs), side(s.l_rhs)))
    assert got == want, name
    assert s.slots == s.val.size + s.l_val.size
    assert np.asarray(s.ii_g)[nz].tolist() == np.asarray(p.is_int)[c[nz]].astype(int).tolist()


@pytest.mark.parametrize("name,p", _packing_instances())
def test_packed_rows_are_disjoint_segments(name, p):
    s = _stream(p)
    v = np.asarray(s.val).reshape(-1, K)
    c = np.asarray(s.col).reshape(-1, K)
    seg = np.asarray(s.seg).reshape(-1, K).astype(np.int64)
    lhs = np.asarray(s.lhs_s).reshape(-1, K)
    assert ((seg < 0) == (v == 0)).all(), name  # padding is exactly seg -1
    for i in range(v.shape[0]):
        ids = seg[i][seg[i] >= 0]
        # Segments are contiguous, numbered in order, then padding.
        assert (np.diff(ids) >= 0).all() and (np.diff(ids) <= 1).all()
        assert ids.size == 0 or (ids[0] == 0 and (seg[i][ids.size:] < 0).all())
        cols = c[i][seg[i] >= 0]
        assert np.unique(cols).size == cols.size, (name, i)  # column-disjoint
        for g in np.unique(ids):
            assert np.unique(lhs[i][seg[i] == g]).size == 1  # one row's sides


def test_long_and_repeating_rows_keep_their_chunks():
    rows, n = _edge_rows()
    s = _stream(_problem(rows, n))
    # Row 2 (K + 1 nonzeros) and row 4 (repeats column 7) are the long ones.
    assert s.n_long == 2
    assert s.long_chunks == 2 + 1
    lr = np.asarray(s.l_row)
    lv = np.asarray(s.l_val)
    assert sorted(np.unique(lr[lr < s.n_long]).tolist()) == [0, 1]
    assert ((lv != 0).any(axis=-1) == (lr < s.n_long)).all()
    assert (np.asarray(s.val) != 0).sum() == sum(len(r) for r in rows) - (K + 1) - 3


def _shape_cases():
    """Short rows (packed many to a chunk row), rows of exactly K (one to a
    chunk row) and rows longer than K (the long sub-stream alone)."""
    return [
        ("short", _problem([[i % 30, (i + 1) % 30] for i in range(64)], 30), 2 * 64, 0),
        ("full", _problem([list(range(i, i + K)) for i in range(12)], 40), 12 * K, 0),
        ("long", _problem([list(range(i, i + K + 2)) for i in range(12)], 40), 0, 12),
    ]


@pytest.mark.parametrize("name,p,packed_nnz,n_long", _shape_cases())
def test_auto_packs_whatever_the_row_lengths(name, p, packed_nnz, n_long):
    s = _stream(p)
    assert (np.asarray(s.val) != 0).sum() == packed_nnz, name
    assert s.n_long == n_long and s.long_chunks == 2 * n_long, name
    a = propagate_sequential(p)
    b = propagate_block_ell(p, tile_rows=4, tile_width=K)
    call = [x for x in PROGRAM.spans() if x.name == "prop.presolve"][-1]
    assert call.attrs["packed"] is True
    assert bool(a.infeasible) == bool(b.infeasible), name
    if not bool(a.infeasible):
        assert bounds_equal(a.lb, a.ub, b.lb, b.ub), name


def test_packed_stream_is_never_built_off_the_packed_path():
    p = make_mixed(m=60, n=45, seed=2)
    for kw in (dict(use_pallas=False), dict(fused="no"), dict(scatter="segment"),
               dict(scatter="partitioned", slab=128)):
        propagate_block_ell(p, tile_rows=4, tile_width=K, **kw)
        assert not prepare_block_ell(p, 4, K)._packed, kw
    lb = np.repeat(np.asarray(p.lb)[None], 2, axis=0)
    ub = np.repeat(np.asarray(p.ub)[None], 2, axis=0)
    propagate_nodes(p, lb, ub)  # the node engine keeps the block-ELL tiles
    assert not prepare_block_ell(p)._packed
    propagate_block_ell(p, tile_rows=4, tile_width=K)
    assert "stream" in prepare_block_ell(p, 4, K)._packed


def test_presolve_span_counts_slots_and_nnz():
    p = make_mixed(m=60, n=45, seed=5)
    prepare_block_ell(p, 4, K)  # a build first: the call below is a hit
    propagate_block_ell(p, tile_rows=4, tile_width=K)
    spans = PROGRAM.spans()
    call = [s for s in spans if s.name == "prop.presolve"][-1]
    s = _stream(p)
    assert call.attrs["slots"] == s.slots and call.attrs["nnz"] == p.nnz
    assert call.attrs["packed"] is True
    propagate_block_ell(p, tile_rows=4, tile_width=K, fused="no")
    call = [s for s in PROGRAM.spans() if s.name == "prop.presolve"][-1]
    assert call.attrs["slots"] == prepare_block_ell(p, 4, K).d.val.size
    assert call.attrs["packed"] is False


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("name,p", _packing_instances())
def test_packed_kernel_matches_ref(name, p, dtype, rng):
    """Integer coefficients and bounds on a half-integer grid keep every
    row sum exact in either summation order, so kernel and oracle agree
    bitwise."""
    s = prepare_block_ell(p, 4, K, dtype).packed_stream()
    n_pad = col_pad(p.n)
    lb = rng.integers(-6, 1, size=n_pad).astype(dtype) / 2
    ub = rng.integers(0, 7, size=n_pad).astype(dtype) / 2
    lb[rng.random(n_pad) < 0.15] = -INF
    ub[rng.random(n_pad) < 0.15] = INF
    args = (s.val, s.col, s.ii_g, s.seg, s.lhs_s, s.rhs_s, lb, ub, n_pad)
    got = packed_round_tiles(*args, int_eps=1e-6, interpret=True)
    want = kref.packed_round_ref(*args, int_eps=1e-6)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_packed_kernel_matches_ref_on_wide_values(rng):
    """Non-integer float32 bounds: the segment sums run in another order
    than the oracle's, so the two agree to float32 rounding."""
    p = make_mixed(m=120, n=70, seed=4)
    s = prepare_block_ell(p, 4, K, np.float32).packed_stream()
    n_pad = col_pad(p.n)
    lb = rng.uniform(-50, 0, size=n_pad).astype(np.float32)
    ub = rng.uniform(0, 50, size=n_pad).astype(np.float32)
    args = (s.val, s.col, np.zeros_like(s.ii_g), s.seg, s.lhs_s, s.rhs_s, lb, ub, n_pad)
    got = packed_round_tiles(*args, int_eps=1e-6, interpret=True)
    want = kref.packed_round_ref(*args, int_eps=1e-6)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-3)


# ---------------------------------------------------------------------------
# Engine vs the float64 oracle
# ---------------------------------------------------------------------------


def _engine_cases():
    rows, n = _edge_rows()
    return [
        ("edge", _problem(rows, n, seed=1)),
        ("edge_inf", _problem(rows, n, seed=2, all_inf=True)),
        ("mixed", make_mixed(m=80, n=60, seed=0)),
        ("mixed7", make_mixed(m=100, n=50, seed=7)),
    ]


@pytest.mark.parametrize("name,p", _engine_cases())
def test_packed_engine_matches_oracle(name, p):
    a = propagate_sequential(p)
    b = propagate_block_ell(p, tile_rows=4, tile_width=K)
    assert bool(a.infeasible) == bool(b.infeasible), name
    if not bool(a.infeasible):
        assert bounds_equal(a.lb, a.ub, b.lb, b.ub), name


@pytest.mark.parametrize("name,p", _engine_cases())
def test_packed_engine_float32_within_band(name, p):
    """At float32 the packed engine never tightens past the float64
    oracle by more than the float32 band, and finds its verdict."""
    a = propagate_sequential(p)
    b = propagate_block_ell(p, tile_rows=4, tile_width=K, dtype=np.float32)
    if bool(b.infeasible):
        assert bool(a.infeasible), name
        return
    assert not bool(a.infeasible), name
    msg = overtightening(b.lb, b.ub, np.asarray(a.lb), np.asarray(a.ub),
                         np.asarray(p.is_int, bool), F32_BAND)
    assert msg is None, f"{name}: {msg}"
