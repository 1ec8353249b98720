"""Fused-scatter engine validation.

Three layers:
  * kernel vs oracle: the in-VMEM gather/scatter kernels (D, E, A', F)
    against their jnp oracles in ``ref.py``, interpret mode;
  * engine vs engine: ``scatter='fused'`` is ``bounds_equal`` to the
    segment-op engine and to ``seq_ref`` on random instances, including
    empty columns, all-infinite bounds, and rows spanning multiple chunks;
  * prepare(): instance caching and donation-safety of the cached bounds.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    INF,
    Problem,
    bounds_equal,
    csr_from_coo,
    propagate_sequential,
)
from repro.core import bounds as bnd
from repro.data import make_cascade_chain, make_knapsack, make_mixed, make_set_cover
from repro.kernels import (
    activities_tiles,
    apply_updates_tiles,
    candidates_scatter_tiles,
    col_pad,
    fused_scatter_round_tiles,
    prepare_block_ell,
    propagate_block_ell,
)
from repro.kernels import ref as kref


# ---------------------------------------------------------------------------
# Kernel vs oracle
# ---------------------------------------------------------------------------


def _tiles(rng, t, r, k, n, dtype=np.float64, inf_frac=0.15):
    """Random candidate-kernel inputs with block-ELL conventions
    (val == 0 and col == 0 mark padding)."""
    val = rng.choice([-2.0, -1.0, 0.0, 1.0, 3.0], size=(t, r, k)).astype(dtype)
    col = rng.integers(0, n, size=(t, r, k)).astype(np.int32)
    col[val == 0] = 0
    n_pad = col_pad(n)
    lb = rng.uniform(-5, 0, size=n_pad).astype(dtype)
    ub = rng.uniform(0, 5, size=n_pad).astype(dtype)
    lb[rng.random(n_pad) < inf_frac] = -INF
    ub[rng.random(n_pad) < inf_frac] = INF
    ii = rng.random((t, r, k)) < 0.5
    lhs = rng.uniform(-10, 0, size=(t, r)).astype(dtype)
    rhs = rng.uniform(0, 10, size=(t, r)).astype(dtype)
    j = jnp.asarray
    return j(val), j(col), j(lb), j(ub), j(ii), j(lhs), j(rhs), n_pad


@pytest.mark.parametrize("t,r,k,n", [(1, 2, 4, 3), (3, 4, 8, 20), (2, 8, 16, 150)])
def test_fused_scatter_kernel_matches_ref(t, r, k, n, rng):
    val, col, lb, ub, ii, lhs, rhs, n_pad = _tiles(rng, t, r, k, n)
    got = fused_scatter_round_tiles(
        val, col, ii, lhs, rhs, lb, ub, n_pad, int_eps=1e-6, interpret=True
    )
    want = kref.fused_scatter_round_tiles_ref(
        val, col, ii, lhs, rhs, lb, ub, n_pad, int_eps=1e-6
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("t,r,k,n", [(2, 2, 4, 10), (3, 4, 8, 140)])
def test_candidates_scatter_kernel_matches_ref(t, r, k, n, rng):
    val, col, lb, ub, ii, lhs, rhs, n_pad = _tiles(rng, t, r, k, n)
    mf, mc, xf, xc = kref.activities_tiles_ref(val, lb[col], ub[col])
    got = candidates_scatter_tiles(
        val, col, ii, mf, mc, xf, xc, lhs, rhs, lb, ub, n_pad,
        int_eps=1e-6, interpret=True,
    )
    want = kref.candidates_scatter_tiles_ref(
        val, col, ii, mf, mc, xf, xc, lhs, rhs, lb, ub, n_pad, int_eps=1e-6
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("t,r,k,n", [(2, 2, 4, 10), (2, 4, 8, 130)])
def test_activities_gather_kernel_matches_ref(t, r, k, n, rng):
    from repro.kernels import activities_gather_tiles

    val, col, lb, ub, _, _, _, n_pad = _tiles(rng, t, r, k, n)
    got = activities_gather_tiles(val, col, lb, ub, n_pad, interpret=True)
    want = kref.activities_gather_tiles_ref(val, col, lb, ub, n_pad)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-12, atol=1e-12)


def test_in_kernel_gather_matches_xla_gather(rng):
    """The one-hot in-kernel gather is exact (single-term sums), so the
    gathered activities must be bitwise equal to XLA-gathered ones."""
    from repro.kernels import activities_gather_tiles

    val, col, lb, ub, _, _, _, n_pad = _tiles(rng, 3, 4, 8, 60)
    got = activities_gather_tiles(val, col, lb, ub, n_pad, interpret=True)
    want = activities_tiles(val, lb[col], ub[col], interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-12, atol=1e-12)


def _wide_f32(rng, shape, sign):
    """float32 magnitudes across the range below the sentinel, 10% of
    them the infinity sentinel itself."""
    x = sign * 10.0 ** rng.uniform(-30, 19.9, size=shape)
    x[rng.random(shape) < 0.1] = sign * INF
    return x.astype(np.float32)


def test_float32_gather_is_exact(rng):
    """At float32 the gather's one-hot matmuls carry bounds as three
    bfloat16 pieces; the gathered bounds must still be bitwise those of an
    XLA gather, for magnitudes across the float32 range."""
    from repro.kernels import activities_gather_tiles

    t, r, k, n = 4, 8, 32, 300
    n_pad = col_pad(n)
    val = rng.choice([-2.5, -1.0, 0.0, 1.0, 3.0], size=(t, r, k)).astype(np.float32)
    col = rng.integers(0, n, size=(t, r, k)).astype(np.int32)
    lb, ub = _wide_f32(rng, n_pad, -1.0), _wide_f32(rng, n_pad, 1.0)
    val, col, lb, ub = map(jnp.asarray, (val, col, lb, ub))
    got = activities_gather_tiles(val, col, lb, ub, n_pad, interpret=True)
    want = activities_tiles(val, lb[col], ub[col], interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("repeat", [False, True], ids=["distinct", "repeated"])
def test_float32_scatter_is_exact(rng, repeat):
    """The scatter places float32 candidates through the MXU as bfloat16
    pieces when a tile's rows hold distinct columns, and compares
    otherwise; either way the accumulators are bitwise a segment max/min."""
    from jax.experimental import pallas as pl

    from repro.kernels import prop_round as kern

    r, k, n = 8, 32, 300
    n_pad = col_pad(n)
    if repeat:
        col = rng.integers(0, 8, size=(r, k))
    else:
        col = np.stack([rng.choice(n, size=k, replace=False) for _ in range(r)])
    lc, uc = _wide_f32(rng, (r, k), -1.0), _wide_f32(rng, (r, k), 1.0)
    lc[:, ::2] *= -1.0  # both signs on both sides
    uc[:, ::3] *= -1.0

    def kernel(lc_ref, uc_ref, col_ref, bl_ref, bu_ref):
        kern._init_accumulators(bl_ref, bu_ref, INF)
        kern._scatter_tile(lc_ref[...], uc_ref[...], col_ref[...], bl_ref, bu_ref,
                           inf=INF, block=128)

    plane = jax.ShapeDtypeStruct((n_pad // 128, 128), jnp.float32)
    bl, bu = pl.pallas_call(
        kernel, grid=(1,), out_shape=[plane, plane], interpret=True
    )(jnp.asarray(lc), jnp.asarray(uc), jnp.asarray(col, jnp.int32))
    want_l = np.full(n_pad, -INF, np.float32)
    want_u = np.full(n_pad, INF, np.float32)
    np.maximum.at(want_l, col.ravel(), lc.ravel())
    np.minimum.at(want_u, col.ravel(), uc.ravel())
    np.testing.assert_array_equal(np.asarray(bl).ravel(), want_l)
    np.testing.assert_array_equal(np.asarray(bu).ravel(), want_u)


def test_apply_updates_kernel_matches_shared_semantics(rng):
    n_pad = 128
    lb = jnp.asarray(rng.uniform(-5, 0, n_pad))
    ub = jnp.asarray(rng.uniform(0, 5, n_pad))
    best_l = jnp.asarray(rng.uniform(-6, 2, n_pad))
    best_u = jnp.asarray(rng.uniform(-2, 6, n_pad))
    got = apply_updates_tiles(lb, ub, best_l, best_u, eps=1e-9, interpret=True)
    want = bnd.apply_updates(lb, ub, best_l, best_u, eps=1e-9)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert bool(got[2]) == bool(want[2])


# ---------------------------------------------------------------------------
# Engine vs engine (property sweep over random instances)
# ---------------------------------------------------------------------------


def _random_problem(seed, m=None, n=None, empty_col_frac=0.0, all_inf_bounds=False):
    rng = np.random.default_rng(seed)
    m = m or int(rng.integers(3, 25))
    n = n or int(rng.integers(3, 20))
    mask = rng.random((m, n)) < rng.uniform(0.2, 0.6)
    for i in range(m):
        if not mask[i].any():
            mask[i, rng.integers(0, n)] = True
    if empty_col_frac:
        dead = rng.random(n) < empty_col_frac
        mask[:, dead] = False
        for i in range(m):  # keep rows nonempty among live columns
            if not mask[i].any():
                live = np.nonzero(~dead)[0]
                mask[i, rng.choice(live)] = True
    rows, cols = np.nonzero(mask)
    vals = rng.choice([-3.0, -2.0, -1.0, 1.0, 2.0, 3.0], size=rows.size)
    csr = csr_from_coo(rows.astype(np.int32), cols.astype(np.int32), vals, m, n)
    if all_inf_bounds:
        lb = np.full(n, -INF)
        ub = np.full(n, INF)
    else:
        lb = -rng.integers(0, 3, size=n).astype(np.float64)
        ub = rng.integers(1, 8, size=n).astype(np.float64)
        lb[rng.random(n) < 0.15] = -INF
        ub[rng.random(n) < 0.15] = INF
    is_int = rng.random(n) < 0.5
    row_abs = np.zeros(m)
    np.add.at(row_abs, rows, np.abs(vals) * 2.0)
    lhs = np.where(rng.random(m) < 0.4, -INF, -row_abs * rng.uniform(0.1, 0.5, m))
    rhs = np.where(rng.random(m) < 0.2, INF, row_abs * rng.uniform(0.1, 0.5, m))
    swap = lhs > rhs
    lhs[swap], rhs[swap] = rhs[swap], lhs[swap]
    return Problem(csr=csr, lhs=lhs, rhs=rhs, lb=lb, ub=ub, is_int=is_int)


def _check_engines_agree(p, tile_rows=4, tile_width=16):
    a = propagate_sequential(p)
    fused = propagate_block_ell(
        p, tile_rows=tile_rows, tile_width=tile_width, scatter="fused",
        driver="host_loop",
    )
    seg = propagate_block_ell(
        p, tile_rows=tile_rows, tile_width=tile_width, scatter="segment",
        driver="host_loop",
    )
    if bool(a.infeasible) or bool(fused.infeasible):
        return  # infeasibility verdicts may be reached at different rounds
    assert bounds_equal(fused.lb, fused.ub, seg.lb, seg.ub)
    if not (a.converged and bool(fused.converged)):
        return
    assert bounds_equal(a.lb, a.ub, fused.lb, fused.ub)


@pytest.mark.parametrize("seed", range(8))
def test_fused_engine_random_instances(seed):
    _check_engines_agree(_random_problem(seed))


@pytest.mark.parametrize("seed", [100, 101, 102])
def test_fused_engine_empty_columns(seed):
    p = _random_problem(seed, m=15, n=18, empty_col_frac=0.3)
    # Some column really is empty.
    assert (np.bincount(p.csr.col, minlength=p.n) == 0).any()
    _check_engines_agree(p)


@pytest.mark.parametrize("seed", [200, 201])
def test_fused_engine_all_infinite_bounds(seed):
    p = _random_problem(seed, m=12, n=10, all_inf_bounds=True)
    _check_engines_agree(p)


def test_fused_engine_rows_span_chunks():
    """tile_width far below the longest row forces the multi-chunk
    (activities-gather + candidates-scatter) path."""
    p = make_knapsack(n=40, m=6, seed=5)
    assert int(np.diff(p.csr.row_ptr).max()) > 8
    _check_engines_agree(p, tile_rows=2, tile_width=8)


@pytest.mark.parametrize("gen,kwargs", [
    (make_mixed, dict(m=40, n=30, seed=11)),
    (make_set_cover, dict(n=40, m=12, seed=6)),
])
def test_fused_engine_generators(gen, kwargs):
    _check_engines_agree(gen(**kwargs), tile_rows=4, tile_width=32)


def test_fused_engine_cascade_device_loop():
    p = make_cascade_chain(16)
    a = propagate_sequential(p)
    b = propagate_block_ell(p, tile_rows=2, tile_width=4, scatter="fused",
                            driver="device_loop")
    assert bounds_equal(a.lb, a.ub, b.lb, b.ub)


def test_fused_pallas_vs_jnp_close():
    """Pallas and jnp engines share all candidate formulas; lowering-level
    reduction-order/FMA differences may cost a couple of ulps at most."""
    p = make_mixed(m=30, n=25, seed=13)
    a = propagate_block_ell(p, tile_rows=4, tile_width=8, scatter="fused",
                            use_pallas=True, driver="host_loop")
    b = propagate_block_ell(p, tile_rows=4, tile_width=8, scatter="fused",
                            use_pallas=False, driver="host_loop")
    np.testing.assert_allclose(np.asarray(a.lb), np.asarray(b.lb), rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(a.ub), np.asarray(b.ub), rtol=1e-12, atol=1e-12)
    assert bounds_equal(a.lb, a.ub, b.lb, b.ub)


# ---------------------------------------------------------------------------
# prepare(): caching and donation safety
# ---------------------------------------------------------------------------


def test_prepare_cache_reuses_instance():
    p = make_mixed(m=20, n=15, seed=3)
    a = prepare_block_ell(p, 4, 16)
    b = prepare_block_ell(p, 4, 16)
    assert a is b
    c = prepare_block_ell(p, 4, 32)  # different layout -> different entry
    assert c is not a


def test_repeated_propagation_with_donation_is_stable():
    """Donated drivers must never invalidate the cached initial bounds:
    propagating the same instance twice gives identical results."""
    p = make_set_cover(n=30, m=10, seed=8)
    kw = dict(tile_rows=4, tile_width=32, scatter="fused", donate=True,
              driver="host_loop")
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # CPU warns that donation is a no-op
        r1 = propagate_block_ell(p, **kw)
        r2 = propagate_block_ell(p, **kw)
    np.testing.assert_array_equal(np.asarray(r1.lb), np.asarray(r2.lb))
    np.testing.assert_array_equal(np.asarray(r1.ub), np.asarray(r2.ub))


def test_result_has_unpadded_shape():
    p = _random_problem(42, m=9, n=7)
    r = propagate_block_ell(p, tile_rows=2, tile_width=8, scatter="fused")
    assert r.lb.shape == (7,) and r.ub.shape == (7,)
