"""Peaks, work model and reference: the parts of the yardstick that a
change to the program cannot move."""
import numpy as np
import pytest

from bench import peaks, reference, work
from bench.families import mixed


def test_unknown_device_kind_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v9 imaginary")
    assert peaks.lookup("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_work_model_counts_no_padding():
    """Two tilings of one instance pad differently; the work model reads
    only nonzeros, so both give the same bytes."""
    from repro.core.sparse import CSR, csr_to_block_ell

    inst = mixed.make(dict(m=300, n=200, instance_seed=3, density_rows=8, dense_rows=2))
    csr = CSR(inst.row_ptr.astype(np.int32), inst.col.astype(np.int32), inst.val,
              np.int32(inst.n))
    counted = []
    for k in (128, 32):
        tiles = csr_to_block_ell(csr, tile_rows=8, tile_width=k).val
        counted.append((tiles.size, int(np.count_nonzero(tiles))))
    assert counted[0][0] != counted[1][0]  # the padded sizes differ
    assert counted[0][1] == counted[1][1] == inst.nnz
    b = [work.round_bytes(nnz, inst.m, inst.n, 4) for _, nnz in counted]
    assert b[0] == b[1]


def test_work_model_at_cell_sizes_is_bandwidth_bound():
    pk = peaks.lookup("TPU v5 lite")
    for nnz, m, n in ((1576476, 100000, 20000), (1736476, 100000, 120000), (323041, 20000, 10000)):
        assert work.bound(nnz, m, n, 4, pk) == "bandwidth"
    # Bound sets that share the matrix cost far less than separate rounds.
    one, many = (work.round_bytes(1576476, 100000, 20000, 4, batch=b) for b in (1, 64))
    assert one < many < 64 * one


def test_generator_is_fixed_by_the_configuration():
    params = dict(m=400, n=300, instance_seed=3, density_rows=8, dense_rows=2)
    a, b = mixed.make(params), mixed.make(params)
    assert np.array_equal(a.col, b.col) and np.array_equal(a.lhs, b.lhs)
    c = mixed.make({**params, "instance_seed": 4})
    assert not np.array_equal(a.lhs, c.lhs)
    row = np.repeat(np.arange(a.m), np.diff(a.row_ptr))
    assert np.all(np.diff(row * a.n + a.col) > 0)  # distinct, sorted columns


def test_reference_matches_sequential_oracle():
    """A second witness: the program's sequential Algorithm 1 in float64
    reaches the same limit point as the reference on these instances."""
    from repro.core import propagate_sequential
    from repro.core.sparse import CSR, Problem

    for seed in (0, 1, 2):
        i = mixed.make(dict(m=400, n=300, instance_seed=seed, density_rows=8, dense_rows=2))
        a = reference.Csr(i.row_ptr, i.col, i.val, i.m, i.n)
        lb, ub, _, inf = reference.propagate(a, i.lhs, i.rhs, i.is_int, i.lb, i.ub)
        p = Problem(CSR(i.row_ptr.astype(np.int32), i.col.astype(np.int32), i.val,
                        np.int32(i.n)), i.lhs, i.rhs, i.lb, i.ub, i.is_int)
        s = propagate_sequential(p)
        assert s.infeasible == inf
        assert reference.compare((s.lb, s.ub, s.infeasible), (lb, ub, inf), (i.lb, i.ub),
                                 i.is_int)[:2] == (0.0, 0)


def test_compare_readings():
    lb0, ub0 = np.zeros(3), np.array([5.0, 5.0, reference.INF])
    ref = (np.array([1.0, 0.0, 0.0]), np.array([4.0, 5.0, reference.INF]), False)
    is_int = np.array([True, False, False])
    assert reference.compare(ref, ref, (lb0, ub0), is_int) == (0.0, 0, 2)
    looser = (np.array([0.5, 0.0, 0.0]), ref[1], False)
    assert reference.compare(looser, ref, (lb0, ub0), is_int) == (0.0, 1, 2)
    tighter_int = (np.array([2.0, 0.0, 0.0]), ref[1], False)
    assert reference.compare(tighter_int, ref, (lb0, ub0), is_int)[0] == 1.0
    tighter_cont = (ref[0], np.array([4.0, 4.5, reference.INF]), False)
    assert reference.compare(tighter_cont, ref, (lb0, ub0), is_int)[0] == pytest.approx(0.5 / 6)
    made_finite = (ref[0], np.array([4.0, 5.0, 9.0]), False)
    assert reference.compare(made_finite, ref, (lb0, ub0), is_int)[0] == 1.0
    assert reference.compare((*ref[:2], True), ref, (lb0, ub0), is_int) == (1.0, 6, 6)
    assert reference.compare(ref, (*ref[:2], True), (lb0, ub0), is_int) == (0.0, 6, 6)


def _nodes(seed, count):
    """Nodes below the reference's root of a small instance."""
    from bench.loops import nodes

    i = mixed.make(dict(m=600, n=400, instance_seed=seed, density_rows=8, dense_rows=2))
    a = reference.Csr(i.row_ptr, i.col, i.val, i.m, i.n)
    lb, ub, rounds, _ = reference.propagate(a, i.lhs, i.rhs, i.is_int, i.lb, i.ub)
    assert rounds < reference.MAX_ROUNDS
    lbn, ubn = nodes.draw_frontiers(lb, ub, i.is_int, dict(
        frontier_seed=seed, frontiers=1, batch=count, depth_min=1, depth_max=8))
    return i, a, (lb, ub), zip(lbn[0], ubn[0])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_node_reference_from_touched_rows_is_the_full_one(seed):
    """Below a propagated root, starting from the rows a node's branchings
    touch gives the fixed point that every row gives."""
    i, a, (rlb, rub), nodes = _nodes(seed, 24)
    for lb, ub in nodes:
        assert np.all(lb >= rlb) and np.all(ub <= rub)
        full = reference.propagate(a, i.lhs, i.rhs, i.is_int, lb, ub)
        rows = reference.rows_of_columns(a, (lb != rlb) | (ub != rub))
        part = reference.propagate(a, i.lhs, i.rhs, i.is_int, lb, ub, rows=rows)
        assert full[3] == part[3]
        assert np.array_equal(full[0], part[0]) and np.array_equal(full[1], part[1])


def test_bfloat16_rounding_keeps_sentinels():
    import ml_dtypes

    q = reference.rounding_to(ml_dtypes.bfloat16)
    x = np.array([1.0 / 3.0, -reference.INF, reference.INF, 257.0])
    y = q(x)
    assert y[1] == -reference.INF and y[2] == reference.INF
    assert y[0] != x[0] and abs(y[0] - x[0]) < 2e-3 and y[3] == 256.0
    assert reference.rounding_to(np.float64)(x) is x
