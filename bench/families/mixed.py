"""The ``mixed`` instance family: MIPLIB-like rows, drawn on the host.

The same distribution as ``make_mixed`` in the program's data module
(power-law row lengths, a few dense connecting rows, coefficients
``±{1..9}``, mostly ``[0, U]`` bounds with some infinite, an integrality
mix, sides scaled to each row's content), drawn with vectorised numpy so
that set-up stays short.  The draw is not the same random stream: each
row's columns are a uniform subset of the right size, reached by
redrawing repeated columns instead of one ``choice`` per row.

``make`` builds the configuration's instance from its ``instance_seed``
alone.  The run's seed does not change it: the program compiles an
instance's arrays into its fixed point, so another instance in every run
would compile in every run's set-up.
"""
from __future__ import annotations

import numpy as np

INF = 1e20
COEF_MAX = 9          # coefficients are sign * {1, ..., COEF_MAX}
DENSE_SHARE = 0.8     # a connecting row covers this share of the columns
SIDE_SCALE = 3.0      # sides are drawn against SIDE_SCALE * sum |a| of a row


class Instance:
    """``lhs <= A x <= rhs``, ``lb <= x <= ub`` with CSR ``A`` (columns
    sorted within each row), all host numpy in float64."""

    def __init__(self, row_ptr, col, val, lhs, rhs, lb, ub, is_int):
        self.row_ptr, self.col, self.val = row_ptr, col, val
        self.lhs, self.rhs, self.lb, self.ub, self.is_int = lhs, rhs, lb, ub, is_int
        self.m, self.n, self.nnz = len(lhs), len(lb), len(col)


def _row_columns(rng, lengths, n):
    """A uniform subset of ``lengths[i]`` distinct columns for every row,
    as (row, col) pairs sorted by row then column."""
    m = len(lengths)
    row = np.repeat(np.arange(m, dtype=np.int64), lengths)
    col = rng.integers(0, n, size=row.size)
    starts = np.concatenate([[0], np.cumsum(lengths)])
    for i in np.flatnonzero(lengths > n // 4):  # rejection is slow for these
        col[starts[i]:starts[i + 1]] = rng.permutation(n)[: lengths[i]]
    key = row * n + col
    while True:
        key.sort()
        dup = np.flatnonzero(key[1:] == key[:-1]) + 1
        if dup.size == 0:
            return key // n, key % n
        key[dup] = (key[dup] // n) * n + rng.integers(0, n, size=dup.size)


def base_instance(m: int, n: int, instance_seed: int, density: float,
                  dense_rows: int, int_frac: float = 0.6,
                  inf_bound_frac: float = 0.15) -> Instance:
    """The configuration's instance, fixed by ``instance_seed``."""
    rng = np.random.default_rng(instance_seed)
    base = max(2, int(density * n))
    raw = rng.pareto(2.0, size=m) + 1.0
    lengths = np.clip((raw * base).astype(np.int64), 2, max(3, n // 2))
    dense_idx = rng.choice(m, size=min(dense_rows, m), replace=False)
    lengths[dense_idx] = max(3, int(n * DENSE_SHARE))
    row, col = _row_columns(rng, lengths, n)
    val = rng.choice([-1.0, 1.0], size=row.size) * rng.integers(1, COEF_MAX + 1, size=row.size)
    row_ptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)

    ub = rng.integers(1, 10, size=n).astype(np.float64)
    lb = np.zeros(n)
    ub[rng.random(n) < inf_bound_frac] = INF
    lb[rng.random(n) < inf_bound_frac * 0.5] = -INF
    is_int = rng.random(n) < int_frac
    absrow = np.bincount(row, np.abs(val) * SIDE_SCALE, minlength=m)
    kind = rng.random(m)
    lhs = np.where(kind < 0.35, -INF, -absrow * rng.uniform(0.1, 0.4, m))
    rhs = np.where(kind > 0.85, INF, absrow * rng.uniform(0.1, 0.4, m))
    return Instance(row_ptr, col, val, lhs, rhs, lb, ub, is_int)


def make(params: dict) -> Instance:
    """The instance a cell runs, from the configuration's and the traffic's
    ``params`` (``m``, ``n``, ``instance_seed``, ``density_rows``,
    ``dense_rows``)."""
    n = int(params["n"])
    return base_instance(
        m=int(params["m"]), n=n, instance_seed=int(params["instance_seed"]),
        density=float(params["density_rows"]) / n,
        dense_rows=int(params["dense_rows"]),
    )
