"""The plain reference: domain propagation in float64 numpy.

A straightforward transcription of the paper's parallel round
(arXiv:2009.07785, Algorithm 2 with the §3.4 infinity counting): every
round computes all row activities from the bounds of the previous round,
every nonzero's bound candidates from its row's residual activities,
rounds integer candidates, and keeps the best candidate per column when
it improves the bound by more than ``eps`` (scale-aware).  The fixed
point is reached when a round changes nothing.  It imports nothing of
the program under test and runs on the host, on CSR arrays.

``compare`` holds one answer of a timed call to this reference's: how
far its bounds lie inside the reference's, and how many miss the
paper's §4.3 tolerance.
"""
from __future__ import annotations

import copy

import numpy as np

INF = 1e20
EPS = 1e-9         # least accepted improvement, relative (float64)
INT_EPS = 1e-6     # integrality tolerance of ceil/floor
FEAS_EPS = 1e-8    # an empty domain is lb > ub + FEAS_EPS
MAX_ROUNDS = 100   # the paper's round cap (§4.1)


class Csr:
    """Row-major sparse matrix with the per-nonzero row ids precomputed,
    plus the column-sorted order used for the column reductions."""

    def __init__(self, row_ptr, col, val, m: int, n: int):
        self.row_ptr = np.asarray(row_ptr, np.int64)
        self.col = np.asarray(col, np.int64)
        self.val = np.asarray(val, np.float64)
        self.m, self.n = int(m), int(n)
        self.row = np.repeat(np.arange(self.m), np.diff(self.row_ptr))
        self.by_col = np.argsort(self.col, kind="stable")
        sorted_col = self.col[self.by_col]
        self.col_start = np.searchsorted(sorted_col, np.arange(self.n))
        self.has_col = np.bincount(self.col, minlength=self.n) > 0


def _exact(x):
    return x


def rounding_to(dtype):
    """Every computed value rounded to ``dtype`` (float64: left as it is);
    the infinite sentinels, ``|x| >= INF``, are kept."""
    if dtype is None or np.dtype(dtype) == np.float64:
        return _exact
    dt = np.dtype(dtype)

    def q(x):
        x = np.asarray(x, np.float64)
        return np.where(np.abs(x) >= INF, x, x.astype(dt).astype(np.float64))

    return q


def _column_best(a: Csr, c, cand, reduce, empty, every_nz: bool):
    """Per-column ``reduce`` (np.maximum / np.minimum) of per-nonzero
    candidates at columns ``c``; ``empty`` where a column has none."""
    out = np.full(a.n, empty)
    if not every_nz:
        reduce.at(out, c, cand)
    elif a.col.size:
        s = cand[a.by_col]
        red = reduce.reduceat(s, np.minimum(a.col_start, s.size - 1))
        out[a.has_col] = red[a.has_col]
    return out


def round_candidates(a: Csr, lhs, rhs, is_int, lb, ub, nz=None, q=_exact):
    """One round's best lower and upper candidate per column, from the
    nonzeros ``nz`` (whole rows; ``None``: every nonzero), each computed
    value rounded by ``q``."""
    if nz is None:
        v, c, r = a.val, a.col, a.row
    else:
        v, c, r = a.val[nz], a.col[nz], a.row[nz]
    pos = v > 0
    bmin = np.where(pos, lb[c], ub[c])
    bmax = np.where(pos, ub[c], lb[c])
    min_inf = np.abs(bmin) >= INF
    max_inf = np.abs(bmax) >= INF
    cmin = np.where(min_inf, 0.0, q(v * bmin))
    cmax = np.where(max_inf, 0.0, q(v * bmax))
    row_min = q(np.bincount(r, cmin, a.m))[r]
    row_max = q(np.bincount(r, cmax, a.m))[r]
    n_min = np.bincount(r, min_inf, a.m)[r]
    n_max = np.bincount(r, max_inf, a.m)[r]
    # Residual activities (Eqs. 5a/5b) with the single-infinity rule.
    min_res = np.where(min_inf, np.where(n_min == 1, row_min, -INF),
                       np.where(n_min == 0, q(row_min - cmin), -INF))
    max_res = np.where(max_inf, np.where(n_max == 1, row_max, INF),
                       np.where(n_max == 0, q(row_max - cmax), INF))
    lo, hi = lhs[r], rhs[r]
    with np.errstate(invalid="ignore", over="ignore"):
        num_l = q(np.where(pos, lo - max_res, hi - min_res))
        num_u = q(np.where(pos, hi - min_res, lo - max_res))
        ok_l = np.where(pos, (lo > -INF) & (max_res < INF), (hi < INF) & (min_res > -INF))
        ok_u = np.where(pos, (hi < INF) & (min_res > -INF), (lo > -INF) & (max_res < INF))
        lcand = np.where(ok_l, np.clip(q(num_l / v), -INF, INF), -INF)
        ucand = np.where(ok_u, np.clip(q(num_u / v), -INF, INF), INF)
    ints = is_int[c]
    lcand = np.where(ints & (np.abs(lcand) < INF), np.ceil(lcand - INT_EPS), lcand)
    ucand = np.where(ints & (np.abs(ucand) < INF), np.floor(ucand + INT_EPS), ucand)
    return (_column_best(a, c, lcand, np.maximum, -INF, nz is None),
            _column_best(a, c, ucand, np.minimum, INF, nz is None))


def _nonzeros_of_rows(a: Csr, rows):
    return np.flatnonzero(rows[a.row])


def propagate(a: Csr, lhs, rhs, is_int, lb, ub, eps: float = EPS,
              max_rounds: int = MAX_ROUNDS, rows=None, dtype=None):
    """Run rounds to the fixed point from ``(lb, ub)``.  Returns
    ``(lb, ub, rounds, infeasible)``, the rounds counting the last one,
    which changed nothing.

    ``rows`` (a boolean mask over the rows) is for a start that differs
    from a fixed point of this reference only in columns of those rows,
    such as a branch-and-bound node below a propagated root: the first
    round then reads those rows alone, and each later round the rows of
    the columns that the round before changed.  A row none of whose
    columns changed gives the candidates it gave before, which were taken
    or did not improve, so the fixed point is the one every row gives.

    ``dtype`` rounds every value the rounds compute to that type (the
    control of the check: this reference one precision below the
    program's); row sums accumulate in float64 and are rounded once."""
    q = rounding_to(dtype)
    lhs, rhs = q(np.asarray(lhs, np.float64)), q(np.asarray(rhs, np.float64))
    if q is not _exact:
        a = copy.copy(a)
        a.val = q(a.val)
    is_int = np.asarray(is_int, bool)
    lb, ub = q(np.array(lb, np.float64)), q(np.array(ub, np.float64))
    nz = None if rows is None else _nonzeros_of_rows(a, np.asarray(rows, bool))
    rounds = 0
    while rounds < max_rounds:
        rounds += 1
        best_l, best_u = round_candidates(a, lhs, rhs, is_int, lb, ub, nz, q)
        take_l = best_l > lb + eps * np.maximum(1.0, np.abs(lb))
        take_u = best_u < ub - eps * np.maximum(1.0, np.abs(ub))
        lb = np.where(take_l, np.clip(best_l, -INF, INF), lb)
        ub = np.where(take_u, np.clip(best_u, -INF, INF), ub)
        if not (take_l.any() or take_u.any()):
            break
        if rows is not None:
            nz = _nonzeros_of_rows(a, rows_of_columns(a, take_l | take_u))
    return lb, ub, rounds, bool(np.any(lb > ub + FEAS_EPS))


def rows_of_columns(a: Csr, columns):
    """The rows with a nonzero in one of ``columns`` (a boolean mask)."""
    rows = np.zeros(a.m, bool)
    rows[a.row[columns[a.col]]] = True
    return rows


T_ABS, T_REL = 1e-8, 1e-5   # the paper's §4.3 tolerance


def _outside(x, y):
    """Bounds of ``x`` outside §4.3 of ``y`` (both-infinite counts equal)."""
    same_inf = ((x >= INF) & (y >= INF)) | ((x <= -INF) & (y <= -INF))
    return ~(same_inf | (np.abs(x - y) <= T_ABS + T_REL * np.abs(y)))


def compare(answer, ref, start, is_int):
    """Hold one answer to the reference's.  ``answer`` and ``ref`` are
    ``(lb, ub, infeasible)``; ``start`` is the ``(lb, ub)`` both began from.

    Returns ``(tighter, missed, moved)``:

    * ``tighter``: how far a bound lies inside the reference's, as
      ``(x - ref) / (1 + |ref|)`` for a lower bound (mirrored for an upper
      one); 1.0 for any tightening of an integer variable's bound, for an
      infinite bound made finite, and for an infeasible verdict where the
      reference finds the domain non-empty.  Never tighter is the
      configuration's guarantee.
    * ``missed``: bounds outside §4.3 of the reference's;
    * ``moved``: bounds the reference moved away from ``start`` (§4.3).

    A verdict that differs counts every bound as missed and moved; two
    infeasible verdicts count nothing (an empty domain's bounds are not
    defined)."""
    lb, ub, infeasible = answer
    rlb, rub, rinf = ref
    n2 = 2 * len(rlb)
    if bool(infeasible) != bool(rinf):
        return (1.0 if infeasible else 0.0), n2, n2
    if rinf:
        return 0.0, 0, 0
    lb, ub = np.asarray(lb, np.float64), np.asarray(ub, np.float64)
    if lb.shape != rlb.shape or ub.shape != rub.shape or np.isnan(lb).any() or np.isnan(ub).any():
        return 1.0, n2, n2
    is_int = np.asarray(is_int, bool)
    lo_inf, hi_inf = rlb <= -INF, rub >= INF
    inward = np.concatenate([np.where(lo_inf, 0.0, (lb - rlb) / (1.0 + np.abs(rlb))),
                             np.where(hi_inf, 0.0, (rub - ub) / (1.0 + np.abs(rub)))])
    ints = np.concatenate([is_int, is_int])
    tighter = float(np.max(np.where(ints, 0.0, inward), initial=0.0))
    if (np.any(ints & (inward > 1e-9)) or np.any(lo_inf & (lb > -INF))
            or np.any(hi_inf & (ub < INF))):
        tighter = 1.0
    slb, sub = start
    missed = int(_outside(lb, rlb).sum() + _outside(ub, rub).sum())
    moved = int(_outside(rlb, np.asarray(slb, np.float64)).sum()
                + _outside(rub, np.asarray(sub, np.float64)).sum())
    return min(tighter, 1.0), missed, moved
