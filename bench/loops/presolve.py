"""Closed loop of root presolves.

One caller propagates the cell's instance to its fixed point with
``propagate_block_ell(problem)`` -- a host ``Problem`` in, bounds back on
the host -- and calls again as soon as the answer is in.  Every call of
the window is checked against one run of the reference.  The instance is
the configuration's own; the run's seed does not change it (see
``families/mixed.py``).
"""
from __future__ import annotations

from contextlib import nullcontext
from types import SimpleNamespace

import numpy as np

from bench import reference, work


def control(program):
    """The check's control: the reference, computed in bfloat16, in the
    program's place.  (The program's own bfloat16 path stops in the chip's
    compiler, so it gives no reading there.)"""
    import ml_dtypes

    def presolve(p, **_):
        csr = p.csr
        a = reference.Csr(csr.row_ptr, csr.col, csr.val, len(p.lhs), int(csr.n_cols))
        lb, ub, rounds, inf = reference.propagate(a, p.lhs, p.rhs, p.is_int, p.lb, p.ub,
                                                  dtype=ml_dtypes.bfloat16)
        return SimpleNamespace(lb=lb, ub=ub, infeasible=inf, rounds=rounds)

    return SimpleNamespace(**{**vars(program), "propagate_block_ell": presolve})


class Loop:
    at_boundary = True  # every call is a whole unit of work

    def __init__(self, params, seed, program, family):
        self.inst = family.make(params)
        self.problem = program.problem(self.inst)
        self.entry = program.propagate_block_ell
        self.answers, self.rounds, self.value_bytes = [], [], 4

    def _call(self, annotate):
        with annotate("dispatch"):
            r = self.entry(self.problem)
        with annotate("readback"):
            lb, ub = np.asarray(r.lb), np.asarray(r.ub)
            answer = (lb, ub, bool(r.infeasible))
            rounds = int(r.rounds)
        self.value_bytes = lb.dtype.itemsize
        return answer, rounds

    def warm(self):
        for _ in range(2):
            self._call(lambda name: nullcontext())

    def step(self, annotate):
        answer, rounds = self._call(annotate)
        self.answers.append(answer)
        self.rounds.append(rounds)

    @property
    def attempted(self) -> int:
        return len(self.answers)

    def end_to_end(self, elapsed):
        return {"presolve_s": elapsed / len(self.answers)}

    def counters(self, peaks):
        i = self.inst
        per_round = work.least_seconds(
            work.round_bytes(i.nnz, i.m, i.n, self.value_bytes),
            work.round_ops(i.nnz), peaks)
        return {"presolve_rounds": list(self.rounds),
                "presolve_least_s": per_round * sum(self.rounds)}

    def check(self):
        """``tighter`` and ``miss_pct`` of the worst answer."""
        i = self.inst
        a = reference.Csr(i.row_ptr, i.col, i.val, i.m, i.n)
        rlb, rub, _, rinf = reference.propagate(a, i.lhs, i.rhs, i.is_int, i.lb, i.ub)
        tighter, miss_pct = 0.0, 0.0
        for ans in self.answers:
            t, missed, moved = reference.compare(ans, (rlb, rub, rinf), (i.lb, i.ub), i.is_int)
            tighter = max(tighter, t)
            miss_pct = max(miss_pct, 100.0 * missed / max(1, moved))
        return {"tighter": tighter, "miss_pct": miss_pct}
