"""Closed loop of branch-and-bound frontiers.

Set-up propagates the configuration's instance at its root with the
reference (float64) and draws ``frontiers`` frontiers of ``batch`` nodes
below that root from the traffic's ``frontier_seed``.  Each node bounds
``depth`` integer variables, ``depth`` uniform in ``depth_min`` to
``depth_max``: for each it draws a point inside the variable's root
domain and keeps the down branch (upper bound at the point's floor) or
the up branch (lower bound at its ceiling).  The run's seed only orders
them -- which frontier comes when, and which slot each node takes in its
frontier -- so every seed does the same work on other inputs.

One caller sends frontiers with ``propagate_nodes(problem, lb_nodes,
ub_nodes)`` -- host bound planes in, bounds back on the host -- and keeps
``ahead`` of them dispatched beyond the one whose answer it reads back, so
the chip stays fed while the host stands still for a moment.  A pass sends
every frontier once.  Once the window has reached its length the caller
sends to the end of the pass in hand and then nothing more, and the window
closes when every answer sent is back on the host, so a run measures whole
passes, all of them answered.  The check holds every answer to
``check_frontiers`` frontiers, drawn from the run's seed, to the reference
run from the same node bounds.
"""
from __future__ import annotations

from collections import deque
from contextlib import nullcontext
from functools import partial
from types import SimpleNamespace

import jax
import numpy as np

from bench import reference, work


def control(program):
    """The check's control: the program's own bfloat16 path
    (``propagate_nodes(..., dtype=bfloat16)``)."""
    import jax.numpy as jnp

    return SimpleNamespace(**{**vars(program),
                              "propagate_nodes": partial(program.propagate_nodes,
                                                         dtype=jnp.bfloat16)})


def draw_frontiers(lb, ub, is_int, params):
    """``(lb_nodes, ub_nodes)`` of shape ``(frontiers, batch, n)`` below the
    root box ``(lb, ub)``, drawn from ``params["frontier_seed"]``."""
    rng = np.random.default_rng(int(params["frontier_seed"]))
    f, b = int(params["frontiers"]), int(params["batch"])
    free = np.flatnonzero(is_int & (ub - lb >= 1) & (np.abs(lb) < reference.INF)
                          & (np.abs(ub) < reference.INF))
    lbn = np.repeat(lb[None, None, :], f * b, axis=0).reshape(f, b, -1)
    ubn = np.repeat(ub[None, None, :], f * b, axis=0).reshape(f, b, -1)
    depth = rng.integers(int(params["depth_min"]), int(params["depth_max"]) + 1, size=(f, b))
    for k in range(f):
        for s in range(b):
            var = rng.choice(free, size=depth[k, s], replace=False)
            point = rng.uniform(lb[var], ub[var])
            down = rng.random(var.size) < 0.5
            ubn[k, s, var[down]] = np.floor(point[down])
            lbn[k, s, var[~down]] = np.ceil(point[~down])
    return lbn, ubn


class Loop:
    def __init__(self, params, seed, program, family):
        i = self.inst = family.make(params)
        self.problem = program.problem(i)
        self.entry = program.propagate_nodes
        self.a = reference.Csr(i.row_ptr, i.col, i.val, i.m, i.n)
        rlb, rub, rounds, infeasible = reference.propagate(
            self.a, i.lhs, i.rhs, i.is_int, i.lb, i.ub)
        if infeasible:
            raise ValueError("the configuration's instance is infeasible at its root")
        # The reference may start a node from the rows its branching touches
        # only below a root it has propagated to the end.
        self.root = (rlb, rub) if rounds < reference.MAX_ROUNDS else None
        lbn, ubn = draw_frontiers(rlb, rub, i.is_int, params)
        rng = np.random.default_rng(seed)
        self.order = rng.permutation(len(lbn))
        slots = np.stack([rng.permutation(lbn.shape[1]) for _ in range(len(lbn))])
        self.lbn = np.take_along_axis(lbn, slots[:, :, None], axis=1)
        self.ubn = np.take_along_axis(ubn, slots[:, :, None], axis=1)
        self.checked = {int(k): [] for k in
                        rng.choice(len(lbn), size=int(params["check_frontiers"]), replace=False)}
        self.ahead, self.in_flight = int(params["ahead"]), deque()
        self.next, self.nodes, self.node_rounds, self.value_bytes = 0, 0, [], 4

    @property
    def at_boundary(self) -> bool:
        """The frontiers sent so far make whole passes."""
        return self.next == 0

    def _send(self, k, annotate):
        with annotate("dispatch"):
            self.in_flight.append((k, self.entry(self.problem, self.lbn[k], self.ubn[k])))

    def _receive(self, annotate):
        k, r = self.in_flight.popleft()
        with annotate("readback"):
            lb, ub, infeasible, rounds = (np.asarray(x) for x in
                                          jax.device_get((r.lb, r.ub, r.infeasible, r.rounds)))
        self.value_bytes = lb.dtype.itemsize
        self.nodes += len(lb)
        self.node_rounds.append(rounds)
        if k in self.checked:
            self.checked[k].append((lb, ub, infeasible))

    def warm(self):
        for _ in range(2):
            self._send(self.order[0], lambda name: nullcontext())
            jax.block_until_ready(self.in_flight.popleft()[1])

    def step(self, annotate):
        """Send the next frontier; read back the oldest beyond ``ahead``."""
        self._send(int(self.order[self.next]), annotate)
        self.next = (self.next + 1) % len(self.order)
        if len(self.in_flight) > self.ahead:
            self._receive(annotate)

    def finish(self, annotate):
        """Read back every answer still in flight."""
        while self.in_flight:
            self._receive(annotate)

    @property
    def attempted(self) -> int:
        return self.nodes

    def end_to_end(self, elapsed):
        return {"nodes_per_s": self.nodes / elapsed}

    def counters(self, peaks):
        """Rounds per node, and the least time of every dispatch: round ``r``
        of a dispatch reads the matrix once for the nodes still running."""
        i = self.inst
        least = 0.0
        for rounds in self.node_rounds:
            for r in range(1, int(rounds.max(initial=0)) + 1):
                active = int(np.sum(rounds >= r))
                least += work.least_seconds(
                    work.round_bytes(i.nnz, i.m, i.n, self.value_bytes, batch=active),
                    work.round_ops(i.nnz, batch=active), peaks)
        return {"node_rounds": np.concatenate(self.node_rounds).tolist(),
                "node_least_s": least}

    def check(self):
        """``tighter`` of the worst node and ``miss_pct`` of the worst
        answer, its nodes' missed bounds over the bounds the reference
        moved."""
        i = self.inst
        tighter, miss_pct = 0.0, 0.0
        for k, answers in self.checked.items():
            refs = []
            for s in range(len(self.lbn[k])):
                start = (self.lbn[k, s], self.ubn[k, s])
                rows = (None if self.root is None else reference.rows_of_columns(
                    self.a, (start[0] != self.root[0]) | (start[1] != self.root[1])))
                rlb, rub, _, rinf = reference.propagate(self.a, i.lhs, i.rhs, i.is_int,
                                                        *start, rows=rows)
                refs.append(((rlb, rub, rinf), start))
            for lb, ub, infeasible in answers:
                missed = moved = 0
                for s, (ref, start) in enumerate(refs):
                    node = ((lb[s], ub[s], infeasible[s]) if s < min(len(lb), len(ub))
                            else (np.empty(0), np.empty(0), False))
                    t, mi, mo = reference.compare(node, ref, start, i.is_int)
                    tighter, missed, moved = max(tighter, t), missed + mi, moved + mo
                miss_pct = max(miss_pct, 100.0 * missed / max(1, moved))
        return {"tighter": tighter, "miss_pct": miss_pct}
