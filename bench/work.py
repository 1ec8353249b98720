"""What one propagation round needs, counted from the instance alone.

The least time of a round is the larger of the bytes it must move over
the chip's peak bandwidth and the operations it must perform over the
chip's peak rate.  Both come from ``nnz``, ``m``, ``n``, the number of
bound sets propagated together and the value type -- never from a
tiling, padding or a compiler's cost analysis -- so a later change to
how the round is implemented cannot change what it is measured against.

Bytes of one round over ``batch`` bound sets that share one matrix:

* the matrix once: ``nnz`` values and ``nnz`` column indices (the
  smallest of 1, 2 or 4 bytes that numbers ``n`` columns), and the
  ``m + 1`` row offsets at 4 bytes;
* the two sides, ``2 m`` values, and the integrality marks, ``n`` bytes;
* per bound set, both bounds read and written: ``4 n`` values.

Operations of one round, per bound set: ``OPS_PER_NNZ`` for each nonzero
(two products and two sums for the activities, two residuals, two
numerator differences and two divisions for the candidates, and the
column-wise max and min of the candidates).
"""
from __future__ import annotations

OPS_PER_NNZ = 12
ROW_OFFSET_BYTES = 4


def index_bytes(n: int) -> int:
    """The smallest integer width that numbers ``n`` columns."""
    return 1 if n <= 1 << 8 else 2 if n <= 1 << 16 else 4


def round_bytes(nnz: int, m: int, n: int, value_bytes: int, batch: int = 1) -> float:
    matrix = nnz * (value_bytes + index_bytes(n)) + (m + 1) * ROW_OFFSET_BYTES
    shared = 2 * m * value_bytes + n
    return float(matrix + shared + batch * 4 * n * value_bytes)


def round_ops(nnz: int, batch: int = 1) -> float:
    return float(OPS_PER_NNZ * nnz * batch)


def least_seconds(bytes_, ops, peaks: dict) -> float:
    """The larger of the bandwidth and compute bounds."""
    return max(bytes_ / peaks["hbm_bytes_per_s"], ops / peaks["flops"])


def bound(nnz, m, n, value_bytes, peaks) -> str:
    """Which roof a single-set round meets: ``bandwidth`` or ``compute``."""
    t_mem = round_bytes(nnz, m, n, value_bytes) / peaks["hbm_bytes_per_s"]
    t_ops = round_ops(nnz) / peaks["flops"]
    return "bandwidth" if t_mem >= t_ops else "compute"
