"""Shared value types and numeric conventions for domain propagation.

Conventions (SCIP / PaPILO style, see paper §3.4):
  * Infinite bounds are encoded with the finite sentinel ``INF = 1e20``.
    Any value ``|v| >= INF`` is treated as infinite.  All arithmetic therefore
    stays finite (no NaNs from ``0 * inf``), and "counting infinite
    contributions" is a plain comparison against the sentinel.
  * A *bound change* only counts if it improves the bound by more than a
    scale-aware epsilon -- this is the tolerance-based termination the paper
    uses to guarantee finite convergence (§1.1).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# SCIP-style infinity sentinel.  Values beyond this magnitude are "infinite".
INF = 1e20


def resolve_dtype(dtype, fallback) -> np.dtype:
    """The engines' value dtype: ``dtype`` when given; otherwise float32 on
    a TPU -- Mosaic kernels take no 64-bit operand, and the float32 engines
    merge with outward rounding (``PropagatorConfig.outward_eps_f32``) so
    they are never tighter than float64 -- and ``fallback`` (the instance's
    own dtype) elsewhere."""
    if dtype is not None:
        return np.dtype(dtype)
    if jax.default_backend() == "tpu":
        return np.dtype(np.float32)
    return np.dtype(fallback)


def _is_low_precision(dtype) -> bool:
    return jnp.dtype(dtype) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def int_round_slack(dtype) -> float:
    """Scale-aware integrality-rounding slack of a tier dtype.

    ``ceil``/``floor`` amplify arithmetic error discontinuously: an fp32
    candidate a few ulps above ``k - int_eps`` rounds to ``k`` where the
    exact candidate rounds to ``k - 1`` -- an O(1) overtightening no merge-
    side widening can undo.  Low-precision rounding therefore subtracts
    (adds) ``slack * max(1, |candidate|)`` before the ceil (floor), treating
    anything within the tier's accumulated-error margin of an integer as
    that integer.  Same magnitude as the merge widening
    (``PropagatorConfig.outward_eps_f32``); 0.0 for fp64 (exact rounding,
    bitwise-identical to the pre-tier engines)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.float32):
        return 2.0**-17
    if dt == jnp.dtype(jnp.bfloat16):
        return 2.0**-6
    return 0.0


@dataclasses.dataclass(frozen=True)
class PropagatorConfig:
    """Numeric + termination knobs shared by all propagator implementations."""

    max_rounds: int = 100          # paper §4.1: round cap
    tighten_eps: float = 1e-9      # scale-aware minimum improvement (fp64)
    tighten_eps_f32: float = 1e-5  # minimum improvement when running in fp32
    int_eps: float = 1e-6          # integrality rounding tolerance
    feas_eps: float = 1e-8         # empty-domain detection: l > u + feas_eps
    inf: float = INF
    # fp32-tier outward rounding: every accepted tightening is widened back
    # toward the old bound by ``outward_eps_f32 * max(1, |bound|)`` in the
    # merge, so accumulated fp32 arithmetic error can never push a bound
    # INSIDE the fp64 fixed point (no false infeasibility, promotion-safe).
    # Must stay < tighten_eps_f32 so accepted updates still make strict
    # progress and the fp32 fixed point terminates.
    outward_eps_f32: float = 2.0**-17

    def eps_for(self, dtype) -> float:
        if _is_low_precision(dtype):
            return self.tighten_eps_f32
        return self.tighten_eps

    def outward_for(self, dtype) -> float:
        """Outward-rounding width for a tier dtype (0.0 = exact merge).

        fp64 merges stay exact (bitwise-compatible with every pre-tier
        engine and oracle); low-precision tiers widen accepted tightenings
        by this relative amount."""
        return self.outward_eps_f32 if _is_low_precision(dtype) else 0.0


DEFAULT_CONFIG = PropagatorConfig()


@dataclasses.dataclass(frozen=True)
class TierPolicy:
    """Runtime policy for two-tier adaptive precision + progress control.

    The *measure of progress* (Sofranac et al., arXiv:2106.07573, adapted
    to sentinel-infinite bounds -- see ``bounds.progress_measure``) is a
    per-round device scalar: the scale-normalized total bound movement of
    the round.  Two decisions hang off it:

      * **tier switch** (``two_tier``): rounds run in fp32 (half the
        bytes/round of the fused dataflow, double the effective slab
        width) while per-round progress stays >= ``switch_progress``;
        once it drops below for ``patience`` consecutive rounds the
        bounds are promoted (exact fp32->fp64 cast -- they are outward-
        rounded, so never inside the fp64 fixed point) and the fp64
        engine finishes the endgame.
      * **early stop** (``stop_progress``): a fixed point whose progress
        stays below this for ``patience`` rounds is declared flatlined
        and stopped even though epsilon-level changes continue; the
        service pump retires such slots early to keep occupancy high.
        ``None`` disables the early stop (iterate to exact convergence).
    """

    two_tier: bool = True          # run an fp32 tier before the fp64 endgame
    switch_progress: float = 1e-3  # fp32 tier: promote below this progress
    stop_progress: float | None = None  # early stop threshold (None = off)
    patience: int = 2              # consecutive low-progress rounds to act
    fp32_round_frac: float = 0.5   # fp32 tier's share of the round cap


DEFAULT_TIER_POLICY = TierPolicy()


class Bounds(NamedTuple):
    """Variable domains ``lb <= x <= ub`` (sentinel-infinite)."""

    lb: jnp.ndarray  # (n,)
    ub: jnp.ndarray  # (n,)


class Activities(NamedTuple):
    """Per-row activity aggregates with infinity counters (paper §3.4).

    ``min_act = -inf`` iff ``min_inf_count > 0`` else ``min_finite``;
    symmetric for the maximum activity (whose infinite contributions are
    all ``+inf``).
    """

    min_finite: jnp.ndarray     # (m,) finite part of the minimum activity
    min_inf_count: jnp.ndarray  # (m,) int32 number of -inf contributions
    max_finite: jnp.ndarray     # (m,) finite part of the maximum activity
    max_inf_count: jnp.ndarray  # (m,) int32 number of +inf contributions


class PropagationResult(NamedTuple):
    """Outcome of one propagation fixed point (any engine, any driver).

    ``lb``/``ub`` are the tightened ``(n,)`` bound vectors (device arrays,
    sentinel-infinite); the scalars are device arrays too so batched
    drivers can return them without host syncs.  ``infeasible`` means some
    variable's domain emptied (``lb > ub + feas_eps``) -- in tree search,
    prune the node."""

    lb: jnp.ndarray            # (n,) tightened lower bounds
    ub: jnp.ndarray            # (n,) tightened upper bounds
    rounds: jnp.ndarray        # () int32: propagation rounds executed
    converged: jnp.ndarray     # () bool: fixed point reached within cap
    infeasible: jnp.ndarray    # () bool: some variable domain became empty
    progress: jnp.ndarray = jnp.nan    # () last round's progress measure
    tier_rounds: jnp.ndarray = 0       # () int32: rounds run in the fp32 tier
    # obs.telemetry.TelemetrySnapshot when the driver was called with a
    # telemetry capacity (lazy: holds device arrays, no sync on attach).
    telemetry: object | None = None


def is_pos_inf(v, inf: float = INF):
    return v >= inf


def is_neg_inf(v, inf: float = INF):
    return v <= -inf


def is_inf(v, inf: float = INF):
    return jnp.abs(v) >= inf if isinstance(v, jnp.ndarray) else abs(v) >= inf


def clamp_to_sentinel(v, inf: float = INF):
    """Clamp values into the representable range [-INF, INF] (``jnp.clip``
    semantics, spelled so a TPU kernel can lower it under x64: see
    :func:`scalar_like`)."""
    return jnp.minimum(jnp.maximum(v, -inf), inf)


def any_true(x, axis=None):
    """``jnp.any`` spelled as a float32 max: JAX lowers a boolean reduction
    inside a TPU kernel through a float of the default width, float64 under
    x64, which the kernel cannot hold."""
    one, zero = np.float32(1.0), np.float32(0.0)
    return jnp.max(jnp.where(x, one, zero), axis=axis) > zero


def scalar_like(v: float, x):
    """``v`` as a 0-d array of ``x``'s dtype.  ``jnp.where`` and
    ``jnp.clip`` take a bare Python float as a float64 operand when x64 is
    on, which a TPU kernel cannot lower; a typed scalar holds the same
    value (``v`` rounded to the dtype once, as the implicit cast did)."""
    return np.asarray(v, dtype=x.dtype)


def np_is_inf(v: np.ndarray, inf: float = INF) -> np.ndarray:
    return np.abs(v) >= inf
