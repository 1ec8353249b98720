"""Bound-candidate computation and update (paper Eqs. 4a/4b via 5a/5b).

Candidate formulas, written with residual activities (derivation in
DESIGN.md §1):

  a_ij > 0:  lcand = (lhs_i - maxres_ij) / a_ij    ucand = (rhs_i - minres_ij) / a_ij
  a_ij < 0:  lcand = (rhs_i - minres_ij) / a_ij    ucand = (lhs_i - maxres_ij) / a_ij

A candidate is *valid* only if the side it uses is finite (lhs > -INF resp.
rhs < +INF) and the residual activity it uses is finite.  Invalid candidates
are emitted as -INF (lower) / +INF (upper) so that the column-wise max/min
reduction ignores them -- this is the mask-before-reduce that replaces the
paper's "check before atomic" trick (§3.5) on TPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from .types import INF, any_true, clamp_to_sentinel, int_round_slack


def bound_candidates(a, lhs_row, rhs_row, min_res, max_res, inf: float = INF):
    """Per-nonzero lower/upper bound candidates.

    Args:
      a: (nnz,) coefficients (0 == padding).
      lhs_row, rhs_row: (nnz,) constraint sides of each nonzero's row.
      min_res, max_res: (nnz,) residual activities (sentinel-infinite).

    Returns:
      (lcand, ucand): candidates with invalid entries at -inf/+inf.
    """
    pos = a > 0
    pad = a == 0
    safe_a = jnp.where(pad, 1.0, a)

    # Numerators per Eqs. 4a/4b in residual form.
    num_l = jnp.where(pos, lhs_row - max_res, rhs_row - min_res)
    num_u = jnp.where(pos, rhs_row - min_res, lhs_row - max_res)

    lcand = num_l / safe_a
    ucand = num_u / safe_a

    valid_l = jnp.where(
        pos,
        (lhs_row > -inf) & (max_res < inf),
        (rhs_row < inf) & (min_res > -inf),
    ) & ~pad
    valid_u = jnp.where(
        pos,
        (rhs_row < inf) & (min_res > -inf),
        (lhs_row > -inf) & (max_res < inf),
    ) & ~pad

    lcand = jnp.where(valid_l, jnp.clip(lcand, -inf, inf), -inf)
    ucand = jnp.where(valid_u, jnp.clip(ucand, -inf, inf), inf)
    return lcand, ucand


def round_candidates(lcand, ucand, is_int_col, int_eps: float, inf: float = INF):
    """Integrality strengthening: ceil lower / floor upper (paper Step 3).

    Low-precision candidates get the dtype's scale-aware rounding slack
    (:func:`core.types.int_round_slack`) so tier-arithmetic error can
    never push a ceil/floor across an integer the exact candidate would
    not cross; fp64 rounds exactly."""
    do_round_l = is_int_col & (jnp.abs(lcand) < inf)
    do_round_u = is_int_col & (jnp.abs(ucand) < inf)
    slack = int_round_slack(jnp.result_type(lcand))
    sl = su = int_eps
    if slack:  # static per dtype: fp64 keeps the exact scalar subtraction
        sl = int_eps + slack * jnp.maximum(1.0, jnp.abs(lcand))
        su = int_eps + slack * jnp.maximum(1.0, jnp.abs(ucand))
    lcand = jnp.where(do_round_l, jnp.ceil(lcand - sl), lcand)
    ucand = jnp.where(do_round_u, jnp.floor(ucand + su), ucand)
    return lcand, ucand


def improved_lb(new_lb, old_lb, eps: float):
    """Scale-aware strict improvement test (tolerance-based termination)."""
    return new_lb > old_lb + eps * jnp.maximum(1.0, jnp.abs(old_lb))


def improved_ub(new_ub, old_ub, eps: float):
    return new_ub < old_ub - eps * jnp.maximum(1.0, jnp.abs(old_ub))


def widen_outward(lcand, ucand, outward: float):
    """Round accepted tightenings *outward* (fp32-tier safety widening).

    Nextafter-style: the accepted lower candidate is pushed DOWN and the
    upper candidate UP by ``outward * max(1, |candidate|)`` -- a scale-aware
    multiple of the fp32 ulp (``outward`` defaults to ``2**-17``, ~64 ulps,
    see ``PropagatorConfig.outward_eps_f32``) that dominates the rounding
    error the fp32 activity/candidate arithmetic can accumulate within a
    round.  Widened bounds are therefore never TIGHTER than the exact-
    arithmetic round would produce from the same state; by induction the
    whole fp32 trajectory stays outside the fp64 fixed point, so promotion
    is an exact cast and infeasibility is never falsely declared.
    ``outward == 0.0`` is the exact fp64 merge (identity)."""
    lcand = lcand - outward * jnp.maximum(1.0, jnp.abs(lcand))
    ucand = ucand + outward * jnp.maximum(1.0, jnp.abs(ucand))
    return lcand, ucand


def canonical_infinite(lb, ub, inf: float = INF):
    """Restore exact ``+-inf`` sentinels after a cross-dtype cast.

    fp32 rounds the sentinel ``1e20`` up to ``1.00000002e20``, so bounds
    promoted from an fp32 tier carry a non-canonical (though still
    semantically infinite -- every engine tests ``|v| >= inf``) sentinel.
    Called on the CAST bounds at every two-tier promotion so untouched
    infinite bounds come out of a tiered run bitwise identical to the
    single-dtype run's.  Clamping in fp32 would be a no-op (the canonical
    value is not representable); always canonicalize in the final dtype."""
    lb = jnp.where(lb <= -inf, -inf, lb)
    ub = jnp.where(ub >= inf, inf, ub)
    return lb, ub


def apply_updates(
    lb, ub, best_lcand, best_ucand, eps: float, inf: float = INF,
    outward: float = 0.0,
):
    """Merge column-reduced candidates into the bounds.

    Returns (new_lb, new_ub, changed) where ``changed`` is a scalar bool.
    Non-improving candidates leave the bound untouched (so no epsilon drift
    accumulates across rounds).  ``outward > 0`` (the fp32 tier) widens
    every accepted tightening back toward the old bound by
    :func:`widen_outward`; the improvement test runs on the UNwidened
    candidate, so ``outward < eps`` keeps accepted updates strictly
    improving and the fixed point terminating.
    """
    take_l = improved_lb(best_lcand, lb, eps)
    take_u = improved_ub(best_ucand, ub, eps)
    if outward:
        best_lcand, best_ucand = widen_outward(best_lcand, best_ucand, outward)
    new_lb = jnp.where(take_l, clamp_to_sentinel(best_lcand, inf), lb)
    new_ub = jnp.where(take_u, clamp_to_sentinel(best_ucand, inf), ub)
    changed = any_true(take_l) | any_true(take_u)
    return new_lb, new_ub, changed


def apply_updates_batch(
    lb, ub, best_lcand, best_ucand, eps: float, inf: float = INF,
    outward: float = 0.0,
):
    """Batched merge: ``(B, n_pad)`` bounds/candidates -> per-instance change.

    Identical elementwise semantics to :func:`apply_updates` (including the
    fp32-tier ``outward`` widening); only the ``changed`` reduction stays
    per instance (axis -1), which is what lets a batched fixed point
    converge each instance independently.
    """
    take_l = improved_lb(best_lcand, lb, eps)
    take_u = improved_ub(best_ucand, ub, eps)
    if outward:
        best_lcand, best_ucand = widen_outward(best_lcand, best_ucand, outward)
    new_lb = jnp.where(take_l, clamp_to_sentinel(best_lcand, inf), lb)
    new_ub = jnp.where(take_u, clamp_to_sentinel(best_ucand, inf), ub)
    changed = jnp.any(take_l, axis=-1) | jnp.any(take_u, axis=-1)
    return new_lb, new_ub, changed


def progress_measure(lb_old, ub_old, lb_new, ub_new):
    """Per-round *measure of progress* (Sofranac et al., arXiv:2106.07573,
    adapted to sentinel-infinite bounds).

    Scale-normalized total bound movement of one round, reduced over the
    trailing (variable) axis of the two bound planes:

        sum_j  (lb' - lb) / (1 + max(|lb|, |lb'|))
             + (ub - ub') / (1 + max(|ub|, |ub'|))

    Each term is ~1 for an infinite->finite jump (the sentinel dominates
    the denominator), ~|delta|/|bound| for a finite tighten, and exactly 0
    for an untouched variable -- so the scalar is comparable across rounds
    and instances regardless of scaling, and monotone tightening keeps it
    >= 0.  Cheap: elementwise + one reduction over ``(2, n_pad)`` planes,
    computed inside the device fixed-point loops (no host sync).  Batched
    ``(B, n_pad)`` inputs reduce per instance."""
    dl = lb_new - lb_old
    du = ub_old - ub_new
    sl = 1.0 + jnp.maximum(jnp.abs(lb_old), jnp.abs(lb_new))
    su = 1.0 + jnp.maximum(jnp.abs(ub_old), jnp.abs(ub_new))
    return jnp.sum(dl / sl + du / su, axis=-1)
