"""GPU-parallel domain propagation (paper Algorithm 2), in pure JAX.

One *round* (Alg. 3 at nonzero granularity, re-expressed for TPU):

  1. activities + infinity counters per row        (segment-sum over nonzeros)
  2. residual activities + bound candidates        (elementwise over nonzeros)
  3. column-wise best candidate                    (segment-max/min over nonzeros)
  4. integrality rounding + monotone update        (elementwise over columns)

Loop drivers (paper §3.7 / App. C):

  * ``host_loop``   -- Python loop, one jitted round per iteration, host reads
                       a 1-byte converged flag each round (paper: cpu_loop).
  * ``device_loop`` -- ``jax.lax.while_loop``: the entire fixed point is ONE
                       XLA dispatch with zero host synchronization
                       (paper: gpu_loop; on TPU this is the natural form).
  * ``unrolled``    -- while_loop whose body fuses ``unroll`` rounds before
                       re-checking convergence (megakernel-flavored trade-off:
                       fewer sync points, possibly wasted rounds).

All drivers share the exact same round function so they converge to the same
fixed point by construction.
"""
from __future__ import annotations

import dataclasses
import functools
import jax
import jax.numpy as jnp
import numpy as np

from . import activities as act
from . import bounds as bnd
from ..obs import telemetry as obs
from .sparse import Problem
from .types import (
    DEFAULT_CONFIG,
    INF,
    PropagationResult,
    PropagatorConfig,
    TierPolicy,
    resolve_dtype,
)


# ---------------------------------------------------------------------------
# Device-side problem representation (static shapes, jit-friendly)
# ---------------------------------------------------------------------------


class DeviceProblem:
    """Static-shape device arrays + metadata for the parallel propagator."""

    def __init__(self, p: Problem, dtype=None):
        csr = p.csr
        dtype = dtype or csr.val.dtype
        self.m = csr.m
        self.n = csr.n
        self.nnz = csr.nnz
        self.row_id = jnp.asarray(csr.row_ids())
        self.col = jnp.asarray(csr.col)
        self.val = jnp.asarray(csr.val, dtype=dtype)
        self.lhs = jnp.asarray(p.lhs, dtype=dtype)
        self.rhs = jnp.asarray(p.rhs, dtype=dtype)
        self.lb0 = jnp.asarray(p.lb, dtype=dtype)
        self.ub0 = jnp.asarray(p.ub, dtype=dtype)
        self.is_int = jnp.asarray(p.is_int)
        self.dtype = dtype


# ---------------------------------------------------------------------------
# One propagation round
# ---------------------------------------------------------------------------


def propagation_round(
    row_id,
    col,
    val,
    lhs,
    rhs,
    is_int,
    lb,
    ub,
    m: int,
    n: int,
    eps: float,
    int_eps: float,
    inf: float = INF,
    outward: float = 0.0,
):
    """Pure function: one parallel propagation round.  Returns (lb, ub, changed)."""
    lb_col = lb[col]
    ub_col = ub[col]
    min_fin, min_inf, max_fin, max_inf = act.nnz_contributions(val, lb_col, ub_col, inf)

    seg = lambda x: jax.ops.segment_sum(x, row_id, num_segments=m)
    row_min_fin = seg(min_fin)
    row_min_inf = seg(min_inf)
    row_max_fin = seg(max_fin)
    row_max_inf = seg(max_inf)

    min_res = act.residual_activities(
        val, min_fin, min_inf, row_min_fin[row_id], row_min_inf[row_id], "min", inf
    )
    max_res = act.residual_activities(
        val, max_fin, max_inf, row_max_fin[row_id], row_max_inf[row_id], "max", inf
    )

    lcand, ucand = bnd.bound_candidates(
        val, lhs[row_id], rhs[row_id], min_res, max_res, inf
    )
    lcand, ucand = bnd.round_candidates(lcand, ucand, is_int[col], int_eps, inf)

    best_l = jax.ops.segment_max(lcand, col, num_segments=n)
    best_u = jax.ops.segment_min(ucand, col, num_segments=n)
    # Columns with no nonzeros get segment identity (-inf/+inf fill is fine).

    return bnd.apply_updates(lb, ub, best_l, best_u, eps, inf, outward)


def _round_fn(dp: DeviceProblem, cfg: PropagatorConfig):
    eps = cfg.eps_for(dp.dtype)
    return functools.partial(
        propagation_round,
        dp.row_id,
        dp.col,
        dp.val,
        dp.lhs,
        dp.rhs,
        dp.is_int,
        m=dp.m,
        n=dp.n,
        eps=eps,
        int_eps=cfg.int_eps,
        inf=cfg.inf,
        outward=cfg.outward_for(dp.dtype),
    )


def check_infeasible(lb, ub, feas_eps: float):
    return jnp.any(lb > ub + feas_eps)


# ---------------------------------------------------------------------------
# Loop drivers
# ---------------------------------------------------------------------------


def donate_supported() -> bool:
    """XLA implements buffer donation on accelerators only; on CPU it is a
    no-op that warns, so zero-copy drivers request it where it works."""
    return jax.default_backend() in ("tpu", "gpu", "cuda", "rocm")


def donate_kwargs(argnums=None, argnames=None) -> dict:
    """``jax.jit`` donation kwargs for the zero-copy drivers, empty on
    backends without donation support (single place for the gating policy)."""
    if not donate_supported():
        return {}
    out = {}
    if argnums is not None:
        out["donate_argnums"] = tuple(argnums)
    if argnames is not None:
        out["donate_argnames"] = tuple(argnames)
    return out


def owned_copy(x):
    """Private copy of a cached device array.  The zero-copy drivers donate
    their bound buffers; handing them copies keeps the DeviceProblem /
    prepare() caches' initial bounds valid across repeated propagations."""
    return jnp.array(x, copy=True)


def initial_bounds(dp_or_arrays, lb0=None, ub0=None, dtype=None, n: int | None = None):
    """Resolve the warm-start bound overrides of a driver call.

    ``(lb0, ub0)`` are RUNTIME arguments, not prepare-time constants: a
    branch-and-bound node that differs from its parent by one branching
    bound propagates through the same prepared engine by passing its bounds
    here.  ``None`` falls back to the prepared root bounds.  The returned
    arrays are private copies, so donation into a zero-copy fixed point can
    never invalidate caller-held buffers or the prepare() caches.
    """
    default_lb, default_ub = dp_or_arrays
    dtype = dtype or default_lb.dtype
    n = int(default_lb.shape[-1]) if n is None else n

    def pick(override, default):
        if override is None:
            return owned_copy(default)
        arr = jnp.asarray(override, dtype)
        if arr.shape != (n,):
            raise ValueError(f"bounds override has shape {arr.shape}, expected {(n,)}")
        return owned_copy(arr)

    return pick(lb0, default_lb), pick(ub0, default_ub)


def propagate_host_loop(
    dp: DeviceProblem,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    lb0=None,
    ub0=None,
    stop_progress: float | None = None,
    patience: int = 1,
    telemetry: int | None = None,
) -> PropagationResult:
    """cpu_loop analogue: host iterates rounds, syncing one flag per round.

    Zero-copy: (lb, ub) are donated each call, so XLA reuses the same two
    bound buffers round over round instead of allocating fresh ones.
    ``lb0``/``ub0`` warm-start the fixed point from caller-supplied bounds
    (default: the problem's root bounds).  ``stop_progress`` arms the
    progress-based early stop (see :func:`_device_fixed_point`); on this
    driver the measure is read back per round like the changed flag.
    ``telemetry`` (a ring capacity) records the per-round trajectory
    host-side -- this driver syncs every round anyway -- into the same
    snapshot shape the device drivers produce."""
    base = _round_fn(dp, cfg)
    tel_on = bool(telemetry)

    def step(lb, ub):
        # Progress is computed INSIDE the jit, while the pre-round bounds
        # are still live -- the donated input buffers are gone afterwards.
        nlb, nub, ch = base(lb=lb, ub=ub)
        out = nlb, nub, ch, bnd.progress_measure(lb, ub, nlb, nub)
        if tel_on:
            out = out + (check_infeasible(nlb, nub, cfg.feas_eps),)
        return out

    round_fn = jax.jit(step, **donate_kwargs(argnums=(0, 1)))
    lb, ub = initial_bounds((dp.lb0, dp.ub0), lb0, ub0, dp.dtype, dp.n)
    rounds = 0
    changed = True
    prog = float("nan")
    flat = 0
    history: list[float] = []
    stop_round = -1
    infeas_round = -1
    while changed and rounds < cfg.max_rounds:
        lb, ub, changed_dev, prog_dev, *infeas_dev = round_fn(lb, ub)
        changed = bool(changed_dev)  # the per-round host<->device sync point
        rounds += 1
        if tel_on:
            history.append(float(prog_dev))
            if infeas_round < 0 and bool(infeas_dev[0]):
                infeas_round = rounds
        if stop_progress is not None:
            prog = float(prog_dev)
            flat = flat + 1 if prog < stop_progress else 0
            if flat >= patience:
                stop_round = rounds
                break
    infeasible = bool(check_infeasible(lb, ub, cfg.feas_eps))
    snap = None
    if tel_on:
        snap = obs.host_snapshot(
            history, telemetry, stop_round=stop_round, infeas_round=infeas_round
        )
    return PropagationResult(
        lb=lb,
        ub=ub,
        rounds=jnp.int32(rounds),
        converged=jnp.asarray(not changed),
        infeasible=jnp.asarray(infeasible),
        progress=jnp.asarray(prog),
        telemetry=snap,
    )


def _device_fixed_point(
    round_fn, lb0, ub0, max_rounds: int, unroll: int = 1,
    stop_progress: float | None = None, patience: int = 1,
    plane=None, feas_eps: float | None = None,
):
    """while_loop fixed point; ``unroll`` rounds per convergence check.

    Carries the per-check progress measure (``bounds.progress_measure`` over
    the bound planes -- a device scalar, no host sync).  ``stop_progress``
    arms the early stop: once progress stays below it for ``patience``
    consecutive checks the loop exits even though epsilon-level changes
    continue (a flatlined instance).  Returns ``(lb, ub, changed, rounds,
    progress)`` -- ``progress`` is the last check's measure (NaN before the
    first round).

    ``plane`` (an ``obs.telemetry.TelemetryPlane``, scalar layout) arms the
    device-resident telemetry: the plane joins the loop carry, each check
    appends its progress sample and latches early-stop / infeasibility
    rounds (the probe needs ``feas_eps``), and the final plane is appended
    to the return tuple.  Recording reads the same progress scalar the
    carry already computes and never feeds back into the bounds, so the
    fixed point's arithmetic is unchanged -- still zero host syncs."""

    def body(state):
        lb, ub, _, rounds, _, flat = state[:6]
        lb_in, ub_in = lb, ub
        changed_any = jnp.asarray(False)
        for _ in range(unroll):
            lb, ub, changed = round_fn(lb=lb, ub=ub)
            changed_any = changed_any | changed
            rounds = rounds + 1
        prog = bnd.progress_measure(lb_in, ub_in, lb, ub)
        if stop_progress is not None:
            flat = jnp.where(prog < stop_progress, flat + 1, 0)
        out = (lb, ub, changed_any, rounds, prog, flat)
        if plane is not None:
            stopped = (flat >= patience) if stop_progress is not None else None
            tel = obs.record_round(
                state[6], prog, rounds,
                check_infeasible(lb, ub, feas_eps), stopped,
            )
            out = out + (tel,)
        return out

    def cond(state):
        changed, rounds, flat = state[2], state[3], state[5]
        go = changed & (rounds < max_rounds)
        if stop_progress is not None:
            go = go & (flat < patience)
        return go

    nan = jnp.asarray(jnp.nan, lb0.dtype)
    init = (lb0, ub0, jnp.asarray(True), jnp.int32(0), nan, jnp.int32(0))
    if plane is not None:
        init = init + (plane,)
    # First iteration must run: seed changed=True, but do not count it.
    final = jax.lax.while_loop(cond, body, init)
    lb, ub, changed, rounds, prog = final[:5]
    if plane is not None:
        return lb, ub, changed, rounds, prog, final[6]
    return lb, ub, changed, rounds, prog


def batched_step_rounds(
    round_fn, lb, ub, active, last_changed, rounds, max_rounds: int,
    budget: int | None = None, *,
    stop_progress: float | None = None, patience: int = 1,
    progress=None, flat=None, with_progress: bool = False,
    plane=None, feas_eps: float | None = None,
):
    """Run up to ``budget`` rounds of a batched fixed point and return the
    carried state -- the RESUMABLE core of :func:`batched_fixed_point`.

    ``round_fn(lb, ub, active) -> (lb, ub, changed)`` as there; the state
    quintuple ``(lb, ub, active, last_changed, rounds)`` is exactly the
    fixed point's loop carry, so feeding one call's output to the next
    continues the per-instance round trajectories bit-for-bit -- where the
    step boundary falls cannot change any instance's arithmetic, because a
    round only reads the instance's own tiles and bounds.  The loop exits
    early when every instance converged, so a step over an all-converged
    batch costs one predicate evaluation, not ``budget`` rounds.

    This is the continuous-batching service's device step
    (``core.service``): each pump runs a *bounded* number of rounds per
    bucket -- the per-slot round budget -- then returns control to the host
    so converged slots retire and free slots admit, without any one slow
    instance holding the bucket hostage.  ``budget=None`` (run to
    convergence) makes :func:`batched_fixed_point` a single call of this.

    Progress control (all keyword-only, default off so the 5-tuple
    contract below is unchanged): ``stop_progress`` arms the per-instance
    flatline stop -- an instance whose per-round ``progress_measure``
    stays below it for ``patience`` consecutive rounds drops out of
    ``active`` with ``last_changed`` still True (stopped, not converged).
    ``progress``/``flat`` are the carried ``(B,)`` measure and low-progress
    streak (pass a previous call's values to resume bit-for-bit across
    step boundaries); ``with_progress=True`` appends them to the return,
    making it ``(lb, ub, active, last_changed, rounds, progress, flat)``.

    ``plane`` (an ``obs.telemetry.TelemetryPlane``, batched layout) arms
    device-resident telemetry: the plane rides the carry, every round
    records per-instance progress / early-stop / infeasibility (probe
    needs ``feas_eps``) for the instances that actually ran, and the final
    plane is appended to the return -- the 8-tuple ``(..., progress, flat,
    plane)``.  Passing a previous step's plane back resumes its rings
    bit-for-bit, exactly like the rest of the carry.  Recording never
    touches the bound dataflow (bitwise-identical bounds, zero host
    syncs); its masks reuse the round's own ``active``/``flat`` values.
    """
    track = with_progress or stop_progress is not None or plane is not None
    bsz = lb.shape[0]
    if progress is None:
        progress = jnp.full((bsz,), jnp.nan, lb.dtype)
    if flat is None:
        flat = jnp.zeros((bsz,), jnp.int32)

    def body(state):
        lb, ub, active, last_changed, rounds, progress, flat, k = state[:8]
        lb_in, ub_in = lb, ub
        ran = active
        with jax.named_scope("prop_round"):
            lb, ub, changed = round_fn(lb, ub, active)
        with jax.named_scope("fixed_point"):
            rounds = rounds + active.astype(jnp.int32)
            last_changed = jnp.where(active, changed, last_changed)
            if track:
                prog = bnd.progress_measure(lb_in, ub_in, lb, ub)
                progress = jnp.where(active, prog, progress)
                if stop_progress is not None:
                    flat = jnp.where(
                        active, jnp.where(prog < stop_progress, flat + 1, 0), flat
                    )
            active = active & changed & (rounds < max_rounds)
            if stop_progress is not None:
                active = active & (flat < patience)
            out = (lb, ub, active, last_changed, rounds, progress, flat, k + 1)
            if plane is not None:
                stopped = (flat >= patience) if stop_progress is not None else None
                tel = obs.record_round(
                    state[8], prog,
                    rounds, jnp.any(lb > ub + feas_eps, axis=-1), stopped,
                    active=ran,
                )
                out = out + (tel,)
        return out

    @jax.named_scope("fixed_point")
    def cond(state):
        go = jnp.any(state[2])
        if budget is not None:
            go = go & (state[7] < budget)
        return go

    init = (lb, ub, active, last_changed, rounds, progress, flat, jnp.int32(0))
    if plane is not None:
        init = init + (plane,)
    final = jax.lax.while_loop(cond, body, init)
    lb, ub, active, last_changed, rounds, progress, flat = final[:7]
    if plane is not None:
        return lb, ub, active, last_changed, rounds, progress, flat, final[8]
    if with_progress:
        return lb, ub, active, last_changed, rounds, progress, flat
    return lb, ub, active, last_changed, rounds


def batched_fixed_point(
    round_fn, lb0, ub0, max_rounds: int, active0=None, *,
    stop_progress: float | None = None, patience: int = 1,
    with_progress: bool = False, plane=None, feas_eps: float | None = None,
):
    """Batched while_loop fixed point with a per-instance convergence mask.

    ``round_fn(lb, ub, active) -> (lb, ub, changed)`` operates on
    ``(B, n_pad)`` bounds and per-instance ``(B,)`` flags.  The loop runs
    until *every* instance has converged (or hit ``max_rounds``); an
    instance whose round produced no change drops out of ``active`` and its
    bounds are frozen -- finished instances are no-ops, not stragglers'
    hostages.  Per-instance round counts match what each instance would
    have seen in its own single-instance ``device_loop``.

    Returns ``(lb, ub, rounds, converged)`` with ``rounds``/``converged``
    per instance; ``with_progress=True`` appends the per-instance last
    progress measure (``(lb, ub, rounds, converged, progress)``).
    ``stop_progress``/``patience`` arm the per-instance flatline stop (see
    :func:`batched_step_rounds`): a stopped instance reports
    ``converged=False`` at ``rounds < max_rounds``.

    ``plane``/``feas_eps`` arm per-instance device telemetry (see
    :func:`batched_step_rounds`); the final plane is appended to either
    return shape.
    """
    bsz = lb0.shape[0]
    if active0 is None:
        active0 = jnp.ones((bsz,), dtype=bool)

    out = batched_step_rounds(
        round_fn, lb0, ub0, active0, active0,
        jnp.zeros((bsz,), jnp.int32), max_rounds, budget=None,
        stop_progress=stop_progress, patience=patience, with_progress=True,
        plane=plane, feas_eps=feas_eps,
    )
    lb, ub, _, last_changed, rounds, progress, _ = out[:7]
    tail = (out[7],) if plane is not None else ()
    if with_progress:
        return (lb, ub, rounds, ~last_changed, progress) + tail
    return (lb, ub, rounds, ~last_changed) + tail


def propagate_batch(
    problems,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    tile_rows: int = 8,
    tile_width: int = 128,
    dtype=None,
    use_pallas: bool = True,
    driver: str = "device_loop",
    interpret: bool | None = None,
    donate: bool | None = None,
    bounds=None,
    slab: int | None = None,
    stop_progress: float | None = None,
    patience: int = 1,
    policy: TierPolicy | None = None,
    telemetry: int | None = None,
):
    """Propagate a batch of instances, thousands per device dispatch.

    Front end over the batched block-ELL engine: instances are bucketed by
    padded column width (``core.sparse.pack_problems``), each bucket runs
    its fixed point in ONE dispatch with a per-instance convergence mask,
    and results come back as one ``PropagationResult`` per instance, input
    order (``(n_i,)`` bounds each).  Buckets whose ``n_pad`` exceeds the
    VMEM accumulator budget ride the column-slab partitioned kernels
    automatically.  ``bounds`` (one ``(lb_i, ub_i)`` pair of ``(n_i,)``
    arrays or ``None`` per problem) warm-starts instances from caller
    bounds without repacking.  Packing, device transfer and the compiled
    runners are LRU-cached on the identity of the problem list / packed
    batch (see ``kernels.cache_info()``), so a serving loop pays them
    once.  See ``kernels.ops.propagate_batch_block_ell`` for the engine
    knobs; ``stop_progress``/``patience`` arm the per-instance
    progress-based early stop, ``policy`` the two-tier precision
    scheme, and ``telemetry`` per-instance device telemetry snapshots
    (all documented there)."""
    from ..kernels.ops import propagate_batch_block_ell  # lazy: kernels imports core

    return propagate_batch_block_ell(
        problems,
        cfg=cfg,
        tile_rows=tile_rows,
        tile_width=tile_width,
        dtype=dtype,
        use_pallas=use_pallas,
        driver=driver,
        interpret=interpret,
        donate=donate,
        bounds=bounds,
        slab=slab,
        stop_progress=stop_progress,
        patience=patience,
        policy=policy,
        telemetry=telemetry,
    )


def propagate_device_loop(
    dp: DeviceProblem,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    unroll: int = 1,
    lb0=None,
    ub0=None,
    stop_progress: float | None = None,
    patience: int = 1,
    telemetry: int | None = None,
) -> PropagationResult:
    """gpu_loop analogue: the whole fixed point is one XLA dispatch.

    Zero-copy: the initial bounds are donated into the while_loop carry, so
    the fixed point runs in place on two device buffers.  ``lb0``/``ub0``
    warm-start the fixed point from caller-supplied bounds;
    ``stop_progress``/``patience`` arm the in-dispatch progress-based early
    stop (see :func:`_device_fixed_point`).  ``telemetry`` (a ring
    capacity) carries a device telemetry plane through the loop and
    attaches its snapshot to the result -- still one dispatch, zero added
    host syncs."""
    round_fn = _round_fn(dp, cfg)
    tel_cap = int(telemetry or 0)

    @functools.partial(jax.jit, **donate_kwargs(argnums=(0, 1)))
    def run(lb0, ub0):
        plane = obs.device_plane(tel_cap, dtype=lb0.dtype) if tel_cap else None
        out = _device_fixed_point(
            round_fn, lb0, ub0, cfg.max_rounds, unroll=unroll,
            stop_progress=stop_progress, patience=patience,
            plane=plane, feas_eps=cfg.feas_eps,
        )
        lb, ub, changed, rounds, prog = out[:5]
        infeasible = check_infeasible(lb, ub, cfg.feas_eps)
        res = (lb, ub, rounds, ~changed, infeasible, prog)
        return res + ((out[5],) if tel_cap else ())

    lb_init, ub_init = initial_bounds((dp.lb0, dp.ub0), lb0, ub0, dp.dtype, dp.n)
    out = run(lb_init, ub_init)
    lb, ub, rounds, converged, infeasible, prog = out[:6]
    snap = obs.TelemetrySnapshot(plane=out[6]) if tel_cap else None
    return PropagationResult(
        lb, ub, rounds, converged, infeasible, prog, telemetry=snap
    )


def propagate_unrolled(
    dp: DeviceProblem,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    unroll: int = 4,
    lb0=None,
    ub0=None,
    stop_progress: float | None = None,
    patience: int = 1,
    telemetry: int | None = None,
) -> PropagationResult:
    """megakernel-flavored driver: k fused rounds per convergence check."""
    return propagate_device_loop(
        dp, cfg, unroll=unroll, lb0=lb0, ub0=ub0,
        stop_progress=stop_progress, patience=patience, telemetry=telemetry,
    )


def two_tier_bounds_dtypes(policy: TierPolicy, dtype):
    """Resolve the (fp32 tier, endgame) dtype pair of a tiered run, or
    ``None`` when the policy degenerates to single-tier (disabled, or the
    requested dtype is already low-precision)."""
    import numpy as np

    final = jnp.dtype(resolve_dtype(
        dtype, jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    ))
    if not policy.two_tier or final in (
        jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)
    ):
        return None
    return np.float32, final


def propagate(
    p: Problem,
    cfg: PropagatorConfig = DEFAULT_CONFIG,
    driver: str = "device_loop",
    dtype=None,
    lb0=None,
    ub0=None,
    policy: TierPolicy | None = None,
    telemetry: int | None = None,
) -> PropagationResult:
    """Convenience front end: Problem -> PropagationResult (pure-jnp round,
    no Pallas -- the kernel-backed sibling is ``kernels.propagate_block_ell``).

    ``driver`` picks the loop (``host_loop`` syncs one flag per round,
    ``device_loop`` runs the whole fixed point as one dispatch,
    ``unrolled`` checks convergence every k rounds); ``dtype`` overrides
    the value dtype (default: the CSR's, f64 under x64).  ``lb0``/``ub0``
    are ``(n,)`` warm-start overrides for this call only (the tree-search
    path: propagate a B&B node's domain through the root problem's device
    arrays without rebuilding anything); the returned bounds are ``(n,)``
    device arrays in that dtype.

    ``policy`` (a :class:`TierPolicy`) turns on runtime progress control:
    with ``two_tier`` the fixed point runs an fp32 tier (outward-rounded
    merges, so its bounds are never inside the fp64 fixed point) until
    per-round progress drops below ``switch_progress``, promotes the
    bounds by exact cast, and finishes in the requested dtype -- landing
    on the same fixed point the untied run reaches; ``stop_progress``
    additionally early-stops flatlined runs.  ``result.tier_rounds``
    counts the fp32-tier rounds.

    ``telemetry`` (a ring capacity, e.g. ``obs.DEFAULT_CAPACITY``) attaches
    an ``obs.TelemetrySnapshot`` to the result: per-round progress ring,
    early-stop / infeasibility rounds, accumulated on device and read back
    only at exit.  Under a two-tier policy the snapshot is the endgame's,
    with ``tier_switch_round`` stamped (at the host decision point that
    already reads the fp32 round count) and the fp32 tier's own snapshot
    under ``.fp32``."""
    pair = two_tier_bounds_dtypes(policy, dtype) if policy is not None else None
    if pair is not None:
        dt32, final = pair
        cap32 = max(1, int(cfg.max_rounds * policy.fp32_round_frac))
        r32 = _propagate_single(
            p, dataclasses.replace(cfg, max_rounds=cap32), driver, dt32,
            lb0, ub0, stop_progress=policy.switch_progress,
            patience=policy.patience, telemetry=telemetry,
        )
        if bool(r32.infeasible):
            # Never trust an fp32 infeasibility verdict: outward rounding
            # makes it overwhelmingly a true positive, but a cancellation-
            # heavy row can overtighten past the widening, so the endgame
            # re-derives the verdict in the final dtype from scratch.
            r = _propagate_single(
                p, cfg, driver, final, lb0, ub0,
                stop_progress=policy.stop_progress, patience=policy.patience,
                telemetry=telemetry,
            )
            if r.telemetry is not None:
                r = r._replace(
                    telemetry=dataclasses.replace(r.telemetry, fp32=r32.telemetry)
                )
            return r._replace(tier_rounds=r32.rounds)
        tier_rounds = int(r32.rounds)
        rem = dataclasses.replace(
            cfg, max_rounds=max(1, cfg.max_rounds - tier_rounds)
        )
        warm_lb, warm_ub = bnd.canonical_infinite(
            jnp.asarray(r32.lb, final), jnp.asarray(r32.ub, final)
        )
        r = _propagate_single(
            p, rem, driver, final, warm_lb, warm_ub,
            stop_progress=policy.stop_progress, patience=policy.patience,
            telemetry=telemetry,
        )
        if r.telemetry is not None:
            r = r._replace(
                telemetry=dataclasses.replace(
                    r.telemetry,
                    tier_switch_round=tier_rounds,
                    fp32=r32.telemetry,
                )
            )
        return r._replace(
            rounds=r.rounds + r32.rounds, tier_rounds=r32.rounds
        )
    stop = policy.stop_progress if policy is not None else None
    patience = policy.patience if policy is not None else 1
    return _propagate_single(
        p, cfg, driver, dtype, lb0, ub0, stop_progress=stop, patience=patience,
        telemetry=telemetry,
    )


def _propagate_single(
    p: Problem, cfg, driver, dtype, lb0, ub0,
    stop_progress=None, patience: int = 1, telemetry: int | None = None,
) -> PropagationResult:
    """One single-dtype fixed point (the tiered front end calls this twice)."""
    dp = DeviceProblem(p, dtype=dtype)
    kw = dict(
        lb0=lb0, ub0=ub0, stop_progress=stop_progress, patience=patience,
        telemetry=telemetry,
    )
    if driver == "host_loop":
        return propagate_host_loop(dp, cfg, **kw)
    if driver == "device_loop":
        return propagate_device_loop(dp, cfg, **kw)
    if driver == "unrolled":
        return propagate_unrolled(dp, cfg, **kw)
    raise ValueError(f"unknown driver: {driver}")


def fresh_instance_runner(p: Problem, cfg: PropagatorConfig = DEFAULT_CONFIG):
    """One jitted fixed point whose matrix arrays are RUNTIME arguments.

    Returns ``propagate_fresh(lb, ub) -> (lb, ub, rounds)``.  Each call
    re-expands the CSR row structure on the host and re-uploads the whole
    matrix before its single dispatch -- i.e. it treats the node as a
    brand-new instance.  Shapes are stable across calls, so XLA compiles
    once; this is the honest "repack each node" baseline the warm-start
    engines are benchmarked against (``benchmarks/bench_prop.py``,
    ``examples/bnb_dive.py``), and doubles as a one-off runner for streams
    of same-shape instances."""
    eps = cfg.eps_for(p.csr.val.dtype)
    round_fn = functools.partial(
        propagation_round, m=p.m, n=p.n, eps=eps, int_eps=cfg.int_eps, inf=cfg.inf
    )

    @jax.jit
    def run(row_id, col, val, lhs, rhs, is_int, lb0, ub0):
        def body(s):
            lb, ub, _, r = s
            lb, ub, ch = round_fn(row_id, col, val, lhs, rhs, is_int, lb, ub)
            return lb, ub, ch, r + 1

        def cond(s):
            return s[2] & (s[3] < cfg.max_rounds)

        lb, ub, ch, r = jax.lax.while_loop(
            cond, body, (lb0, ub0, jnp.asarray(True), jnp.int32(0))
        )
        return lb, ub, r

    def propagate_fresh(lb, ub):
        # The per-node repack: row expansion on the host + full re-upload.
        return run(
            jnp.asarray(p.csr.row_ids()), jnp.asarray(p.csr.col),
            jnp.asarray(p.csr.val), jnp.asarray(p.lhs), jnp.asarray(p.rhs),
            jnp.asarray(p.is_int), jnp.asarray(lb), jnp.asarray(ub),
        )

    return propagate_fresh


# ---------------------------------------------------------------------------
# Result comparison (paper §4.3)
# ---------------------------------------------------------------------------


def bounds_equal(
    a_lb, a_ub, b_lb, b_ub, t_abs: float = 1e-8, t_rel: float = 1e-5, inf: float = INF
) -> bool:
    """Paper §4.3: |a-b| <= t_abs + t_rel*|b|, with both-infinite counted equal."""
    a_lb, a_ub = np.asarray(a_lb, np.float64), np.asarray(a_ub, np.float64)
    b_lb, b_ub = np.asarray(b_lb, np.float64), np.asarray(b_ub, np.float64)

    def eq(a, b):
        both_pinf = (a >= inf) & (b >= inf)
        both_ninf = (a <= -inf) & (b <= -inf)
        close = np.abs(a - b) <= (t_abs + t_rel * np.abs(b))
        return both_pinf | both_ninf | close

    return bool(np.all(eq(a_lb, b_lb)) and np.all(eq(a_ub, b_ub)))


# fp32-representation / cancellation band of the outward-rounded float32
# engines: observed worst case 6.4e-8 relative ("mixed" family,
# cancellation-heavy rows), asserted with ~15x headroom -- still 10x
# tighter than the paper's bounds_equal criterion (t_rel=1e-5).
F32_BAND = 1e-6


def overtightening(lb_t, ub_t, lb_o, ub_o, is_int, band: float = F32_BAND,
                   inf: float = INF) -> "str | None":
    """The never-tighter contract of a low-precision run (``lb_t``/``ub_t``)
    against an exact oracle (``lb_o``/``ub_o``): no oracle-infinite bound
    made finite, integer bounds never tighter at all, continuous ones at
    most ``band`` relative.  Returns ``None`` when it holds, else what
    broke it."""
    lb_t, ub_t = np.asarray(lb_t, np.float64), np.asarray(ub_t, np.float64)
    lb_o, ub_o = np.asarray(lb_o, np.float64), np.asarray(ub_o, np.float64)
    is_int = np.asarray(is_int, bool)
    if np.any((lb_o <= -inf / 2) & (lb_t > -inf / 2)):
        return "an infinite lower bound became finite"
    if np.any((ub_o >= inf / 2) & (ub_t < inf / 2)):
        return "an infinite upper bound became finite"
    fin_l, fin_u = lb_o > -inf / 2, ub_o < inf / 2
    tol = np.where(is_int, 0.0, band * (1.0 + np.abs(lb_o)))
    if not np.all(lb_t[fin_l] <= (lb_o + tol)[fin_l]):
        return f"lb overtightened by {np.max((lb_t - lb_o - tol)[fin_l]):.3e}"
    tol = np.where(is_int, 0.0, band * (1.0 + np.abs(ub_o)))
    if not np.all(ub_t[fin_u] >= (ub_o - tol)[fin_u]):
        return f"ub overtightened by {np.max((ub_o - tol - ub_t)[fin_u]):.3e}"
    return None
