"""Continuous-batching propagation service: slot-recycled resident
super-tiles with AOT-warmed engines.

The paper's headline is throughput -- propagation rounds run entirely on the
accelerator with no host synchronization -- but a fixed-batch driver
(:func:`repro.core.propagator.propagate_batch`) still stops the world at
batch boundaries: every new batch repacks, re-uploads and (first time)
recompiles, and the whole batch waits for its slowest instance.  This module
is the serving loop that removes those stalls, in the spirit of the
progress-measure serving loop of Sofranac et al. (arXiv:2106.07573) and the
fully device-resident search loop of Talbot et al. (arXiv:2207.12116):

* Each :class:`BucketSpec` keeps ONE device-resident super-tile of
  ``slots`` fixed-shape slots.  An arriving instance is packed host-side to
  the slot shape (:func:`repro.core.sparse.pack_into_slot`) and admitted by
  scattering its tiles/bounds into a free slot in one device op -- the
  resident batch is never repacked or reshaped.
* The per-instance ``converged``/``active`` mask of the batched kernels IS
  the slot-occupancy mask: a free (or just-retired) slot is an inactive
  instance, so its tiles skip gather/compute/scatter in-kernel and an empty
  slot costs ~nothing.  Retirement is pure host bookkeeping plus an async
  readback of the bound plane; the device loop never stops for it.
* Every compiled engine (the budgeted step and the power-of-two admission
  scatters) is built and warmed when the service is constructed, and cached
  process-wide by bucket shape -- admission and backfill NEVER compile.
* Each pump runs a bounded number of rounds per bucket
  (:func:`repro.core.propagator.batched_step_rounds` with a ``budget``), so
  one slow instance cannot hold a bucket hostage: converged co-residents
  retire and their slots backfill at the next step boundary while the slow
  instance keeps iterating.

Bitwise contract: a slot-resident instance follows the exact round
trajectory of a one-shot ``propagate_batch`` of the same instance (same
tile parameters) -- a round only reads the instance's own tiles, bounds and
rows, co-residents and step boundaries cannot perturb its arithmetic, and
retirement reads back the converged plane unchanged.  ``tests/test_service.py``
asserts this bit-for-bit through admit -> converge -> retire -> backfill.
One caveat, by construction: the service's matrix buffers are RUNTIME
arguments of its compiled step (that is what makes admission compile-free),
while the one-shot engines close over them as jit constants -- XLA may
compile the two dataflow graphs with differently-associated reductions, so
equality of every float op is only guaranteed up to reassociation ulps.
Whenever the per-row dot products are exactly representable (integral
coefficient/bound families like set covers or knapsacks -- and any engine
whose round runs as a Pallas kernel, whose in-kernel order is fixed), the
trajectories are identical bit-for-bit, and the tests pin exactly that.

Observability rides the same zero-sync discipline (``repro.obs``): an
optional per-slot telemetry plane lives in the resident state (entries
13-16) and is read back only at the retirement sync; a host-side tracer
emits pump/admit/step/readback spans plus one ``ticket`` span per
request; and ``stats()`` carries a unified metrics-registry snapshot.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import default_registry
from ..obs.telemetry import TelemetryPlane, TelemetrySnapshot, reset_rows
from ..obs.trace import NULL_TRACER
from .propagator import batched_step_rounds, donate_kwargs
from .sparse import LANE, Problem, SlotPayload, col_pad, evict_slot, pack_into_slot
from .types import DEFAULT_CONFIG, PropagationResult, PropagatorConfig, resolve_dtype

# Resident bucket state layout (flat tuple, so the jitted step/admit engines
# can donate individual buffers):
#   0 val    (slots*slot_tiles, R, K)  tile values; 0 == padding
#   1 col    (slots*slot_tiles, R, K)  int32 SLOT-LOCAL columns
#   2 ii     (slots*slot_tiles, R, K)  int32 integrality gather
#   3 crow   (slots*slot_tiles, R)    int32 GLOBAL rows (slot-offset applied)
#   4 lhs_c  (slots*slot_tiles, R)    per-chunk lhs (0 at dummy rows)
#   5 rhs_c  (slots*slot_tiles, R)    per-chunk rhs
#   6 lb     (slots, n_pad)           bound plane
#   7 ub     (slots, n_pad)
#   8 active (slots,) bool            occupancy mask == still-running mask
#   9 last_changed (slots,) bool      convergence evidence (as in fixed point)
#  10 rounds (slots,) int32           per-slot rounds executed
#  11 progress (slots,)               last round's progress measure (NaN fresh)
#  12 flat   (slots,) int32           consecutive low-progress rounds
#  13 ring   (slots, tel_cap)         telemetry progress rings (tel_cap may be 0)
#  14 ticks  (slots,) int32           telemetry rounds recorded per slot
#  15 stop_round (slots,) int32       early-stop round latch (-1 = never)
#  16 infeas_round (slots,) int32     first crossed-bounds round (-1 = never)
# The telemetry entries exist in EVERY state (zero-width ring when the
# service runs without telemetry), so there is exactly one state layout and
# one donation signature per bucket shape regardless of the telemetry knob.
_LB, _UB, _ACTIVE, _LAST_CHANGED, _ROUNDS = 6, 7, 8, 9, 10
_PROGRESS, _FLAT = 11, 12
_RING, _TICKS, _STOPR, _INFSR = 13, 14, 15, 16
_MATRIX_ARGS = 6          # state[:6] is the scattered matrix payload
_STATE_ARGS = 17

_TW_CANDIDATES = (8, 16, 32, 64, 128)


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    """Fixed slot geometry of one resident bucket.

    Slot ``i`` of a bucket owns tiles ``[i*slot_tiles, (i+1)*slot_tiles)``
    of the flat tile stream, the column window ``[i*n_pad, (i+1)*n_pad)``
    of the bound plane and the row range ``[i*(slot_rows+1),
    (i+1)*(slot_rows+1))`` (one dummy row per slot, at the resident
    instance's local ``m``).  Payloads are slot-local
    (:class:`repro.core.sparse.SlotPayload`); the admission scatter adds
    the slot offsets on device, so any payload fits any free slot.

    ``fits_one_chunk`` is the engine-path policy bit: when True the bucket
    runs the fused round (every row of every admitted instance must fit one
    ``tile_width`` chunk -- :meth:`admits` enforces it); otherwise the
    multichunk dataflow round handles split rows.
    """

    n_pad: int
    slots: int
    slot_tiles: int
    slot_rows: int
    tile_rows: int = 8
    tile_width: int = 128
    fits_one_chunk: bool = False

    @property
    def m_total(self) -> int:
        """Total rows of the resident bucket (one dummy row per slot)."""
        return self.slots * (self.slot_rows + 1)

    def chunks_needed(self, row_lengths: np.ndarray) -> int:
        """Chunks an instance with these row lengths occupies at this tile
        width (the :func:`repro.core.sparse.csr_to_block_ell` count: every
        row gets ``max(1, ceil(len/K))`` chunks, empty rows included)."""
        lengths = np.asarray(row_lengths, dtype=np.int64)
        return int(np.maximum(1, -(-lengths // self.tile_width)).sum())

    def tiles_needed(self, row_lengths: np.ndarray) -> int:
        """Tiles an instance with these row lengths occupies in a slot."""
        return max(1, -(-self.chunks_needed(row_lengths) // self.tile_rows))

    def fits_problem(self, p: Problem) -> bool:
        """Whether one instance fits a slot of this bucket (dimension,
        tile-count and -- on fused buckets -- row-width checks)."""
        if p.m > self.slot_rows or p.n > self.n_pad:
            return False
        lengths = np.diff(p.csr.row_ptr)
        max_row = int(lengths.max()) if lengths.size else 0
        if self.fits_one_chunk and max_row > self.tile_width:
            return False
        return self.tiles_needed(lengths) <= self.slot_tiles

    def admits(self, payload: SlotPayload) -> bool:
        """Whether an already-packed payload can occupy a slot: exact slot
        shape match plus the fused-path row-width contract."""
        if payload.val.shape != (self.slot_tiles, self.tile_rows, self.tile_width):
            return False
        if payload.n_pad != self.n_pad or payload.m > self.slot_rows:
            return False
        return not (self.fits_one_chunk and payload.max_row_nnz > self.tile_width)

    def pack(self, p: Problem, dtype=None) -> SlotPayload:
        """Pack one instance to this bucket's slot shape."""
        return pack_into_slot(
            p, self.slot_tiles, self.slot_rows, self.n_pad,
            tile_rows=self.tile_rows, tile_width=self.tile_width, dtype=dtype,
        )

    @classmethod
    def for_problems(
        cls,
        problems: Sequence[Problem],
        slots: int = 8,
        tile_rows: int = 8,
        tile_width: int | None = None,
        size_classes: int = 1,
    ) -> "list[BucketSpec]":
        """Derive bucket specs from a sample population: one spec per
        ``col_pad(n)`` class, slot capacity = the max over the class, tile
        width chosen (when not pinned) to maximize estimated slot fill --
        the same padding model as ``csr_to_block_ell`` -- so resident
        super-tiles stay dense instead of inheriting the default layout's
        worst-case padding.

        ``size_classes > 1`` additionally splits each ``col_pad`` class
        into that many tile-count quantiles with their own slot shapes.
        Slot capacity is the max over a bucket's population, so one
        outsized instance otherwise pads EVERY slot to its size; with
        quantile sub-buckets a small instance routes to a small slot
        (``fits_problem`` picks the first -- tightest -- fitting spec) and
        the resident super-tiles stay near the population's density."""
        groups: dict[int, list[Problem]] = {}
        for p in problems:
            groups.setdefault(col_pad(p.n), []).append(p)
        specs = []
        for n_pad in sorted(groups):
            ps = groups[n_pad]
            all_lens = [np.diff(p.csr.row_ptr) for p in ps]
            nnz = float(sum(p.nnz for p in ps))
            if tile_width is not None:
                tw = tile_width
            else:
                def padded(tw_):
                    tot = 0
                    for ls in all_lens:
                        chunks = int(np.maximum(1, -(-ls.astype(np.int64) // tw_)).sum())
                        tot += max(1, -(-chunks // tile_rows)) * tile_rows * tw_
                    return tot
                tw = max(_TW_CANDIDATES, key=lambda t: (nnz / padded(t), t))
            probe = cls(
                n_pad=n_pad, slots=slots, slot_tiles=1, slot_rows=1,
                tile_rows=tile_rows, tile_width=tw,
            )
            by_tiles = sorted(ps, key=lambda p: probe.tiles_needed(
                np.diff(p.csr.row_ptr)
            ))
            q = max(1, -(-len(by_tiles) // max(1, size_classes)))
            subs = [by_tiles[i:i + q] for i in range(0, len(by_tiles), q)]
            # Suffix-max slot_rows: classes are split by TILE count, so a
            # small-tiles instance may still carry more rows than its own
            # class max; widening every class to the row max of itself and
            # all larger classes guarantees each sampled instance fits the
            # first spec whose tile capacity admits it.
            row_caps = [max(p.m for p in sub) for sub in subs]
            for i in range(len(row_caps) - 2, -1, -1):
                row_caps[i] = max(row_caps[i], row_caps[i + 1])
            for sub, slot_rows in zip(subs, row_caps):
                lens = [np.diff(p.csr.row_ptr) for p in sub]
                slot_tiles = max(probe.tiles_needed(ls) for ls in lens)
                max_row = max((int(ls.max()) if ls.size else 0) for ls in lens)
                specs.append(cls(
                    n_pad=n_pad, slots=slots, slot_tiles=slot_tiles,
                    slot_rows=slot_rows, tile_rows=tile_rows, tile_width=tw,
                    fits_one_chunk=max_row <= tw,
                ))
        # Tightest spec first, so routing admits each instance to the
        # smallest slot shape that fits it.
        specs.sort(key=lambda s: (s.n_pad, s.slot_tiles, s.slot_rows))
        return specs


def _pow2_decomposition(n: int) -> list[int]:
    """``n`` as descending powers of two (the admission group sizes)."""
    return [1 << b for b in range(n.bit_length() - 1, -1, -1) if (n >> b) & 1]


class ServiceTicket:
    """Future for one submitted instance.

    Carries the packed payload until admission and the
    :class:`repro.core.types.PropagationResult` (host numpy arrays) after
    retirement; ``submit_t``/``admit_t``/``done_t`` are ``perf_counter``
    stamps for the latency percentiles in the bench's ``service`` row.
    """

    __slots__ = (
        "problem", "payload", "submit_t", "admit_t", "done_t",
        "slot", "_result", "_event",
    )

    def __init__(self, problem: Problem | None, payload: SlotPayload):
        self.problem = problem
        self.payload = payload
        self.submit_t = time.perf_counter()
        self.admit_t: float | None = None
        self.done_t: float | None = None
        self.slot: int | None = None
        self._result: PropagationResult | None = None
        self._event = threading.Event()

    def done(self) -> bool:
        """Whether the instance has retired (result available)."""
        return self._event.is_set()

    def result(self, timeout: float | None = None) -> PropagationResult:
        """Block until the instance retires and return its result."""
        if not self._event.wait(timeout):
            raise TimeoutError("instance has not retired yet")
        assert self._result is not None
        return self._result

    def latency(self) -> float | None:
        """Submit-to-retire wall seconds (``None`` until retirement)."""
        if self.done_t is None:
            return None
        return self.done_t - self.submit_t

    def queue_latency(self) -> float | None:
        """Submit-to-admit wall seconds (``None`` until admission) -- how
        long the instance waited for a free slot."""
        if self.admit_t is None:
            return None
        return self.admit_t - self.submit_t

    def service_latency(self) -> float | None:
        """Admit-to-retire wall seconds (``None`` until retirement) -- the
        resident time actually spent propagating."""
        if self.done_t is None or self.admit_t is None:
            return None
        return self.done_t - self.admit_t


class _BucketEngine:
    """The AOT-warmed compiled engines of one bucket shape.

    ``step`` runs up to ``rounds_per_step`` occupancy-masked rounds over the
    resident state (matrix buffers are RUNTIME arguments, so scattering a
    new instance never retraces); ``admits[k]`` scatters ``k`` payloads
    into ``k`` slots in one dispatch (one compiled function per power of
    two bounds compiles at ~log2(slots) per bucket, all warmed up front).

    The step also carries the per-slot *measure of progress* and its
    low-progress streak (state planes 11/12); with ``stop_progress`` set,
    a slot whose progress flatlines for ``patience`` consecutive rounds
    drops out of ``active`` inside the device loop, so the pump's normal
    retire path frees its slot early (``last_changed`` still True marks it
    stopped-not-converged).

    ``telemetry`` (a ring capacity) arms the per-slot device telemetry
    plane (state entries 13-16): every round of every step records the
    slot's progress / early-stop / infeasibility on device, admission
    resets the recycled slot's rows in the same scatter, and the pump
    reads the rows back only at retirement -- where it already syncs for
    the bound plane.  ``telemetry=0`` carries zero-width buffers through
    the identical state layout, so the two modes share one step/admit
    signature and compile count.
    """

    def __init__(
        self,
        spec: BucketSpec,
        dtype,
        cfg: PropagatorConfig,
        rounds_per_step: int,
        use_pallas: bool,
        interpret: bool | None,
        stop_progress: float | None = None,
        patience: int = 1,
        telemetry: int = 0,
    ):
        from ..kernels import ops as kops  # lazy: kernels imports core at module scope
        from ..kernels import prop_round as kern

        self.spec = spec
        self.cfg = cfg
        self.rounds_per_step = rounds_per_step
        self.telemetry = tel_cap = int(telemetry or 0)
        self.np_dtype = np.dtype(dtype)
        self.dev_dtype = jnp.asarray(np.zeros(0, self.np_dtype)).dtype
        self.eps = cfg.eps_for(self.dev_dtype)
        if use_pallas:
            interpret = kern.resolve_interpret(interpret, self.dev_dtype)
        self._lock = threading.RLock()
        self.warmed = False

        s, t, r, k = spec.slots, spec.slot_tiles, spec.tile_rows, spec.tile_width
        n_pad, m_total = spec.n_pad, spec.m_total
        tile_inst = np.repeat(np.arange(s, dtype=np.int32), t)
        pallas_ok = (
            use_pallas and spec.fits_one_chunk
            and n_pad <= kops.SCATTER_MAX_NPAD and n_pad % LANE == 0
        )
        eps, int_eps, inf = self.eps, cfg.int_eps, cfg.inf
        outward = cfg.outward_for(self.dev_dtype)
        max_rounds, budget = cfg.max_rounds, rounds_per_step
        feas_eps = cfg.feas_eps

        def step(val, col, ii, crow, lhs_c, rhs_c,
                 lb, ub, active, last_changed, rounds, progress, flat,
                 ring, ticks, stopr, infsr):
            ti = jnp.asarray(tile_inst)
            if pallas_ok:
                def round_fn(lb_, ub_, act):
                    return kern.batched_occupancy_round_tiles(
                        val, col, ii, lhs_c, rhs_c, lb_, ub_, ti, act,
                        n_pad, eps, int_eps, inf, interpret, outward=outward,
                    )
            else:
                col_g = col + ti[:, None, None] * n_pad
                def round_fn(lb_, ub_, act):
                    return kops.batched_reference_round(
                        val, col_g, ii, crow, lhs_c, rhs_c, lb_, ub_, act,
                        m_total=m_total, n_pad=n_pad,
                        fits_one_chunk=spec.fits_one_chunk,
                        eps=eps, int_eps=int_eps, inf=inf, outward=outward,
                    )
            if tel_cap:
                out = batched_step_rounds(
                    round_fn, lb, ub, active, last_changed, rounds,
                    max_rounds, budget=budget,
                    stop_progress=stop_progress, patience=patience,
                    progress=progress, flat=flat, with_progress=True,
                    plane=TelemetryPlane(ring, ticks, stopr, infsr),
                    feas_eps=feas_eps,
                )
                return out[:7] + tuple(out[7])
            out = batched_step_rounds(
                round_fn, lb, ub, active, last_changed, rounds,
                max_rounds, budget=budget,
                stop_progress=stop_progress, patience=patience,
                progress=progress, flat=flat, with_progress=True,
            )
            # Telemetry off: the zero-width plane rides through unchanged
            # so the state layout (and donation signature) never varies.
            return out + (ring, ticks, stopr, infsr)

        self.step = jax.jit(
            step, **donate_kwargs(argnums=range(_MATRIX_ARGS, _STATE_ARGS))
        )

        srows1 = spec.slot_rows + 1

        def make_admit(kk: int):
            def admit(val, col, ii, crow, lhs_c, rhs_c,
                      lb, ub, active, last_changed, rounds, progress, flat,
                      ring, ticks, stopr, infsr,
                      p_val, p_col, p_ii, p_crow, p_lhs, p_rhs, p_lb, p_ub,
                      slot_ids, on):
                tix = (slot_ids[:, None] * t + jnp.arange(t)[None, :]).reshape(-1)
                val = val.at[tix].set(p_val.reshape(kk * t, r, k))
                col = col.at[tix].set(p_col.reshape(kk * t, r, k))
                ii = ii.at[tix].set(p_ii.reshape(kk * t, r, k))
                crow_g = p_crow + (slot_ids * srows1)[:, None, None]
                crow = crow.at[tix].set(crow_g.reshape(kk * t, r))
                lhs_c = lhs_c.at[tix].set(p_lhs.reshape(kk * t, r))
                rhs_c = rhs_c.at[tix].set(p_rhs.reshape(kk * t, r))
                lb = lb.at[slot_ids].set(p_lb)
                ub = ub.at[slot_ids].set(p_ub)
                active = active.at[slot_ids].set(on)
                last_changed = last_changed.at[slot_ids].set(on)
                rounds = rounds.at[slot_ids].set(0)
                progress = progress.at[slot_ids].set(jnp.nan)
                flat = flat.at[slot_ids].set(0)
                # Slot recycling: the admitted slots' telemetry rows return
                # to the fresh-plane state inside the same fused dispatch.
                plane = reset_rows(
                    TelemetryPlane(ring, ticks, stopr, infsr), slot_ids
                )
                return (val, col, ii, crow, lhs_c, rhs_c,
                        lb, ub, active, last_changed, rounds, progress, flat,
                        *plane)
            return jax.jit(admit, **donate_kwargs(argnums=range(_STATE_ARGS)))

        self.admits = {
            kk: make_admit(kk)
            for kk in (1 << b for b in range(s.bit_length()))
            if kk <= s
        }

    def init_state(self) -> tuple:
        """Fresh all-empty resident state: zero tiles, every chunk parked on
        its slot's dummy row, every slot inactive (== unoccupied)."""
        spec = self.spec
        s, t, r, k = spec.slots, spec.slot_tiles, spec.tile_rows, spec.tile_width
        dt = self.np_dtype
        crow = np.repeat(
            np.arange(s, dtype=np.int32) * (spec.slot_rows + 1) + spec.slot_rows,
            t * r,
        ).reshape(s * t, r)
        return (
            jnp.asarray(np.zeros((s * t, r, k), dt)),
            jnp.asarray(np.zeros((s * t, r, k), np.int32)),
            jnp.asarray(np.zeros((s * t, r, k), np.int32)),
            jnp.asarray(crow),
            jnp.asarray(np.zeros((s * t, r), dt)),
            jnp.asarray(np.zeros((s * t, r), dt)),
            jnp.asarray(np.zeros((s, spec.n_pad), dt)),
            jnp.asarray(np.zeros((s, spec.n_pad), dt)),
            jnp.asarray(np.zeros((s,), bool)),
            jnp.asarray(np.zeros((s,), bool)),
            jnp.asarray(np.zeros((s,), np.int32)),
            jnp.asarray(np.full((s,), np.nan, dt)),
            jnp.asarray(np.zeros((s,), np.int32)),
            jnp.asarray(np.full((s, self.telemetry), np.nan, dt)),
            jnp.asarray(np.zeros((s,), np.int32)),
            jnp.asarray(np.full((s,), -1, np.int32)),
            jnp.asarray(np.full((s,), -1, np.int32)),
        )

    def admit_args(self, payloads: Sequence[SlotPayload], slot_ids, on: bool):
        """Host-side stacking of ``k`` payloads into the admit operands."""
        stacks = tuple(
            np.stack([np.asarray(getattr(p, f), dtype=None) for p in payloads])
            for f in ("val", "col", "ii", "chunk_row", "lhs_c", "rhs_c", "lb", "ub")
        )
        k = len(payloads)
        return stacks + (
            np.asarray(slot_ids, np.int32),
            np.full((k,), on, dtype=bool),
        )

    def warm(self) -> None:
        """Compile every engine up front (idempotent): one step and one
        admission per group size, each against a throwaway empty state --
        after this, admission/backfill/step never hit compile."""
        with self._lock:
            if self.warmed:
                return
            state = self.init_state()
            out = self.step(*state)
            jax.block_until_ready(out)
            for kk, fn in self.admits.items():
                state = self.init_state()
                pay = [evict_slot(
                    self.spec.slot_tiles, self.spec.slot_rows, self.spec.n_pad,
                    self.spec.tile_rows, self.spec.tile_width, self.np_dtype,
                )] * kk
                res = fn(*state, *self.admit_args(pay, list(range(kk)), False))
                jax.block_until_ready(res)
            self.warmed = True

    def compile_counts(self) -> dict:
        """Compiled-trace counts of the step and admit engines (for the
        no-recompile-on-backfill assertion in the tests)."""
        def count(fn):
            get = getattr(fn, "_cache_size", None)
            return int(get()) if callable(get) else None
        return {
            "step": count(self.step),
            "admit": {kk: count(fn) for kk, fn in self.admits.items()},
        }


_engine_cache = None
_engine_cache_lock = threading.Lock()


def _engine_lru():
    """Process-wide engine cache (thread-safe LRU from ``kernels.ops``)."""
    global _engine_cache
    with _engine_cache_lock:
        if _engine_cache is None:
            from ..kernels.ops import LRU  # lazy: kernels imports core
            _engine_cache = LRU(16)
        return _engine_cache


def _get_engine(spec, dtype, cfg, rounds_per_step, use_pallas, interpret,
                stop_progress=None, patience=1, telemetry=0):
    """Fetch-or-build the warmed engine of one bucket shape."""
    key = (
        spec, np.dtype(dtype).str, dataclasses.astuple(cfg),
        rounds_per_step, use_pallas, interpret, stop_progress, patience,
        int(telemetry or 0),
    )
    lru = _engine_lru()
    eng = lru.get(key, ())
    if eng is None:
        eng = _BucketEngine(
            spec, dtype, cfg, rounds_per_step, use_pallas, interpret,
            stop_progress=stop_progress, patience=patience,
            telemetry=int(telemetry or 0),
        )
        lru.put(key, (), eng)
    eng.warm()
    return eng


class _Bucket:
    """Runtime state of one resident bucket: device state tuple, the
    slot->ticket table (the host half of the occupancy mask) and the
    admission queue."""

    def __init__(self, spec: BucketSpec, engine: _BucketEngine):
        self.spec = spec
        self.engine = engine
        self.state = engine.init_state()
        self.slot_tickets: list[ServiceTicket | None] = [None] * spec.slots
        self.queue: deque[ServiceTicket] = deque()
        self.retired = 0
        self.early_stopped = 0
        self.occupancy_sum = 0.0
        self.pumps = 0

    def occupied(self) -> int:
        return sum(t is not None for t in self.slot_tickets)


class PropagationService:
    """Continuous-batching domain-propagation service.

    Construct with bucket specs (or :meth:`from_problems`), then either
    drive it synchronously (``submit`` + ``pump``/``drain``/``serve``) or
    start the background device-loop thread (``start``/``stop``) and treat
    ``submit`` as a fully asynchronous request API.  All compiled engines
    are built and warmed at construction; steady-state operation never
    compiles, repacks a batch, or stops the device loop to retire/admit.

    ``stop_progress``/``patience`` arm the progress-based early retire
    (see :class:`repro.core.types.TierPolicy`): a resident slot whose
    per-round *measure of progress* flatlines below ``stop_progress`` for
    ``patience`` consecutive rounds is deactivated inside the device step
    and retired at the next step boundary with ``converged=False`` and the
    last measure in ``PropagationResult.progress`` -- freeing the slot for
    the backlog instead of grinding out epsilon-level tail rounds.
    ``dtype=None`` serves in float64, or in float32 on a TPU; a float32
    service applies the outward-rounded merge automatically.  Per-slot tier
    promotion is not a service feature -- resubmit promoted instances to
    an fp64 service.

    Observability: ``telemetry`` (a ring capacity) arms per-slot device
    telemetry -- retired tickets' results carry an
    ``obs.telemetry.TelemetrySnapshot`` read back at the retirement sync
    the pump already performs.  ``tracer`` (an ``obs.trace.Tracer``) emits
    structured spans for every pump/admit/step/readback plus one
    ``ticket`` span per retired instance; the default ``NULL_TRACER``
    no-ops.  ``metrics`` is a :class:`~repro.obs.metrics.MetricsRegistry`
    preloaded with the kernel/engine caches, compile counts and service
    counters; its pinned-schema snapshot rides ``stats()['metrics']``.
    """

    def __init__(
        self,
        specs: Sequence[BucketSpec],
        cfg: PropagatorConfig = DEFAULT_CONFIG,
        dtype=None,
        rounds_per_step: int = 8,
        use_pallas: bool | None = None,
        interpret: bool | None = None,
        stop_progress: float | None = None,
        patience: int = 1,
        telemetry: int | None = None,
        tracer=None,
    ):
        if not specs:
            raise ValueError("PropagationService needs at least one BucketSpec")
        from ..kernels import prop_round as kern  # lazy: kernels imports core
        if use_pallas is None:
            use_pallas = not kern._on_cpu()
        self._cfg = cfg
        dtype = self._dtype = resolve_dtype(dtype, np.float64)
        self._stop_progress = stop_progress
        self._telemetry = int(telemetry or 0)
        self._tracer = NULL_TRACER if tracer is None else tracer
        self._lock = threading.RLock()
        self._wake = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: threading.Thread | None = None
        self._submitted = 0
        self._buckets = [
            _Bucket(spec, _get_engine(
                spec, dtype, cfg, rounds_per_step, use_pallas, interpret,
                stop_progress=stop_progress, patience=patience,
                telemetry=self._telemetry,
            ))
            for spec in specs
        ]
        self.metrics = default_registry()
        self.metrics.register("engine_cache", lambda: _engine_lru().info())
        self.metrics.register("compile_counts", self.compile_counts)
        self.metrics.register("service", self._counters)

    @classmethod
    def from_problems(
        cls,
        problems: Sequence[Problem],
        slots: int = 8,
        tile_rows: int = 8,
        tile_width: int | None = None,
        size_classes: int = 1,
        **kwargs,
    ) -> "PropagationService":
        """Build a service sized for a sample population (one bucket per
        ``col_pad`` class -- or per tile-count quantile within it when
        ``size_classes > 1`` -- with fill-tuned tile width; see
        :meth:`BucketSpec.for_problems`)."""
        specs = BucketSpec.for_problems(
            problems, slots=slots, tile_rows=tile_rows,
            tile_width=tile_width, size_classes=size_classes,
        )
        return cls(specs, **kwargs)

    # -- request path ------------------------------------------------------

    def submit(
        self, problem: Problem | None = None, payload: SlotPayload | None = None
    ) -> ServiceTicket:
        """Enqueue one instance and return its ticket.

        Routing picks the first bucket that fits; packing to the slot shape
        happens here (host-side, outside the service lock) unless a
        pre-packed ``payload`` is supplied -- the bench pre-packs to keep
        the measured loop device-bound."""
        if payload is None:
            if problem is None:
                raise ValueError("submit() needs a problem or a payload")
            for bk in self._buckets:
                if bk.spec.fits_problem(problem):
                    payload = bk.spec.pack(problem, dtype=self._dtype)
                    break
            else:
                raise ValueError(
                    f"no bucket fits instance m={problem.m} n={problem.n}"
                )
        ticket = ServiceTicket(problem, payload)
        with self._lock:
            for bk in self._buckets:
                if bk.spec.admits(payload):
                    bk.queue.append(ticket)
                    self._submitted += 1
                    break
            else:
                raise ValueError("no bucket admits the given payload")
        self._wake.set()
        return ticket

    # -- device loop -------------------------------------------------------

    def pump(self) -> dict:
        """One service cycle over every bucket: admit into free slots
        (power-of-two grouped scatters), run one budgeted step where any
        slot is occupied, retire newly converged slots (async readback +
        host bookkeeping only -- their tiles are already gated off by the
        occupancy mask).  Returns the cycle's counters.

        With a tracer attached the cycle emits one ``pump`` span with
        nested ``admit``/``step``/``readback`` spans per bucket, plus one
        ``ticket`` span per retirement built from the timestamps the
        ticket already carries (zero tracing work on the submit path)."""
        admitted = retired = stepped = 0
        tr = self._tracer
        with tr.span("pump"), self._lock:
            for bk in self._buckets:
                label = f"n_pad={bk.spec.n_pad}/tw={bk.spec.tile_width}"
                free = [i for i, tk in enumerate(bk.slot_tickets) if tk is None]
                take = min(len(free), len(bk.queue))
                if take:
                    with tr.span("admit", bucket=label, count=take):
                        tickets = [bk.queue.popleft() for _ in range(take)]
                        pos = 0
                        for k in _pow2_decomposition(take):
                            group = tickets[pos:pos + k]
                            slot_ids = free[pos:pos + k]
                            pos += k
                            bk.state = bk.engine.admits[k](
                                *bk.state,
                                *bk.engine.admit_args(
                                    [tk.payload for tk in group], slot_ids, True
                                ),
                            )
                            now = time.perf_counter()
                            for s, tk in zip(slot_ids, group):
                                bk.slot_tickets[s] = tk
                                tk.admit_t = now
                                tk.slot = s
                    admitted += take
                occ = bk.occupied()
                bk.occupancy_sum += occ / bk.spec.slots
                bk.pumps += 1
                if not occ:
                    continue
                with tr.span("step", bucket=label, occupied=occ):
                    bk.state = bk.state[:_MATRIX_ARGS] + tuple(
                        bk.engine.step(*bk.state)
                    )
                stepped += 1
                active_h = np.asarray(bk.state[_ACTIVE])
                done_slots = [
                    i for i, tk in enumerate(bk.slot_tickets)
                    if tk is not None and not active_h[i]
                ]
                if not done_slots:
                    continue
                tel_on = bool(self._telemetry)
                planes = (_LB, _UB, _LAST_CHANGED, _ROUNDS, _PROGRESS)
                if tel_on:
                    planes += (_RING, _TICKS, _STOPR, _INFSR)
                with tr.span("readback", bucket=label, retired=len(done_slots)):
                    for idx in planes:
                        hint = getattr(bk.state[idx], "copy_to_host_async", None)
                        if callable(hint):
                            hint()
                    lb_h = np.asarray(bk.state[_LB])
                    ub_h = np.asarray(bk.state[_UB])
                    lc_h = np.asarray(bk.state[_LAST_CHANGED])
                    rd_h = np.asarray(bk.state[_ROUNDS])
                    pg_h = np.asarray(bk.state[_PROGRESS])
                    if tel_on:
                        ring_h = np.asarray(bk.state[_RING])
                        ticks_h = np.asarray(bk.state[_TICKS])
                        stopr_h = np.asarray(bk.state[_STOPR])
                        infsr_h = np.asarray(bk.state[_INFSR])
                now = time.perf_counter()
                for i in done_slots:
                    tk = bk.slot_tickets[i]
                    n = tk.payload.n
                    lb_i = lb_h[i, :n].copy()
                    ub_i = ub_h[i, :n].copy()
                    # An early-retired (flatlined) slot leaves last_changed
                    # True with rounds below the cap: stopped, not converged.
                    conv = not bool(lc_h[i])
                    if (self._stop_progress is not None and not conv
                            and int(rd_h[i]) < self._cfg.max_rounds):
                        bk.early_stopped += 1
                    tel = None
                    if tel_on:
                        # The slot will be recycled, so copy its rows out of
                        # the shared plane into a scalar-layout host plane.
                        tel = TelemetrySnapshot(plane=TelemetryPlane(
                            ring=ring_h[i].copy(),
                            ticks=ticks_h[i],
                            stop_round=stopr_h[i],
                            infeas_round=infsr_h[i],
                        ))
                    tk._result = PropagationResult(
                        lb=lb_i,
                        ub=ub_i,
                        rounds=int(rd_h[i]),
                        converged=conv,
                        infeasible=bool(
                            np.any(lb_i > ub_i + self._cfg.feas_eps)
                        ),
                        progress=float(pg_h[i]),
                        telemetry=tel,
                    )
                    tk.done_t = now
                    tr.record(
                        "ticket", tk.submit_t, now,
                        bucket=label,
                        slot=i,
                        queue_ms=(tk.admit_t - tk.submit_t) * 1e3,
                        service_ms=(now - tk.admit_t) * 1e3,
                        rounds=int(rd_h[i]),
                        converged=conv,
                    )
                    bk.slot_tickets[i] = None
                    bk.retired += 1
                    tk._event.set()
                retired += len(done_slots)
            pending = sum(len(bk.queue) for bk in self._buckets)
            occupied = sum(bk.occupied() for bk in self._buckets)
        return {
            "admitted": admitted,
            "retired": retired,
            "stepped": stepped,
            "pending": pending,
            "occupied": occupied,
        }

    def drain(self, max_pumps: int | None = None) -> None:
        """Pump until every submitted instance has retired."""
        pumps = 0
        while True:
            res = self.pump()
            pumps += 1
            if res["pending"] == 0 and res["occupied"] == 0:
                return
            if max_pumps is not None and pumps >= max_pumps:
                raise RuntimeError(f"drain did not finish in {max_pumps} pumps")

    def serve(self, problems: Sequence[Problem]) -> list[PropagationResult]:
        """Submit a population and return results in submit order (pumps
        inline unless the background thread is running)."""
        tickets = [self.submit(p) for p in problems]
        if self._thread is not None and self._thread.is_alive():
            return [tk.result() for tk in tickets]
        while not all(tk.done() for tk in tickets):
            self.pump()
        return [tk.result() for tk in tickets]

    # -- background thread -------------------------------------------------

    def start(self) -> None:
        """Start the background device-loop thread (idempotent): pumps
        continuously while work exists, parks on an event when idle."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop_evt.clear()
            self._thread = threading.Thread(
                target=self._loop, name="propagation-service", daemon=True
            )
            self._thread.start()

    def _loop(self) -> None:
        while not self._stop_evt.is_set():
            res = self.pump()
            if not (res["admitted"] or res["stepped"]):
                self._wake.wait(timeout=0.002)
                self._wake.clear()

    def stop(self, timeout: float = 10.0) -> None:
        """Stop the background thread (idempotent; queued work stays)."""
        self._stop_evt.set()
        self._wake.set()
        th = self._thread
        if th is not None:
            th.join(timeout)
        self._thread = None

    def __enter__(self) -> "PropagationService":
        """Context manager: run the background loop for the block."""
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- observability -----------------------------------------------------

    @property
    def tracer(self):
        """The attached span tracer (``NULL_TRACER`` when tracing is off)."""
        return self._tracer

    def _counters(self) -> dict:
        """The registry's ``service`` source: the live global counters."""
        with self._lock:
            return {
                "submitted": self._submitted,
                "retired": sum(bk.retired for bk in self._buckets),
                "early_stopped": sum(bk.early_stopped for bk in self._buckets),
                "pending": sum(len(bk.queue) for bk in self._buckets),
                "occupied": sum(bk.occupied() for bk in self._buckets),
                "telemetry_capacity": self._telemetry,
            }

    def stats(self) -> dict:
        """Service stats endpoint: per-bucket occupancy/padding histogram in
        the same shape as ``batch_stats()['per_bucket']`` (computed over the
        RESIDENT instances), queue depths, retire counters, mean occupancy,
        the engine-cache and kernel-cache counters, and the unified
        ``metrics`` registry snapshot (pinned schema -- see
        ``repro.obs.metrics``)."""
        from ..kernels.ops import cache_info  # lazy: kernels imports core
        with self._lock:
            buckets = []
            for bk in self._buckets:
                spec = bk.spec
                resident = [tk for tk in bk.slot_tickets if tk is not None]
                nnz = int(sum(tk.payload.nnz for tk in resident))
                padded = (
                    len(resident) * spec.slot_tiles
                    * spec.tile_rows * spec.tile_width
                )
                fill = nnz / padded if padded else 0.0
                buckets.append({
                    "n_pad": spec.n_pad,
                    "slots": spec.slots,
                    "slot_tiles": spec.slot_tiles,
                    "slot_rows": spec.slot_rows,
                    "tile_rows": spec.tile_rows,
                    "tile_width": spec.tile_width,
                    "occupied": bk.occupied(),
                    "pending": len(bk.queue),
                    "retired": bk.retired,
                    "early_stopped": bk.early_stopped,
                    "mean_occupancy": (
                        bk.occupancy_sum / bk.pumps if bk.pumps else 0.0
                    ),
                    "histogram": {
                        "n_pad": spec.n_pad,
                        "instances": len(resident),
                        "tiles": len(resident) * spec.slot_tiles,
                        "tile_rows": spec.tile_rows,
                        "tile_width": spec.tile_width,
                        "nnz": nnz,
                        "padded_slots": padded,
                        "fill": fill,
                        "padding_fraction": 1.0 - fill if padded else 0.0,
                    },
                })
            return {
                "submitted": self._submitted,
                "retired": sum(bk.retired for bk in self._buckets),
                "early_stopped": sum(bk.early_stopped for bk in self._buckets),
                "pending": sum(len(bk.queue) for bk in self._buckets),
                "occupied": sum(bk.occupied() for bk in self._buckets),
                "buckets": buckets,
                "engine_cache": _engine_lru().info(),
                "kernel_caches": cache_info(),
                "metrics": self.metrics.snapshot(),
            }

    def compile_counts(self) -> dict:
        """Per-bucket compiled-trace counts (steady state: unchanged across
        any number of admissions/backfills -- the AOT warmup covers every
        engine the service can ever dispatch)."""
        return {
            f"n_pad={bk.spec.n_pad}/tw={bk.spec.tile_width}":
                bk.engine.compile_counts()
            for bk in self._buckets
        }
