"""Observability plane: device telemetry, span tracing, metrics registry.

The load-bearing contract is non-interference -- telemetry-on must return
BITWISE-identical bounds with identical compile counts across every engine
(fused, partitioned, batched, nodes, service), because the plane rides the
while_loop carry without touching the bound dataflow.  The rest pins ring
truncation semantics, host/device telemetry agreement, the span schema,
the registry snapshot envelope, and the shared timing utilities.
"""
import glob
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import INF, Problem, TierPolicy, csr_from_dense, propagate
from repro.core.nodes import propagate_nodes
from repro.core.propagator import propagate_batch
from repro.core.service import BucketSpec, PropagationService
from repro.data import make_knapsack, make_set_cover
from repro.kernels import prepare_block_ell, propagate_block_ell
from repro.obs import (
    SNAPSHOT_KEYS,
    SPAN_KEYS,
    MetricsRegistry,
    PROGRAM,
    NullTracer,
    TelemetryPlane,
    Tracer,
    device_plane,
    host_snapshot,
    median_of,
    median_ratio,
    default_registry,
    paired_trials,
    record_round,
    reset_rows,
    run_metadata,
    time_fenced,
    time_phases,
)

CAP = 16


def contraction_chain(n: int = 32, rho: float = 0.9) -> Problem:
    """Cyclic contraction with a geometric epsilon tail: rounds >> CAP, the
    ring-truncation workload (same construction as benchmarks.precision)."""
    dense = np.zeros((n, n))
    for j in range(n):
        dense[j, j] = 1.0
        dense[j, (j + 1) % n] = -rho
    return Problem(
        csr=csr_from_dense(dense),
        lhs=np.full(n, -INF),
        rhs=np.zeros(n),
        lb=np.zeros(n),
        ub=np.ones(n),
        is_int=np.zeros(n, dtype=bool),
    )


def assert_same_bounds(a, b):
    assert np.array_equal(np.asarray(a.lb), np.asarray(b.lb))
    assert np.array_equal(np.asarray(a.ub), np.asarray(b.ub))
    assert int(a.rounds) == int(b.rounds)


# -- bitwise non-interference, engine by engine ---------------------------


def test_fused_bitwise_and_snapshot():
    p = make_set_cover(60, 30, seed=0)
    off = propagate_block_ell(p, use_pallas=False)
    on = propagate_block_ell(p, use_pallas=False, telemetry=CAP)
    assert_same_bounds(off, on)
    assert off.telemetry is None
    t = on.telemetry
    assert t.capacity == CAP
    assert t.rounds_recorded == int(on.rounds)
    hist = t.progress_history()
    assert hist.shape == (min(CAP, t.rounds_recorded),)
    assert not np.any(np.isnan(hist))
    assert t.infeasible_round == -1 and t.stop_round == -1


def test_partitioned_bitwise():
    p = make_knapsack(300, 40, seed=1)
    kw = dict(use_pallas=False, scatter="partitioned", slab=128)
    off = propagate_block_ell(p, **kw)
    on = propagate_block_ell(p, telemetry=CAP, **kw)
    assert_same_bounds(off, on)
    assert on.telemetry.rounds_recorded == int(on.rounds)


def test_batched_bitwise_per_instance_snapshots():
    probs = [make_set_cover(40, 20, seed=s) for s in range(3)] + [
        make_knapsack(40, 10, seed=s) for s in range(3)
    ]
    off = propagate_batch(probs, use_pallas=False)
    on = propagate_batch(probs, use_pallas=False, telemetry=CAP)
    for a, b in zip(off, on):
        assert_same_bounds(a, b)
        # Instances of one bucket share a batched plane; each snapshot
        # views its own row.
        assert b.telemetry.rounds_recorded == int(b.rounds)
        assert len(b.telemetry.progress_history()) == min(CAP, int(b.rounds))


def test_batched_host_loop_bitwise():
    probs = [make_set_cover(40, 20, seed=s) for s in range(3)]
    off = propagate_batch(probs, use_pallas=False, driver="host_loop")
    on = propagate_batch(
        probs, use_pallas=False, driver="host_loop", telemetry=CAP
    )
    for a, b in zip(off, on):
        assert_same_bounds(a, b)
        assert b.telemetry.rounds_recorded == int(b.rounds)


def test_nodes_bitwise():
    p = make_set_cover(40, 20, seed=0)
    lb = np.repeat(np.asarray(p.lb, np.float64)[None, :], 4, axis=0)
    ub = np.repeat(np.asarray(p.ub, np.float64)[None, :], 4, axis=0)
    off = propagate_nodes(p, lb, ub, use_pallas=False)
    on = propagate_nodes(p, lb, ub, use_pallas=False, telemetry=CAP)
    assert np.array_equal(np.asarray(off.lb), np.asarray(on.lb))
    assert np.array_equal(np.asarray(off.ub), np.asarray(on.ub))
    assert off.node_telemetry(0) is None
    for i in range(4):
        snap = on.node_telemetry(i)
        assert snap.rounds_recorded == int(np.asarray(on.rounds)[i])


def test_two_tier_snapshot_chain():
    p = make_knapsack(80, 20, seed=2)
    pol = TierPolicy()
    off = propagate(p, policy=pol)
    on = propagate(p, policy=pol, telemetry=CAP)
    assert_same_bounds(off, on)
    t = on.telemetry
    if int(on.tier_rounds) > 0:  # promotion happened: fp32 tier recorded
        assert t.tier_switch_round == int(on.tier_rounds)
        assert t.fp32 is not None
        assert t.fp32.rounds_recorded == int(on.tier_rounds)


# -- ring truncation + host/device agreement ------------------------------


def test_ring_truncation_keeps_tail():
    p = contraction_chain()
    r = propagate(p, telemetry=8)
    t = r.telemetry
    assert t.rounds_recorded == int(r.rounds) > 8
    hist = t.progress_history()
    assert hist.shape == (8,)
    # The tail of a contraction is monotone decreasing progress.
    assert np.all(np.diff(hist) <= 1e-12)
    # host_loop reproduces the device ring layout exactly.
    rh = propagate(p, driver="host_loop", telemetry=8)
    np.testing.assert_allclose(
        rh.telemetry.progress_history(), hist, rtol=1e-12
    )
    assert rh.telemetry.rounds_recorded == t.rounds_recorded


def test_infeasible_round_latches_first():
    plane = device_plane(4)
    plane = record_round(plane, 0.5, 1, jnp.asarray(False))
    plane = record_round(plane, 0.4, 2, jnp.asarray(True))
    plane = record_round(plane, 0.3, 3, jnp.asarray(True))
    assert int(plane.infeas_round) == 2  # first firing round, never moves
    assert int(plane.ticks) == 3


def test_batched_record_respects_active_mask():
    plane = device_plane(4, batch=2)
    active = jnp.asarray([True, False])
    plane = record_round(
        plane, jnp.asarray([0.5, 0.7]), jnp.asarray([1, 1]),
        jnp.asarray([False, False]), active=active,
    )
    assert plane.ticks.tolist() == [1, 0]
    assert np.isnan(np.asarray(plane.ring)[1]).all()
    plane = reset_rows(plane, jnp.asarray([0]))
    assert plane.ticks.tolist() == [0, 0]
    assert np.isnan(np.asarray(plane.ring)).all()


def test_host_snapshot_matches_device_wrap():
    history = [2.0 ** -i for i in range(11)]
    snap = host_snapshot(history, capacity=4)
    assert snap.rounds_recorded == 11
    np.testing.assert_allclose(snap.progress_history(), history[-4:])


# -- service: bitwise, snapshots, zero extra compiles ---------------------


def test_service_bitwise_compiles_and_snapshots():
    probs = [make_set_cover(40, 20, seed=s) for s in range(4)] + [
        make_knapsack(40, 10, seed=s) for s in range(2)
    ]
    specs = BucketSpec.for_problems(probs, slots=2)
    svc_off = PropagationService(specs, use_pallas=False)
    svc_on = PropagationService(specs, use_pallas=False, telemetry=CAP)
    res_off = svc_off.serve(probs)
    res_on = svc_on.serve(probs)
    for a, b in zip(res_off, res_on):
        assert_same_bounds(a, b)
        assert a.telemetry is None
        # Retired snapshots are host copies: they survive slot recycling.
        assert b.telemetry.rounds_recorded == int(b.rounds)
        assert len(b.telemetry.progress_history()) == min(CAP, int(b.rounds))
    # Telemetry adds NO compiled traces: same engine structure, and a
    # second serve (retire + backfill churn) compiles nothing new.
    counts = svc_on.compile_counts()
    assert counts == svc_off.compile_counts()
    svc_on.serve(probs)
    assert svc_on.compile_counts() == counts


def test_service_latency_split_and_metrics():
    probs = [make_set_cover(40, 20, seed=s) for s in range(3)]
    specs = BucketSpec.for_problems(probs, slots=2)
    svc = PropagationService(specs, use_pallas=False, telemetry=CAP)
    tickets = [svc.submit(p) for p in probs]
    svc.drain()
    for tk in tickets:
        assert tk.queue_latency() >= 0.0
        assert tk.service_latency() >= 0.0
        assert tk.latency() == pytest.approx(
            tk.queue_latency() + tk.service_latency()
        )
    st = svc.stats()
    snap = st["metrics"]
    assert set(snap) == SNAPSHOT_KEYS
    assert snap["errors"] == {}
    assert {"compile_counts", "engine_cache", "kernel_caches", "service"} <= set(
        snap["sources"]
    )
    assert snap["sources"]["service"]["retired"] == len(probs)


def test_service_tracer_spans():
    probs = [make_set_cover(40, 20, seed=s) for s in range(3)]
    specs = BucketSpec.for_problems(probs, slots=2)
    tr = Tracer()
    svc = PropagationService(
        specs, use_pallas=False, telemetry=CAP, tracer=tr
    )
    svc.serve(probs)
    names = {s.name for s in tr.spans()}
    assert {"pump", "admit", "step", "readback", "ticket"} <= names
    tickets = [s for s in tr.spans() if s.name == "ticket"]
    assert len(tickets) == len(probs)
    for s in tickets:
        assert s.attrs["queue_ms"] >= 0.0 and s.attrs["service_ms"] >= 0.0
    # admit/step/readback nest under a pump span.
    pump_ids = {s.span_id for s in tr.spans() if s.name == "pump"}
    for s in tr.spans():
        if s.name in ("admit", "step", "readback"):
            assert s.parent_id in pump_ids


# -- tracer / registry / timing: pure host, dtype-agnostic ----------------


@pytest.mark.f32native
def test_tracer_schema_nesting_export(tmp_path):
    tr = Tracer()
    with tr.span("outer", kind="test"):
        with tr.span("inner"):
            pass
    tr.record("external", 1.0, 2.0, answer=42)
    spans = {s.name: s for s in tr.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["external"].attrs == {"answer": 42}
    path = tmp_path / "trace.jsonl"
    text = tr.export(path)
    lines = [json.loads(ln) for ln in text.strip().splitlines()]
    assert len(lines) == 3
    for d in lines:
        assert set(d) == SPAN_KEYS
        assert d["dur_ms"] >= 0.0
    assert path.read_text() == text
    tr.clear()
    assert tr.spans() == []


@pytest.mark.f32native
def test_null_tracer_is_noop():
    tr = NullTracer()
    with tr.span("anything"):
        tr.record("x", 0.0, 1.0)
    assert tr.spans() == []
    assert tr.export() == ""


@pytest.mark.f32native
def test_registry_schema_and_error_isolation():
    reg = MetricsRegistry()
    reg.register("good", lambda: {"v": 1})
    reg.register("bad", lambda: 1 / 0)
    with pytest.raises(ValueError):
        reg.register("good", lambda: 2)
    snap = reg.snapshot()
    assert set(snap) == SNAPSHOT_KEYS
    assert snap["sources"] == {"good": {"v": 1}}
    assert "bad" in snap["errors"] and "ZeroDivisionError" in snap["errors"]["bad"]
    reg.register("good", lambda: 2, replace=True)
    assert reg.snapshot()["sources"]["good"] == 2
    reg.unregister("bad")
    assert reg.source_names() == ("good",)


@pytest.mark.f32native
def test_run_metadata_shape():
    meta = run_metadata()
    assert set(meta) == {
        "git_commit", "timestamp", "jax_version", "x64", "backend",
    }
    assert meta["git_commit"] != ""
    assert isinstance(meta["x64"], bool)


@pytest.mark.f32native
def test_timing_utilities():
    xs = jnp.arange(1024.0)
    t = time_fenced(lambda: xs * 2.0, repeats=2)
    assert 0.0 < t < 10.0
    trials = paired_trials(
        [lambda: xs + 1.0, lambda: xs + 2.0], trials=3, repeats=2
    )
    assert len(trials) == 3 and all(len(row) == 2 for row in trials)
    assert median_ratio(trials) > 0.0
    assert median_of(trials, 0) > 0.0
    phases = time_phases(
        {"a": lambda: xs * 3.0, "b": lambda: xs * 4.0}, repeats=1
    )
    assert set(phases) == {"a", "b"} and all(v > 0.0 for v in phases.values())


# -- the program tracer: entry-point spans, the ring, the profiler join ---


def _children(spans, parent):
    return {s.name: s for s in spans if s.parent_id == parent.span_id}


def test_presolve_spans_nest_under_the_call():
    p = make_set_cover(60, 30, seed=3)
    PROGRAM.clear()
    propagate_block_ell(p, use_pallas=False)
    propagate_block_ell(
        p, use_pallas=False, lb0=np.asarray(p.lb), ub0=np.asarray(p.ub)
    )
    spans = PROGRAM.spans()
    calls = [s for s in spans if s.name == "prop.presolve"]
    assert len(calls) == 2 and all(c.parent_id is None for c in calls)
    slots = prepare_block_ell(p).d.val.size  # the jnp round runs the tiles
    for c in calls:
        assert c.attrs == {
            "engine": "fused", "n_pad": 128, "packed": False, "slots": slots, "nnz": p.nnz,
        }
    first, second = (_children(spans, c) for c in calls)
    # The first call prepares (tiles up) and builds its runner; the
    # second hits both caches and uploads only its two bound vectors.
    assert set(first) == {"prop.prepare", "prop.launch"}
    assert first["prop.prepare"].attrs == {"hit": False}
    assert first["prop.launch"].attrs == {"built": True}
    up = [s for s in spans if s.parent_id == first["prop.prepare"].span_id]
    assert [s.name for s in up] == ["prop.upload"]
    assert up[0].attrs["site"] == "prepare" and up[0].attrs["bytes"] > 0
    assert set(second) == {"prop.prepare", "prop.upload", "prop.launch"}
    assert second["prop.prepare"].attrs == {"hit": True}
    assert second["prop.launch"].attrs == {"built": False}
    assert second["prop.upload"].attrs == {"site": "bounds", "bytes": 2 * p.n * 8}
    for c in calls:
        for child in _children(spans, c).values():
            assert c.t_start <= child.t_start <= child.t_end <= c.t_end
    h2d = PROGRAM.counters()["h2d_bytes"]
    assert h2d["bounds"] == 2 * p.n * 8
    assert h2d["prepare"] == up[0].attrs["bytes"]


def test_node_spans_count_plane_bytes():
    p = make_set_cover(40, 20, seed=0)
    bsz, n_pad = 4, 128
    lb = np.repeat(np.asarray(p.lb, np.float64)[None, :], bsz, axis=0)
    ub = np.repeat(np.asarray(p.ub, np.float64)[None, :], bsz, axis=0)
    PROGRAM.clear()
    for _ in range(2):
        propagate_nodes(p, lb, ub, use_pallas=False, dtype=np.float32)
    spans = PROGRAM.spans()
    calls = [s for s in spans if s.name == "prop.nodes"]
    assert len(calls) == 2
    plane_bytes = 2 * bsz * n_pad * 4
    for c, built in zip(calls, (True, False)):
        assert c.parent_id is None
        assert c.attrs == {"nodes": bsz, "n_pad": n_pad}
        kids = _children(spans, c)
        assert set(kids) == {"prop.prepare", "prop.upload", "prop.launch"}
        assert kids["prop.upload"].attrs == {"site": "nodes", "bytes": plane_bytes}
        assert kids["prop.launch"].attrs == {"built": built}
    assert PROGRAM.counters()["h2d_bytes"]["nodes"] == 2 * plane_bytes
    assert PROGRAM.totals()["prop.nodes"]["count"] == 2


def test_two_tier_call_nests_its_tiers():
    p = make_knapsack(80, 20, seed=2)
    PROGRAM.clear()
    propagate_block_ell(p, policy=TierPolicy())
    spans = PROGRAM.spans()
    outer = [s for s in spans if s.name == "prop.presolve" and s.parent_id is None]
    assert len(outer) == 1
    tiers = [s for s in spans if s.name == "prop.presolve" and s.parent_id is not None]
    assert len(tiers) == 2  # the float32 tier, then the endgame
    assert all(t.parent_id == outer[0].span_id for t in tiers)


@pytest.mark.f32native
def test_ring_drops_oldest_while_totals_keep_counting():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span("call", i=i) as attrs:
            attrs["seen"] = True
        tr.add("h2d_bytes", "nodes", 8)
    assert [s.attrs["i"] for s in tr.spans()] == [6, 7, 8, 9]
    assert all(s.attrs["seen"] for s in tr.spans())
    assert tr.totals()["call"]["count"] == 10
    assert tr.totals()["call"]["seconds"] >= 0.0
    assert tr.counters() == {"h2d_bytes": {"nodes": 80}}
    assert len(tr.export().splitlines()) == 4
    tr.clear()
    assert tr.spans() == [] and tr.totals() == {} and tr.counters() == {}


@pytest.mark.f32native
def test_traced_and_note():
    tr = Tracer()

    @tr.traced("outer")
    def work(x):
        """Doubles x."""
        tr.note(x=x)
        with tr.span("inner"):
            tr.note(deep=True)
        return 2 * x

    assert work(3) == 6 and work.__doc__ == "Doubles x."
    spans = {s.name: s for s in tr.spans()}
    assert spans["outer"].attrs == {"x": 3}
    assert spans["inner"].attrs == {"deep": True}
    assert spans["inner"].parent_id == spans["outer"].span_id
    tr.note(lost=True)  # no span open: nothing to add to
    assert NullTracer().traced("x")(lambda: 1)() == 1


def test_registry_reads_the_program_tracer():
    p = make_set_cover(40, 20, seed=5)
    propagate_block_ell(p, use_pallas=False)
    snap = default_registry().snapshot()
    assert snap["errors"] == {}
    prog = snap["sources"]["program"]
    assert prog["spans"]["prop.presolve"]["count"] >= 1
    assert prog["counters"]["h2d_bytes"]["prepare"] > 0


def _xplane_spans(trace_dir):
    """``{span_id: (name, parent_id)}`` of the annotated host events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "span_id" in stats:
                    out[int(stats["span_id"])] = (ev.name, stats.get("parent_id"))
    return out


def test_spans_join_the_profiler_trace_on_span_id(tmp_path):
    p = make_set_cover(60, 30, seed=4)
    lb = np.repeat(np.asarray(p.lb, np.float64)[None, :], 2, axis=0)
    ub = np.repeat(np.asarray(p.ub, np.float64)[None, :], 2, axis=0)
    propagate_block_ell(p, use_pallas=False)  # compile outside the trace
    propagate_nodes(p, lb, ub, use_pallas=False)
    PROGRAM.clear()
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(propagate_block_ell(p, use_pallas=False).lb)
        jax.block_until_ready(propagate_nodes(p, lb, ub, use_pallas=False).lb)
    traced = _xplane_spans(tmp_path)
    spans = PROGRAM.spans()
    assert {s.name for s in spans} >= {
        "prop.presolve", "prop.nodes", "prop.prepare", "prop.upload", "prop.launch",
    }
    for s in spans:
        name, parent = traced[s.span_id]
        assert name == s.name
        assert parent == s.parent_id


def test_bounds_bitwise_with_the_profiler_on(tmp_path):
    p = make_knapsack(120, 30, seed=6)
    lb = np.repeat(np.asarray(p.lb, np.float64)[None, :], 3, axis=0)
    ub = np.repeat(np.asarray(p.ub, np.float64)[None, :], 3, axis=0)
    ub[1, :5] = 0.0
    off = propagate_block_ell(p, use_pallas=False)
    off_n = propagate_nodes(p, lb, ub, use_pallas=False)
    with jax.profiler.trace(str(tmp_path)):
        on = propagate_block_ell(p, use_pallas=False)
        on_n = propagate_nodes(p, lb, ub, use_pallas=False)
        jax.block_until_ready((on.lb, on_n.lb))
    assert_same_bounds(off, on)
    for a, b in ((off_n.lb, on_n.lb), (off_n.ub, on_n.ub), (off_n.rounds, on_n.rounds)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
