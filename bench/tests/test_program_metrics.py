"""The per-layer metrics that read the program's own instrumentation --
its kernel names on the device trace and the spans of its tracer --
against a made-up reduced trace, made-up loop counters and a ring filled
by hand.  Each returns ``None`` where there is nothing sound to read."""
from types import SimpleNamespace

import pytest

from bench import run
from repro.kernels import prop_round
from repro.obs import trace as obs_trace

ROUNDS = [8] * 57
DEVICE_OPS = [["%prop_candidates_scatter.10", 18.3], ["_prop_activities_gather.9", 8.83],
              ["%fusion.49", 0.4], ["%prop_apply_updates.3", 0.3], ["%body.7", 0.2]]
BUSY = 30.45
PLANE = 2 * 64 * 10112 * 4  # two float32 (64, 10112) planes


def metric(name):
    return run.load_module("metrics", name).read


def ctx(trace=None, **counters):
    return SimpleNamespace(trace=trace, counters=counters)


def reduced(ops=DEVICE_OPS, busy=BUSY):
    return {"window_s": 31.0, "busy_s": busy, "device_ops": ops, "idle_gaps": []}


@pytest.fixture
def ring(monkeypatch):
    tracer = obs_trace.Tracer(capacity=64)
    monkeypatch.setattr(obs_trace, "PROGRAM", tracer)
    return tracer


def call(tracer, name, t0, seconds, **attrs):
    return tracer.record(name, t0, t0 + seconds, **attrs)


def test_kernel_split_sums_to_busy_time():
    c = ctx(reduced(), presolve_rounds=ROUNDS)
    kern = metric("round_kernel_ms.presolve")(c)
    outside = metric("outside_kernels_ms.presolve")(c)
    assert kern == pytest.approx(1e3 * (18.3 + 8.83 + 0.3) / sum(ROUNDS))
    assert (kern + outside) * sum(ROUNDS) / 1e3 == pytest.approx(BUSY, rel=1e-12)


def test_kernel_split_counts_no_unnamed_op():
    c = ctx(reduced(ops=[["%body.10", 18.3], ["%fusion.1", 1.0]]), presolve_rounds=ROUNDS)
    assert metric("round_kernel_ms.presolve")(c) == 0.0
    assert metric("outside_kernels_ms.presolve")(c) == pytest.approx(1e3 * BUSY / sum(ROUNDS))


@pytest.mark.parametrize("name", ["round_kernel_ms.presolve", "outside_kernels_ms.presolve"])
def test_kernel_split_needs_a_trace_and_rounds(name):
    assert metric(name)(ctx(None, presolve_rounds=ROUNDS)) is None
    assert metric(name)(ctx(reduced(), node_rounds=[3] * 64)) is None


def test_host_call_presolve_is_the_median_of_the_window_calls(ring):
    for i, seconds in enumerate([0.5, 0.4, 0.003, 0.001, 0.002]):  # two warm calls first
        sid = call(ring, "prop.presolve", float(i), seconds)
        call(ring, "prop.launch", float(i), seconds / 2, parent_id=sid)
    # A tier's own span, under a call, is not a call.
    call(ring, "prop.presolve", 4.0, 0.0015, parent_id=sid)
    read = metric("host_call_ms.presolve")
    assert read(ctx(presolve_rounds=[8, 8, 8])) == pytest.approx(2.0)
    assert read(ctx(presolve_rounds=[8] * 6)) is None  # the ring holds five calls
    assert read(ctx(presolve_rounds=[])) is None


def test_node_metrics_read_the_window_frontiers(ring):
    for i in range(4):  # two warm frontiers, then a window of two
        sid = call(ring, "prop.nodes", float(i), 0.002 * (i + 1), nodes=64, n_pad=10112)
        call(ring, "prop.upload", float(i), 0.001, parent_id=sid, site="nodes", bytes=PLANE)
        prep = call(ring, "prop.prepare", float(i), 0.0001, parent_id=sid, hit=i != 3)
        if i == 3:  # a prepare that uploads counts too: it is on the call's path
            call(ring, "prop.upload", float(i), 0.001, parent_id=prep, site="prepare",
                 bytes=1000)
    c = ctx(node_rounds=[3] * 128)
    assert metric("host_call_ms.nodes")(c) == pytest.approx(7.0)
    assert metric("h2d_bytes.nodes")(c) == (2 * PLANE + 1000) / 128
    assert metric("h2d_bytes.nodes")(ctx(node_rounds=[3] * 64)) == (PLANE + 1000) / 64


@pytest.mark.parametrize("name", ["host_call_ms.nodes", "h2d_bytes.nodes"])
def test_node_metrics_need_whole_window_frontiers(ring, name):
    call(ring, "prop.nodes", 0.0, 0.002, nodes=64)
    assert metric(name)(ctx(node_rounds=[3] * 128)) is None  # one frontier held
    assert metric(name)(ctx(node_rounds=[3] * 100)) is None  # not whole frontiers
    assert metric(name)(ctx()) is None


@pytest.mark.parametrize("name", ["round_kernel_ms.presolve", "outside_kernels_ms.presolve",
                                  "host_call_ms.presolve", "host_call_ms.nodes",
                                  "h2d_bytes.nodes"])
def test_a_program_without_the_instrumentation_reads_none(monkeypatch, name):
    monkeypatch.delattr(prop_round, "KERNEL_NAMES")
    monkeypatch.delattr(obs_trace, "PROGRAM")
    c = ctx(reduced(), presolve_rounds=ROUNDS, node_rounds=[3] * 64)
    assert metric(name)(c) is None
