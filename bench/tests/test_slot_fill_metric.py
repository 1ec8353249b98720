"""``slot_fill.presolve`` against a ring of ``prop.presolve`` spans filled by
hand: the share of the round's slots that hold a nonzero, over the window's
calls, and ``None`` where the program's spans carry no slot count."""
from types import SimpleNamespace

import pytest

from bench import run
from repro.obs import trace as obs_trace


def read(**counters):
    return run.load_module("metrics", "slot_fill.presolve").read(
        SimpleNamespace(trace=None, counters=counters))


@pytest.fixture
def ring(monkeypatch):
    tracer = obs_trace.Tracer(capacity=64)
    monkeypatch.setattr(obs_trace, "PROGRAM", tracer)
    return tracer


def test_slot_fill_presolve_reads_the_window_calls(ring):
    for i, (slots, nnz) in enumerate([(1024, 10), (2048, 20), (4096, 1000), (8192, 3000)]):
        sid = ring.record("prop.presolve", float(i), i + 0.1, engine="fused", packed=True,
                          slots=slots, nnz=nnz)
        ring.record("prop.prepare", float(i), i + 0.01, parent_id=sid, hit=True)
    assert read(presolve_rounds=[8, 8]) == pytest.approx(100.0 * 4000 / 12288)
    assert read(presolve_rounds=[8] * 5) is None  # the ring holds four calls
    assert read(presolve_rounds=[]) is None
    # A program whose spans carry no slot count (an older program) reads None.
    ring.record("prop.presolve", 5.0, 5.1, engine="fused", n_pad=128)
    assert read(presolve_rounds=[8]) is None


def test_slot_fill_presolve_without_the_program_tracer_reads_none(monkeypatch):
    monkeypatch.delattr(obs_trace, "PROGRAM")
    assert read(presolve_rounds=[8] * 57) is None
