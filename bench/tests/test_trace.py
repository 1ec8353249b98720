"""The trace reduction, on a small trace recorded on the CPU.

``data/cpu_window.xplane.pb`` holds four jitted calls inside the
harness's window span, each wrapped in its ``dispatch`` and ``readback``
spans.  On the CPU the device operations run on the XLA client's threads
of the host plane, so the test points the reduction there.
"""
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).parent / "data" / "cpu_window.xplane.pb"
CPU_OPS = (("/host:CPU", "tf_XLAPjRtCpuClient"),)


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(T.load(str(DATA)), device_ops=CPU_OPS)


def test_recorded_window(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.011100221, abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.001677066, abs=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_recorded_ops_and_gaps(reduced):
    ops = reduced["device_ops"]
    assert ops[0][0] == "dot_general.1"
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    gaps = reduced["idle_gaps"]
    assert len(gaps) == T.TOP
    assert {nm for nm, _ in gaps} <= set(T.HOST_PHASES) | {T.IDLE_OTHER}
    assert gaps[0] == ["readback", pytest.approx(0.002519397, abs=1e-9)]
    # Busy time plus every gap is the window, when the gaps are all listed.
    assert sum(t for _, t in gaps) <= reduced["window_s"] - reduced["busy_s"] + 1e-12


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="span"):
        T.reduce(T.load(str(DATA)), device_ops=CPU_OPS, window="absent")


def test_no_device_ops_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        T.reduce(T.load(str(DATA)))  # the TPU planes are not in a CPU trace


def test_union_and_gap_naming():
    assert T._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    spans = [(0, 10, "dispatch"), (10, 30, "readback"), (40, 50, "dispatch")]
    gaps = [(5, 12), (20, 45), (60, 70)]
    assert T._name_gaps(gaps, spans) == [(7, "dispatch"), (25, "readback"),
                                         (10, T.IDLE_OTHER)]
