"""Every cell end to end on the CPU at a tiny size, and the check that
decides ``correct`` seeing the control and each fault the cells can have.

The harness's look for a chip is skipped (``require_tpu=False``), the
sizes are cut through ``overrides`` and the device operations are read
from the CPU's threads; everything else is the run the driver makes.
Pallas kernels run in interpret mode on the CPU.
"""
import json
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from bench import run
from bench.peaks import PEAKS

SEED = 2**31 + 17
TINY = {
    "presolve_set5.narrow": {"m": 400, "n": 300},
    "bnb_set3.frontier64": {"m": 400, "n": 300, "batch": 8, "frontiers": 3,
                            "check_frontiers": 2},
    "presolve_set5.wide": {"m": 200, "n": 66000},  # n_pad past SCATTER_MAX_NPAD
}
CPU_OPS = (("/host:CPU", "tf_XLA"),)


def measure(cell, trace=0, seed=SEED, program=None, **overrides):
    args = run.parse(["--workload", cell, "--seed", str(seed), "--seconds", "0.5",
                      "--trace", str(trace)])
    return run.measure(args, require_tpu=False, overrides={**TINY[cell], **overrides},
                       program=program, peaks=PEAKS["TPU v5 lite"], device_ops=CPU_OPS)


def spec():
    return json.loads((run.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", list(TINY))
def test_cell_runs_and_is_correct(cell):
    out = measure(cell)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in run.listed_for(spec()["end_to_end"], cell)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("cell", list(TINY))
def test_traced_run_reads_every_listed_metric(cell):
    out = measure(cell, trace=1)
    want = {m["name"] for m in run.listed_for(spec()["per_layer"], cell)}
    assert set(out["metrics"]) == want
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert out["correct"]


@pytest.mark.parametrize("cell", ["presolve_set5.narrow", "bnb_set3.frontier64"])
def test_control_is_not_correct(cell):
    """The control, one precision below the configuration's: bfloat16
    (the reference in the program's place for presolve, the program's own
    bfloat16 path for nodes)."""
    loop = run.load_module("loops", run.load_cell(cell)[3]["loop"])
    out = measure(cell, program=loop.control(run.load_program()))
    assert not out["correct"], out["check"]


def _program(**swap):
    return SimpleNamespace(**{**vars(run.load_program()), **swap})


def _result(lb, ub, infeasible, rounds):
    return SimpleNamespace(lb=lb, ub=ub, infeasible=infeasible, rounds=rounds)


def _altered(lb, ub):
    """One bound moved inward, halfway across its domain."""
    lb, ub = np.array(lb), np.array(ub)
    width = np.where((np.abs(lb) < 1e20) & (np.abs(ub) < 1e20), ub - lb, -1)
    j = int(np.argmax(width))
    lb[j] += 0.5 * width[j]
    return lb, ub


def _presolve_fault(fault, calls=None):
    """``propagate_block_ell`` with ``fault`` applied to the calls numbered
    in ``calls`` (every call when ``None``), the two warm calls counted."""
    real, seen = run.load_program(), []

    def presolve(p, **kw):
        seen.append(1)
        if calls is not None and len(seen) not in calls:
            return real.propagate_block_ell(p, **kw)
        if fault == "unchanged":
            return _result(np.asarray(p.lb, np.float32), np.asarray(p.ub, np.float32),
                           False, 1)
        r = real.propagate_block_ell(p, **kw)
        return _result(*_altered(np.asarray(r.lb), np.asarray(r.ub)), r.infeasible, r.rounds)

    return _program(propagate_block_ell=presolve)


@pytest.mark.parametrize("fault,calls", [("unchanged", None), ("unchanged", {4}),
                                         ("altered", None)])
def test_presolve_fault_is_not_correct(fault, calls):
    """A fixed point that returns the bounds it was given (in every call,
    or in one call of many), and one answer moved inward."""
    out = measure("presolve_set5.narrow", program=_presolve_fault(fault, calls))
    assert out["attempted"] >= (3 if calls else 1) and not out["correct"], out["check"]


def _nodes_fault(fault):
    real = run.load_program()

    def nodes(p, lb_nodes, ub_nodes, **kw):
        r = real.propagate_nodes(p, lb_nodes, ub_nodes, **kw)
        lb, ub = np.array(r.lb), np.array(r.ub)
        if fault == "unchanged":
            lb, ub = np.asarray(lb_nodes, lb.dtype), np.asarray(ub_nodes, ub.dtype)
        elif fault == "half_batch":
            half = len(lb) // 2
            lb[half:], ub[half:] = lb_nodes[half:], ub_nodes[half:]
        else:
            lb[0], ub[0] = _altered(lb[0], ub[0])
        return r._replace(lb=lb, ub=ub)

    return _program(propagate_nodes=nodes)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_nodes_fault_is_not_correct(fault):
    """A frontier returned as it was sent, half of each frontier left out
    (those nodes come back as sent), and one node's answer moved inward."""
    out = measure("bnb_set3.frontier64", program=_nodes_fault(fault))
    assert not out["correct"], out["check"]


def test_command_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "presolve_set5.narrow", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_command_refuses_without_the_program(tmp_path):
    """A checkout with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "presolve_set5.narrow",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_device_kind_refuses_before_set_up():
    """Past the look for a chip, a device kind missing from the peak table
    stops the run before any data is built."""
    from bench.peaks import UnknownDevice

    args = run.parse(["--workload", "presolve_set5.narrow", "--seed", "1", "--seconds", "1",
                      "--trace", "0"])
    with pytest.raises(UnknownDevice):
        run.measure(args, require_tpu=False, overrides=TINY["presolve_set5.narrow"])


@pytest.mark.parametrize("ahead", [0, 2, 6])
def test_node_window_answers_every_frontier_sent(ahead):
    """With frontiers kept in flight (more of them than a pass holds, too),
    the window closes on whole passes, every frontier of them answered."""
    tiny = TINY["bnb_set3.frontier64"]
    out = measure("bnb_set3.frontier64", ahead=ahead)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["attempted"] % (tiny["frontiers"] * tiny["batch"]) == 0
