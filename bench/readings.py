"""Readings that the limits of the check are set from, on the chip.

    python bench/readings.py --workload <cell> --seeds 1,2,3 [--control] [--seconds 2]

Runs the cell once per seed, in this one process, with a short window at
the cell's own sizes and load, and prints one JSON line per seed with the
numbers the check compares.  ``--control`` puts the cell's control in
the program's place (``control`` of the cell's loop: one precision below
the configuration's float32, bfloat16): its readings give each limit its
upper end, and sound runs give its lower end.  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys

from run import Refused, load_cell, load_module, load_program, measure, parse


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args(argv)
    program = load_program()
    if a.control:
        program = load_module("loops", load_cell(a.workload)[3]["loop"]).control(program)
    for seed in (int(s) for s in a.seeds.split(",")):
        args = parse(["--workload", a.workload, "--seed", str(seed),
                      "--seconds", str(a.seconds), "--trace", "0"])
        try:
            out = measure(args, program=program)
        except Refused as e:
            print(f"readings: {e}", file=sys.stderr)
            return 1
        print(json.dumps({"workload": a.workload, "seed": seed, "control": a.control,
                          "attempted": out["attempted"], "correct": out["correct"],
                          **{k: c["value"] for k, c in out["check"].items()},
                          "metrics": {k: m["value"] for k, m in out["metrics"].items()
                                      if k != "setup_s"}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
