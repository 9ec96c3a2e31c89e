#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness check.

For each seed, one run of the cell (a short window at the cell's own load
and sizes, untimed) prints the numbers its check compares for the program
and for the control: the plain reference in the program's place one
precision step down (each configuration's check module says which).  All
seeds run in one process, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 5]

One JSON line per seed; the limits in the check modules lie between the
program's largest reading and the control's smallest.
"""
import argparse
import gc
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   with_control=True)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": out["compared"],
                          "control": out["control"]}, default=str),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
