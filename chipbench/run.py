#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the repository root; the cells are those of ``BENCHMARK.json``.
"""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Replace the script's own directory: its module names (trace, stats)
# would shadow the standard library's.
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
