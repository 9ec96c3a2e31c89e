#!/usr/bin/env python3
"""Run-to-run spread of a cell's end-to-end metrics, for setting bounds.

    python3 chipbench/spread.py results.jsonl [more.jsonl ...]

Each input line is ``{"cell": ..., "set": ..., "seed": ..., "result":
<a run's result line>}``.  Per cell, metric and set it prints the median
and the spread: the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) over the median.  A bound is set
at about five times the widest spread over the sets and cells, never
under 1%.
"""
import collections
import json
import statistics
import sys


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths) -> int:
    runs = collections.defaultdict(list)
    for path in paths:
        with open(path) as f:
            for line in f:
                if line.strip():
                    r = json.loads(line)
                    runs[(r["cell"], r["set"])].append(r)
    for (cell, set_), rs in sorted(runs.items()):
        bad = [r["seed"] for r in rs if not r["result"]["correct"]]
        print(f"{cell} set {set_}: {len(rs)} runs, incorrect seeds {bad}")
        names = sorted({m for r in rs for m in r["result"]["metrics"]})
        for m in names:
            vals = [r["result"]["metrics"][m]["value"] for r in rs
                    if m in r["result"]["metrics"]]
            if len(vals) >= 2:
                print(f"  {m}: median {statistics.median(vals)!r} "
                      f"spread {spread(vals):.5f} values {vals}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
