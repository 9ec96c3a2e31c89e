"""Benchmark harness entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (assignment contract).
Usage: PYTHONPATH=src python -m benchmarks.run
       [--only ann|kde|kernels|ingest|pipeline|cluster|rpc|sharded|query|serve|tenant]
(``query`` additionally writes BENCH_query.json — see bench_query.py;
``ingest``, ``pipeline``, ``cluster``, ``rpc`` and ``tenant`` share
BENCH_ingest.json — see bench_ingest.py, bench_pipeline.py,
bench_cluster.py and bench_tenant.py; ``serve`` writes BENCH_serve.json —
the micro-batching load test, see bench_serve.py.  ``rpc`` is the
multi-process network cluster variant of ``cluster`` and spawns worker
processes — it is not part of the default all-suites run.)
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    choices=[None, "ann", "kde", "kernels", "ingest",
                             "pipeline", "cluster", "rpc", "sharded",
                             "query", "serve", "tenant"])
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (bench_ann, bench_cluster, bench_ingest, bench_kde,
                   bench_kernels, bench_pipeline, bench_query, bench_serve,
                   bench_sharded, bench_tenant)
    rows: list[tuple] = []
    suites = {"ann": bench_ann.run, "kde": bench_kde.run,
              "kernels": bench_kernels.run, "ingest": bench_ingest.run,
              "pipeline": bench_pipeline.run, "cluster": bench_cluster.run,
              "rpc": bench_cluster.run_rpc, "sharded": bench_sharded.run,
              "query": bench_query.run, "serve": bench_serve.run,
              "tenant": bench_tenant.run}
    for name, fn in suites.items():
        if args.only and args.only != name:
            continue
        if args.only is None and name == "rpc":
            continue        # spawns worker processes; opt-in via --only rpc
        print(f"# suite: {name}", file=sys.stderr, flush=True)
        fn(rows)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
