"""Chip benchmark of the sketch services: one cell of BENCHMARK.json per run.

Run ``python3 chipbench/run.py --help`` from the repository root.
"""
