"""One run of one cell: build the cell's service, warm it, measure for
``--seconds``, check the answers against the plain reference, print the
result line.

The result is the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, in
a traced run, ``breakdown``), then ``compared``: each number the check
compared, with its limit.  The same comparisons are the last lines of
standard error.  Without an accelerator, or with fewer chips than the cell
asks for, the run prints no result and exits 2.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from chipbench import drivers, spec, streams
from chipbench import trace as trace_mod
from chipbench.spans import Spans

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
SPAN_NAMES = ("prepare", "commit")


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def use_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program is kept,
    so a second run of a cell compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def service_seed(seed: int) -> int:
    """The services draw their keys from ``PRNGKey(seed)`` and
    ``PRNGKey(seed + 1)``: fold any benchmark seed into that range."""
    return int(seed) % (2**31 - 2)


def _resolve(path: str):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


class Run:
    """What one run built and saw; readers and checks read it."""

    def __init__(self, cell: spec.Cell, seed: int, overrides: dict):
        self.cell = cell
        self.seed = int(seed)
        # A configuration may fix the service's seed (its hash draws), so
        # that every run seed gives the sketch the same functions.
        fixed = cell.config.get("service_seed")
        self.svc_seed = service_seed(seed) if fixed is None else int(fixed)
        overrides = dict(overrides)
        stream_spec = {**cell.config["stream"], **overrides.pop("stream", {})}
        self.settings = {**cell.config["settings"], **overrides}
        self.chunk = int(self.settings["ingest_chunk"])
        self.stream = streams.make_stream(self.seed, self.settings["dim"],
                                          self.chunk, stream_spec)
        self.check = cell.check_module()
        self.chunks_ingested = 0
        self.svc = None
        self.spans: Spans | None = None
        self.seconds = 0.0

    def build(self, traced: bool) -> None:
        cfg_cls = _resolve(self.cell.config["service_config"])
        svc_cls = _resolve(self.cell.config["service"])
        self.svc = svc_cls(cfg_cls(**self.settings, seed=self.svc_seed))
        self.spans = Spans(traced)
        # Every chunk the drivers submit holds exactly `chunk` rows.
        self.spans.wrap(self.svc, "_commit_one", "commit",
                        rows=lambda a: self.chunk)
        if traced:
            self.spans.wrap(self.svc, "_prepare_ready", "prepare",
                            rows=lambda a: int(a[0][0].shape[0]))

    def rows(self, idx) -> np.ndarray:
        """Ingested stream points ``idx``."""
        return streams.rows(self.stream, idx)

    def make_queries(self, n: int, near: float, noise: float, r):
        """``n`` query rows: a share ``near`` around points the sketch
        holds (per the configuration's reference), the rest around
        ingested points."""
        held = self.check.held_points(self)
        n_near = int(round(n * near))
        n_seen = self.chunks_ingested * self.chunk
        src = np.concatenate([
            held[r.integers(0, len(held), n_near)],
            self.rows(r.integers(0, n_seen, n - n_near))])
        src = src[r.permutation(n)]
        return streams.perturbed(src, noise, r)


class Context:
    """What a metric reader sees."""

    def __init__(self, run: Run, setup_s: float, window: dict,
                 summary, device_kind: str, traced_span: tuple):
        self.run = run
        self.traced_span = traced_span
        self.cell = run.cell
        self.setup_s = setup_s
        self.window = window
        self.trace = summary
        self.device_kind = device_kind

    def spans(self, name: str) -> list:
        """Host spans ``name`` that ended inside the measured window."""
        return self.run.spans.of(name, self.window["t0"], self.window["t1"])

    def traced_spans(self, name: str) -> list:
        """Host spans ``name`` that ended inside the traced window (the
        measured window and the drain after it)."""
        return self.run.spans.of(name, *self.traced_span)


def check_devices(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip(f"no accelerator: JAX platform is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(name: str, seed: int, seconds: float, traced: bool, *,
             require_chip: bool = True, overrides: dict | None = None,
             traffic_overrides: dict | None = None, tamper=None,
             with_control: bool = False) -> dict:
    """One run of cell ``name``; returns the result object.

    ``require_chip=False``, ``overrides`` (configuration settings),
    ``traffic_overrides`` and ``tamper`` (called with the built service,
    to break the timed path on purpose) exist for the harness's own tests
    on the CPU at small sizes.  ``with_control`` adds the control's
    readings (`chipbench.control`)."""
    t_setup = time.perf_counter()
    cell = spec.load_cell(name, ROOT)
    if traffic_overrides:
        cell.traffic = {**cell.traffic, **traffic_overrides}
    import jax
    if require_chip:
        devices = check_devices(cell.chips)[:cell.chips]
        use_cache()
    else:
        devices = jax.devices()[:1]
    run = Run(cell, seed, overrides or {})
    run.seconds = seconds
    run.build(traced)
    if tamper is not None:
        tamper(run.svc)
    driver = drivers.DRIVERS[cell.traffic["kind"]](run, cell.traffic)
    driver.setup()
    # Everything set-up made (JAX's and the program's modules, the
    # reference's tables, the request plan) lives for the whole run: keep
    # it out of the collector's full passes, which otherwise stop every
    # thread for ~0.1 s each time they scan it (a pause of the benchmark's
    # process, not of the system under test).
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_setup

    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    summary = None
    try:
        if traced:
            jax.profiler.start_trace(log_dir)
        try:
            w0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                driver.window(seconds)
            w1 = time.perf_counter()
        finally:
            if traced:
                jax.profiler.stop_trace()
        if traced:
            summary = trace_mod.reduce(trace_mod.find_xplane(log_dir),
                                       SPAN_NAMES)
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    window = driver.window_stats
    memory_peak = peak_bytes(devices)

    # The check: what the timed path produced, read off the live service,
    # then the reference on the host once the service is closed.
    observed = run.check.observe(run)
    run.svc.close()
    run.svc = None
    gc.unfreeze()          # the closed service's cycles can go now
    gc.collect()
    compared = run.check.compare(run, observed)
    control = run.check.control(run, observed) if with_control else None

    kind = devices[0].device_kind
    ctx = Context(run, setup_s, window, summary, kind, (w0, w1))
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"correct": all(v <= lim for _, v, lim in compared),
           "attempted": int(window["attempted"]),
           "failed": int(window["failed"]),
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.top_gaps(10)}
    out["compared"] = {n: {"value": v, "limit": lim}
                       for n, v, lim in compared}
    if control is not None:
        out["control"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in control}
    return out


def _finite(x):
    return x if not (isinstance(x, float) and not math.isfinite(x)) \
        else str(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for n, c in out["compared"].items():
        print(f"compared {n} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    out["compared"] = {n: {k: _finite(v) for k, v in c.items()}
                       for n, c in out["compared"].items()}
    print(json.dumps(out), flush=True)
    return 0
