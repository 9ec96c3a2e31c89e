"""Where the harness finds a cell's parts: everything is named in
``BENCHMARK.json`` and lives in a file of its own under ``chipbench/``.

* a configuration ``configs/<config>.json`` (service, settings, source,
  guarantees) and its comparison ``checks/<check>.py`` (``check`` defaults
  to the configuration's name);
* a traffic mix ``traffic/<traffic>.json``, read by the one general driver
  of its ``kind`` (`chipbench.drivers`);
* a metric, end-to-end or per-layer, ``metrics/<name>.py`` with
  ``read(ctx) -> float | None``.

A later change adds a cell, a configuration, a mix or a metric by adding
files and entries; no file that is already here needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list
    bench_dir: pathlib.Path   # the directory that holds configs/, ...

    def check_module(self):
        name = self.config.get("check", self.config["name"])
        return load_module(self.bench_dir / "checks" / f"{name}.py")

    def reader(self, metric: str):
        return load_module(self.bench_dir / "metrics" / f"{metric}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: pathlib.Path,
              bench_dir: pathlib.Path = HERE) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def load_module(path: pathlib.Path):
    """Import a reader or check by its file path (names may hold dots),
    once per process."""
    path = pathlib.Path(path).resolve()
    mod_name = "chipbench_file_" + str(abs(hash(str(path))))
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[mod_name]
