"""Correctness of sann-bigann128-50M-4chip: the comparisons of
sann-sift128-1M (`checks/sann-sift128-1M.py`: the plain S-ANN reference,
the same limits, each argued there) on an index whose tables are split
over the chips, plus one of the sharded layout:

* ``shard_layout_mismatch`` — exact: table blocks that are not rows
  ``[i·L/n, (i+1)·L/n)`` on the ``i``-th chip of the mesh, chips whose
  block of the table pointers does not sum to the reference's appends to
  those rows (every kept point once a row), and chips whose copy of a
  replicated array (point store, valid mask, stamps, counters) differs
  from the first chip's, bit for bit.

The reads of the tables and the top-k queries run on the sharded state:
the ring membership gathers from the tables where they lie (each chip
reads its own block; compiled for a v5e 2x2 host it moves no table
between chips), and `query_topk` merges the chips' candidates.
``observe`` also keeps the service's counters (``run.svc_stats``) for
the per-layer metrics that read them.
"""
from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import spec

base = spec.load_module(pathlib.Path(__file__).with_name(
    "sann-sift128-1M.py"))
held_points = base.held_points
control = base.control


@jax.jit
def _differs(a, b):
    if jnp.issubdtype(a.dtype, jnp.floating):
        a = lax.bitcast_convert_type(a, jnp.int32)
        b = lax.bitcast_convert_type(b, jnp.int32)
    return jnp.any(a != b)


def _layout_faults(run, st) -> int:
    n_kept = len(base._reference(run)["idx"])
    L = int(run.settings["L"])
    bad = 0
    for name in ("tables", "table_ptr"):
        x = getattr(st, name)
        mesh = list(x.sharding.mesh.devices.flat)
        per = L // len(mesh)
        for s in x.addressable_shards:
            i = mesh.index(s.device)
            rows = s.index[0]
            bad += int((rows.start or 0, L if rows.stop is None
                        else rows.stop) != (i * per, (i + 1) * per))
            if name == "table_ptr":
                bad += int(int(jnp.sum(s.data)) != n_kept * per)
    for name, x in st._asdict().items():
        if not x.sharding.is_fully_replicated:
            continue
        first, *rest = x.addressable_shards
        for s in rest:
            other = jax.device_put(s.data, first.device)
            bad += int(_differs(first.data, other))
    return bad


def observe(run) -> dict:
    run.svc_stats = run.svc.stats()
    obs = base.observe(run)
    obs["shard_layout"] = _layout_faults(run, run.svc.snapshot()[0])
    return obs


def compare(run, obs) -> list:
    return [*base.compare(run, obs),
            ("shard_layout_mismatch", float(obs["shard_layout"]), 0.0)]
