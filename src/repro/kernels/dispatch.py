"""Kernel dispatch policy — the single source of truth.

Every kernel op in `ops.py` takes one of two implementations:

  * ``"pallas"`` — the compiled Pallas kernel;
  * ``"xla"`` — the pure-jnp oracle (`ref.py`), jitted by XLA.

On a TPU the route is the static table `TPU_ROUTE`: an op runs as a Pallas
kernel only if that kernel compiles for the chip (tests/test_tpu_compile.py
compiles every "pallas" entry for a described v5e).  Off the TPU every op
takes its oracle — the Pallas interpreter is a Python-level emulator
(correct but slow) — unless ``REPRO_FORCE_PALLAS=1`` sends the ops through
the interpreter, which is how tests compare kernels against the oracles.

Both `ops.py` (oracle-vs-kernel routing) and every kernel wrapper's
``interpret=None`` default resolve through here, so the policy cannot
drift between call sites: a kernel never runs in interpret mode on a TPU.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

# Implementation of each op on a TPU.  "sann_table_scatter" is Pallas
# because the oracle's flat scatter makes XLA relayout the whole S-ANN
# table twice around it; the kernel appends in place (and
# `ops.sann_table_scatter` keeps the oracle where the appends outnumber
# the tables' cost: `ingest_commit.append_in_place`).  The "xla" entries
# are kernels the TPU compiler refuses:
#   * srp_hash — reductions over unsigned integers are not implemented in
#     Mosaic; the served path hashes with plain jnp (core.lsh) anyway.
#   * swakde_segment_pass — its body is `ref.swakde_segment_pass_ref`, whose
#     shape-changing take_along_axis gathers Mosaic cannot lower and whose
#     (rows, G, C) intermediates exceed VMEM.
#   * sketch_decode_attn — its (block, 1, dh) K/V blocks break the (8, 128)
#     block rule on an (S, Hkv, dh) cache.
TPU_ROUTE = {
    "race_hist": "pallas",
    "cand_score": "pallas",
    "batch_score_topk": "pallas",
    "srp_hash": "xla",
    "swakde_segment_pass": "xla",
    "sann_table_scatter": "pallas",
    "sketch_decode_attn": "xla",
}


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def route(op: str) -> str:
    """``"pallas"`` or ``"xla"``: the implementation ``op`` takes here."""
    if on_tpu():
        return TPU_ROUTE[op]
    return "pallas" if os.environ.get("REPRO_FORCE_PALLAS") == "1" else "xla"


def use_pallas(op: str) -> bool:
    """Route ``op`` through its Pallas kernel (vs the jnp oracle)?"""
    return route(op) == "pallas"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → derive from the backend: compiled on real TPUs,
    interpret mode everywhere else."""
    return (not on_tpu()) if interpret is None else interpret
