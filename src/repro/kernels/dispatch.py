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

# Implementation of each op on a TPU.  The "xla" entries are kernels the
# TPU compiler refuses (reasons in ROADMAP.md, Queue 1 item 2):
#   * srp_hash — reductions over unsigned integers are not implemented in
#     Mosaic; the served path hashes with plain jnp (core.lsh) anyway.
#   * swakde_segment_pass — its body is `ref.swakde_segment_pass_ref`, whose
#     shape-changing take_along_axis gathers Mosaic cannot lower and whose
#     (rows, G, C) intermediates exceed VMEM.
#   * sann_table_scatter — one table row (n_buckets × bucket_cap int32) is
#     far larger than VMEM, so no block layout fits; 1-D entry blocks and
#     dynamic scalar reads come next.
#   * sketch_decode_attn — its (block, 1, dh) K/V blocks break the (8, 128)
#     block rule on an (S, Hkv, dh) cache.
TPU_ROUTE = {
    "race_hist": "pallas",
    "cand_score": "pallas",
    "batch_score_topk": "pallas",
    "srp_hash": "xla",
    "swakde_segment_pass": "xla",
    "sann_table_scatter": "xla",
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
