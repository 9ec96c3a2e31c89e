"""Pallas TPU kernel: fused batched candidate scoring for the query engine.

The batched query path (core.sann.sann_query_batch / sann_query_topk_batch)
gathers, for a whole block of B queries at once, each query's candidate
vectors — a ``(B, M, d)`` tensor.  This kernel scores the entire block in
one pass:

  * squared L2 from the difference, like the oracle: per query row the
    ``(TM, d)`` tile ``(c − q)²`` is lane-summed on the MXU as
    ``ones (8, d) · ((c − q)²)ᵀ`` at HIGHEST precision, which lays the TM
    distances along the lanes of one ``(TB, TM)`` score tile;
  * a masked **fused top-k** (k = 1 ⇒ argmin) carried across the M tiles in
    the revisited output block, so the full ``(B, M)`` distance matrix never
    leaves VMEM.  k = 1 is a running min/argmin; k > 1 merges the tile into
    the running list by k rounds of select-the-minimum.

Grid: ``(B tiles, M tiles)`` with M innermost; the ``(TB, k)`` output block
is revisited across the M tiles (TPU grids run sequentially, so the running
top-k accumulation is safe — same pattern as `race_update.race_hist`).

Tie-breaking: equal distances resolve to the lowest candidate index, within
and across tiles (like `lax.top_k` on the full matrix).  Entries past the
last finite one (masked or padding) carry distance inf and an unspecified
index; callers map them to NULL by the distance.

Numerics: the same differences and squares as the diff-based oracle
`ref.batch_score_ref`; only the order of the d-term sum differs, so d2
agrees to float32 rounding.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import resolve_interpret

_BIG = jnp.iinfo(jnp.int32).max


def _row_min(d, idx):
    """Per-row minimum of ``d (TB, n)`` and the lowest ``idx`` attaining it
    → two ``(TB, 1)`` columns."""
    m = d.min(axis=1, keepdims=True)
    return m, jnp.where(d == m, idx, _BIG).min(axis=1, keepdims=True)


def _kernel(q_ref, c_ref, ok_ref, od_ref, oi_ref, d2_scr, *, k: int, M: int):
    j = pl.program_id(1)
    tb, tm = d2_scr.shape

    @pl.when(j == 0)
    def _():
        od_ref[...] = jnp.full_like(od_ref, jnp.inf)
        oi_ref[...] = jnp.zeros_like(oi_ref)

    q = q_ref[...].astype(jnp.float32)                    # (TB, d)
    ones = jnp.ones((8, q.shape[1]), jnp.float32)
    for r in range(tb):
        diff = c_ref[r].astype(jnp.float32) - q[r:r + 1, :]   # (TM, d)
        row = jax.lax.dot_general(
            ones, diff * diff, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)           # (8, TM)
        d2_scr[r:r + 1, :] = row[0:1, :]
    gidx = j * tm + jax.lax.broadcasted_iota(jnp.int32, (tb, tm), 1)
    d2 = jnp.where((ok_ref[...] != 0) & (gidx < M), d2_scr[...], jnp.inf)

    run_d, run_i = od_ref[...], oi_ref[...]
    if k == 1:
        m, i = _row_min(d2, gidx)
        better = m < run_d              # strict: an earlier tile wins ties
        od_ref[...] = jnp.where(better, m, run_d)
        oi_ref[...] = jnp.where(better, i, run_i)
        return

    # k rounds over the pool (running list ∪ tile): take the minimum, ties
    # to the lowest index (running entries come from earlier tiles, so
    # their indices are lower), then retire that index from the pool.
    slot = jax.lax.broadcasted_iota(jnp.int32, (tb, k), 1)

    def pick(t, carry):
        run_d, d2, new_d, new_i = carry
        m_run, i_run = _row_min(run_d, run_i)
        m_til, i_til = _row_min(d2, gidx)
        m = jnp.minimum(m_run, m_til)
        i = jnp.minimum(jnp.where(m_run == m, i_run, _BIG),
                        jnp.where(m_til == m, i_til, _BIG))
        run_d = jnp.where(run_i == i, jnp.inf, run_d)
        d2 = jnp.where(gidx == i, jnp.inf, d2)
        new_d = jnp.where(slot == t, m, new_d)
        new_i = jnp.where(slot == t, i, new_i)
        return run_d, d2, new_d, new_i

    _, _, new_d, new_i = jax.lax.fori_loop(
        0, k, pick, (run_d, d2, run_d, run_i))
    od_ref[...] = new_d
    oi_ref[...] = new_i


@functools.partial(
    jax.jit, static_argnames=("k", "block_b", "block_m", "interpret"))
def batch_score_topk(
    qs: jax.Array,       # (B, d)
    cands: jax.Array,    # (B, M, d) — per-query candidate vectors
    ok: jax.Array,       # (B, M) bool — score mask (False ⇒ distance inf)
    k: int,
    block_b: int = 8,
    block_m: int = 512,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    """Masked squared-L2 top-k per query: ``(d2 (B, k) ascending, idx (B, k)
    int32 into M)``.  Fully-masked rows return d2 = inf.

    On a TPU ``block_b`` must be a multiple of 8 (or cover B), and
    ``block_m`` a multiple of 128 (or cover M)."""
    interpret = resolve_interpret(interpret)
    B, M, d = cands.shape
    if B == 0 or M == 0:   # empty batch/candidates: no grid to launch
        return (jnp.full((B, k), jnp.inf, jnp.float32),
                jnp.zeros((B, k), jnp.int32))
    tb = min(block_b, B)
    tm = M if M <= 2 * block_m else block_m
    grid = (pl.cdiv(B, tb), pl.cdiv(M, tm))
    out_d, out_i = pl.pallas_call(
        functools.partial(_kernel, k=k, M=M),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tb, tm, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((tb, tm), lambda i, j: (i, j)),
        ],
        out_specs=(
            pl.BlockSpec((tb, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tb, k), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
        ),
        scratch_shapes=[pltpu.VMEM((tb, tm), jnp.float32)],
        interpret=interpret,
    )(qs, cands, ok.astype(jnp.int32))
    return out_d, out_i
