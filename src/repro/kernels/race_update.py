"""Pallas TPU kernel: RACE histogram accumulation.

Given LSH codes (B, L) the sketch update increments ``counts[l, codes[b, l]]``
for every (b, l).  Scatter is hostile to the VPU, so the TPU-native form is a
**one-hot compare + reduce** per row tile, accumulated in a VMEM-resident
output block that the sequential grid revisits across batch chunks — a
classic TPU histogram.

Layout: the codes are transposed to (L, B) so one row's codes lie along the
lanes.  The one-hot tile of row l is ``hit[w, b] = (w == codes[l, b])``
(buckets on sublanes, a sublane broadcast of the code row), and its lane
sum is taken on the MXU as ``ones (8, CB) · hitᵀ`` — a transposed-RHS
matmul whose first row is the histogram laid out along the lanes, ready to
add into the (TL, TW) output block.  0/1 operands in bf16 and an f32
accumulator keep every count exact (CB < 2^24).

Grid: (L tiles, W tiles, B chunks), B innermost; the output block is
revisited for every batch chunk (TPU grids execute sequentially, so
accumulation is safe).  L pads to a multiple of 8, W to a multiple of 128
and B to a multiple of the chunk; padded codes are -1 and match no bucket.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import resolve_interpret


def _kernel(codes_ref, o_ref, *, tl: int, tw: int):
    bc = pl.program_id(2)

    @pl.when(bc == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    cb = codes_ref.shape[1]
    buckets = pl.program_id(1) * tw + jax.lax.broadcasted_iota(
        jnp.int32, (tw, cb), 0)
    ones = jnp.ones((8, cb), jnp.bfloat16)
    for i in range(tl):
        hit = (buckets == codes_ref[i:i + 1, :]).astype(jnp.bfloat16)
        cnt = jax.lax.dot_general(
            ones, hit, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (8, TW)
        o_ref[i:i + 1, :] += cnt[0:1, :].astype(jnp.int32)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit, static_argnames=("W", "block_b", "interpret"))
def race_hist(
    codes: jax.Array,      # (B, L) int32
    W: int,
    block_b: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Histogram of codes per row: out[l, w] = #{b : codes[b, l] == w}."""
    # None = derive from the backend, the same policy ops.py applies.
    interpret = resolve_interpret(interpret)
    B, L = codes.shape
    if B == 0:
        return jnp.zeros((L, W), jnp.int32)
    tl = 8
    Lp = _round_up(L, tl)
    Wp = _round_up(W, 128)
    tw = next(t for t in (512, 256, 128) if Wp % t == 0)
    cb = _round_up(min(block_b, B), 128)
    Bp = _round_up(B, cb)
    codes_t = jnp.pad(codes.astype(jnp.int32).T, ((0, Lp - L), (0, Bp - B)),
                      constant_values=-1)                    # (Lp, Bp)
    out = pl.pallas_call(
        functools.partial(_kernel, tl=tl, tw=tw),
        grid=(Lp // tl, Wp // tw, Bp // cb),
        in_specs=[pl.BlockSpec((tl, cb), lambda l, w, bc: (l, bc))],
        out_specs=pl.BlockSpec((tl, tw), lambda l, w, bc: (l, w)),
        out_shape=jax.ShapeDtypeStruct((Lp, Wp), jnp.int32),
        interpret=interpret,
    )(codes_t)
    return out[:L, :W]
