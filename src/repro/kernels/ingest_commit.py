"""Pallas ingest-commit kernels: the two-phase commit half as tiled passes.

Two kernels (DESIGN.md §12):

  * ``swakde_segment_pass`` — the closed-form segment-reduce SumEH commit:
    one (row_block, segment_block) tile walk over the per-row sorted cell
    segments emitted by `core.swakde.swakde_prepare_chunk`, applying the
    Corollary-4.2 cascade per segment in one fused pass (no per-add loop).
    The tile math *is* `kernels.ref.swakde_segment_pass_ref` — the kernel
    loads a tile, runs the identical closed form, and stores it back, so
    CPU-oracle and Pallas paths are one implementation.
  * ``sann_table_scatter`` — the sorted-segment ring append of
    `core.sann.sann_commit_chunk`: entries arrive sorted by (row, code), so
    each grid step owns one row's (n_buckets, bucket_cap) table block and
    replays its appends as one coalesced write pass.

Both kernels run only in interpret mode: tests/test_kernels.py pins their
equality against the `ref.py` oracles, and every backend, the TPU included,
runs the oracles (`dispatch.TPU_ROUTE`).

TPU note: the TPU compiler refuses both at real widths (a described v5e,
SW-AKDE at L=64, W=1024, chunk 4096; S-ANN at 16 tables of 2,845,244 ×
16 slots):
  * ``swakde_segment_pass`` — the ``(1, bg)`` segment blocks on ``(R, G)``
    break the (8, 128) block rule.  With aligned ``(8, G)`` blocks the
    body, which is `ref.swakde_segment_pass_ref`, fails next: Mosaic lowers
    no shape-changing ``take_along_axis`` gather, and the pass materialises
    ``(rows, G, C)`` intermediates (128 MiB per 8 rows) that exceed VMEM.
  * ``sann_table_scatter`` — the ``(1, NB)`` pointer block on ``(L, NB)``
    breaks the block rule, and one table row (182 MB) cannot be a VMEM
    block at all; the 1-D ``(E,)`` entry blocks and the dynamic scalar
    reads of the body come next.  A TPU form keeps the tables in HBM and
    writes each entry by DMA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .dispatch import resolve_interpret


def _seg_pass_kernel(cell_ts_ref, cell_num_ref, done_ref, sorted_ts_ref,
                     seg_first_ref, seg_len_ref,
                     ts_out_ref, num_out_ref, done_out_ref,
                     *, window: int, maxb: int, n_levels: int, cap: int):
    """One (row, segment_block) tile: run the closed-form pass on the tile."""
    cts, cnum, done = ref.swakde_segment_pass_ref(
        cell_ts_ref[...], cell_num_ref[...], done_ref[...],
        sorted_ts_ref[...], seg_first_ref[...], seg_len_ref[...],
        window=window, maxb=maxb, n_levels=n_levels, cap=cap)
    ts_out_ref[...] = cts
    num_out_ref[...] = cnum
    done_out_ref[...] = done


@functools.partial(
    jax.jit, static_argnames=("window", "maxb", "n_levels", "cap",
                              "block_g", "interpret"))
def swakde_segment_pass(cell_ts: jax.Array, cell_num: jax.Array,
                        done: jax.Array, sorted_ts: jax.Array,
                        seg_first: jax.Array, seg_len: jax.Array,
                        *, window: int, maxb: int, n_levels: int,
                        cap: int = 0, block_g: int = 8,
                        interpret: bool | None = None):
    """Tiled closed-form sub-chunk commit pass (see
    `ref.swakde_segment_pass_ref` for the contract): grid walks
    (row, segment_block) tiles; each tile holds its segments' EH rings and
    the whole row's sorted stamps (the strided carry windows read from it).
    """
    interpret = resolve_interpret(interpret)
    R, G, LV, S = cell_ts.shape
    C = sorted_ts.shape[1]
    bg = min(block_g, G)
    pad = (-G) % bg
    if pad:
        # Padding segments are empty (seg_len = 0) → the pass is identity.
        zi = lambda a: jnp.pad(a, ((0, 0), (0, pad)))
        cell_ts = jnp.pad(cell_ts, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cell_num = jnp.pad(cell_num, ((0, 0), (0, pad), (0, 0)))
        done, seg_first, seg_len = zi(done), zi(seg_first), zi(seg_len)
    Gp = G + pad
    grid = (R, Gp // bg)
    seg_spec = pl.BlockSpec((1, bg), lambda i, j: (i, j))
    out = pl.pallas_call(
        functools.partial(_seg_pass_kernel, window=window, maxb=maxb,
                          n_levels=n_levels, cap=cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bg, LV, S), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bg, LV), lambda i, j: (i, j, 0)),
            seg_spec,
            pl.BlockSpec((1, C), lambda i, j: (i, 0)),
            seg_spec,
            seg_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, bg, LV, S), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, bg, LV), lambda i, j: (i, j, 0)),
            seg_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Gp, LV, S), cell_ts.dtype),
            jax.ShapeDtypeStruct((R, Gp, LV), cell_num.dtype),
            jax.ShapeDtypeStruct((R, Gp), done.dtype),
        ],
        interpret=interpret,
    )(cell_ts, cell_num, done, sorted_ts, seg_first, seg_len)
    cts, cnum, dn = out
    if pad:
        cts, cnum, dn = cts[:, :G], cnum[:, :G], dn[:, :G]
    return cts, cnum, dn


def _sann_scatter_kernel(ptr_ref, s_l_ref, s_c_ref, rank_ref, val_ref,
                         mask_ref, tab_in_ref, tab_ref, *, bucket_cap: int):
    """One row's table block: replay the row's sorted appends in order.

    ``tab_in_ref`` is aliased onto ``tab_ref`` (input_output_aliases), so
    untouched buckets keep their prior contents without an explicit copy.
    """
    del tab_in_ref
    row = pl.program_id(0)
    E = s_l_ref.shape[0]

    def body(e, _):
        @pl.when(mask_ref[e] & (s_l_ref[e] == row))
        def _():
            c = s_c_ref[e]
            rp = (ptr_ref[0, c] + rank_ref[e]) % bucket_cap
            tab_ref[0, c, rp] = val_ref[e]
        return 0

    jax.lax.fori_loop(0, E, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sann_table_scatter(tables: jax.Array, table_ptr: jax.Array,
                       s_l: jax.Array, s_c: jax.Array, rank: jax.Array,
                       val: jax.Array, mask: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """Sorted-segment ring append (see `ref.sann_table_scatter_ref`): one
    grid step per row; appends are sorted by (row, code), so each step's
    writes walk its block's buckets coalesced, in append order."""
    interpret = resolve_interpret(interpret)
    L, NB, cap = tables.shape
    E = s_l.shape[0]
    if E == 0:
        return tables
    return pl.pallas_call(
        functools.partial(_sann_scatter_kernel, bucket_cap=cap),
        grid=(L,),
        in_specs=[
            pl.BlockSpec((1, NB), lambda i: (i, 0)),
            pl.BlockSpec((E,), lambda i: (0,)),
            pl.BlockSpec((E,), lambda i: (0,)),
            pl.BlockSpec((E,), lambda i: (0,)),
            pl.BlockSpec((E,), lambda i: (0,)),
            pl.BlockSpec((E,), lambda i: (0,)),
            pl.BlockSpec((1, NB, cap), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, NB, cap), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((L, NB, cap), tables.dtype),
        input_output_aliases={6: 0},
        interpret=interpret,
    )(table_ptr, s_l, s_c, rank, val, mask, tables)
