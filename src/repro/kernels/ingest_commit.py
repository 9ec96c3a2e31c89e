"""Pallas ingest-commit kernels: the two-phase commit half (DESIGN.md §12).

  * ``swakde_segment_pass`` — the closed-form segment-reduce SumEH commit:
    one (row_block, segment_block) tile walk over the per-row sorted cell
    segments emitted by `core.swakde.swakde_prepare_chunk`, applying the
    Corollary-4.2 cascade per segment in one fused pass (no per-add loop).
    The tile math *is* `kernels.ref.swakde_segment_pass_ref` — the kernel
    loads a tile, runs the identical closed form, and stores it back, so
    CPU-oracle and Pallas paths are one implementation.  It runs only in
    interpret mode: the TPU compiler refuses it at real widths (SW-AKDE at
    L=64, W=1024, chunk 4096): its ``(1, bg)`` segment blocks on ``(R, G)``
    break the (8, 128) block rule, and with aligned ``(8, G)`` blocks the
    body fails next — Mosaic lowers no shape-changing ``take_along_axis``
    gather, and the pass materialises ``(rows, G, C)`` intermediates
    (128 MiB per 8 rows) that exceed VMEM.  Every backend runs the oracle.
  * ``sann_table_scatter`` — the ring append of `core.sann.sann_commit_chunk`,
    in place and on the TPU.  The oracle scatters into
    ``tables.reshape(-1)``, tiled (1024,) where the table is tiled
    (8, 128): XLA relayouts the whole table into the flat view in one while
    loop, scatters, and relayouts it back in a second — a table's worth of
    temporaries and most of the commit's time, to write a few thousand
    entries.  The kernel leaves the tables in HBM, aliased from input to
    output, and writes only the tiles the live entries land in: Mosaic
    moves whole (8, 128) HBM tiles (fewer rows for L ≤ 4), so each is
    read, patched in VMEM and written back by DMA.  Its cost grows with
    the entries, the oracle's with the tables: `append_in_place` picks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# Rows of the HBM tile an S-ANN table row lies in.  XLA tiles an int32
# (L, N) array (r, 128) with r = 8 for L > 4, else the next power of two,
# and pads both axes to whole tiles; a DMA window must be whole tiles.
def _tile_rows(L: int) -> int:
    return min(8, 1 << (L - 1).bit_length())


_TILE_COLS = 128


# Entries staged into SMEM at a time: the whole entry list would not fit
# (SMEM holds 1 MiB; a 4,096-point chunk at L = 16 is 65,536 entries).
_STAGE = 2048
# Tiles held in VMEM, and DMAs in flight, at a time.
_TILES = 256


def _sann_append_kernel(n_ref, row_hbm, col_hbm, val_hbm, tab_in_ref, tab_ref,
                        row_ref, col_ref, val_ref, key_ref, tiles_ref,
                        stage_sem, read_sem, write_sem, *, tile_rows: int,
                        tile_cols: int):
    """Write entry e's value at ``tab[row[e], col[e]]`` for every e < n with
    ``col[e] >= 0``, read-modify-writing the (tile_rows, 128) HBM tiles
    that hold them.

    Entries are staged into SMEM a block at a time and applied in
    segments.  A segment is a run of entries whose tiles (row group, tile
    column) ascend, so it names each tile once: all its tile reads are in
    flight together, then its entries are applied in VMEM, then all its
    writes are in flight together.  Entries sorted by (row, column) break
    a segment only where a row starts, or after `_TILES` tiles.  A segment
    ends before the next one reads, so no tile is read while a write to
    it is in flight.

    ``tab_in_ref`` is aliased onto ``tab_ref`` (input_output_aliases): the
    tables stay in HBM and nothing but the touched tiles moves.
    """
    del tab_in_ref
    TR, TC = tile_rows, _TILE_COLS
    n = n_ref[0]

    def tile_key(i):
        # Row group and tile column as one int; -1 for a dropped entry.
        col = col_ref[i]
        return jnp.where(col >= 0, (row_ref[i] // TR) * tile_cols + col // TC,
                         -1)

    def dma(slot, key, to_hbm):
        r0 = pl.multiple_of((key // tile_cols) * TR, TR)
        c0 = pl.multiple_of((key % tile_cols) * TC, TC)
        hbm = tab_ref.at[pl.ds(r0, TR), pl.ds(c0, TC)]
        vmem = tiles_ref.at[slot]
        if to_hbm:
            return pltpu.make_async_copy(vmem, hbm, write_sem)
        return pltpu.make_async_copy(hbm, vmem, read_sem)

    def wait_all(count, to_hbm):
        def body(_, c):
            dma(0, 0, to_hbm).wait()
            return c
        jax.lax.fori_loop(0, count, body, 0)

    def segment(i, m):
        def more(c):
            j, nt, last = c
            k = tile_key(jnp.minimum(j, _STAGE - 1))
            fits = (k <= last) | (nt < _TILES)
            return (j < m) & ((k < 0) | (k >= last)) & fits

        def read(c):
            j, nt, last = c
            k = tile_key(j)
            new = k > last

            @pl.when(new)
            def _():
                key_ref[nt] = k
                dma(nt, k, False).start()
            return j + 1, nt + new.astype(jnp.int32), jnp.maximum(k, last)

        end, nt, _ = jax.lax.while_loop(more, read, (i, 0, -1))
        wait_all(nt, False)

        def apply(j, c):
            t, last = c
            k = tile_key(j)
            t = t + (k > last).astype(jnp.int32)

            @pl.when(k >= 0)
            def _():
                tile = tiles_ref.at[t]
                ii = jax.lax.broadcasted_iota(jnp.int32, (TR, TC), 0)
                jj = jax.lax.broadcasted_iota(jnp.int32, (TR, TC), 1)
                hit = ((ii == row_ref[j] - (k // tile_cols) * TR)
                       & (jj == col_ref[j] - (k % tile_cols) * TC))
                tile[...] = jnp.where(hit, val_ref[j], tile[...])
            return t, jnp.maximum(k, last)

        jax.lax.fori_loop(i, end, apply, (-1, -1))

        def write(t, c):
            dma(t, key_ref[t], True).start()
            return c

        jax.lax.fori_loop(0, nt, write, 0)
        wait_all(nt, True)
        return end

    def block(b, c):
        start = pl.multiple_of(b * _STAGE, _STAGE)
        copies = [pltpu.make_async_copy(src.at[pl.ds(start, _STAGE)], dst,
                                        stage_sem.at[k])
                  for k, (src, dst) in enumerate(((row_hbm, row_ref),
                                                  (col_hbm, col_ref),
                                                  (val_hbm, val_ref)))]
        for cp in copies:
            cp.start()
        for cp in copies:
            cp.wait()
        m = jnp.minimum(n - start, _STAGE)
        jax.lax.while_loop(lambda i: i < m, lambda i: segment(i, m), 0)
        return c

    jax.lax.fori_loop(0, pl.cdiv(n, _STAGE), block, 0)


# Table entries that cost XLA's flat scatter what one append costs the
# in-place kernel.  On a v5e the scatter's relayout loops take ≈ 0.26 ns a
# table entry and the kernel ≈ 0.17 µs an append that has a tile of its
# own (PERF.md §6).
_TABLE_ENTRIES_PER_APPEND = 650


def append_in_place(n_entries: int, table_entries: int) -> bool:
    """Is the in-place append the cheaper for ``n_entries`` appends (all
    live, each in a tile of its own) into tables of ``table_entries``?"""
    return n_entries * _TABLE_ENTRIES_PER_APPEND <= table_entries


@functools.partial(jax.jit, static_argnames=("interpret",))
def sann_table_scatter(tables: jax.Array, table_ptr: jax.Array,
                       s_l: jax.Array, s_c: jax.Array, rank: jax.Array,
                       val: jax.Array, mask: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """Sorted-segment ring append (see `ref.sann_table_scatter_ref`) into
    flat-per-row ``tables (L, n_buckets * bucket_cap)``, in place: the
    entries up to the last masked one are patched into their HBM tiles
    (`_sann_append_kernel`).  The cost is the live entries', not the
    tables'."""
    interpret = resolve_interpret(interpret)
    L, NB = table_ptr.shape
    N = tables.shape[1]
    cap = N // NB
    E = s_l.shape[0]
    if E == 0:
        return tables
    ring_pos = (table_ptr[s_l, s_c] + rank) % cap
    col = jnp.where(mask, s_c * cap + ring_pos, -1)
    # Loop bound: one past the last masked entry (the live prefix).
    n_live = jnp.max(jnp.where(mask, jnp.arange(1, E + 1, dtype=jnp.int32),
                               0), keepdims=True)
    pad = -E % _STAGE
    row, col, val = (jnp.pad(a, (0, pad), constant_values=-1)
                     for a in (s_l, col, val))
    TR = _tile_rows(L)
    if interpret:
        # The interpreter clamps a window that runs past the array; the
        # chip's HBM holds whole tiles.  Pad to whole tiles to match.
        tables = jnp.pad(tables, ((0, -L % TR), (0, -N % _TILE_COLS)))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    stage = pltpu.SMEM((_STAGE,), jnp.int32)
    out = pl.pallas_call(
        functools.partial(_sann_append_kernel, tile_rows=TR,
                          tile_cols=pl.cdiv(N, _TILE_COLS)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[any_spec] * 4, out_specs=any_spec,
            scratch_shapes=[stage, stage, stage,
                            pltpu.SMEM((_TILES,), jnp.int32),
                            pltpu.VMEM((_TILES, TR, _TILE_COLS), tables.dtype),
                            pltpu.SemaphoreType.DMA((3,)),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(tables.shape, tables.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
    )(n_live, row, col, val, tables)
    return out[:L, :N] if interpret else out
