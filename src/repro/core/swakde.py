"""SW-AKDE — Sliding-Window Approximate KDE (paper §4, Algorithm 2).

A RACE grid in which **every cell is an Exponential Histogram**: cell
(i, h_i(x)) records a 1 at the arrival timestep, and a query reads the EH
estimate of "how many increments in the last N steps".  The estimator is the
row *average* (the paper uses the average for SW-AKDE, not median-of-means).

Guarantees (paper Thm 4.1): with EH relative error eps', the estimate is a
(1±eps) multiplicative KDE approximation, eps = 2*eps' + eps'^2, using
O(R*W * (1/(sqrt(1+eps)-1)) * log^2 N) space.

State layout (DESIGN.md §5.3): the EH grid is a single pytree of dense
arrays ``ts: (L, W, levels, slots)``, ``num: (L, W, levels)``; one stream
step touches L cells (one per row) via gather → vmapped eh_add → scatter.
Batch updates (Corollary 4.2) use SumEH cells instead.

Ingest paths:
  * ``swakde_update`` / ``swakde_stream`` — per-point reference semantics
    (one `lax.scan` step per stream element, each step scattering into the
    full EH grid);
  * ``swakde_update_chunk`` / ``swakde_stream_batched`` — the batched-update
    contract: one hash matmul per chunk, then per row the chunk's codes are
    sorted into per-cell segments and each hit cell folds its own adds (own
    timestamps, stream order) in closed-form segment-reduce passes
    (DESIGN.md §12).  The grid is read and written **once per chunk**
    instead of once per point, and the result is bit-identical to the
    per-point path (tests/test_batched_ingest.py).
  * ``swakde_prepare_chunk`` / ``swakde_commit_chunk`` — the two-phase form
    of the same contract (DESIGN.md §10): prepare is the pure hash + sort
    half (timestamps as chunk-relative offsets), commit the closed-form
    segment fold (`kernels.ops.swakde_segment_pass`, optionally capped via
    ``heavy_cell_cap``); ``swakde_update_chunk`` is their composition, and
    the serving engine overlaps prepare of chunk k+1 with commit of chunk k.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import lsh
from .eh import (
    EHConfig, EHState, eh_add, eh_init, eh_merge, eh_query, eh_query_cells,
    SumEHConfig, SumEHState, sum_eh_add, sum_eh_init, sum_eh_query,
)
from .util import saturating_add
from repro.kernels import ops as kernel_ops


@dataclasses.dataclass(frozen=True)
class SWAKDEConfig:
    L: int               # rows (repetitions R in the paper's space bound)
    W: int               # LSH range (bucket count after rehash)
    window: int          # N
    eh_eps: float        # eps' — EH relative error
    heavy_cell_cap: int = 0
    """Skew guard for the chunked commit (DESIGN.md §12): bound how many
    adds one (row, cell) segment may absorb per closed-form commit pass.
    0 = uncapped (a pass still splits only at EH-expiry boundaries).  The
    result is bit-identical for every value — capping only splits a
    segment's pass into shorter sub-chunk passes — so this is purely a
    per-tile work bound for the Pallas kernels."""

    @property
    def kde_eps(self) -> float:
        """Paper Lemma 4.3: eps = 2*eps' + eps'^2."""
        return 2 * self.eh_eps + self.eh_eps**2

    def eh_config(self) -> EHConfig:
        return EHConfig.create(self.window, self.eh_eps)


class SWAKDEState(NamedTuple):
    ts: jax.Array     # (L, W, levels, slots) int32
    num: jax.Array    # (L, W, levels) int32
    t: jax.Array      # () int32 current timestep, saturating (core.util)


def swakde_init(cfg: SWAKDEConfig) -> SWAKDEState:
    """Empty sketch: ``ts (L, W, levels, slots) int32`` (-1 = empty bucket),
    ``num (L, W, levels) int32``, ``t () int32``."""
    eh = cfg.eh_config()
    return SWAKDEState(
        ts=jnp.full((cfg.L, cfg.W, eh.levels, eh.slots), -1, jnp.int32),
        num=jnp.zeros((cfg.L, cfg.W, eh.levels), jnp.int32),
        t=jnp.zeros((), jnp.int32),
    )


def swakde_update(state: SWAKDEState, params, x: jax.Array, cfg: SWAKDEConfig) -> SWAKDEState:
    """One stream element ``x (d,) float32``: hash with L rows, `eh_add` the
    L hit cells at timestep ``t``.  Per-point reference path; the production
    chunked path `swakde_update_chunk` is bit-identical."""
    eh = cfg.eh_config()
    codes = lsh.hash_points(params, x)                      # (L,)
    rows = jnp.arange(cfg.L)
    cell = EHState(ts=state.ts[rows, codes], num=state.num[rows, codes])
    new_cell = jax.vmap(lambda s: eh_add(s, state.t, eh))(cell)
    return SWAKDEState(
        ts=state.ts.at[rows, codes].set(new_cell.ts),
        num=state.num.at[rows, codes].set(new_cell.num),
        t=saturating_add(state.t, 1),
    )


def swakde_stream(state: SWAKDEState, params, xs: jax.Array, cfg: SWAKDEConfig) -> SWAKDEState:
    """Scan a stream of points (T, d) through the sketch, one step per point."""

    def step(s, x):
        return swakde_update(s, params, x, cfg), None

    state, _ = jax.lax.scan(step, state, xs)
    return state


class SWAKDEPrep(NamedTuple):
    """Pure per-chunk precomputation (the *prepare* phase of the two-phase
    ingest contract, DESIGN.md §10): the hash matmul plus the per-row
    sort-into-cell-segments structure.  Depends only on (params, chunk) —
    never on sketch state — so preparing chunk k+1 can overlap committing
    chunk k.  Per-add timestamps are stored as *offsets* within the chunk
    (the stable-sort order); the commit rebases them on the state clock."""
    order: jax.Array      # (L, C) int32 — per-row stable sort order of codes
    seg_code: jax.Array   # (L, SW) int32 — cell code per segment (W = pad)
    seg_len: jax.Array    # (L, SW) int32 — points hitting each segment
    seg_first: jax.Array  # (L, SW) int32 — first sorted position of segment


def segment_width(C: int, cfg: SWAKDEConfig,
                  code_range: Optional[int]) -> int:
    """Segments per row of a prepared ``C``-point chunk: the most distinct
    cells one row can hit, ``min(C, W)``, further bounded by the hash's
    reachable codes (`lsh.code_range`: 2^k for SRP) when known."""
    return min(C, cfg.W) if code_range is None else min(C, cfg.W, code_range)


def swakde_prepare_chunk(params, xs: jax.Array, cfg: SWAKDEConfig,
                         mask: Optional[jax.Array] = None) -> SWAKDEPrep:
    """Prepare phase for ``xs (C, d)``: one hash matmul, then per row a
    stable sort of the chunk's codes into `segment_width` cell segments
    (each hit cell's points form a contiguous run in stream order).  All of
    it is state-independent — the embarrassingly parallel half of an update.

    ``mask`` (optional, (C,) bool) drops rows from the chunk: masked-out
    rows are hashed to the sentinel code ``W`` — they sort last, land in a
    zero-length sentinel segment, and never touch the grid.  For the result
    to be bit-identical to preparing the compacted chunk, the live rows
    must form a **prefix** (``mask = arange(C) < count``): the stable sort
    then assigns live rows exactly the offsets the unpadded chunk would
    get.  This is the tenant-fleet padding contract (`core.fleet`); pair it
    with ``swakde_commit_chunk(..., count=count)``."""
    return swakde_prepare_from_codes(lsh.hash_points(params, xs), cfg,
                                     lsh.code_range(params), mask)


def swakde_prepare_from_codes(codes: jax.Array, cfg: SWAKDEConfig,
                              code_range: Optional[int],
                              mask: Optional[jax.Array] = None) -> SWAKDEPrep:
    """`swakde_prepare_chunk` with the hash codes ``(C, L)`` supplied by the
    caller — the sort-into-segments half alone.  The tenant-routed fleet
    ingest (`core.fleet`) hashes one mixed multi-tenant chunk with the
    shared params in a single matmul and feeds each tenant's routed code
    block through this entry point.

    ``code_range`` (static) bounds the distinct codes a row can hold
    (`lsh.code_range` of the params that made ``codes``); None leaves the
    segment axis at ``min(C, W)``.  A masked chunk's sentinel segment may
    then fall one past the axis and be dropped, which is what it needs."""
    C = codes.shape[0]
    SW = segment_width(C, cfg, code_range)   # max distinct cells hit per row
    if mask is not None:
        codes = jnp.where(mask[:, None], codes, jnp.int32(cfg.W))
    pos = jnp.arange(C, dtype=jnp.int32)

    def row_prep(codes_l):
        order = jnp.argsort(codes_l, stable=True)
        sc = codes_l[order]
        is_start = jnp.concatenate([jnp.ones((1,), bool), sc[1:] != sc[:-1]])
        seg_id = jnp.cumsum(is_start).astype(jnp.int32) - 1   # (C,) ≤ SW
        seg_len = jnp.zeros((SW,), jnp.int32).at[seg_id].add(1, mode="drop")
        seg_code = jnp.full((SW,), cfg.W, jnp.int32).at[seg_id].set(
            sc, mode="drop")
        seg_first = jnp.full((SW,), C, jnp.int32).at[seg_id].min(
            pos, mode="drop")
        # Sentinel segments (unused slots *and* the masked-row segment)
        # carry code W and must stay empty so the commit never drains them;
        # real codes are < W, so this is a no-op without a mask.  Only the
        # masked-row segment can get id SW (all SW live codes present), and
        # the "drop" scatters above leave it out.
        seg_len = jnp.where(seg_code == cfg.W, 0, seg_len)
        return order.astype(jnp.int32), seg_code, seg_len, seg_first

    order, seg_code, seg_len, seg_first = jax.vmap(row_prep)(codes.T)
    return SWAKDEPrep(order=order, seg_code=seg_code, seg_len=seg_len,
                      seg_first=seg_first)


def swakde_commit_chunk(state: SWAKDEState, prep: SWAKDEPrep,
                        cfg: SWAKDEConfig,
                        count: Optional[jax.Array] = None) -> SWAKDEState:
    """Commit phase: fold a prepared chunk into the EH grid — the
    state-sequential half, as closed-form segment-reduce passes
    (`kernels.ops.swakde_segment_pass`, DESIGN.md §12) instead of a
    per-add replay.  Per pass, every hit (row, cell) segment absorbs its
    longest expiry-free (and, with ``cfg.heavy_cell_cap``, capped) prefix
    of remaining adds in one Corollary-4.2 cascade settle; the outer while
    loop runs until all segments are drained — O(max splits) iterations,
    not O(max per-cell hit count).  Bit-identical to the per-point path
    (tests/test_batched_ingest.py, tests/test_two_phase.py), including
    dead ring slots.  The (L, W, levels, slots) grid is still read and
    written once per chunk.

    ``count`` (optional, traced) overrides the clock advance: the chunk
    counts as ``count`` stream steps instead of its static row count C.
    Pair it with a prefix ``mask`` on `swakde_prepare_chunk` — masked
    chunks fold only their live prefix, and the clock must advance by the
    live count (the tenant-fleet padding contract, `core.fleet`)."""
    return swakde_commit_chunk_passes(state, prep, cfg, count)[0]


def swakde_commit_chunk_passes(
        state: SWAKDEState, prep: SWAKDEPrep, cfg: SWAKDEConfig,
        count: Optional[jax.Array] = None) -> tuple[SWAKDEState, jax.Array]:
    """`swakde_commit_chunk`, also returning the number of segment passes
    its while loop ran (int32 scalar; 1 when no segment splits)."""
    eh = cfg.eh_config()
    C = prep.order.shape[1]

    # Per-add timestamps in sorted-segment order; saturating like the
    # per-point path's t counter.
    sorted_ts = saturating_add(state.t, prep.order)          # (L, C)
    gcode = jnp.minimum(prep.seg_code, cfg.W - 1)            # clamp padding
    rows = jnp.arange(cfg.L)[:, None]
    cell_ts = state.ts[rows, gcode]                          # (L, SW, lv, S)
    cell_num = state.num[rows, gcode]                        # (L, SW, lv)
    done = jnp.zeros_like(prep.seg_len)

    def cond(carry):
        return (carry[2] < prep.seg_len).any()

    def body(carry):
        cts, cnum, dn, n = carry
        return (*kernel_ops.swakde_segment_pass(
            cts, cnum, dn, sorted_ts, prep.seg_first, prep.seg_len,
            window=cfg.window, maxb=eh.max_buckets_per_level,
            n_levels=eh.levels, cap=cfg.heavy_cell_cap), n + 1)

    cell_ts, cell_num, _, passes = lax.while_loop(
        cond, body, (cell_ts, cell_num, done, jnp.int32(0)))
    ts = state.ts.at[rows, prep.seg_code].set(cell_ts, mode="drop")
    num = state.num.at[rows, prep.seg_code].set(cell_num, mode="drop")
    return SWAKDEState(ts=ts, num=num, t=saturating_add(
        state.t, C if count is None else count)), passes


def swakde_update_chunk(state: SWAKDEState, params, xs: jax.Array,
                        cfg: SWAKDEConfig) -> SWAKDEState:
    """Consume a whole chunk ``xs (C, d)`` in one step, bit-identical to C
    calls of ``swakde_update``.

    Composition of `swakde_prepare_chunk` (hash + sort-into-segments, pure)
    and `swakde_commit_chunk` (EH replay, sequential) — the same ops, fused
    under one jit when called directly.
    """
    return swakde_commit_chunk(state, swakde_prepare_chunk(params, xs, cfg),
                               cfg)


def swakde_stream_batched(state: SWAKDEState, params, xs: jax.Array,
                          cfg: SWAKDEConfig, chunk: int = 1024) -> SWAKDEState:
    """Stream (T, d) points through ``swakde_update_chunk`` in fixed chunks —
    same final state as ``swakde_stream``, O(T / chunk) XLA steps."""
    T = xs.shape[0]
    n_full = T // chunk
    if n_full:
        def step(s, c):
            return swakde_update_chunk(s, params, c, cfg), None
        state, _ = lax.scan(
            step, state, xs[: n_full * chunk].reshape(n_full, chunk, -1))
    if T % chunk:
        state = swakde_update_chunk(state, params, xs[n_full * chunk:], cfg)
    return state


def swakde_row_estimates(state: SWAKDEState, params, q: jax.Array,
                         cfg: SWAKDEConfig) -> jax.Array:
    """Per-row EH window counts at ``q (d,) float32`` → (L,) float32.

    One gather + vmapped `eh_query` over the L hit cells.  Shared by
    `swakde_query` and the sharded query path
    (`repro.parallel.sketch_sharding.sharded_swakde_query_batch`), which
    all-gathers each shard's rows and applies the same mean — making the
    sharded estimate bit-identical to the single-device one."""
    eh = cfg.eh_config()
    codes = lsh.hash_points(params, q)
    rows = jnp.arange(cfg.L)
    cell = EHState(ts=state.ts[rows, codes], num=state.num[rows, codes])
    return jax.vmap(lambda s: eh_query(s, state.t - 1, eh))(cell)


def swakde_grid_estimates(state: SWAKDEState, cfg: SWAKDEConfig) -> jax.Array:
    """EH window counts of **every** cell in the grid → (L, W) float32.

    One vectorised `eh_query_cells` pass over the (L, W) grid at the query
    clock ``t - 1``.  O(L·W·levels·slots) regardless of the batch size —
    once B ≥ W this is cheaper than reading B·L cells, and the per-cell
    arithmetic is identical to `eh_query`, so estimates read from this
    table are bit-identical to the per-query path."""
    return eh_query_cells(state.ts, state.num, state.t - 1, cfg.eh_config())


def swakde_row_estimates_batch(state: SWAKDEState, params, qs: jax.Array,
                               cfg: SWAKDEConfig) -> jax.Array:
    """Batched per-row EH window counts: ``qs (B, d)`` → (B, L) float32.

    The fused read path: one hash matmul for the whole batch, then either

      * B ≥ W — precompute the full (L, W) estimate table
        (`swakde_grid_estimates`, O(L·W) cell queries) and gather (B, L)
        entries, or
      * B < W — gather the (B, L) hit cells once and run one batched
        `eh_query_cells` over them (O(B·L) cell queries);

    both branches are bit-identical to vmapping `swakde_row_estimates`
    over the batch (tests/test_query_batched.py checks each).  Shared by
    `swakde_query_batch` and the sharded query path
    (`repro.parallel.sketch_sharding.sharded_swakde_query_batch`)."""
    codes = lsh.hash_points(params, qs)                 # (B, L) — one matmul
    rows = jnp.arange(cfg.L)[None, :]
    if qs.shape[0] >= cfg.W:
        grid = swakde_grid_estimates(state, cfg)
        if cfg.W <= 256:
            # Read the table through a one-hot contraction rather than a
            # gather: XLA (CPU at least) fuses a gather into its producer
            # and recomputes the cell queries per (b, l) read — the O(B·L)
            # cell work this branch exists to avoid — while a dot forces
            # the (L, W) table to materialise once.  Exact: each one-hot
            # row has a single 1, so the contraction *is* the gather.
            onehot = (codes[..., None] == jnp.arange(cfg.W)).astype(
                grid.dtype)                             # (B, L, W)
            return jnp.einsum("lw,blw->bl", grid, onehot)
        # Large-W fallback: the B·L·W one-hot would dominate; a fusion
        # barrier still keeps the per-read recompute mostly at bay.
        grid = lax.optimization_barrier(grid)
        return grid[rows, codes]
    cell_ts = state.ts[rows, codes]                     # (B, L, levels, slots)
    cell_num = state.num[rows, codes]                   # (B, L, levels)
    return eh_query_cells(cell_ts, cell_num, state.t - 1, cfg.eh_config())


def swakde_row_estimates_from_grid(grid: jax.Array, params, qs: jax.Array,
                                   cfg: SWAKDEConfig) -> jax.Array:
    """Read batched per-row window counts from a precomputed estimate table:
    ``grid (L, W)`` (from `swakde_grid_estimates`), ``qs (B, d)`` → (B, L).

    One hash matmul + one gather — no EH arithmetic at all.  Because the
    grid is pure given (state, t) and per-cell arithmetic matches
    `eh_query` exactly, reads are bit-identical to
    `swakde_row_estimates_batch` on the state the grid was built from.
    This is the query-side snapshot-cache path (`repro.serve.engine`): the
    serving layer caches the grid per committed state (invalidated on
    commit), so B < W query batches hit the table too."""
    codes = lsh.hash_points(params, qs)                 # (B, L)
    return grid[jnp.arange(cfg.L)[None, :], codes]


def swakde_query_from_grid(grid: jax.Array, params, qs: jax.Array,
                           cfg: SWAKDEConfig) -> jax.Array:
    """Batched Ŷ estimates served from a cached grid: ``qs (B, d)`` → (B,)
    float32, bit-identical to `swakde_query_batch` on the grid's state."""
    return swakde_row_estimates_from_grid(grid, params, qs, cfg).mean(-1)


def swakde_query(state: SWAKDEState, params, q: jax.Array, cfg: SWAKDEConfig) -> jax.Array:
    """Average of the L EH estimates — the paper's SW-AKDE estimator Ŷ.

    ``q (d,) float32`` → () float32 (unnormalised window density)."""
    return swakde_row_estimates(state, params, q, cfg).mean()


def swakde_query_batch(state: SWAKDEState, params, qs: jax.Array, cfg: SWAKDEConfig):
    """Fused batch queries: ``qs (B, d) float32`` → (B,) float32.

    One hash matmul + one row gather for the whole batch
    (`swakde_row_estimates_batch`) instead of a vmap over the per-query
    pipeline; estimates are bit-identical to vmapping `swakde_query`."""
    return swakde_row_estimates_batch(state, params, qs, cfg).mean(-1)


def swakde_merge(a: SWAKDEState, b: SWAKDEState, cfg: SWAKDEConfig) -> SWAKDEState:
    """Combine two sketches built (with identical params and a shared clock)
    over different sub-streams — e.g. two ingest workers splitting one
    logical stream.

    Cell-wise exact EH bucket-union merge (`core.eh.eh_merge`): every cell's
    merged estimate counts the window hits of *both* sub-streams, so the
    merged Ŷ ≈ Ŷ_a + Ŷ_b with the standard mergeable-summaries error
    accumulation (eps' per input sketch).  Commutative bit-exactly; total
    bucket mass is preserved exactly, but the bucket *structure* after
    ``merge(merge(a,b),c)`` vs ``merge(a,merge(b,c))`` may differ by one
    cascade level, so associativity holds at the estimate level (within the
    EH error bound), not bitwise — see docs/DESIGN.md §8.3."""
    eh = cfg.eh_config()
    t = jnp.maximum(a.t, b.t)
    shape = a.ts.shape                                  # (L, W, levels, slots)
    flat = lambda x: x.reshape((-1,) + x.shape[2:])

    def cell(ts_a, num_a, ts_b, num_b):
        # Expire at the *query* clock t - 1 (every query path reads
        # eh_query(state, t - 1)): expiring at t would drop the boundary
        # bucket stamped exactly t - window that queries still count.
        m = eh_merge(EHState(ts_a, num_a), EHState(ts_b, num_b), t - 1, eh)
        return m.ts, m.num

    ts, num = jax.vmap(cell)(flat(a.ts), flat(a.num), flat(b.ts), flat(b.num))
    return SWAKDEState(ts=ts.reshape(shape), num=num.reshape(shape[:3]), t=t)


def swakde_kde(state: SWAKDEState, params, q: jax.Array, cfg: SWAKDEConfig) -> jax.Array:
    """Normalised sliding-window density: Ŷ / min(t, N)."""
    denom = jnp.minimum(state.t, cfg.window).astype(jnp.float32)
    return swakde_query(state, params, q, cfg) / jnp.maximum(denom, 1.0)


def swakde_bytes(cfg: SWAKDEConfig) -> int:
    """Concrete sketch footprint (for the §4 space-bound benchmarks)."""
    eh = cfg.eh_config()
    return cfg.L * cfg.W * (eh.levels * eh.slots * 8 + eh.levels * 4) + 8


# ---------------------------------------------------------------------------
# Batch-update variant (Corollary 4.2): window = last N *batches*
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BatchSWAKDEConfig:
    L: int
    W: int
    window: int        # N batches
    eh_eps: float
    batch_size: int    # R

    def eh_config(self) -> SumEHConfig:
        return SumEHConfig.create(self.window, self.eh_eps, self.batch_size)


class BatchSWAKDEState(NamedTuple):
    ts: jax.Array     # (L, W, levels, slots) int32
    num: jax.Array    # (L, W, levels) int32
    t: jax.Array      # () int32 — batch timestep


def batch_swakde_init(cfg: BatchSWAKDEConfig) -> BatchSWAKDEState:
    eh = cfg.eh_config().base
    return BatchSWAKDEState(
        ts=jnp.full((cfg.L, cfg.W, eh.levels, eh.slots), -1, jnp.int32),
        num=jnp.zeros((cfg.L, cfg.W, eh.levels), jnp.int32),
        t=jnp.zeros((), jnp.int32),
    )


def batch_swakde_update(
    state: BatchSWAKDEState, params, batch: jax.Array, cfg: BatchSWAKDEConfig
) -> BatchSWAKDEState:
    """One *batch* arrives at one timestep: each cell's increment is the
    number of batch elements hashing to it (0..R)."""
    eh = cfg.eh_config()
    codes = lsh.hash_points(params, batch)                # (R, L)
    incr = kernel_ops.race_hist(codes, cfg.W)             # (L, W)

    def upd_cell(ts, num, v):
        s = sum_eh_add(SumEHState(ts, num), state.t, v, eh)
        return s.ts, s.num

    ts, num = jax.vmap(jax.vmap(upd_cell))(state.ts, state.num, incr)
    return BatchSWAKDEState(ts=ts, num=num, t=saturating_add(state.t, 1))


def batch_swakde_query(
    state: BatchSWAKDEState, params, q: jax.Array, cfg: BatchSWAKDEConfig
) -> jax.Array:
    eh = cfg.eh_config()
    codes = lsh.hash_points(params, q)
    rows = jnp.arange(cfg.L)
    cell = SumEHState(state.ts[rows, codes], state.num[rows, codes])
    vals = jax.vmap(lambda s: sum_eh_query(s, state.t - 1, eh))(cell)
    return vals.mean()
