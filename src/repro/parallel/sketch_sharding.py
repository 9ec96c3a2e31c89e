"""Multi-device sharded ingest & query for the three sketches (shard_map).

All three sketches are mergeable histograms, which is exactly what makes
them shardable (the property RACE [CS20] exploits for distributed
sketching, and the batch/Turnstile model of the paper's Corollary 4.2
assumes for partitioned updates):

  * **RACE** — ``counts (L, W)`` sharded along the **L (rows)** axis.  Each
    device hashes the chunk with its own row block of the LSH params and
    updates its rows; a query all-gathers the per-row counter reads and
    applies `core.race.estimate_from_vals` — the psum-style combine is the
    row mean, and the result is *bit-identical* to single-device.
  * **SW-AKDE** — the EH grid ``(L, W, levels, slots)`` sharded along L,
    same scheme: rows are untouched by sharding, queries all-gather the
    per-row EH estimates (`core.swakde.swakde_row_estimates`).  Cross-
    *worker* combine (different sub-streams, same clock) is the exact EH
    merge `core.swakde.swakde_merge`.
  * **S-ANN** — sharded **by table**: ``tables (L, n_buckets * cap)`` and
    ``table_ptr`` split along L; the point store / valid mask / counters
    are replicated (every device makes the same keep decisions from the
    same key, so the replicas stay bit-identical by construction).  Queries
    either all-gather the candidate blocks and reuse the single-device
    truncate-and-score (`sharded_sann_query_batch`, bit-identical), or
    merge per-shard top-ks (`sharded_sann_query_topk_batch` — exact, since
    the global top-k is contained in the union of per-shard top-ks).

Every sharded ingest entry point reuses the PR-1 batched kernel per shard
(`race_update_batch`, `swakde_update_chunk`, `sann_insert_batch`): the
per-shard computation *is* the single-device computation on a row/table
block, so sharded state equals single-device state block-for-block
(tests/test_distributed.py asserts this bitwise on 8 host devices).

The two-phase ingest contract (DESIGN.md §10) is sharded the same way:
``sharded_*_prepare_chunk`` runs the pure prepare per shard (prep pytrees
stay row/table-sharded; S-ANN's keep decisions are replicated by
construction) and ``sharded_*_commit_chunk`` folds them in —
``sharded_commit(sharded_prepare(...))`` is bit-identical to the fused
sharded ingest call, which is what lets `repro.serve.engine` overlap
preparing chunk k+1 with committing chunk k on a mesh too.

The mesh is a 1-D ``("shard",)`` mesh built with the existing
`ShardingCtx`/`make_ctx` machinery (`make_sketch_ctx`); ``ctx.mesh is
None`` short-circuits every function here back to the plain single-device
call, so services can hold one code path.

Works on any backend; CPU CI forces 8 host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding

from repro.core import fleet, lsh, race, sann, swakde
from repro.core.race import race_merge  # noqa: F401  (re-export: merge API)
from repro.core.sann import sann_merge  # noqa: F401  (re-export)
from repro.core.swakde import swakde_merge  # noqa: F401  (re-export)

from .sharding import ShardingCtx, make_ctx

SHARD_AXIS = "shard"

def _smap(fn, mesh, in_specs, out_specs):
    # Replication checking is disabled: outputs marked P() are replicated by
    # construction (identical per-device computation), which the static
    # checker cannot see through all_gather + gather chains.
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Mesh / ctx / placement helpers
# ---------------------------------------------------------------------------

def make_sketch_mesh(num_shards: int) -> Mesh:
    """1-D ``("shard",)`` mesh over the first ``num_shards`` local devices."""
    devs = jax.devices()
    if len(devs) < num_shards:
        raise ValueError(
            f"num_shards={num_shards} but only {len(devs)} devices visible "
            "(CPU CI: set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.asarray(devs[:num_shards]).reshape(num_shards),
                (SHARD_AXIS,))


def make_sketch_ctx(mesh: Optional[Mesh]) -> ShardingCtx:
    """ShardingCtx over ``mesh`` with the sketch logical-axis rules
    (``sketch_rows``/``sketch_tables`` → the "shard" mesh axis).

    A mesh without a "shard" axis would silently replicate everything and
    crash later inside ingest — reject it up front."""
    if mesh is not None and SHARD_AXIS not in mesh.axis_names:
        raise ValueError(
            f"sketch sharding needs a ('{SHARD_AXIS}',) mesh axis, got "
            f"{mesh.axis_names} (build one with make_sketch_mesh)")
    return make_ctx(mesh)


def make_service_ctx(mesh: Optional[Mesh], num_shards: int) -> ShardingCtx:
    """The services' config contract: an explicit ``mesh`` wins, else
    ``num_shards > 1`` builds one, else single-device (``ctx.mesh=None``)."""
    if mesh is None and num_shards > 1:
        mesh = make_sketch_mesh(num_shards)
    return make_sketch_ctx(mesh)


def ctx_num_shards(ctx: ShardingCtx) -> int:
    """Shard count of a sketch ctx (1 for the single-device path)."""
    return 1 if ctx.mesh is None else ctx.mesh.shape[SHARD_AXIS]


_num_shards = ctx_num_shards


def _check_rows(L: int, n: int, what: str) -> int:
    if L % n:
        raise ValueError(f"{what}: L={L} not divisible by num_shards={n}")
    return L // n


def _local_params(params, L_local: int):
    """Rebind the static row count on a row-block of LSH params (the arrays
    arrive already sliced by shard_map; only the static L must follow)."""
    return dataclasses.replace(params, L=L_local)


def _param_specs(params, ctx: ShardingCtx):
    """Spec pytree for LSH params, row-sharded: ``proj (d, L*k)`` splits its
    column axis (L-major, so contiguous blocks are whole rows), ``bias
    (L*k,)`` and ``mix (L, k)`` split their L axis."""
    col = ctx.spec(None, "sketch_rows")
    row = ctx.spec("sketch_rows")
    rowk = ctx.spec("sketch_rows", None)
    if isinstance(params, lsh.SRPParams):
        return dataclasses.replace(params, proj=col, mix=rowk)
    return dataclasses.replace(params, proj=col, bias=row, mix=rowk)


def _put(tree, spec_tree, mesh: Mesh):
    """device_put ``tree`` with a matching pytree of PartitionSpecs."""
    leaves, treedef = jax.tree.flatten(tree)
    specs = treedef.flatten_up_to(spec_tree)
    return treedef.unflatten(
        jax.device_put(x, NamedSharding(mesh, s))
        for x, s in zip(leaves, specs))


def _race_state_specs(ctx: ShardingCtx):
    return race.RACEState(counts=ctx.spec("sketch_rows", None), n=ctx.spec())


def _swakde_state_specs(ctx: ShardingCtx):
    return swakde.SWAKDEState(
        ts=ctx.spec("sketch_rows", None, None, None),
        num=ctx.spec("sketch_rows", None, None),
        t=ctx.spec())


def _sann_state_specs(ctx: ShardingCtx):
    r = ctx.spec()
    return sann.SANNState(
        points=r, valid=r, write_ptr=r, n_seen=r, n_stored=r,
        tables=ctx.spec("sketch_tables", None),
        table_ptr=ctx.spec("sketch_tables", None),
        stamps=r)


def shard_race(state: race.RACEState, params, ctx: ShardingCtx):
    """Place a RACE sketch onto the mesh (rows split, ``n`` replicated)."""
    return (_put(state, _race_state_specs(ctx), ctx.mesh),
            _put(params, _param_specs(params, ctx), ctx.mesh))


def shard_swakde(state: swakde.SWAKDEState, params, ctx: ShardingCtx):
    """Place an SW-AKDE sketch onto the mesh (rows split, ``t`` replicated)."""
    return (_put(state, _swakde_state_specs(ctx), ctx.mesh),
            _put(params, _param_specs(params, ctx), ctx.mesh))


def shard_sann(state: sann.SANNState, params, ctx: ShardingCtx):
    """Place an S-ANN sketch onto the mesh (tables split, points/counters
    replicated)."""
    return (_put(state, _sann_state_specs(ctx), ctx.mesh),
            _put(params, _param_specs(params, ctx), ctx.mesh))


# ---------------------------------------------------------------------------
# RACE
# ---------------------------------------------------------------------------

def sharded_race_update_batch(state: race.RACEState, params, xs: jax.Array,
                              ctx: ShardingCtx, sign: int = 1) -> race.RACEState:
    """Sharded turnstile batch update: ``xs (B, d)`` replicated to every
    device, each device running `race_update_batch` on its row block.
    Counters are bit-identical to the single-device call."""
    if ctx.mesh is None:
        return race.race_update_batch(state, params, xs, sign)
    Lsh = _check_rows(params.L, _num_shards(ctx), "RACE")

    def body(st, p, xs):
        return race.race_update_batch(st, _local_params(p, Lsh), xs, sign)

    return _smap(
        body, ctx.mesh,
        in_specs=(_race_state_specs(ctx), _param_specs(params, ctx),
                  ctx.spec()),
        out_specs=_race_state_specs(ctx))(state, params, xs)


def _race_prep_specs(ctx: ShardingCtx):
    return race.RACEPrep(hist=ctx.spec("sketch_rows", None), count=ctx.spec())


def sharded_race_prepare_chunk(params, xs: jax.Array, n_buckets: int,
                               ctx: ShardingCtx) -> race.RACEPrep:
    """Sharded prepare phase: each device hashes the (replicated) chunk with
    its row block of the LSH params and histograms its own rows — pure, no
    state input, so it can run ahead of pending commits.  The prep pytree
    stays row-sharded, ready for `sharded_race_commit_chunk`."""
    if ctx.mesh is None:
        return race.race_prepare_chunk(params, xs, n_buckets)
    Lsh = _check_rows(params.L, _num_shards(ctx), "RACE")

    def body(p, xs):
        return race.race_prepare_chunk(_local_params(p, Lsh), xs, n_buckets)

    return _smap(
        body, ctx.mesh,
        in_specs=(_param_specs(params, ctx), ctx.spec()),
        out_specs=_race_prep_specs(ctx))(params, xs)


def sharded_race_commit_chunk(state: race.RACEState, prep: race.RACEPrep,
                              ctx: ShardingCtx, sign: int = 1) -> race.RACEState:
    """Sharded commit phase: fold a row-sharded prep into the row-sharded
    counters.  ``sharded_commit(sharded_prepare(...))`` is bit-identical to
    `sharded_race_update_batch` (same per-shard ops)."""
    if ctx.mesh is None:
        return race.race_commit_chunk(state, prep, sign)

    def body(st, pr):
        return race.race_commit_chunk(st, pr, sign)

    return _smap(
        body, ctx.mesh,
        in_specs=(_race_state_specs(ctx), _race_prep_specs(ctx)),
        out_specs=_race_state_specs(ctx))(state, prep)


def sharded_race_query_batch(state: race.RACEState, params, qs: jax.Array,
                             ctx: ShardingCtx,
                             median_of_means: int = 0) -> jax.Array:
    """Sharded batched query ``qs (B, d)`` → (B,) float32.

    Each device runs the fused batched read (`race.race_row_reads` — one
    hash matmul + one gather) on its row block, the (B, L_local) blocks are
    all-gathered into the full (B, L) value matrix in row order, and the
    single-device reduction `estimate_from_vals` runs replicated —
    bit-identical to `race_query_batch`."""
    if ctx.mesh is None:
        return race.race_query_batch(state, params, qs, median_of_means)
    Lsh = _check_rows(params.L, _num_shards(ctx), "RACE")

    def body(st, p, qs):
        vals = race.race_row_reads(st, _local_params(p, Lsh), qs)  # (B, Lsh)
        vals = lax.all_gather(vals, SHARD_AXIS, axis=1, tiled=True)  # (B, L)
        return race.estimate_from_vals(vals, median_of_means)

    return _smap(
        body, ctx.mesh,
        in_specs=(_race_state_specs(ctx), _param_specs(params, ctx),
                  ctx.spec()),
        out_specs=ctx.spec())(state, params, qs)


# ---------------------------------------------------------------------------
# SW-AKDE
# ---------------------------------------------------------------------------

def sharded_swakde_update_chunk(state: swakde.SWAKDEState, params,
                                xs: jax.Array, cfg: swakde.SWAKDEConfig,
                                ctx: ShardingCtx) -> swakde.SWAKDEState:
    """Sharded exact chunk ingest: each device replays the chunk into its
    row block via `swakde_update_chunk` (rows are independent given the
    shared timestep counter, which every device advances identically).
    Bit-identical to the single-device call."""
    if ctx.mesh is None:
        return swakde.swakde_update_chunk(state, params, xs, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "SW-AKDE")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st, p, xs):
        return swakde.swakde_update_chunk(st, _local_params(p, Lsh), xs,
                                          cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_swakde_state_specs(ctx), _param_specs(params, ctx),
                  ctx.spec()),
        out_specs=_swakde_state_specs(ctx))(state, params, xs)


def _swakde_prep_specs(ctx: ShardingCtx):
    row = ctx.spec("sketch_rows", None)
    return swakde.SWAKDEPrep(order=row, seg_code=row, seg_len=row,
                             seg_first=row)


def sharded_swakde_prepare_chunk(params, xs: jax.Array,
                                 cfg: swakde.SWAKDEConfig,
                                 ctx: ShardingCtx) -> swakde.SWAKDEPrep:
    """Sharded prepare phase: each device hashes the (replicated) chunk with
    its row block and builds its rows' sort-into-segments structure — pure,
    no state input.  The prep stays row-sharded for
    `sharded_swakde_commit_chunk`."""
    if ctx.mesh is None:
        return swakde.swakde_prepare_chunk(params, xs, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "SW-AKDE")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(p, xs):
        return swakde.swakde_prepare_chunk(_local_params(p, Lsh), xs,
                                           cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_param_specs(params, ctx), ctx.spec()),
        out_specs=_swakde_prep_specs(ctx))(params, xs)


def sharded_swakde_commit_chunk(state: swakde.SWAKDEState,
                                prep: swakde.SWAKDEPrep,
                                cfg: swakde.SWAKDEConfig,
                                ctx: ShardingCtx) -> swakde.SWAKDEState:
    """Sharded commit phase: each device folds its row block's prepared
    segments into its EH rows via the closed-form segment passes
    (`kernels.ops.swakde_segment_pass` — the per-shard call dispatches the
    ingest kernels exactly like the single-device path, so the 8-device
    tests cover them; the shared clock advances identically on every
    device).  ``sharded_commit(sharded_prepare(...))`` is bit-identical to
    `sharded_swakde_update_chunk`."""
    if ctx.mesh is None:
        return swakde.swakde_commit_chunk(state, prep, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "SW-AKDE")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st, pr):
        return swakde.swakde_commit_chunk(st, pr, cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_swakde_state_specs(ctx), _swakde_prep_specs(ctx)),
        out_specs=_swakde_state_specs(ctx))(state, prep)


def sharded_swakde_grid_estimates(state: swakde.SWAKDEState,
                                  cfg: swakde.SWAKDEConfig,
                                  ctx: ShardingCtx) -> jax.Array:
    """Sharded full-grid EH window counts → (L, W) float32, row-sharded.

    Each device runs `swakde.swakde_grid_estimates` over its row block; the
    result concatenates shard blocks in row order, so reads from it are
    bit-identical to the single-device table.  This is the query-side
    snapshot-cache producer for the sharded KDE service."""
    if ctx.mesh is None:
        return swakde.swakde_grid_estimates(state, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "SW-AKDE")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st):
        return swakde.swakde_grid_estimates(st, cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_swakde_state_specs(ctx),),
        out_specs=ctx.spec("sketch_rows", None))(state)


def sharded_swakde_query_from_grid(grid: jax.Array, params, qs: jax.Array,
                                   cfg: swakde.SWAKDEConfig,
                                   ctx: ShardingCtx) -> jax.Array:
    """Sharded cached-grid queries: ``grid (L, W)`` row-sharded (from
    `sharded_swakde_grid_estimates`), ``qs (B, d)`` → (B,) float32.

    Per device: hash with the local row block, gather the local grid rows,
    all-gather to (B, L) in row order, take the same mean the single-device
    estimator takes — bit-identical to `swakde.swakde_query_from_grid` and
    therefore to `sharded_swakde_query_batch` on the grid's state."""
    if ctx.mesh is None:
        return swakde.swakde_query_from_grid(grid, params, qs, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "SW-AKDE")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(g, p, qs):
        vals = swakde.swakde_row_estimates_from_grid(
            g, _local_params(p, Lsh), qs, cfg_local)             # (B, Lsh)
        vals = lax.all_gather(vals, SHARD_AXIS, axis=1, tiled=True)  # (B, L)
        return vals.mean(-1)

    return _smap(
        body, ctx.mesh,
        in_specs=(ctx.spec("sketch_rows", None), _param_specs(params, ctx),
                  ctx.spec()),
        out_specs=ctx.spec())(grid, params, qs)


def sharded_swakde_query_batch(state: swakde.SWAKDEState, params,
                               qs: jax.Array, cfg: swakde.SWAKDEConfig,
                               ctx: ShardingCtx) -> jax.Array:
    """Sharded batched query ``qs (B, d)`` → (B,) float32 (unnormalised Ŷ).

    Per-device fused batched row estimates
    (`swakde.swakde_row_estimates_batch` — one hash matmul + one gather,
    grid-precompute when B ≥ W) → all-gather to (B, L) in row order → the
    same mean the single-device estimator takes.  Bit-identical to
    `swakde_query_batch` (the EH-merge-style combine across devices reduces
    to concatenation because row cells are never split)."""
    if ctx.mesh is None:
        return swakde.swakde_query_batch(state, params, qs, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "SW-AKDE")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st, p, qs):
        vals = swakde.swakde_row_estimates_batch(
            st, _local_params(p, Lsh), qs, cfg_local)            # (B, Lsh)
        vals = lax.all_gather(vals, SHARD_AXIS, axis=1, tiled=True)  # (B, L)
        return vals.mean(-1)

    return _smap(
        body, ctx.mesh,
        in_specs=(_swakde_state_specs(ctx), _param_specs(params, ctx),
                  ctx.spec()),
        out_specs=ctx.spec())(state, params, qs)


# ---------------------------------------------------------------------------
# S-ANN
# ---------------------------------------------------------------------------

def sharded_sann_insert_batch(state: sann.SANNState, params, xs: jax.Array,
                              key: jax.Array, cfg: sann.SANNConfig,
                              ctx: ShardingCtx) -> sann.SANNState:
    """Sharded batched ingest of ``xs (B, d)``: every device runs
    `sann_insert_batch` over its table block with the *same* key, so the
    replicated point store / keep decisions / counters are computed
    identically everywhere and the table blocks line up with the
    single-device tables row-for-row.  Bit-identical to the single-device
    call under the same key."""
    if ctx.mesh is None:
        return sann.sann_insert_batch(state, params, xs, key, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "S-ANN")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st, p, xs, key):
        return sann.sann_insert_batch(st, _local_params(p, Lsh), xs, key,
                                      cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_sann_state_specs(ctx), _param_specs(params, ctx),
                  ctx.spec(), ctx.spec()),
        out_specs=_sann_state_specs(ctx))(state, params, xs, key)


def _sann_prep_specs(ctx: ShardingCtx):
    r = ctx.spec()                       # replicated: keep decisions + chunk
    t = ctx.spec("sketch_tables")        # flat (B * L,) append structure
    return sann.SANNPrep(
        xs=r, keep=r, kept_rank=r, n_kept=r, winner=r,
        s_l=t, s_c=t, s_b=t, rank=t, entry_win=t,
        counts=ctx.spec("sketch_tables", None))


def sharded_sann_prepare_chunk(params, xs: jax.Array, key: jax.Array,
                               cfg: sann.SANNConfig,
                               ctx: ShardingCtx) -> sann.SANNPrep:
    """Sharded prepare phase: every device draws the *same* keep decisions
    from the same key (replicated outputs, identical by construction) and
    builds the sort-by-(row, code) append structure for its own table block
    (table-sharded outputs) — pure, no state input.  Ready for
    `sharded_sann_commit_chunk`."""
    if ctx.mesh is None:
        return sann.sann_prepare_chunk(params, xs, key, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "S-ANN")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(p, xs, key):
        return sann.sann_prepare_chunk(_local_params(p, Lsh), xs, key,
                                       cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_param_specs(params, ctx), ctx.spec(), ctx.spec()),
        out_specs=_sann_prep_specs(ctx))(params, xs, key)


def sharded_sann_commit_chunk(state: sann.SANNState, prep: sann.SANNPrep,
                              cfg: sann.SANNConfig,
                              ctx: ShardingCtx) -> sann.SANNState:
    """Sharded commit phase: every device rebases the replicated slot ranks
    on the replicated pointers (identical point-store/counter updates
    everywhere) and scatters its own table block's prepared appends
    through `kernels.ops.sann_table_scatter` (per-shard kernel dispatch,
    same as single-device).  ``sharded_commit(sharded_prepare(...))`` is
    bit-identical to `sharded_sann_insert_batch`."""
    if ctx.mesh is None:
        return sann.sann_commit_chunk(state, prep, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "S-ANN")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st, pr):
        return sann.sann_commit_chunk(st, pr, cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_sann_state_specs(ctx), _sann_prep_specs(ctx)),
        out_specs=_sann_state_specs(ctx))(state, prep)


def sharded_sann_merge(a: sann.SANNState, b: sann.SANNState, params,
                       cfg: sann.SANNConfig, ctx: ShardingCtx) -> sann.SANNState:
    """Sharded disjoint-stream union (`core.sann.sann_merge`): the
    stamp-interleaved union of the replicated point stores is computed
    identically on every device, and each device re-derives its own table
    block by hashing the union with its row block of the LSH params —
    exactly the sharded-ingest decomposition, so the merged sharded state
    equals the single-device merge block-for-block."""
    if ctx.mesh is None:
        return sann.sann_merge(a, b, params, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "S-ANN")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(sa, sb, p):
        return sann.sann_merge(sa, sb, _local_params(p, Lsh), cfg_local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_sann_state_specs(ctx), _sann_state_specs(ctx),
                  _param_specs(params, ctx)),
        out_specs=_sann_state_specs(ctx))(a, b, params)


def sharded_sann_delete(state: sann.SANNState, params, x: jax.Array,
                        cfg: sann.SANNConfig, ctx: ShardingCtx,
                        tol: float = 1e-5) -> sann.SANNState:
    """Sharded turnstile delete-by-value (§3.4): the hit mask over the
    replicated point store is computed identically on every device; each
    device tombstones its own table block.  Bit-identical to
    `sann_delete`."""
    if ctx.mesh is None:
        return sann.sann_delete(state, params, x, cfg, tol)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "S-ANN")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st, x):
        return sann.sann_delete(st, None, x, cfg_local, tol)

    return _smap(
        body, ctx.mesh,
        in_specs=(_sann_state_specs(ctx), ctx.spec()),
        out_specs=_sann_state_specs(ctx))(state, x)


def sharded_sann_query_batch(state: sann.SANNState, params, qs: jax.Array,
                             cfg: sann.SANNConfig,
                             ctx: ShardingCtx) -> sann.SANNResult:
    """Sharded (c, r)-queries ``qs (B, d)`` → `SANNResult` with (B,) fields.

    Each device runs the fused batched bucket gather on its table block
    (`sann_bucket_candidates_batch` — one hash matmul + one gather for the
    whole batch); all-gather concatenates the (B, L_local·cap) blocks in
    shard order — which *is* the single-device row-major candidate order —
    and the batched truncate-and-score (`sann_score_candidates_batch`, 3L
    budget with the global L, fused scorer kernel) runs replicated.
    Bit-identical to `sann_query_batch`."""
    if ctx.mesh is None:
        return sann.sann_query_batch(state, params, qs, cfg)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "S-ANN")
    cfg_local = dataclasses.replace(cfg, L=Lsh)

    def body(st, p, qs):
        cand, ok = sann.sann_bucket_candidates_batch(
            st, _local_params(p, Lsh), qs, cfg_local)
        cand = lax.all_gather(cand, SHARD_AXIS, axis=1, tiled=True)
        ok = lax.all_gather(ok, SHARD_AXIS, axis=1, tiled=True)
        return sann.sann_score_candidates_batch(
            st.points, cand, ok, qs, 3 * cfg.L, cfg)

    return _smap(
        body, ctx.mesh,
        in_specs=(_sann_state_specs(ctx), _param_specs(params, ctx),
                  ctx.spec()),
        out_specs=sann.SANNResult(*(ctx.spec(),) * 4))(state, params, qs)


def sharded_sann_query_topk_batch(state: sann.SANNState, params,
                                  qs: jax.Array, cfg: sann.SANNConfig,
                                  ctx: ShardingCtx, topk: int = 50):
    """Sharded top-k ``qs (B, d)`` → ``(ids (B, k), dists (B, k))`` with
    ``k = min(topk, L * bucket_cap)`` — the cross-device combine is a
    top-k merge: per-shard fused `sann_query_topk_batch` results are
    all-gathered,
    duplicate slot ids (a point stored in tables on two shards) are masked
    to inf, and a final top-k selects across shards.  Exact: every global
    top-k entry is in its own shard's local top-k, and distances are
    computed identically everywhere (replicated point store)."""
    if ctx.mesh is None:
        return sann.sann_query_topk_batch(state, params, qs, cfg, topk)
    Lsh = _check_rows(cfg.L, _num_shards(ctx), "S-ANN")
    cfg_local = dataclasses.replace(cfg, L=Lsh)
    k_out = min(topk, cfg.L * cfg.bucket_cap)

    def merge(ids, dists):
        # ids/dists (B, n_shards * k_local): drop cross-shard duplicates
        # (identical distance on every shard — keep the first), then take
        # the global top-k by ascending distance.
        order = jnp.argsort(ids, axis=1)
        sid = jnp.take_along_axis(ids, order, axis=1)
        dup = jnp.concatenate(
            [jnp.zeros_like(sid[:, :1], bool),
             (sid[:, 1:] == sid[:, :-1]) & (sid[:, 1:] >= 0)], axis=1)
        dupmask = jnp.zeros_like(dup).at[
            jnp.arange(ids.shape[0])[:, None], order].set(dup)
        d = jnp.where(dupmask | (ids < 0), jnp.inf, dists)
        neg, sel = lax.top_k(-d, k_out)
        out_ids = jnp.where(jnp.isfinite(-neg),
                            jnp.take_along_axis(ids, sel, axis=1), -1)
        return out_ids, -neg

    def body(st, p, qs):
        ids, dists = sann.sann_query_topk_batch(
            st, _local_params(p, Lsh), qs, cfg_local, topk)
        ids = lax.all_gather(ids, SHARD_AXIS, axis=1, tiled=True)
        dists = lax.all_gather(dists, SHARD_AXIS, axis=1, tiled=True)
        return merge(ids, dists)

    return _smap(
        body, ctx.mesh,
        in_specs=(_sann_state_specs(ctx), _param_specs(params, ctx),
                  ctx.spec()),
        out_specs=(ctx.spec(), ctx.spec()))(state, params, qs)


# ---------------------------------------------------------------------------
# Tenant fleets (repro.core.fleet): shard the leading [T] tenant axis
# ---------------------------------------------------------------------------
#
# Orthogonal to the row/table sharding above: a stacked fleet state keeps
# every sketch's (L, ...) block whole and splits the *tenant* axis across
# the mesh instead — each shard owns T/num_shards complete sketches and the
# fleet's single set of LSH params is replicated (hash the whole mixed
# chunk once per shard).  Routing is local: each shard remaps global tenant
# slots to its own block (slots owned elsewhere become -1, which the fleet
# ingest drops), so a mixed chunk commits with one vmapped dispatch per
# shard and NO cross-device traffic on the ingest path.  Queries read each
# request's row block locally, zero the rows the shard does not own, and
# psum — exactly one shard contributes each request's values, so adding the
# other shards' zeros is bit-exact.

def _fleet_state_specs(ctx: ShardingCtx, state_like):
    """Spec pytree for a stacked fleet: every leaf splits its leading
    tenant axis."""
    return jax.tree.map(
        lambda x: ctx.spec("tenants", *([None] * (jnp.ndim(x) - 1))),
        state_like)


def _param_replicated_specs(params, ctx: ShardingCtx):
    r = ctx.spec()
    if isinstance(params, lsh.SRPParams):
        return dataclasses.replace(params, proj=r, mix=r)
    return dataclasses.replace(params, proj=r, bias=r, mix=r)


def _check_tenants(T: int, n: int) -> int:
    if T % n:
        raise ValueError(f"fleet: T={T} not divisible by num_shards={n}")
    return T // n


def shard_fleet(stacked, params, ctx: ShardingCtx):
    """Place a stacked fleet onto the mesh (tenant axis split, params
    replicated)."""
    if ctx.mesh is None:
        return stacked, params
    return (_put(stacked, _fleet_state_specs(ctx, stacked), ctx.mesh),
            _put(params, _param_replicated_specs(params, ctx), ctx.mesh))


def _local_tids(tids: jax.Array, T_local: int) -> tuple[jax.Array, jax.Array]:
    """Remap global tenant slots to this shard's block: slots outside
    ``[shard*T_local, (shard+1)*T_local)`` become -1 (dropped/zeroed)."""
    sh = lax.axis_index(SHARD_AXIS)
    local = tids - sh * T_local
    owned = (local >= 0) & (local < T_local)
    return jnp.where(owned, local, -1), owned


def sharded_race_fleet_ingest(stacked: race.RACEState, params, xs: jax.Array,
                              tids: jax.Array,
                              ctx: ShardingCtx) -> race.RACEState:
    """Tenant-sharded fleet ingest: the mixed chunk is replicated, each
    shard scatters only the points of tenants it owns.  Bit-identical to
    `fleet.race_fleet_ingest` block-for-block."""
    if ctx.mesh is None:
        return fleet.race_fleet_ingest(stacked, params, xs, tids)
    Tl = _check_tenants(stacked.counts.shape[0], _num_shards(ctx))

    def body(st, p, xs, tids):
        local, _ = _local_tids(tids, Tl)
        return fleet.race_fleet_ingest(st, p, xs, local)

    return _smap(
        body, ctx.mesh,
        in_specs=(_fleet_state_specs(ctx, stacked),
                  _param_replicated_specs(params, ctx), ctx.spec(),
                  ctx.spec()),
        out_specs=_fleet_state_specs(ctx, stacked))(stacked, params, xs,
                                                    tids)


def sharded_race_fleet_query(stacked: race.RACEState, params, qs: jax.Array,
                             tids: jax.Array, ctx: ShardingCtx,
                             median_of_means: int = 0) -> jax.Array:
    """Tenant-sharded fleet query: each request's (L,) counter reads come
    from the one shard owning its tenant; the psum over zeroed non-owner
    rows is bit-exact, then the single-device estimator runs replicated."""
    if ctx.mesh is None:
        return fleet.race_fleet_query(stacked, params, qs, tids,
                                      median_of_means)
    Tl = _check_tenants(stacked.counts.shape[0], _num_shards(ctx))

    def body(st, p, qs, tids):
        local, owned = _local_tids(tids, Tl)
        vals = fleet.race_fleet_row_reads(st, p, qs,
                                          jnp.clip(local, 0, Tl - 1))
        vals = lax.psum(jnp.where(owned[:, None], vals, 0.0), SHARD_AXIS)
        return race.estimate_from_vals(vals, median_of_means)

    return _smap(
        body, ctx.mesh,
        in_specs=(_fleet_state_specs(ctx, stacked),
                  _param_replicated_specs(params, ctx), ctx.spec(),
                  ctx.spec()),
        out_specs=ctx.spec())(stacked, params, qs, tids)


def sharded_swakde_fleet_ingest(stacked: swakde.SWAKDEState, params,
                                xs: jax.Array, tids: jax.Array,
                                cfg: swakde.SWAKDEConfig, cap: int,
                                ctx: ShardingCtx) -> swakde.SWAKDEState:
    """Tenant-sharded SW-AKDE fleet ingest: each shard routes the mixed
    chunk against its own tenant block (foreign tenants drop to the
    sentinel) and runs the vmapped two-phase commit locally."""
    if ctx.mesh is None:
        return fleet.swakde_fleet_ingest(stacked, params, xs, tids, cfg, cap)
    Tl = _check_tenants(stacked.t.shape[0], _num_shards(ctx))

    def body(st, p, xs, tids):
        local, _ = _local_tids(tids, Tl)
        return fleet.swakde_fleet_ingest(st, p, xs, local, cfg, cap)

    return _smap(
        body, ctx.mesh,
        in_specs=(_fleet_state_specs(ctx, stacked),
                  _param_replicated_specs(params, ctx), ctx.spec(),
                  ctx.spec()),
        out_specs=_fleet_state_specs(ctx, stacked))(stacked, params, xs,
                                                    tids)


def sharded_swakde_fleet_query(stacked: swakde.SWAKDEState, params,
                               qs: jax.Array, tids: jax.Array,
                               cfg: swakde.SWAKDEConfig,
                               ctx: ShardingCtx) -> jax.Array:
    """Tenant-sharded SW-AKDE fleet query: per-request EH row estimates
    from the owning shard, zero elsewhere, psum, mean — bit-identical to
    `fleet.swakde_fleet_query`."""
    if ctx.mesh is None:
        return fleet.swakde_fleet_query(stacked, params, qs, tids, cfg)
    Tl = _check_tenants(stacked.t.shape[0], _num_shards(ctx))

    def body(st, p, qs, tids):
        local, owned = _local_tids(tids, Tl)
        est = fleet.swakde_fleet_row_estimates(
            st, p, qs, jnp.clip(local, 0, Tl - 1), cfg)
        est = lax.psum(jnp.where(owned[:, None], est, 0.0), SHARD_AXIS)
        return est.mean(-1)

    return _smap(
        body, ctx.mesh,
        in_specs=(_fleet_state_specs(ctx, stacked),
                  _param_replicated_specs(params, ctx), ctx.spec(),
                  ctx.spec()),
        out_specs=ctx.spec())(stacked, params, qs, tids)


def sharded_sann_fleet_ingest(stacked: sann.SANNState, params, xs: jax.Array,
                              tids: jax.Array, keys: jax.Array,
                              cfg: sann.SANNConfig, cap: int,
                              ctx: ShardingCtx) -> sann.SANNState:
    """Tenant-sharded S-ANN fleet ingest: the mixed chunk is replicated,
    the per-tenant chunk keys ``keys (T, 2)`` split with the tenant axis
    (each shard draws only its own tenants' Bernoulli keeps), and foreign
    tenants drop to the -1 sentinel.  Bit-identical to
    `fleet.sann_fleet_ingest` block-for-block: each shard's vmapped
    prepare/commit sees exactly the rows, codes, and keys the unsharded
    fleet hands that tenant block."""
    if ctx.mesh is None:
        return fleet.sann_fleet_ingest(stacked, params, xs, tids, keys, cfg,
                                       cap)
    Tl = _check_tenants(stacked.n_seen.shape[0], _num_shards(ctx))

    def body(st, p, xs, tids, keys):
        local, _ = _local_tids(tids, Tl)
        return fleet.sann_fleet_ingest(st, p, xs, local, keys, cfg, cap)

    return _smap(
        body, ctx.mesh,
        in_specs=(_fleet_state_specs(ctx, stacked),
                  _param_replicated_specs(params, ctx), ctx.spec(),
                  ctx.spec(), ctx.spec("tenants", None)),
        out_specs=_fleet_state_specs(ctx, stacked))(stacked, params, xs,
                                                    tids, keys)


def sharded_sann_fleet_query_topk(stacked: sann.SANNState, params,
                                  qs: jax.Array, tids: jax.Array,
                                  cfg: sann.SANNConfig, ctx: ShardingCtx,
                                  topk: int = 50):
    """Tenant-sharded per-tenant top-k: each request's candidates come
    from the one shard owning its tenant row; non-owners are masked to
    exact zeros before the psum, so the combine is bit-exact for every
    output — including the inf distance and -1 id padding (``inf + 0 =
    inf``, ``-1 + 0 = -1``; `jnp.where` masking, never a multiply, so no
    ``inf * 0`` NaN).  Slot ids index the request's own tenant row, so no
    cross-shard offset is needed."""
    if ctx.mesh is None:
        return fleet.sann_fleet_query_topk(stacked, params, qs, tids, cfg,
                                           topk)
    Tl = _check_tenants(stacked.n_seen.shape[0], _num_shards(ctx))

    def body(st, p, qs, tids):
        local, owned = _local_tids(tids, Tl)
        ids, dists = fleet.sann_fleet_query_topk(
            st, p, qs, jnp.clip(local, 0, Tl - 1), cfg, topk)
        ids = lax.psum(jnp.where(owned[:, None], ids, 0), SHARD_AXIS)
        dists = lax.psum(jnp.where(owned[:, None], dists, 0.0), SHARD_AXIS)
        return ids, dists

    return _smap(
        body, ctx.mesh,
        in_specs=(_fleet_state_specs(ctx, stacked),
                  _param_replicated_specs(params, ctx), ctx.spec(),
                  ctx.spec()),
        out_specs=(ctx.spec(), ctx.spec()))(stacked, params, qs, tids)


def sharded_sann_fleet_query(stacked: sann.SANNState, params, qs: jax.Array,
                             tids: jax.Array, cfg: sann.SANNConfig,
                             ctx: ShardingCtx):
    """Tenant-sharded per-tenant (c, r)-NN queries → `SANNResult` with (B,)
    fields.  Same owner-masked psum as the top-k path; the boolean
    ``found`` field rides as int32 through the psum and casts back."""
    if ctx.mesh is None:
        return fleet.sann_fleet_query(stacked, params, qs, tids, cfg)
    Tl = _check_tenants(stacked.n_seen.shape[0], _num_shards(ctx))

    def body(st, p, qs, tids):
        local, owned = _local_tids(tids, Tl)
        res = fleet.sann_fleet_query(st, p, qs, jnp.clip(local, 0, Tl - 1),
                                     cfg)
        return sann.SANNResult(
            index=lax.psum(jnp.where(owned, res.index, 0), SHARD_AXIS),
            distance=lax.psum(jnp.where(owned, res.distance, 0.0),
                              SHARD_AXIS),
            found=lax.psum(
                jnp.where(owned, res.found.astype(jnp.int32), 0),
                SHARD_AXIS).astype(bool),
            n_candidates=lax.psum(jnp.where(owned, res.n_candidates, 0),
                                  SHARD_AXIS),
        )

    return _smap(
        body, ctx.mesh,
        in_specs=(_fleet_state_specs(ctx, stacked),
                  _param_replicated_specs(params, ctx), ctx.spec(),
                  ctx.spec()),
        out_specs=sann.SANNResult(*(ctx.spec(),) * 4))(stacked, params, qs,
                                                       tids)
