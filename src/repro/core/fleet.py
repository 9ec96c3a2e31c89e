"""Multi-tenant sketch fleets: stacked states + tenant-routed vmapped ingest.

A fleet is T independent sketches of one kind sharing a single set of LSH
params, stored as ONE stacked pytree whose every leaf gains a leading
``[T]`` tenant axis (``RACEState.counts`` becomes ``(T, L, W)``, etc.).
Ingest takes one *mixed* chunk ``xs (B, d)`` tagged with per-point tenant
slots ``tids (B,)`` and commits it with a single device dispatch:

  1. hash the whole mixed chunk once (params are fleet-shared, and
     `lsh.hash_points` is pinned batch-shape invariant);
  2. route: a stable sort by tenant id (`route_chunk` — the same
     sort-by-key machinery as S-ANN's (row, code) append sort) gathers each
     tenant's points into a cap-padded ``(T, cap)`` block, preserving
     stream order within each tenant;
  3. commit: one `jax.vmap` of the existing two-phase prepare/commit over
     the tenant axis (RACE's commit is pure integer addition, so its
     "vmapped commit" collapses into one fused scatter-add).

Every fleet function is pinned bit-identical to the per-tenant oracle loop
of the single-sketch paths (tests/test_tenant_fleet.py).  The padding
contracts that make this exact:

  * routed blocks put the tenant's real points in a *prefix* (pads trail),
  * S-ANN pads get ``keep=False`` (prefix-stable `sann_row_keys` means the
    pad draws never perturb the real ones),
  * SW-AKDE pads hash to the sentinel code W and their segments are
    zeroed (`swakde_prepare_from_codes(mask=...)`),
  * both commits advance their clocks by the *real* count
    (``count=`` kwarg), not the padded block size.

Queries gather per-request tenant rows (tables / cells / counters) from
the stacked state and run the existing fused batch kernels once for the
whole mixed batch.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from . import lsh
from .eh import eh_query_cells
from .race import RACEState, estimate_from_vals
from .sann import (SANNConfig, SANNState, _first_occurrence_mask, bucket_cols,
                   sann_commit_chunk, sann_prepare_given_keep, sann_row_keys)
from .swakde import (SWAKDEConfig, SWAKDEState, swakde_commit_chunk,
                     swakde_prepare_from_codes)
from .util import saturating_add
from ..kernels import ops as kernel_ops

tree_map = jax.tree_util.tree_map


# --------------------------------------------------------------------------
# stacked-state helpers
# --------------------------------------------------------------------------

def fleet_stack(states: Sequence):
    """Stack identically-shaped sketch states into one fleet pytree
    (every leaf gains a leading ``[T]`` axis)."""
    return tree_map(lambda *xs: jnp.stack(xs), *states)


def fleet_row(stacked, i):
    """Extract tenant row ``i`` as a plain single-sketch state."""
    return tree_map(lambda x: x[i], stacked)


def fleet_set_row(stacked, i, row):
    """Functionally replace tenant row ``i`` with ``row``."""
    return tree_map(lambda x, r: x.at[i].set(r), stacked, row)


def fleet_broadcast(state, T: int):
    """A fleet of ``T`` copies of ``state`` (e.g. T empty sketches)."""
    return tree_map(
        lambda x: jnp.broadcast_to(x[None], (T,) + x.shape).copy(), state)


# --------------------------------------------------------------------------
# tenant routing
# --------------------------------------------------------------------------

class FleetRoute(NamedTuple):
    """Gather plan for one mixed chunk: tenant slot t's points are chunk
    rows ``take[t, :counts[t]]`` in stream order; columns >= counts[t] are
    arbitrary in-bounds pads flagged False in ``valid``."""
    take: jax.Array    # (T, cap) int32 — chunk row index per padded block
    valid: jax.Array   # (T, cap) bool  — prefix mask: col < counts[t]
    counts: jax.Array  # (T,) int32     — real points per tenant slot


def route_chunk(tids: jax.Array, num_slots: int, cap: int) -> FleetRoute:
    """Sort/segment a mixed chunk by tenant slot.

    ``tids (B,) int32`` holds per-point tenant slots; ids outside
    ``[0, num_slots)`` (use -1) are dropped.  ``cap`` bounds the per-slot
    count — the caller guarantees every slot receives <= cap points (the
    serve layer splits oversized chunks; `TenantFleet`).

    One stable argsort by slot id groups each tenant's points contiguously
    *in stream order* (stability), exactly like the (row, code) append sort
    in `core.sann.sann_prepare_given_keep`; prefix sums of the per-slot
    histogram locate each group's start."""
    B = tids.shape[0]
    slot = jnp.where((tids >= 0) & (tids < num_slots), tids,
                     jnp.int32(num_slots))
    order = jnp.argsort(slot, stable=True)                      # (B,)
    counts = jnp.zeros((num_slots,), jnp.int32).at[slot].add(1, mode="drop")
    starts = jnp.cumsum(counts) - counts                        # exclusive
    idx = starts[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
    take = order[jnp.clip(idx, 0, B - 1)]
    valid = jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]
    return FleetRoute(take=take, valid=valid, counts=counts)


# --------------------------------------------------------------------------
# RACE fleet
# --------------------------------------------------------------------------

def race_fleet_ingest(stacked: RACEState, params, xs: jax.Array,
                      tids: jax.Array) -> RACEState:
    """Tenant-routed RACE ingest: stacked ``counts (T, L, W)``, one mixed
    chunk, ONE fused scatter-add.

    RACE's commit is pure integer addition, so the vmapped two-phase
    prepare/commit collapses algebraically into a single scatter of all B
    points' (tenant, row, code) triples — bit-identical to the per-tenant
    `race_prepare_chunk` + `race_commit_chunk` loop (integer adds are
    exact and order-free) without even needing `route_chunk`."""
    T = stacked.counts.shape[0]
    codes = lsh.hash_points(params, xs)                         # (B, L)
    L = codes.shape[-1]
    slot = jnp.where((tids >= 0) & (tids < T), tids, jnp.int32(T))
    counts = stacked.counts.at[
        slot[:, None], jnp.arange(L)[None, :], codes].add(1, mode="drop")
    per = jnp.zeros((T,), jnp.int32).at[slot].add(1, mode="drop")
    return RACEState(counts=counts, n=saturating_add(stacked.n, per))


def race_fleet_row_reads(stacked: RACEState, params, qs: jax.Array,
                         tids: jax.Array) -> jax.Array:
    """Per-request row reads from the stacked fleet: ``qs (B, d)``,
    ``tids (B,)`` → (B, L) float32.  One hash matmul + one tenant-indexed
    gather — the tenant-axis form of `core.race.race_row_reads`."""
    codes = lsh.hash_points(params, qs)                         # (B, L)
    L = codes.shape[-1]
    t = jnp.clip(tids, 0, stacked.counts.shape[0] - 1)
    return stacked.counts[
        t[:, None], jnp.arange(L)[None, :], codes].astype(jnp.float32)


def race_fleet_query(stacked: RACEState, params, qs: jax.Array,
                     tids: jax.Array, median_of_means: int = 0) -> jax.Array:
    """Batched per-tenant RACE estimates: (B,) float32, bit-identical to
    `race_query_batch` against each request's own sketch."""
    return estimate_from_vals(race_fleet_row_reads(stacked, params, qs, tids),
                              median_of_means)


def race_fleet_kde(stacked: RACEState, params, qs: jax.Array,
                   tids: jax.Array, median_of_means: int = 0) -> jax.Array:
    """Normalised per-tenant KDE reads (`race_kde` with a tenant axis)."""
    est = race_fleet_query(stacked, params, qs, tids, median_of_means)
    t = jnp.clip(tids, 0, stacked.n.shape[0] - 1)
    return est / jnp.maximum(stacked.n[t], 1).astype(jnp.float32)


# --------------------------------------------------------------------------
# SW-AKDE fleet
# --------------------------------------------------------------------------

def swakde_fleet_ingest(stacked: SWAKDEState, params, xs: jax.Array,
                        tids: jax.Array, cfg: SWAKDEConfig,
                        cap: int) -> SWAKDEState:
    """Tenant-routed SW-AKDE ingest: hash the mixed chunk once, route the
    *codes* (`route_chunk`), and run ONE vmapped two-phase prepare/commit
    over the tenant axis.

    Pads hash to the sentinel code W inside `swakde_prepare_from_codes`
    (mask), so their segments carry zero mass and are dropped by the
    scatter-back; ``count=`` advances each tenant clock by its real count.
    Bit-identical to the per-tenant `swakde_update_chunk` loop."""
    T = stacked.t.shape[0]
    codes = lsh.hash_points(params, xs)                         # (B, L)
    route = route_chunk(tids, T, cap)
    codes_t = codes[route.take]                                 # (T, cap, L)

    def one(st, cb, vb, cnt):
        prep = swakde_prepare_from_codes(cb, cfg, mask=vb)
        return swakde_commit_chunk(st, prep, cfg, count=cnt)

    return jax.vmap(one)(stacked, codes_t, route.valid, route.counts)


def swakde_fleet_grid(stacked: SWAKDEState, cfg: SWAKDEConfig) -> jax.Array:
    """Window-count estimate tables for every tenant: (T, L, W) float32 —
    `swakde_grid_estimates` broadcast over the tenant axis (one
    `eh_query_cells` pass, each tenant expiring at its own clock)."""
    t = (stacked.t - 1)[:, None, None, None, None]
    return eh_query_cells(stacked.ts, stacked.num, t, cfg.eh_config())


def swakde_fleet_row_estimates(stacked: SWAKDEState, params, qs: jax.Array,
                               tids: jax.Array,
                               cfg: SWAKDEConfig) -> jax.Array:
    """Per-request EH row estimates from the stacked fleet: (B, L) float32.

    One hash matmul, one tenant-indexed cell gather, one batched
    `eh_query_cells` at each request's own tenant clock — the per-cell
    arithmetic is identical to `eh_query`, so estimates are bit-identical
    to `swakde_row_estimates_batch` against the request's own sketch."""
    codes = lsh.hash_points(params, qs)                         # (B, L)
    L = codes.shape[-1]
    t = jnp.clip(tids, 0, stacked.t.shape[0] - 1)
    rows = jnp.arange(L)[None, :]
    cell_ts = stacked.ts[t[:, None], rows, codes]    # (B, L, levels, slots)
    cell_num = stacked.num[t[:, None], rows, codes]  # (B, L, levels)
    tq = (stacked.t[t] - 1)[:, None, None, None]
    return eh_query_cells(cell_ts, cell_num, tq, cfg.eh_config())


def swakde_fleet_query(stacked: SWAKDEState, params, qs: jax.Array,
                       tids: jax.Array, cfg: SWAKDEConfig) -> jax.Array:
    """Batched per-tenant Ŷ estimates: (B,) float32, bit-identical to
    `swakde_query_batch` against each request's own sketch."""
    return swakde_fleet_row_estimates(stacked, params, qs, tids, cfg).mean(-1)


def swakde_fleet_kde(stacked: SWAKDEState, params, qs: jax.Array,
                     tids: jax.Array, cfg: SWAKDEConfig) -> jax.Array:
    """Normalised per-tenant window densities (`swakde_kde` + tenant axis)."""
    est = swakde_fleet_query(stacked, params, qs, tids, cfg)
    t = jnp.clip(tids, 0, stacked.t.shape[0] - 1)
    denom = jnp.minimum(stacked.t[t], cfg.window).astype(jnp.float32)
    return est / jnp.maximum(denom, 1.0)


# --------------------------------------------------------------------------
# S-ANN fleet
# --------------------------------------------------------------------------

def sann_fleet_ingest(stacked: SANNState, params, xs: jax.Array,
                      tids: jax.Array, keys: jax.Array, cfg: SANNConfig,
                      cap: int) -> SANNState:
    """Tenant-routed S-ANN ingest: hash once, route points *and* codes,
    ONE vmapped two-phase prepare/commit over the tenant axis.

    ``keys (T, 2)`` holds one PRNG key per tenant slot for this chunk's
    Bernoulli draws.  Because `sann_row_keys` is prefix-stable, drawing
    over the cap-padded block and masking pads to ``keep=False`` yields
    exactly the draws the unpadded per-tenant `sann_prepare_chunk` would
    make; pads write nothing and ``count=`` advances ``n_seen`` by the
    real count, so every tenant row lands bit-identical to the single
    sketch ingesting its own sub-stream under the same key."""
    T = stacked.n_seen.shape[0]
    codes = lsh.hash_points(params, xs)                         # (B, L)
    route = route_chunk(tids, T, cap)
    xs_t = xs[route.take]                                       # (T, cap, d)
    codes_t = codes[route.take]                                 # (T, cap, L)

    def one(st, key_t, xb, cb, vb, cnt):
        rks = sann_row_keys(key_t, cap)
        keep = jax.vmap(
            lambda k: jax.random.bernoulli(k, cfg.keep_prob))(rks) & vb
        prep = sann_prepare_given_keep(params, xb, keep, cfg, codes=cb)
        return sann_commit_chunk(st, prep, cfg, count=cnt)

    return jax.vmap(one)(stacked, keys, xs_t, codes_t, route.valid,
                         route.counts)


def sann_fleet_candidates(stacked: SANNState, params, qs: jax.Array,
                          tids: jax.Array, cfg: SANNConfig):
    """Per-request bucket candidates from the stacked fleet: one hash
    matmul + one tenant-indexed table gather → ``(cand, ok)`` with the
    same row-major (L, bucket_cap) column order as
    `sann_bucket_candidates_batch` on the request's own sketch."""
    codes = lsh.hash_points(params, qs)                         # (B, L)
    t = jnp.clip(tids, 0, stacked.n_seen.shape[0] - 1)
    cand = stacked.tables[t[:, None, None], jnp.arange(cfg.L)[None, :, None],
                          bucket_cols(codes, cfg.bucket_cap)]
    cand = cand.reshape(qs.shape[0], cfg.L * cfg.bucket_cap)
    ok = (cand >= 0) & stacked.valid[t[:, None], jnp.maximum(cand, 0)]
    return cand, ok, t


def sann_fleet_query_topk(stacked: SANNState, params, qs: jax.Array,
                          tids: jax.Array, cfg: SANNConfig, topk: int = 50):
    """Batched per-tenant top-k: ``(ids (B, k), dists (B, k))`` with the
    `sann_query_topk_batch` padding/ordering contract, bit-identical to
    running it against each request's own sketch (slot ids index the
    request's tenant row)."""
    cand, ok, t = sann_fleet_candidates(stacked, params, qs, tids, cfg)
    mask = ok & _first_occurrence_mask(cand, stacked.points.shape[1])
    vecs = stacked.points[t[:, None], jnp.maximum(cand, 0)]  # (B, C, d)
    k = min(topk, cand.shape[1])
    d2, idx = kernel_ops.batch_score_topk(qs, vecs, mask, k)
    ids = jnp.where(jnp.isfinite(d2),
                    jnp.take_along_axis(cand, idx, axis=1), -1)
    return ids, jnp.sqrt(d2)


def sann_fleet_query(stacked: SANNState, params, qs: jax.Array,
                     tids: jax.Array, cfg: SANNConfig):
    """Batched per-tenant (c, r)-NN queries → `SANNResult` with (B,)
    fields, bit-identical to `sann_query_batch` per request.

    Same masked truncate-and-score as `sann_score_candidates_batch`, with
    the candidate-vector gather indexed by tenant row."""
    from .sann import SANNResult                      # local: avoid cycle
    cand, ok, t = sann_fleet_candidates(stacked, params, qs, tids, cfg)
    budget = 3 * cfg.L
    C = cand.shape[1]
    budget_eff = min(budget, C)
    csum = jnp.cumsum(ok, axis=1).astype(jnp.int32)
    targets = jnp.arange(1, budget_eff + 1, dtype=jnp.int32)
    sel = jax.vmap(lambda a: jnp.searchsorted(a, targets, side="left"))(csum)
    sel_ok = sel < C
    sel = jnp.minimum(sel, C - 1)
    sel_cand = jnp.where(sel_ok, jnp.take_along_axis(cand, sel, axis=1), -1)
    vecs = stacked.points[t[:, None], jnp.maximum(sel_cand, 0)]
    d2, idx = kernel_ops.batch_score_topk(qs, vecs, sel_ok, 1)
    dist = jnp.sqrt(d2[:, 0])
    found = dist <= cfg.c * cfg.r
    best = jnp.take_along_axis(sel_cand, idx, axis=1)[:, 0]
    return SANNResult(
        index=jnp.where(found, best, -1),
        distance=jnp.where(found, dist, jnp.inf),
        found=found,
        n_candidates=jnp.minimum(csum[:, -1], budget).astype(jnp.int32),
    )
