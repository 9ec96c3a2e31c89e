"""S-ANN — Streaming (c,r)-Approximate Near Neighbor sketch (paper §3, Alg. 1).

Faithful mechanics:
  * uniform sampling: each arriving point is **kept with probability n^-eta**
    (Fig. 1), so only O(n^{1-eta}) points are stored;
  * L = ceil(n^rho / p1) hash tables, each keyed by a concatenation of
    k = ceil(log_{1/p2} n) p-stable hashes (Lemma 3.2/3.3);
  * query (Fig. 2): union of the L colliding buckets, truncated at **3L
    candidates** (the paper's early-exit budget), return the closest if it is
    within c*r, else NULL;
  * turnstile deletions (§3.4): delete-by-value tombstones;
  * batch queries (§3.3): the fused batch engine (below) — identical
    results to running the per-query pipeline on each element.

Hardware adaptation (DESIGN.md §5.2): pointer-chasing hash buckets become
fixed-capacity **ring buffers** of point ids — `tables (L, n_buckets *
bucket_cap)`, bucket ``c`` of row ``l`` at columns ``[c·cap, (c+1)·cap)`` —
so insertion is a dense scatter and querying is a dense gather + one
distance matmul (`repro.kernels.cand_score`).  The rings are flattened into
one axis because a TPU pads an array's minor dimension to 128 lanes: a
trailing ``bucket_cap = 16`` axis would take 8× its bytes in HBM (23 GB
instead of 2.9 GB for the tables at n_max = 10M).  The early-exit
("stop at 3L") becomes a post-gather priority truncation: we score the same
<=3L candidates the sequential algorithm would, Lemma 3.2's Markov bound is
unchanged.

Query paths (DESIGN.md §9):
  * ``sann_query`` / ``sann_query_topk`` — per-query reference semantics
    (gather L buckets, truncate at 3L, score via `kernels.cand_score`);
  * ``sann_query_batch`` / ``sann_query_topk_batch`` — the fused batch
    engine: one hash matmul + one table gather for the whole batch,
    batch-wide truncation/dedup, one fused scorer call
    (`kernels.batch_score` on TPU).  Results are identical to the
    per-query path (tests/test_query_batched.py).

Ingest paths:
  * ``sann_insert`` / ``sann_insert_stream`` — the per-point reference
    semantics (Alg. 1 verbatim, one `lax.scan` step per stream element);
  * ``sann_insert_batch`` — the production batched-update contract: one hash
    matmul per chunk, keep decisions from the *same* per-point key schedule,
    slots assigned by a prefix sum over kept points, and the ring-buffer
    appends realised as a sort-by-(row, code) segment scatter.  The final
    state is bit-identical to replaying ``sann_insert`` point by point
    (tests/test_batched_ingest.py), but costs O(1) XLA steps per chunk
    instead of O(chunk).
  * ``sann_prepare_chunk`` / ``sann_commit_chunk`` — the two-phase form of
    the same contract (DESIGN.md §10): prepare is the pure half (keep
    decisions, prefix ranks, hashing, the sorted append structure), commit
    rebases it on the live pointers; ``sann_insert_batch`` is their
    composition, and the serving engine overlaps prepare of chunk k+1 with
    commit of chunk k.

Multi-worker merge (DESIGN.md §11.4): ``sann_merge`` unions two sketches
built over disjoint streams — under the paper's n^-eta uniform sampling a
union of independent samples is exactly a sample of the union stream.
Stored points carry logical arrival stamps (`SANNState.stamps`) so the
merge can interleave the two ring buffers deterministically and re-derive
the hash tables through the same sort-by-(row, code) append structure the
ingest path uses.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import lsh, theory
from .util import saturating_add
from repro.kernels import ops as kernel_ops


@dataclasses.dataclass(frozen=True)
class SANNConfig:
    dim: int
    n_max: int             # upper bound on stream size (paper's n)
    eta: float             # sampling exponent: keep prob = n^-eta
    r: float               # near radius
    c: float               # approximation factor (far radius = c*r)
    w: float = 4.0         # p-stable bucket width
    L: Optional[int] = None
    k: Optional[int] = None
    bucket_cap: int = 16
    capacity_slack: float = 4.0

    def resolved(self) -> "SANNConfig":
        p1 = float(theory.pstable_p(self.r, self.w))
        p2 = float(theory.pstable_p(self.c * self.r, self.w))
        k = self.k or max(1, math.ceil(math.log(self.n_max) / math.log(1.0 / p2)))
        rho = math.log(1.0 / p1) / math.log(1.0 / p2)
        L = self.L or max(1, math.ceil(self.n_max**rho / p1))
        return dataclasses.replace(self, L=L, k=k)

    @property
    def keep_prob(self) -> float:
        return self.n_max ** (-self.eta)

    @property
    def capacity(self) -> int:
        """Point-store size: E[stored] = n^{1-eta}, padded by slack for
        concentration (binomial upper tail)."""
        expect = self.n_max ** (1.0 - self.eta)
        return max(64, int(self.capacity_slack * expect))

    @property
    def n_buckets(self) -> int:
        # enough buckets that far points spread out (Lemma 3.2 uses range
        # ~n via k concatenations; after rehash we provision ~4x capacity)
        return max(64, int(4 * self.capacity))


class SANNState(NamedTuple):
    points: jax.Array       # (capacity, dim) float32
    valid: jax.Array        # (capacity,) bool
    write_ptr: jax.Array    # () int32 slot pointer, kept reduced mod capacity
    n_seen: jax.Array       # () int32, saturating (see core.util.saturating_add)
    n_stored: jax.Array     # () int32 — live stored points (== valid.sum())
    tables: jax.Array       # (L, n_buckets * bucket_cap) int32 slot ids,
    #   -1 empty; bucket c's ring is columns [c * bucket_cap, (c+1) * cap)
    table_ptr: jax.Array    # (L, n_buckets) int32 cyclic bucket pointers
    stamps: jax.Array       # (capacity,) int32 — logical arrival time of each
    #   stored point (its stream index, = n_seen at arrival; saturating like
    #   n_seen, -1 for never-written slots).  Ingest never reads it; it is
    #   what lets `sann_merge` interleave two disjoint-stream sketches in a
    #   deterministic logical-time order.


def sann_empty_state(cfg: SANNConfig) -> SANNState:
    """Allocate an empty sketch for a *resolved* config (shapes documented
    on `SANNState`)."""
    return SANNState(
        points=jnp.zeros((cfg.capacity, cfg.dim), jnp.float32),
        valid=jnp.zeros((cfg.capacity,), bool),
        write_ptr=jnp.zeros((), jnp.int32),
        n_seen=jnp.zeros((), jnp.int32),
        n_stored=jnp.zeros((), jnp.int32),
        tables=jnp.full((cfg.L, cfg.n_buckets * cfg.bucket_cap), -1,
                        jnp.int32),
        table_ptr=jnp.zeros((cfg.L, cfg.n_buckets), jnp.int32),
        stamps=jnp.full((cfg.capacity,), -1, jnp.int32),
    )


def sann_init(cfg: SANNConfig, key: jax.Array):
    """Resolve the config (derive L, k from n_max/r/c if unset) and allocate
    an empty sketch.

    Returns ``(resolved cfg, lsh.PStableParams, SANNState)`` — state shapes
    are documented on `SANNState`; all ids/counters are int32, points
    float32."""
    cfg = cfg.resolved()
    params = lsh.init_pstable(key, cfg.dim, cfg.L, cfg.k, cfg.w, cfg.n_buckets)
    return cfg, params, sann_empty_state(cfg)


def sann_insert(state: SANNState, params, x: jax.Array, key: jax.Array,
                cfg: SANNConfig) -> SANNState:
    """Sample-and-store one stream point (Alg. 1 insert; Fig. 1)."""
    keep = jax.random.bernoulli(key, cfg.keep_prob)
    slot = state.write_ptr % cfg.capacity
    evict = keep & state.valid[slot]
    # Recycling a live slot invalidates every table entry still pointing at
    # it (the evicted point's buckets) — tombstone them, or queries would
    # score the *new* vector under the *old* point's bucket membership.
    tables = lax.cond(
        evict,
        lambda tb: jnp.where(tb == slot, jnp.int32(-1), tb),
        lambda tb: tb,
        state.tables)

    points = state.points.at[slot].set(jnp.where(keep, x, state.points[slot]))
    valid = state.valid.at[slot].set(jnp.where(keep, True, state.valid[slot]))
    stamps = state.stamps.at[slot].set(
        jnp.where(keep, state.n_seen, state.stamps[slot]))

    codes = lsh.hash_points(params, x)                          # (L,)
    rows = jnp.arange(cfg.L)
    col = (codes * cfg.bucket_cap
           + state.table_ptr[rows, codes] % cfg.bucket_cap)
    old = tables[rows, col]
    tables = tables.at[rows, col].set(
        jnp.where(keep, slot.astype(jnp.int32), old))
    table_ptr = state.table_ptr.at[rows, codes].add(jnp.where(keep, 1, 0))

    return SANNState(
        points=points, valid=valid,
        write_ptr=(state.write_ptr + jnp.where(keep, 1, 0).astype(jnp.int32))
        % cfg.capacity,
        n_seen=saturating_add(state.n_seen, 1),
        n_stored=state.n_stored + jnp.where(keep & ~evict, 1, 0),
        tables=tables, table_ptr=table_ptr, stamps=stamps,
    )


def sann_row_keys(key: jax.Array, n: int) -> jax.Array:
    """Per-point key schedule for a chunk: ``fold_in(key, i)`` for i < n.

    Unlike ``jax.random.split(key, n)`` (whose threefry counters pair
    ``(i, i + n)``, so every key depends on the total ``n``), this schedule
    is **prefix-stable**: ``sann_row_keys(key, m)[:b] == sann_row_keys(key,
    b)`` for b <= m.  That is what lets the tenant-fleet path
    (`core.fleet`) draw keep decisions for a cap-padded tenant block and
    land bit-identical to the unpadded single-sketch chunk."""
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(n, dtype=jnp.int32))


def sann_insert_stream(state: SANNState, params, xs: jax.Array, key: jax.Array,
                       cfg: SANNConfig) -> SANNState:
    """Per-point reference ingest of ``xs (T, d) float32``: one `lax.scan`
    step per element under the per-point key schedule `sann_row_keys`.
    `sann_insert_batch` is the production path and is bit-identical to this
    one under the same key."""
    keys = sann_row_keys(key, xs.shape[0])

    def step(s, xk):
        x, k = xk
        return sann_insert(s, params, x, k, cfg), None

    state, _ = jax.lax.scan(step, state, (xs, keys))
    return state


class SANNPrep(NamedTuple):
    """Pure per-chunk precomputation (the *prepare* phase of the two-phase
    ingest contract, DESIGN.md §10): keep decisions, relative slot ranks,
    hash codes and the sort-by-(row, code) append structure.  Everything
    here depends only on (params, chunk, key) — never on sketch state — so
    preparing chunk k+1 can overlap committing chunk k.  Slot ids and ring
    positions are *relative* (ranks); the commit rebases them on the
    state's write/table pointers."""
    xs: jax.Array          # (B, d) float32 — the chunk (points to store)
    keep: jax.Array        # (B,) bool — Bernoulli keep decisions
    kept_rank: jax.Array   # (B,) int32 — exclusive prefix sum over keep
    n_kept: jax.Array      # () int32
    winner: jax.Array      # (B,) bool — keep & survives intra-chunk ring lap
    s_l: jax.Array         # (B*L,) int32 — sorted append rows
    s_c: jax.Array         # (B*L,) int32 — sorted append codes
    s_b: jax.Array         # (B*L,) int32 — sorted append point index
    rank: jax.Array        # (B*L,) int32 — within-bucket append rank
    entry_win: jax.Array   # (B*L,) bool — append survives the ring cap
    counts: jax.Array      # (L, n_buckets) int32 — per-bucket append counts


def sann_prepare_chunk(params, xs: jax.Array, key: jax.Array,
                       cfg: SANNConfig) -> SANNPrep:
    """Prepare phase for ``xs (B, d)``: the state-independent half of
    `sann_insert_batch` —

      1. one Bernoulli draw per point from the `sann_row_keys` schedule →
         ``keep`` mask, exclusive prefix ranks, and the last-writer-wins
         mask for chunks that lap the ring (``winner``: the kept points
         within one full lap of the chunk's end — a pure function of ranks
         and capacity);
      2. one hash matmul for the whole chunk;
      3. the ring-buffer append structure: flatten (point, row) pairs, sort
         by (row, code) so each bucket's appends are a contiguous run in
         stream order, with per-bucket append counts and the cap-survivor
         mask (``rank >= seg_total - bucket_cap``).
    """
    keys = sann_row_keys(key, xs.shape[0])
    keep = jax.vmap(lambda k: jax.random.bernoulli(k, cfg.keep_prob))(keys)
    return sann_prepare_given_keep(params, xs, keep, cfg)


def sann_prepare_given_keep(params, xs: jax.Array, keep: jax.Array,
                            cfg: SANNConfig,
                            codes: Optional[jax.Array] = None) -> SANNPrep:
    """`sann_prepare_chunk` with the keep mask supplied by the caller
    (everything after the Bernoulli draws: prefix ranks, last-writer mask,
    one hash matmul, the sort-by-(row, code) append structure).

    This is the entry point for arrival sequences whose sampling already
    happened — `sann_merge` feeds it the stamp-interleaved union of two
    sketches' stored points (all pre-sampled, so ``keep`` = their validity
    mask), reusing the exact append/eviction machinery of the ingest path.

    ``codes`` (optional, (B, L) int32) skips the hash matmul — the
    tenant-fleet ingest (`core.fleet`) hashes one mixed multi-tenant chunk
    once and routes per-tenant code blocks here.
    """
    B = xs.shape[0]
    cap = cfg.capacity

    # --- slot ranks: prefix sum over kept points ---------------------------
    kept_rank = (jnp.cumsum(keep) - keep).astype(jnp.int32)  # exclusive
    n_kept = keep.sum().astype(jnp.int32)
    # Last writer per slot wins (matters only when the chunk laps the ring);
    # ranks assign slots round-robin, so the shadowed writers are exactly
    # the kept points more than one full lap from the end.
    winner = keep & (kept_rank >= n_kept - cap)

    # --- ring-buffer appends: sort-by-(row, code) segment structure --------
    if codes is None:
        codes = lsh.hash_points(params, xs)                  # (B, L)
    l_idx = jnp.broadcast_to(jnp.arange(cfg.L, dtype=jnp.int32), (B, cfg.L))
    bucket_key = l_idx * cfg.n_buckets + codes               # (B, L)
    n_flat = B * cfg.L
    n_keys = cfg.L * cfg.n_buckets
    flat_key = bucket_key.reshape(-1)
    idx = jnp.arange(B, dtype=jnp.int32)
    flat_b = jnp.broadcast_to(idx[:, None], (B, cfg.L)).reshape(-1)
    kept_flat = jnp.broadcast_to(keep[:, None], (B, cfg.L)).reshape(-1)
    sentinel = jnp.int32(n_keys)
    masked_key = jnp.where(kept_flat, flat_key, sentinel)
    if (n_keys + 1) * B < 2**31:
        # Pack (bucket key, point id) into one int32 so XLA runs a plain
        # single-operand sort — several times faster on CPU than the
        # variadic (key, iota) sort argsort lowers to.
        packed = jnp.sort(masked_key * B + flat_b)
        s_key = packed // B
        s_b = packed % B
        s_kept = s_key < sentinel
    else:  # key space too large to pack — fall back to a stable argsort
        order = jnp.argsort(masked_key, stable=True)
        s_key = masked_key[order]
        s_b = flat_b[order]
        s_kept = kept_flat[order]
    pos_idx = jnp.arange(n_flat, dtype=jnp.int32)
    seg_start = jnp.concatenate(
        [jnp.ones((1,), bool), s_key[1:] != s_key[:-1]])
    rank = pos_idx - lax.cummax(jnp.where(seg_start, pos_idx, 0))
    s_l = jnp.minimum(s_key // cfg.n_buckets, cfg.L - 1)     # clamp sentinel
    s_c = s_key % cfg.n_buckets
    # Per-bucket append counts (also the table_ptr advance).  Within a
    # bucket the appends at ring positions r, r+cap, ... shadow each other;
    # the survivors are the last `bucket_cap` ranks.
    counts = jnp.zeros((cfg.L, cfg.n_buckets), jnp.int32).at[
        l_idx, codes].add(kept_flat.reshape(B, cfg.L).astype(jnp.int32))
    seg_total = counts[s_l, s_c]
    entry_win = s_kept & (rank >= seg_total - cfg.bucket_cap)
    return SANNPrep(xs=xs, keep=keep, kept_rank=kept_rank, n_kept=n_kept,
                    winner=winner, s_l=s_l, s_c=s_c, s_b=s_b, rank=rank,
                    entry_win=entry_win, counts=counts)


def sann_commit_chunk(state: SANNState, prep: SANNPrep, cfg: SANNConfig,
                      count: Optional[jax.Array] = None) -> SANNState:
    """Commit phase: rebase a prepared chunk on the state's pointers and
    apply the dense updates — the state-sequential half of
    `sann_insert_batch`:

      1. slots = write_ptr + prepared ranks (mod capacity); point-store and
         valid-mask scatters;
      2. stale table entries pointing at slots recycled this chunk are
         tombstoned in one masked pass (the batched per-insert eviction);
      3. ring-buffer appends land at (table_ptr + prepared rank) % cap via
         one segment scatter; table_ptr advances by the prepared counts.

    ``count`` (optional, traced) overrides the stream-clock advance: the
    chunk counts as ``count`` arrivals instead of its static row count B.
    Used by the tenant-fleet path, whose per-tenant blocks are padded to a
    fixed cap with never-kept rows *after* the real prefix — the pad rows
    write nothing (keep = False ⇒ winner = False) and must not advance
    ``n_seen``, while the real prefix keeps the exact per-row arrival
    stamps of the unpadded chunk.
    """
    B = prep.xs.shape[0]
    cap = cfg.capacity
    slot = (state.write_ptr + prep.kept_rank) % cap          # (B,)
    win_slot = jnp.where(prep.winner, slot, cap)             # OOB → dropped

    points = state.points.at[win_slot].set(prep.xs, mode="drop")
    def recycled(s):
        # Slots recycled this chunk form the ring interval
        # [write_ptr + max(0, n_kept - cap), write_ptr + n_kept).
        return (s - state.write_ptr) % cap < prep.n_kept

    valid = state.valid | recycled(jnp.arange(cap, dtype=jnp.int32))

    # --- tombstone stale references to every slot recycled this chunk ------
    # The interval test runs on the entries themselves: one elementwise
    # pass over the tables, no gather.
    stale = (state.tables >= 0) & recycled(state.tables)
    tables = jnp.where(stale, jnp.int32(-1), state.tables)

    # --- ring-buffer appends: prepared segment scatter ---------------------
    # A loser point's entries are appended then tombstoned by the later
    # overwrite of its slot — net effect: the ring cell holds -1.  Entries
    # are sorted by (row, code), which is what makes this a coalesced
    # per-row write pass (kernels.ops.sann_table_scatter, DESIGN.md §12).
    val = jnp.where(prep.winner[prep.s_b], slot[prep.s_b], jnp.int32(-1))
    tables = kernel_ops.sann_table_scatter(
        tables, state.table_ptr, prep.s_l, prep.s_c, prep.rank, val,
        prep.entry_win)
    table_ptr = state.table_ptr + prep.counts

    # Logical arrival stamps: point i in the chunk arrived at stream time
    # n_seen + i (the same saturating accumulation the per-point path's
    # n_seen chain produces).
    arrival = saturating_add(state.n_seen,
                             jnp.arange(B, dtype=jnp.int32))
    stamps = state.stamps.at[win_slot].set(arrival, mode="drop")

    newly = prep.winner & ~state.valid[jnp.where(prep.winner, slot, 0)]
    return SANNState(
        points=points, valid=valid,
        write_ptr=(state.write_ptr + prep.n_kept) % cap,
        n_seen=saturating_add(state.n_seen, B if count is None else count),
        n_stored=state.n_stored + newly.sum(),
        tables=tables, table_ptr=table_ptr, stamps=stamps,
    )


def sann_insert_batch(state: SANNState, params, xs: jax.Array, key: jax.Array,
                      cfg: SANNConfig) -> SANNState:
    """Batched ingest of a whole chunk ``xs (B, d)`` in O(1) XLA steps.

    Bit-identical to ``sann_insert_stream`` under the same key (the chunk
    shares the per-point `sann_row_keys` schedule).  Composition of
    `sann_prepare_chunk` (keep decisions + hashing + sort-by-(row, code)
    append structure, pure) and `sann_commit_chunk` (pointer rebase + dense
    scatters, sequential) — the same ops, fused under one jit when called
    directly.
    """
    return sann_commit_chunk(state, sann_prepare_chunk(params, xs, key, cfg),
                             cfg)


def sann_insert_chunked(state: SANNState, params, xs: jax.Array,
                        key: jax.Array, cfg: SANNConfig,
                        chunk: int = 1024) -> SANNState:
    """Stream ``xs (T, d)`` through ``sann_insert_batch`` in fixed chunks.

    Equivalent to one big ``sann_insert_batch`` call whose key is split per
    chunk; use when T is too large to hash in one matmul.
    """
    T = xs.shape[0]
    n_full = T // chunk
    n_keys = n_full + (1 if T % chunk else 0)
    ckeys = jax.random.split(key, max(n_keys, 1))
    if n_full:
        def step(s, ck):
            c, k = ck
            return sann_insert_batch(s, params, c, k, cfg), None
        state, _ = lax.scan(
            step, state,
            (xs[: n_full * chunk].reshape(n_full, chunk, -1), ckeys[:n_full]))
    if T % chunk:
        state = sann_insert_batch(state, params, xs[n_full * chunk:],
                                  ckeys[n_full], cfg)
    return state


def sann_merge(a: SANNState, b: SANNState, params, cfg: SANNConfig) -> SANNState:
    """Union of two S-ANN sketches built (with *identical* params and cfg)
    over **disjoint** streams — the multi-worker combine.

    Under the paper's n^-eta uniform sampling, a union of independently
    sampled substreams is exactly a sample of the union stream, so merging
    is semantically just "one sketch that saw both streams".  Mechanically:

      1. **interleave by logical timestamp**: the stored points of both
         sketches are ordered by their arrival stamps (`SANNState.stamps`),
         ties broken a-before-b — a fixed, deterministic interleaving of
         the two streams (for K-way merges, fold left in worker order);
      2. **re-derive the tables**: the interleaved union is replayed as one
         pre-sampled chunk through the existing sort-by-(row, code) append
         structure (`sann_prepare_given_keep` + `sann_commit_chunk` from an
         empty state) — one hash matmul over ≤ 2·capacity points;
      3. **tombstone-consistent eviction**: if the union exceeds
         ``capacity``, the ring keeps the newest ``capacity`` points by
         stamp and the evicted (oldest) points' table entries come out as
         -1 — exactly the eviction rule of the ingest path.

    Counters combine saturating (``n_seen``, like every ingest path);
    ``n_stored``/``write_ptr``/``valid`` are re-derived from the union.
    When neither input has ever evicted or wrapped, the merged sketch is
    bit-identical (modulo ``stamps``, which keep their per-stream clocks)
    to a single sketch fed the interleaved stream with the same keep
    decisions (tests/test_cluster.py); with eviction, the *live point set*
    and query answers still match, but slot ids rotate by the evicted
    count.  Associative at the stored-set level: the newest-``capacity``
    rule commutes with folding (tests/test_distributed.py).
    """
    cap = cfg.capacity
    pts = jnp.concatenate([a.points, b.points])              # (2*cap, d)
    valid = jnp.concatenate([a.valid, b.valid])
    stamps = jnp.concatenate([a.stamps, b.stamps])
    # Stable order: valid entries first, by ascending stamp; ties keep input
    # order (a's entries, then b's — and slot order within one sketch, which
    # is arrival order between two stamps of the same value post-saturation).
    order = jnp.lexsort((stamps, ~valid))
    xs = pts[order]
    keep = valid[order]
    st_sorted = stamps[order]

    prep = sann_prepare_given_keep(params, xs, keep, cfg)
    merged = sann_commit_chunk(sann_empty_state(cfg), prep, cfg)
    # The commit stamped slots with their union-chunk offsets; restore the
    # true per-stream arrival stamps so later merges interleave correctly.
    slot = prep.kept_rank % cap
    win_slot = jnp.where(prep.winner, slot, cap)
    return merged._replace(
        stamps=merged.stamps.at[win_slot].set(st_sorted, mode="drop"),
        n_seen=saturating_add(a.n_seen, b.n_seen),
    )


def sann_delete(state: SANNState, params, x: jax.Array, cfg: SANNConfig,
                tol: float = 1e-5) -> SANNState:
    """Turnstile delete-by-value (§3.4): tombstone every stored copy of x."""
    d2 = jnp.sum((state.points - x) ** 2, axis=-1)
    hit = state.valid & (d2 <= tol)
    valid = state.valid & ~hit
    # Tombstone table entries pointing at deleted slots.
    dead = hit[jnp.maximum(state.tables, 0)] & (state.tables >= 0)
    tables = jnp.where(dead, -1, state.tables)
    return state._replace(valid=valid, tables=tables,
                          n_stored=state.n_stored - hit.sum())


def bucket_cols(codes: jax.Array, bucket_cap: int) -> jax.Array:
    """Table columns of the buckets ``codes (..., L)`` → ``(..., L,
    bucket_cap)``: the ring of bucket c is columns [c·cap, (c+1)·cap)."""
    return (codes[..., None] * bucket_cap
            + jnp.arange(bucket_cap, dtype=codes.dtype))


class SANNResult(NamedTuple):
    index: jax.Array      # slot id of returned point (-1 = NULL)
    distance: jax.Array   # distance to returned point (inf = NULL)
    found: jax.Array      # bool — success per the (c,r) contract
    n_candidates: jax.Array


def sann_bucket_candidates(state: SANNState, params, q: jax.Array,
                           cfg: SANNConfig):
    """Gather the colliding buckets for ``q (d,) float32``.

    Returns ``(cand, ok)``: candidate slot ids ``(cfg.L * bucket_cap,)
    int32`` in row-major table order and their validity mask (entry is a
    live stored point).  Split out from `sann_query` so the table-sharded
    path (`repro.parallel.sketch_sharding`) can gather per-shard candidate
    blocks and concatenate them — shard-order concatenation reproduces this
    exact row-major order."""
    codes = lsh.hash_points(params, q)                          # (L,)
    rows = jnp.arange(cfg.L)
    cand = state.tables[rows[:, None],
                        bucket_cols(codes, cfg.bucket_cap)].reshape(-1)
    ok = (cand >= 0) & state.valid[jnp.maximum(cand, 0)]
    return cand, ok


def sann_score_candidates(points: jax.Array, cand: jax.Array, ok: jax.Array,
                          q: jax.Array, budget: int,
                          cfg: SANNConfig) -> SANNResult:
    """Truncate-and-score: keep the first ``budget`` valid candidates (the
    paper's 3L early-exit), score via `repro.kernels.cand_score`, return the
    argmin if within c*r (Fig. 2).

    ``points (capacity, d) float32`` is the (replicated, in the sharded
    case) point store; ``cand/ok`` come from `sann_bucket_candidates`."""
    # Truncate to the budget: stable-sort invalid entries last, keep the
    # first ``budget``.
    order = jnp.argsort(jnp.where(ok, 0, 1), stable=True)
    sel = order[:budget]
    cand, ok = cand[sel], ok[sel]
    vecs = points[jnp.maximum(cand, 0)]                         # (budget, dim)
    d2 = kernel_ops.cand_score(q, vecs)                         # (budget,)
    d2 = jnp.where(ok, d2, jnp.inf)
    best = jnp.argmin(d2)
    dist = jnp.sqrt(d2[best])
    found = dist <= cfg.c * cfg.r
    return SANNResult(
        index=jnp.where(found, cand[best], -1),
        distance=jnp.where(found, dist, jnp.inf),
        found=found,
        n_candidates=ok.sum(),
    )


def sann_query(state: SANNState, params, q: jax.Array, cfg: SANNConfig) -> SANNResult:
    """Alg. 1 query: gather L buckets, truncate to 3L candidates, score,
    return argmin if within c*r (Fig. 2).

    ``q (d,) float32`` → `SANNResult` of scalars (index -1 / distance inf
    encode the paper's NULL answer)."""
    cand, ok = sann_bucket_candidates(state, params, q, cfg)
    return sann_score_candidates(state.points, cand, ok, q, 3 * cfg.L, cfg)


def sann_bucket_candidates_batch(state: SANNState, params, qs: jax.Array,
                                 cfg: SANNConfig):
    """Batched bucket gather: ``qs (B, d)`` → ``(cand (B, L*bucket_cap)
    int32, ok (B, L*bucket_cap) bool)``.

    One hash matmul + one table gather for the whole batch; per query the
    candidate order is the same row-major table order as
    `sann_bucket_candidates`, so the table-sharded path can all-gather
    per-shard blocks along axis 1 and reproduce the single-device order."""
    codes = lsh.hash_points(params, qs)                         # (B, L)
    cand = state.tables[jnp.arange(cfg.L)[None, :, None],
                        bucket_cols(codes, cfg.bucket_cap)]     # (B, L, cap)
    # explicit flat shape (not -1): keeps B = 0 batches reshapeable
    cand = cand.reshape(qs.shape[0], cfg.L * cfg.bucket_cap)
    ok = (cand >= 0) & state.valid[jnp.maximum(cand, 0)]
    return cand, ok


def sann_score_candidates_batch(points: jax.Array, cand: jax.Array,
                                ok: jax.Array, qs: jax.Array, budget: int,
                                cfg: SANNConfig) -> SANNResult:
    """Batched truncate-and-score: the whole-batch form of
    `sann_score_candidates`, returning a `SANNResult` with (B,) fields.

    The per-query stable argsort that implemented the 3L truncation is
    replaced by a masked cumulative-count keep rule evaluated batch-wide:
    a candidate is kept iff it is valid and fewer than ``budget`` valid
    candidates precede it in its row.  The keep positions are *located*
    (rather than sorted into place) by a binary search on the row's
    running valid count — ``searchsorted(cumsum(ok), j+1)`` is the column
    of the j-th valid candidate — which costs O(B·budget·log C) instead of
    the O(B·C·log C) sort (and avoids XLA/CPU's slow scatter and top_k;
    see the ingest-side precedent in `sann_insert_batch`).  The compacted
    ``(B, budget)`` block preserves table order, so scoring it with the
    fused batch scorer (`repro.kernels.ops.batch_score_topk`, k = 1 ⇒
    masked argmin) returns results identical to vmapping
    `sann_score_candidates` (tests/test_query_batched.py)."""
    C = cand.shape[1]
    budget_eff = min(budget, C)
    csum = jnp.cumsum(ok, axis=1).astype(jnp.int32)         # running count
    targets = jnp.arange(1, budget_eff + 1, dtype=jnp.int32)
    sel = jax.vmap(lambda a: jnp.searchsorted(a, targets, side="left"))(csum)
    sel_ok = sel < C                   # j-th valid exists ⇔ search stayed in
    sel = jnp.minimum(sel, C - 1)
    sel_cand = jnp.where(sel_ok, jnp.take_along_axis(cand, sel, axis=1), -1)
    vecs = points[jnp.maximum(sel_cand, 0)]                 # (B, budget, dim)
    d2, idx = kernel_ops.batch_score_topk(qs, vecs, sel_ok, 1)  # (B, 1) each
    dist = jnp.sqrt(d2[:, 0])
    found = dist <= cfg.c * cfg.r
    best = jnp.take_along_axis(sel_cand, idx, axis=1)[:, 0]
    return SANNResult(
        index=jnp.where(found, best, -1),
        distance=jnp.where(found, dist, jnp.inf),
        found=found,
        n_candidates=jnp.minimum(csum[:, -1], budget).astype(jnp.int32),
    )


def sann_query_batch(state: SANNState, params, qs: jax.Array, cfg: SANNConfig) -> SANNResult:
    """Batch queries (§3.3 / Corollary 3.2) — fused batch-level pipeline.

    ``qs (B, d) float32`` → `SANNResult` with (B,) fields.  One hash matmul
    and one table gather cover the whole batch
    (`sann_bucket_candidates_batch`), the 3L truncation is a batch-wide
    keep-mask, and scoring is one fused kernel call
    (`sann_score_candidates_batch`) — results identical to vmapping
    `sann_query` per query (which remains the oracle path)."""
    cand, ok = sann_bucket_candidates_batch(state, params, qs, cfg)
    return sann_score_candidates_batch(state.points, cand, ok, qs,
                                       3 * cfg.L, cfg)


def sann_bytes(cfg: SANNConfig) -> int:
    """Concrete sketch footprint for the Fig.-5 memory-scaling benchmark."""
    cfg = cfg.resolved()
    # points + valid + arrival stamps
    pts = cfg.capacity * cfg.dim * 4 + cfg.capacity + cfg.capacity * 4
    tbl = cfg.L * cfg.n_buckets * (cfg.bucket_cap + 1) * 4
    return pts + tbl


def sann_query_topk(state: SANNState, params, q: jax.Array, cfg: SANNConfig,
                    topk: int = 50):
    """Top-k variant for recall benchmarks (no 3L truncation, no (c,r)
    contract): score the full bucket union, dedup repeated slot ids, return
    the k closest.

    ``q (d,) float32`` → ``(ids (k,) int32, dists (k,) float32)`` with
    ``k = min(topk, cfg.L * bucket_cap)``, sorted by ascending distance and
    padded with id -1 / distance inf when fewer than k live candidates
    collide.  The table-sharded path merges per-shard results of this
    function (`sketch_sharding.sharded_sann_query_topk_batch`): the global
    top-k is contained in the union of per-shard top-ks, so the merge is
    exact."""
    codes = lsh.hash_points(params, q)
    rows = jnp.arange(cfg.L)
    cand = state.tables[rows[:, None],
                        bucket_cols(codes, cfg.bucket_cap)].reshape(-1)
    ok = (cand >= 0) & state.valid[jnp.maximum(cand, 0)]
    vecs = state.points[jnp.maximum(cand, 0)]
    d2 = jnp.where(ok, kernel_ops.cand_score(q, vecs), jnp.inf)
    # dedup identical slots: keep first occurrence
    sort_idx = jnp.argsort(cand)
    sorted_c = cand[sort_idx]
    dup = jnp.concatenate([jnp.zeros((1,), bool),
                           sorted_c[1:] == sorted_c[:-1]])
    dedup_mask = jnp.zeros_like(ok).at[sort_idx].set(~dup)
    d2 = jnp.where(dedup_mask, d2, jnp.inf)
    neg, idx = jax.lax.top_k(-d2, min(topk, d2.shape[0]))
    ids = jnp.where(jnp.isfinite(-neg), cand[idx], -1)
    return ids, jnp.sqrt(-neg)


def _first_occurrence_mask(cand: jax.Array, capacity: int) -> jax.Array:
    """Batch-wide duplicate-slot mask: True at the first occurrence (lowest
    column) of each slot id per row of ``cand (B, C)`` — the batched form of
    the per-query sort-and-compare dedup in `sann_query_topk`, identical
    masks.

    When the slot-id range is small enough, first occurrences come from a
    scatter-min of column positions keyed by slot id — O(B·(C + capacity))
    and no sort.  Otherwise one batched stable argsort along the candidate
    axis marks duplicates exactly like the per-query path."""
    B, C = cand.shape
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    pos = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32), (B, C))
    if capacity + 1 <= max(4096, 8 * C):
        # cand ∈ [-1, capacity) → key range [0, capacity]; -1 gets its own key
        first = jnp.full((B, capacity + 1), C, jnp.int32).at[
            rows, cand + 1].min(pos)
        return first[rows, cand + 1] == pos
    order = jnp.argsort(cand, axis=1)                      # stable
    sorted_c = jnp.take_along_axis(cand, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((B, 1), bool), sorted_c[:, 1:] == sorted_c[:, :-1]], axis=1)
    return jnp.zeros_like(dup).at[rows, order].set(~dup)


def sann_query_topk_batch(state, params, qs, cfg: SANNConfig, topk: int = 50):
    """Batched `sann_query_topk`: ``qs (B, d)`` → ``(ids (B, k), dists
    (B, k))`` with the same padding/ordering contract.

    Fused batch-level pipeline: one hash matmul + one gather for all B
    queries, batch-wide duplicate-slot dedup (`_first_occurrence_mask`),
    and one fused masked top-k scorer call — identical outputs to vmapping
    `sann_query_topk` per query (which remains the oracle path)."""
    cand, ok = sann_bucket_candidates_batch(state, params, qs, cfg)
    mask = ok & _first_occurrence_mask(cand, state.points.shape[0])
    vecs = state.points[jnp.maximum(cand, 0)]              # (B, C, dim)
    k = min(topk, cand.shape[1])
    d2, idx = kernel_ops.batch_score_topk(qs, vecs, mask, k)   # (B, k) each
    ids = jnp.where(jnp.isfinite(d2), jnp.take_along_axis(cand, idx, axis=1),
                    -1)
    return ids, jnp.sqrt(d2)
