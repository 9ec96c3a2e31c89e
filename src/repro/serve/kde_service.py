"""SW-AKDE density service: streaming sliding-window KDE with pipelined
ingest and batched queries (paper §4).

The serving-side integration of the paper's second sketch, mirroring
`repro.serve.retrieval.RetrievalService`: points arrive as a stream of
embeddings, the service maintains the sliding-window EH grid, and answers
batched density queries — e.g. drift monitoring over a decode-time
activation stream, or novelty scoring of incoming requests.

Runtime: the service is a `repro.serve.engine.SketchEngine` — the shared
streaming runtime owns the lock, the chunk loop, the two-phase pipelined
ingest (`core.swakde.swakde_prepare_chunk` hashing + sorting chunk k+1 on
the prepare thread while `swakde_commit_chunk` replays chunk k into the EH
grid) and the background queue (``ingest_async`` / ``flush``).

Query-side snapshot cache: the (L, W) grid-estimate table
(`core.swakde.swakde_grid_estimates`) is pure given the committed state, so
the service caches it per commit version (``cache_grid=True``) and serves
*every* query batch — including B < W — as one hash matmul + one table
gather, bit-identical to the uncached fused path.  Any commit invalidates
the cache (tests/test_engine.py pins this).

Multi-device: set ``num_shards`` (or pass a ``mesh``) to split the L
sketch rows across devices via `repro.parallel.sketch_sharding` — both
ingest phases run per row shard and queries all-gather the per-row
estimates; results stay bit-identical to the single-device service.
``mesh=None, num_shards<=1`` (the default) keeps the single-device path
untouched.

This is a thin, stateful orchestration layer over repro.core.swakde; all
math lives there (and is what the paper's Theorem 4.1 guarantee covers).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import persist
from repro.core import lsh, swakde
from repro.parallel import sketch_sharding as ss
from repro.serve.engine import SketchEngine, durability_from


@dataclasses.dataclass
class KDEServiceConfig:
    dim: int
    L: int = 16              # sketch rows (repetitions)
    W: int = 128             # LSH range after rehash
    window: int = 10_000     # sliding-window length N (stream steps)
    eh_eps: float = 0.1      # per-cell EH relative error eps'
    hash_family: str = "srp"  # "srp" (angular) | "pstable" (Euclidean)
    k: int = 2               # concatenation power p
    w: float = 4.0           # p-stable bucket width (pstable only)
    seed: int = 0
    # Batched-ingest chunk: one prepare/commit pair per chunk; each distinct
    # partial-chunk size triggers one extra jit trace.
    ingest_chunk: int = 1024
    # Two-phase pipelining: prepare chunk k+1 on the engine's prepare thread
    # while chunk k commits.  False = strictly sequential phases (identical
    # results; the ingest-benchmark baseline).
    pipelined: bool = True
    # Prepare lookahead depth: chunks the prepare pool may run ahead of the
    # commit side (1 = classic double buffering).  Results are
    # bit-identical at any depth; deeper lookahead helps once commits are
    # cheap (the closed-form segment fold) and the producer is bursty.
    prepare_depth: int = 1
    # Skew guard (DESIGN.md §12): bound how many adds one (row, cell)
    # segment absorbs per commit pass; 0 = uncapped.  Bit-identical for
    # any value — a per-tile work bound, not an accuracy knob.
    heavy_cell_cap: int = 0
    # Query block: queries are answered in blocks of this many rows; each
    # distinct partial-block size triggers one extra jit trace.
    query_block: int = 1024
    # Snapshot cache: memoise the (L, W) grid-estimate table per committed
    # state (invalidated on every commit) and serve all query batches from
    # it — one hash matmul + one gather per block, no EH arithmetic.
    # False = recompute through the fused engine every call (bit-identical
    # results either way).
    cache_grid: bool = True
    # Cross-request query micro-batching (DESIGN.md §13): coalesce
    # concurrent clients' queries into one fused batch per scheduler tick
    # (max ``max_batch`` rows, ``max_wait_us`` latency budget), sharing one
    # state snapshot and one grid-cache entry across the coalesced batch.
    # Bit-identical answers; ``submit_query`` works either way.
    batch_queries: bool = False
    max_batch: Optional[int] = None
    max_wait_us: float = 200.0
    # Multi-device sharding: num_shards > 1 splits the L rows across that
    # many local devices (L must divide evenly); ``mesh`` overrides with a
    # prebuilt 1-D ("shard",) mesh.  Both unset → single-device.
    num_shards: int = 0
    mesh: Optional[object] = None   # jax.sharding.Mesh
    # Admission control: bound on queued-but-uncommitted rows; ingest_async
    # blocks (backpressure) at the bound.  None = unbounded queue.
    max_pending: Optional[int] = None
    # Durability (repro.persist): WAL-logged chunks + background snapshots
    # under ``snapshot_dir``; ``recover()`` restores bit-identically.
    snapshot_dir: Optional[str] = None
    snapshot_every: int = 64
    wal_fsync: bool = False
    # Fault-injection site-name prefix (repro.persist.faults,
    # DESIGN.md §14); the cluster sets ``worker_<w>/`` per worker.
    fault_scope: str = ""


class KDEService(SketchEngine):
    """Thread-safe streaming sliding-window KDE with pipelined ingest,
    batched queries and a per-commit grid snapshot cache (shared runtime:
    `repro.serve.engine.SketchEngine`)."""

    def __init__(self, cfg: KDEServiceConfig):
        self.cfg = cfg
        self.sketch_cfg = swakde.SWAKDEConfig(
            L=cfg.L, W=cfg.W, window=cfg.window, eh_eps=cfg.eh_eps,
            heavy_cell_cap=cfg.heavy_cell_cap)
        key = jax.random.PRNGKey(cfg.seed)
        if cfg.hash_family == "srp":
            self.params = lsh.init_srp(key, cfg.dim, L=cfg.L, k=cfg.k,
                                       n_buckets=cfg.W)
        elif cfg.hash_family == "pstable":
            self.params = lsh.init_pstable(key, cfg.dim, L=cfg.L, k=cfg.k,
                                           w=cfg.w, n_buckets=cfg.W)
        else:
            raise ValueError(cfg.hash_family)
        self._segment_width: Optional[int] = None
        super().__init__(ingest_chunk=cfg.ingest_chunk,
                         query_block=cfg.query_block,
                         pipelined=cfg.pipelined,
                         prepare_depth=cfg.prepare_depth,
                         max_pending=cfg.max_pending,
                         durability=durability_from(cfg),
                         batch_queries=cfg.batch_queries,
                         max_batch=cfg.max_batch,
                         max_wait_us=cfg.max_wait_us,
                         fault_scope=cfg.fault_scope)
        self.state = swakde.swakde_init(self.sketch_cfg)

        self._ctx = ss.make_service_ctx(cfg.mesh, cfg.num_shards)
        if self._ctx.mesh is not None:
            self.state, self.params = ss.shard_swakde(self.state, self.params,
                                                      self._ctx)
        self._prepare_fn = jax.jit(
            lambda xs: ss.sharded_swakde_prepare_chunk(
                self.params, xs, self.sketch_cfg, self._ctx))
        self._commit_fn = jax.jit(
            lambda st, prep: ss.sharded_swakde_commit_chunk(
                st, prep, self.sketch_cfg, self._ctx))
        self._query_fn = jax.jit(
            lambda st, qs: ss.sharded_swakde_query_batch(
                st, self.params, qs, self.sketch_cfg, self._ctx))
        self._grid_fn = jax.jit(
            lambda st: ss.sharded_swakde_grid_estimates(
                st, self.sketch_cfg, self._ctx))
        self._grid_query_fn = jax.jit(
            lambda grid, qs: ss.sharded_swakde_query_from_grid(
                grid, self.params, qs, self.sketch_cfg, self._ctx))

    # --- engine hooks (two-phase ingest) -----------------------------------

    def _prepare(self, chunk: jax.Array) -> swakde.SWAKDEPrep:
        return self._prepare_fn(chunk)

    def _commit(self, state: swakde.SWAKDEState, prep: swakde.SWAKDEPrep):
        self._segment_width = prep.seg_code.shape[1]
        return self._commit_fn(state, prep)

    def _place_state(self, state: swakde.SWAKDEState) -> swakde.SWAKDEState:
        if self._ctx.mesh is None:
            return state
        return ss.shard_swakde(state, self.params, self._ctx)[0]

    def _apply_wal_record(self, kind: int, arrays: dict) -> None:
        if kind == persist.KIND_CLOCK:
            t = int(np.asarray(arrays["t"]))
            self._mutate_state(
                lambda st: st._replace(t=jnp.maximum(st.t, jnp.int32(t))))
            return
        super()._apply_wal_record(kind, arrays)

    # --- serving API -------------------------------------------------------

    def advance_clock(self, target: int) -> None:
        """Advance the sliding-window clock to ``max(t, target)`` without
        ingesting points — expiring EH buckets exactly as if ``target - t``
        empty stream steps had passed.

        This is the coordinator-assigned *global clock* option for cluster
        SW-AKDE (`repro.serve.cluster.ClusterKDEService(global_clock=True)`,
        DESIGN.md §10): each worker's local clock counts only its own
        partition's arrivals, so windows expire in partition-local time;
        folding in the coordinator's logical clock after every ingest makes
        every worker expire in *stream* time instead.  Pending async chunks
        flush first; when durable the advance is WAL-logged
        (``KIND_CLOCK``) and replays bit-identically on ``recover()``."""
        t = int(target)
        self._durable_mutate(
            persist.KIND_CLOCK, {"t": np.asarray(t, np.int32)},
            lambda st: st._replace(t=jnp.maximum(st.t, jnp.int32(t))))

    def stats(self) -> dict:
        """`SketchEngine.stats` plus ``segment_width``: the segments per
        row of the prep that the last commit ran over (None before one)."""
        out = super().stats()
        out["segment_width"] = self._segment_width
        return out

    @property
    def num_shards(self) -> int:
        """Devices the rows are split across (1 = single-device path)."""
        return ss.ctx_num_shards(self._ctx)

    # --- query kinds (micro-batching; engine._BatchedQueryMixin) -----------

    _default_query_kind = "kde"

    def _query_snapshot_ctx(self):
        """One lock-consistent ``(state, version, grid)`` serving a whole
        query tick: with ``cache_grid`` the per-version grid table is
        resolved here — computed at most once per commit and shared by
        every query of the coalesced batch (a commit bumps the version, so
        a stale grid can never be paired with a newer state)."""
        state, version = self.snapshot()
        grid = None
        if self.cfg.cache_grid:
            grid = self.cached("grid", version,
                               lambda: jax.block_until_ready(
                                   self._grid_fn(state)))
        return state, version, grid

    def _query_kind_fns(self):
        def kde(ctx, qs):
            state, _, grid = ctx
            if grid is not None:
                out = self._query_blocks(
                    lambda b: self._grid_query_fn(grid, b), qs)
            else:
                out = self._query_blocks(
                    lambda b: self._query_fn(state, b), qs)
            return np.asarray(out)

        def density(ctx, qs):
            # Ŷ and the window clock from the *same* snapshot; the batch-
            # wide scalar divide is elementwise, so per-row results equal
            # an unbatched density() call bit-for-bit.
            state = ctx[0]
            denom = max(min(int(state.t), self.cfg.window), 1)
            return kde(ctx, qs) / float(denom)

        return {"kde": kde, "density": density}

    def query(self, queries: np.ndarray) -> np.ndarray:
        """Batched unnormalised window-density estimates Ŷ (Thm 4.1) against
        one committed snapshot, in ``query_block`` blocks.  With
        ``batch_queries`` the call is coalesced with concurrent clients'
        queries into one fused batch sharing one grid-cache entry
        (bit-identical results)."""
        return self._serve_query("kde", queries)

    def density(self, queries: np.ndarray) -> np.ndarray:
        """Normalised sliding-window density: Ŷ / min(t, N) — the state and
        the clock come from the *same* snapshot (micro-batched like
        `query` when ``batch_queries`` is set)."""
        return self._serve_query("density", queries)

    @property
    def steps(self) -> int:
        """Stream steps consumed so far."""
        return int(self.state.t)

    @property
    def sketch_bytes(self) -> int:
        return swakde.swakde_bytes(self.sketch_cfg)
