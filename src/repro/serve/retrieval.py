"""S-ANN retrieval service: streaming index + batched queries (paper §3).

The serving-side integration of the paper's sketch: documents (or cached
hidden states) arrive as a stream of embeddings; the service maintains the
sublinear S-ANN sketch and answers batched (c, r)-ANN queries — e.g. for
retrieval-augmented decoding, the per-step query batch is the batch of
current decoder hidden states.

Runtime: the service is a `repro.serve.engine.SketchEngine` — the shared
streaming runtime owns the lock, the chunk loop, the two-phase pipelined
ingest (`core.sann.sann_prepare_chunk` hashing chunk k+1 on the prepare
thread while `sann_commit_chunk` folds chunk k in), the background queue
(``ingest_async`` / ``flush``) and the versioned query snapshots.  Queries
always read one committed prefix of the stream (never a torn state).

Multi-device: set ``num_shards`` (or pass a ``mesh``) to split the L hash
tables across devices via `repro.parallel.sketch_sharding` — both ingest
phases run per table shard, queries all-gather candidate blocks, and
results stay bit-identical to the single-device service.  ``mesh=None,
num_shards<=1`` (the default) keeps the single-device path untouched.

This is a thin, stateful orchestration layer over repro.core.sann; all math
lives there (and is what the paper's guarantees cover).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import persist
from repro.core import sann
from repro.parallel import sketch_sharding as ss
from repro.serve.engine import SketchEngine, durability_from


@dataclasses.dataclass
class RetrievalConfig:
    dim: int
    n_max: int = 100_000
    eta: float = 0.5
    r: float = 0.9
    c: float = 2.0
    w: float = 4.0
    L: Optional[int] = 16
    k: Optional[int] = 8
    bucket_cap: int = 16
    seed: int = 0
    # Ingest-key salt: folded into the per-chunk key schedule so cluster
    # workers sharing one `seed` (→ identical LSH params, required for
    # merging) still draw independent keep decisions.
    ingest_salt: int = 0
    # Batched-ingest chunk: each chunk is one prepare (hash matmul + sort)
    # plus one commit (segment scatter).  Larger chunks amortise more; each
    # distinct partial-chunk size triggers one extra jit trace.
    ingest_chunk: int = 1024
    # Two-phase pipelining: prepare chunk k+1 on the engine's prepare thread
    # while chunk k commits.  False = strictly sequential phases (identical
    # results; the ingest-benchmark baseline).
    pipelined: bool = True
    # Prepare lookahead depth: chunks the prepare pool may run ahead of the
    # commit side (1 = classic double buffering; bit-identical either way).
    prepare_depth: int = 1
    # Query block: queries are served through the fused batch engine
    # (core.sann.sann_query_batch) in blocks of this many rows — bounds the
    # (block, 3L, dim) scoring footprint; each distinct partial-block size
    # triggers one extra jit trace.
    query_block: int = 1024
    # Top-k result width for `query_topk` / the "topk" query kind (recall
    # workloads; no (c, r) contract) — capped at L * bucket_cap.
    topk: int = 50
    # Cross-request query micro-batching (DESIGN.md §13): with
    # ``batch_queries`` the sync query wrappers enqueue with the admission
    # scheduler, which coalesces concurrent clients into one fused batch
    # per tick — at most ``max_batch`` rows (None = query_block), waiting
    # at most ``max_wait_us`` for the batch to fill.  Answers stay
    # bit-identical to unbatched calls; ``submit_query`` (future-returning)
    # is available either way.
    batch_queries: bool = False
    max_batch: Optional[int] = None
    max_wait_us: float = 200.0
    # Multi-device sharding: num_shards > 1 splits the L tables across that
    # many local devices (L must divide evenly); ``mesh`` overrides with a
    # prebuilt 1-D ("shard",) mesh.  Both unset → single-device.
    num_shards: int = 0
    mesh: Optional[object] = None   # jax.sharding.Mesh
    # Admission control: bound on queued-but-uncommitted rows; ingest_async
    # blocks (backpressure) at the bound.  None = unbounded queue.
    max_pending: Optional[int] = None
    # Durability (repro.persist): set ``snapshot_dir`` to WAL-log every
    # ingest chunk at enqueue time and write background state snapshots
    # every ``snapshot_every`` committed operations; ``recover()`` then
    # restores snapshot + WAL tail bit-identically after a crash.
    snapshot_dir: Optional[str] = None
    snapshot_every: int = 64
    wal_fsync: bool = False
    # Fault-injection site-name prefix (repro.persist.faults,
    # DESIGN.md §14); the cluster sets ``worker_<w>/`` per worker.
    fault_scope: str = ""


class RetrievalService(SketchEngine):
    """Thread-safe streaming ANN index with pipelined ingest and batched
    queries (shared runtime: `repro.serve.engine.SketchEngine`)."""

    def __init__(self, cfg: RetrievalConfig):
        self.service_cfg = cfg
        base = sann.SANNConfig(
            dim=cfg.dim, n_max=cfg.n_max, eta=cfg.eta, r=cfg.r, c=cfg.c,
            w=cfg.w, L=cfg.L, k=cfg.k, bucket_cap=cfg.bucket_cap)
        # The state is built where it lives (on the mesh when sharded).
        self._ctx = ss.make_service_ctx(cfg.mesh, cfg.num_shards)
        self.cfg, self.params, state = ss.sharded_sann_init(
            base, jax.random.PRNGKey(cfg.seed), self._ctx)
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
        self.state = state
        # Per-chunk keys are fold_in(base, chunk seq): a pure function of
        # the chunk's global sequence number, so the schedule is identical
        # across sync/async ingest and across crash-recovery replay.
        self._ingest_key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.seed + 1), cfg.ingest_salt)

        self._prepare_fn = jax.jit(
            lambda xs, key: ss.sharded_sann_prepare_chunk(
                self.params, xs, key, self.cfg, self._ctx))
        self._commit_fn = jax.jit(
            lambda st, prep: ss.sharded_sann_commit_chunk(
                st, prep, self.cfg, self._ctx))
        self._query_fn = jax.jit(
            lambda st, qs: ss.sharded_sann_query_batch(
                st, self.params, qs, self.cfg, self._ctx))
        self._topk_fn = jax.jit(
            lambda st, qs: ss.sharded_sann_query_topk_batch(
                st, self.params, qs, self.cfg, self._ctx,
                topk=self.service_cfg.topk))
        self._delete_fn = jax.jit(
            lambda st, x: ss.sharded_sann_delete(
                st, self.params, x, self.cfg, self._ctx))

    # --- engine hooks (two-phase ingest) -----------------------------------

    def _make_chunk_item(self, chunk: jax.Array, seq: int) -> tuple:
        # Per-chunk key = fold_in(base, chunk seq): deterministic given the
        # submission order, identical for sync/async ingest and for the
        # crash-recovery replay of the same sequence numbers.
        return (chunk, jax.random.fold_in(self._ingest_key, seq))

    def _prepare(self, chunk: jax.Array, key: jax.Array) -> sann.SANNPrep:
        return self._prepare_fn(chunk, key)

    def _commit(self, state: sann.SANNState, prep: sann.SANNPrep):
        return self._commit_fn(state, prep)

    def _place_state(self, state: sann.SANNState) -> sann.SANNState:
        if self._ctx.mesh is None:
            return state
        return ss.shard_sann(state, self.params, self._ctx)[0]

    def _apply_wal_record(self, kind: int, arrays: dict) -> None:
        if kind == persist.KIND_DELETE:
            x = jnp.asarray(arrays["x"])
            self._mutate_state(lambda st: self._delete_fn(st, x))
            return
        super()._apply_wal_record(kind, arrays)

    # --- query kinds (micro-batching; engine._BatchedQueryMixin) -----------

    _default_query_kind = "cr"

    def _query_kind_fns(self):
        """Both S-ANN query kinds — the (c, r) contract and the top-k
        recall variant — read one snapshot's state through the fused batch
        engine in ``query_block`` blocks, so a coalesced tick can mix
        them against the same committed prefix."""
        def cr(ctx, qs):
            state, _ = ctx
            return self._query_blocks(lambda b: self._query_fn(state, b), qs)

        def topk(ctx, qs):
            state, _ = ctx
            return self._query_blocks(lambda b: self._topk_fn(state, b), qs)

        return {"cr": cr, "topk": topk}

    # --- serving API -------------------------------------------------------

    @property
    def num_shards(self) -> int:
        """Devices the tables are split across (1 = single-device path)."""
        return ss.ctx_num_shards(self._ctx)

    def delete(self, embedding: np.ndarray) -> None:
        """Turnstile deletion (paper §3.4).  Pending async chunks are
        flushed first, then the delete applies atomically — so apply order
        equals submission order (and, with durability, WAL order; the
        delete is logged before it applies)."""
        x = jnp.asarray(embedding)
        self._durable_mutate(persist.KIND_DELETE,
                             {"x": np.asarray(x, np.float32)},
                             lambda st: self._delete_fn(st, x))

    def query(self, queries: np.ndarray) -> sann.SANNResult:
        """Batched (c, r)-queries (paper §3.3) through the fused batch
        engine, in blocks of ``query_block`` rows (one hash matmul + one
        gather + one fused scorer call per block) — all blocks against one
        lock-consistent snapshot of the committed state.  With
        ``batch_queries`` the call is coalesced with concurrent clients'
        queries into one fused batch (bit-identical results)."""
        return self._serve_query("cr", queries)

    def query_topk(self, queries: np.ndarray):
        """Batched top-k queries (recall workloads; no (c, r) contract):
        ``(B, d)`` → ``(ids (B, k), dists (B, k))`` with ``k = min(cfg.topk,
        L * bucket_cap)``, padded with id -1 / distance inf.  Same snapshot
        and micro-batching semantics as `query`."""
        return self._serve_query("topk", queries)

    def stats(self) -> dict:
        """`SketchEngine.stats` plus ``shard_state_bytes``: the bytes of
        index state one chip holds (``per_chip``) and the part that every
        chip holds alike (``replicated``: the point store, valid mask,
        stamps and counters when sharded; everything on one device)."""
        out = super().stats()
        out["shard_state_bytes"] = ss.state_bytes_per_device(
            self.snapshot()[0])
        return out

    @property
    def stored(self) -> int:
        return int(self.state.n_stored)

    @property
    def sketch_bytes(self) -> int:
        return sann.sann_bytes(self.cfg)
