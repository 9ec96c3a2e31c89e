"""Merge-based multi-worker cluster runtime (repro.serve.cluster):

  * hash partitioning is deterministic, order-independent and covering;
  * RACE cluster estimates are bit-exact vs a single engine (counter sums
    are exact) for K in {2, 4};
  * SW-AKDE cluster estimates are bit-exact vs a single engine while the
    window holds everything (the DGIM bucket-union of a partition
    canonicalises to the sequential structure until expiry);
  * S-ANN cluster queries equal a single engine fed the cluster's fixed
    logical-time interleaving (indices exact, distances <= 1e-5) — and the
    full state matches bitwise except the per-stream stamp clocks;
  * the merge cadence: a huge ``merge_every`` still serves every commit
    (query-time tail merge), a tiny one pre-merges — identical answers;
  * per-worker durability: a durable cluster recovers bit-identically.
"""
import numpy as np

import jax

from repro.serve.cluster import (
    ClusterKDEService, ClusterRACEService, ClusterRetrievalService,
    hash_partition,
)
from repro.serve.kde_service import KDEService, KDEServiceConfig
from repro.serve.race_service import RACEService, RACEServiceConfig
from repro.serve.retrieval import RetrievalConfig, RetrievalService

_RACE_KW = dict(dim=8, L=6, W=32, ingest_chunk=64, seed=3)
# window >= stream: nothing expires -> the merged EH structure (and so the
# estimates) is bit-exact vs single-engine; expiry degrades to the usual
# estimate-level EH-merge guarantee (see test_merge.py).
_KDE_KW = dict(dim=8, L=6, W=32, window=100_000, eh_eps=0.2, ingest_chunk=50)
# eta=0: keep prob n^0 = 1, so keep decisions are key-independent and the
# single-engine reference makes the same ones; stream < capacity, so no
# union eviction and slot ids line up exactly.
_SANN_KW = dict(dim=8, n_max=100, eta=0.0, r=0.4, c=2.0, w=1.0, L=6, k=3,
                ingest_chunk=64)


def _data(n=500, d=8, seed=0):
    return np.random.default_rng(seed).uniform(0, 1, (n, d)).astype(
        np.float32)


def _interleave(data: np.ndarray, K: int) -> np.ndarray:
    """The cluster's canonical logical-time order: sort the union by
    (per-worker local index, worker id) — what stamp-sorted fold-left
    merging reconstructs."""
    pid = hash_partition(data, K)
    locals_ = [[i for i in range(len(data)) if pid[i] == w]
               for w in range(K)]
    order = []
    for j in range(max(len(l) for l in locals_)):
        for w in range(K):
            if j < len(locals_[w]):
                order.append(locals_[w][j])
    return data[order]


def test_hash_partition_deterministic_covering_balanced():
    data = _data(n=2000, seed=1)
    pid = hash_partition(data, 4)
    np.testing.assert_array_equal(pid, hash_partition(data, 4))  # stable
    # order-independence: a permuted stream partitions row-for-row equally
    perm = np.random.default_rng(0).permutation(len(data))
    np.testing.assert_array_equal(pid[perm], hash_partition(data[perm], 4))
    assert pid.min() >= 0 and pid.max() < 4
    counts = np.bincount(pid, minlength=4)
    assert counts.sum() == len(data)
    assert counts.min() > len(data) // 10, f"badly skewed: {counts}"
    assert (hash_partition(data, 1) == 0).all()


def test_cluster_race_bit_exact_vs_single_engine():
    data = _data(seed=2)
    qs = data[:7] + 0.01
    for K in (2, 4):
        single = RACEService(RACEServiceConfig(**_RACE_KW))
        single.ingest(data)
        cl = ClusterRACEService(RACEServiceConfig(**_RACE_KW),
                                num_workers=K, merge_every=4)
        cl.ingest(data)
        np.testing.assert_array_equal(cl.query(qs), single.query(qs))
        np.testing.assert_array_equal(cl.kde(qs), single.kde(qs))
        assert cl.count == single.count == len(data)
        # turnstile deletes route to the hash owner and stay exact
        cl.delete(data[:5])
        single.delete(data[:5])
        np.testing.assert_array_equal(cl.query(qs), single.query(qs))
        cl.close()
        single.close()


def test_cluster_kde_bit_exact_vs_single_engine_no_expiry():
    data = _data(seed=3)
    qs = data[:7] + 0.01
    single = KDEService(KDEServiceConfig(**_KDE_KW))
    single.ingest(data)
    for K in (2, 4):
        cl = ClusterKDEService(KDEServiceConfig(**_KDE_KW),
                               num_workers=K, merge_every=4)
        cl.ingest(data)
        np.testing.assert_array_equal(cl.query(qs), single.query(qs))
        assert cl.steps == len(data)
        # normalised density uses the summed worker clocks
        np.testing.assert_allclose(
            cl.density(qs),
            np.asarray(single.query(qs)) / len(data), rtol=1e-6)
        cl.close()
    single.close()


def test_cluster_kde_grid_cache_used_and_invalidated():
    """cfg.cache_grid applies to the merged sketch too: the (L, W) grid
    table is built at most once per merged version (counted via the worker
    grid fn), any new merge invalidates it, and cached reads are bitwise
    equal to the uncached fused path."""
    data = _data(n=300, seed=10)
    qs = data[:5] + 0.01
    cached = ClusterKDEService(KDEServiceConfig(**_KDE_KW), num_workers=2,
                               merge_every=1)
    uncached = ClusterKDEService(
        KDEServiceConfig(**_KDE_KW, cache_grid=False), num_workers=2,
        merge_every=1)
    calls = []
    orig = cached.workers[0]._grid_fn
    cached.workers[0]._grid_fn = lambda st: (calls.append(1), orig(st))[1]

    cached.ingest(data[:200])
    uncached.ingest(data[:200])
    q1 = cached.query(qs)
    cached.query(qs)
    assert len(calls) == 1              # second batch served from the cache
    np.testing.assert_array_equal(q1, uncached.query(qs))

    cached.ingest(data[200:])           # new commits -> new merge -> stale
    uncached.ingest(data[200:])
    q2 = cached.query(qs)
    assert len(calls) == 2, "stale merged grid served after new commits"
    np.testing.assert_array_equal(q2, uncached.query(qs))
    assert not np.array_equal(q1, q2)
    cached.close()
    uncached.close()


def test_cluster_kde_density_denominator_is_summed_coverage():
    """Worker windows tick on local clocks, so the density denominator is
    the *sum of per-worker* min(t_w, window) — not min(sum t_w, window),
    which overestimates density by up to K once clocks pass the window."""
    kw = dict(_KDE_KW, window=300)
    data = _data(n=400, seed=9)
    qs = data[:5] + 0.01
    cl = ClusterKDEService(KDEServiceConfig(**kw), num_workers=2,
                           merge_every=1)
    cl.ingest(data)
    coverage = sum(min(w.steps, kw["window"]) for w in cl.workers)
    assert coverage == 400          # no worker clock reached the window
    np.testing.assert_allclose(cl.density(qs),
                               np.asarray(cl.query(qs)) / coverage,
                               rtol=1e-6)
    cl.close()


def test_cluster_sann_matches_single_engine_fixed_interleaving():
    data = _data(n=300, seed=4)
    qs = data[:9] + 0.01
    for K in (2, 4):
        cl = ClusterRetrievalService(RetrievalConfig(**_SANN_KW),
                                     num_workers=K, merge_every=4)
        cl.ingest(data)
        ref = RetrievalService(RetrievalConfig(**_SANN_KW))
        ref.ingest(_interleave(data, K))

        res_c = cl.query(qs)
        res_r = ref.query(qs)
        np.testing.assert_array_equal(np.asarray(res_c.index),
                                      np.asarray(res_r.index))
        np.testing.assert_allclose(np.asarray(res_c.distance),
                                   np.asarray(res_r.distance), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(res_c.found),
                                      np.asarray(res_r.found))
        # the merged state *is* the single-engine state, modulo the stamp
        # clocks (per-worker local time vs global interleaved time)
        merged = cl.merged_state()
        for name, (u, v) in zip(merged._fields, zip(merged, ref.state)):
            if name == "stamps":
                continue
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                          err_msg=f"field {name!r}")
        assert cl.stored == int(ref.state.n_stored)
        cl.close()
        ref.close()


def test_cluster_merge_cadence_query_time_tail():
    data = _data(n=400, seed=5)
    qs = data[:6] + 0.01
    # merge_every huge: the coordinator never pre-merges — queries must
    # still cover every commit via the query-time tail merge.
    lazy = ClusterRACEService(RACEServiceConfig(**_RACE_KW),
                              num_workers=2, merge_every=10_000)
    eager = ClusterRACEService(RACEServiceConfig(**_RACE_KW),
                               num_workers=2, merge_every=1)
    lazy.ingest(data)
    eager.ingest(data)
    assert lazy._merged_versions is None        # cadence never fired
    assert eager._merged_versions is not None   # pre-merged during ingest
    np.testing.assert_array_equal(lazy.query(qs), eager.query(qs))
    # after the query-time merge the cache is fresh until new commits
    assert lazy._merged_versions == lazy.versions
    lazy.close()
    eager.close()


def test_cluster_async_flush_matches_sync():
    data = _data(n=400, seed=6)
    qs = data[:6] + 0.01
    a = ClusterKDEService(KDEServiceConfig(**_KDE_KW), num_workers=2)
    b = ClusterKDEService(KDEServiceConfig(**_KDE_KW), num_workers=2)
    a.ingest(data)
    b.ingest_async(data)
    b.flush()
    for wa, wb in zip(a.workers, b.workers):
        for x, y in zip(jax.tree.leaves(wa.state), jax.tree.leaves(wb.state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(a.query(qs), b.query(qs))
    a.close()
    b.close()


def test_cluster_sampled_eta_internal_consistency():
    """eta > 0: workers sample independently (salted keys); the merged
    sketch must stay internally consistent — tables reference only live
    slots, n_stored matches the valid mask, and answers come from stored
    points."""
    kw = dict(_SANN_KW, eta=0.3, n_max=1000)
    data = _data(n=600, seed=7)
    cl = ClusterRetrievalService(RetrievalConfig(**kw), num_workers=2,
                                 merge_every=2)
    cl.ingest(data)
    st = cl.merged_state()
    tables = np.asarray(st.tables)
    valid = np.asarray(st.valid)
    live_refs = tables[tables >= 0]
    assert valid[live_refs].all(), "table entry points at a dead slot"
    assert int(st.n_stored) == int(valid.sum())
    # distinct salts -> workers made different keep decisions
    k0 = int(np.asarray(cl.workers[0].state.n_stored))
    k1 = int(np.asarray(cl.workers[1].state.n_stored))
    assert k0 > 0 and k1 > 0
    res = cl.query(data[:20] + 0.01)
    found = np.asarray(res.found)
    assert found.any()
    assert (np.asarray(res.distance)[found] <= kw["c"] * kw["r"] + 1e-5).all()
    cl.close()


def test_cluster_ingest_with_max_pending_matches_unbounded():
    """Admission control on the workers composes with the round-robin
    cluster submission: tiny per-worker bounds change only the pacing,
    never the per-worker chunk boundaries or the merged answers."""
    data = _data(n=400, seed=11)
    qs = data[:6] + 0.01
    chunk = _RACE_KW["ingest_chunk"]
    bounded = ClusterRACEService(
        RACEServiceConfig(**_RACE_KW, max_pending=chunk), num_workers=2)
    free = ClusterRACEService(RACEServiceConfig(**_RACE_KW), num_workers=2)
    bounded.ingest(data)
    free.ingest(data)
    np.testing.assert_array_equal(bounded.query(qs), free.query(qs))
    for wa, wb in zip(bounded.workers, free.workers):
        for x, y in zip(jax.tree.leaves(wa.state), jax.tree.leaves(wb.state)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    bounded.close()
    free.close()


def test_cluster_sann_delete_reaches_near_copies_on_any_worker():
    """sann_delete is tolerance-based, but hash ownership is per bit
    pattern: a within-tol near-copy can live on any worker, so the cluster
    broadcast-deletes.  A perturbed delete value must still tombstone the
    stored original, exactly like the single-engine service."""
    kw = dict(_SANN_KW, n_max=1000)
    data = _data(n=200, seed=13)
    cl = ClusterRetrievalService(RetrievalConfig(**kw), num_workers=2,
                                 merge_every=1)
    cl.ingest(data)
    # different float bits (different hash owner with prob 1/2), within tol
    near = data[0] + np.float32(1e-7)
    assert not np.array_equal(near.view(np.uint32), data[0].view(np.uint32))
    before = int(cl.merged_state().n_stored)
    cl.delete(near)
    assert int(cl.merged_state().n_stored) == before - 1
    res = cl.query(data[:1])
    assert (float(np.asarray(res.distance)[0]) > 1e-4
            or not bool(np.asarray(res.found)[0]))
    cl.close()


def test_cluster_dir_pins_worker_count(tmp_path):
    """Reopening a durable cluster directory with a different worker count
    must fail loudly: hash ownership depends on the count, so a quiet
    reopen would drop the missing workers' data."""
    import pytest
    kw = RACEServiceConfig(**_RACE_KW, snapshot_dir=str(tmp_path))
    cl = ClusterRACEService(kw, num_workers=4)
    cl.ingest(_data(n=100, seed=12))
    cl.close()
    with pytest.raises(RuntimeError, match="num_workers=4"):
        ClusterRACEService(kw, num_workers=2)
    reopened = ClusterRACEService(kw, num_workers=4)   # same count is fine
    assert reopened.recover() >= 0
    reopened.close()
    # single engine <-> cluster reuse of one directory is refused both ways
    with pytest.raises(RuntimeError, match="cluster durability directory"):
        RACEService(kw)
    single_dir = tmp_path / "single"
    solo = RACEService(RACEServiceConfig(**_RACE_KW,
                                         snapshot_dir=str(single_dir)))
    solo.ingest(_data(n=64, seed=13))
    solo.close()
    with pytest.raises(RuntimeError, match="single-engine durable state"):
        ClusterRACEService(
            RACEServiceConfig(**_RACE_KW, snapshot_dir=str(single_dir)),
            num_workers=2)


def test_cluster_recovery_bit_identity(tmp_path):
    data = _data(n=400, seed=8)
    qs = data[:6] + 0.01
    kw = RACEServiceConfig(**_RACE_KW, snapshot_dir=str(tmp_path),
                           snapshot_every=2)
    cl = ClusterRACEService(kw, num_workers=2, merge_every=4)
    cl.ingest(data)
    before = cl.query(qs)
    states = [w.state for w in cl.workers]
    cl.close()                      # "crash": WAL + snapshots on disk

    rec = ClusterRACEService(kw, num_workers=2, merge_every=4)
    rec.recover()
    for w, st in zip(rec.workers, states):
        for x, y in zip(jax.tree.leaves(w.state), jax.tree.leaves(st)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(rec.query(qs), before)
    rec.close()


def test_cluster_close_aggregates_all_worker_failures():
    """A failing worker close must not mask the others: every worker is
    still closed, ALL failures are reported in one exception naming the
    failed workers, and a retry after the partial failure is clean
    (idempotent close)."""
    import pytest

    svc = ClusterRACEService(RACEServiceConfig(**_RACE_KW), num_workers=3,
                             merge_every=4)
    svc.ingest(_data(n=100, seed=9))

    failed_once = {}

    def bomb(w):
        orig = svc.workers[w].close

        def c():
            if w not in failed_once:
                failed_once[w] = True
                raise OSError(f"worker {w} fd leak")
            orig()
        svc.workers[w].close = c

    bomb(0)
    bomb(2)
    with pytest.raises(RuntimeError) as ei:
        svc.close()
    msg = str(ei.value)
    assert "worker_0" in msg and "worker_2" in msg and "2 worker(s)" in msg
    assert isinstance(ei.value.__cause__, OSError)
    assert svc.workers[1]._closed, "healthy worker must still be closed"
    svc.close()                       # retry closes the stragglers cleanly
    assert all(w._closed for w in svc.workers)


def test_cluster_race_delta_merge_bitexact():
    """Incremental coordinator merges: after the first full fold, each
    refresh folds only the per-worker counter DELTAS since the last merge
    — int32 addition is associative/commutative (wrap included), so the
    result must equal the full `race_merge` fold bitwise, every round."""
    import functools

    from repro.core import race

    svc = ClusterRACEService(RACEServiceConfig(**_RACE_KW), num_workers=3,
                             merge_every=1)
    data = _data(n=500, seed=4)
    for i in range(5):
        svc.ingest(data[i * 100:(i + 1) * 100])
        merged = svc.merged_state()
        full = functools.reduce(
            race.race_merge, [w.snapshot()[0] for w in svc.workers])
        np.testing.assert_array_equal(np.asarray(merged.counts),
                                      np.asarray(full.counts))
        assert int(merged.n) == int(full.n)
    counters = svc.stats()["counters"]
    assert counters["delta_merges"] >= 3, counters
    assert counters["full_merges"] >= 1, counters

    # base invalidation (live-set / epoch change stands in): the next
    # refresh falls back to a FULL fold and still matches
    svc._delta_base = None
    full_before = counters["full_merges"]
    svc.ingest(data[:100])
    merged = svc.merged_state()
    full = functools.reduce(
        race.race_merge, [w.snapshot()[0] for w in svc.workers])
    np.testing.assert_array_equal(np.asarray(merged.counts),
                                  np.asarray(full.counts))
    assert svc.stats()["counters"]["full_merges"] == full_before + 1
    svc.close()


# Exact-EH regime: eh_eps=0.01 keeps every (row, cell) EH exact as long as
# no cell holds more than ~k/2 same-size buckets — normal data + k=4 SRP
# spreads the 100-point window to ~6 points/cell, far inside the bound, so
# cluster-vs-single comparisons below can demand bitwise equality.
_GC_KW = dict(dim=8, L=6, W=32, window=100, eh_eps=0.01, k=4,
              ingest_chunk=50, seed=5)


def _gauss(n, d=8, seed=0):
    return np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)


def test_cluster_kde_global_clock_expiry_exact():
    """Global-clock cluster windows expire in STREAM time: fed per-point
    (timestamps == global positions), densities equal a single
    global-window engine bitwise both before AND after expiry; the
    local-clock cluster over-retains once the window saturates
    (eh_eps=0.01 keeps every EH bucket exact, so equality is exact)."""
    data = _gauss(150, seed=6)
    qs = _gauss(16, seed=7)
    single = KDEService(KDEServiceConfig(**_GC_KW))
    gc = ClusterKDEService(KDEServiceConfig(**_GC_KW), num_workers=3,
                           merge_every=1, global_clock=True)
    lc = ClusterKDEService(KDEServiceConfig(**_GC_KW), num_workers=3,
                           merge_every=1)
    for i in range(80):                   # pre-expiry (t <= window)
        for s in (single, gc, lc):
            s.ingest(data[i:i + 1])
    np.testing.assert_array_equal(gc.density(qs), single.density(qs))
    np.testing.assert_array_equal(lc.density(qs), single.density(qs))
    for i in range(80, 150):              # 50 points expire
        for s in (single, gc, lc):
            s.ingest(data[i:i + 1])
    np.testing.assert_array_equal(gc.density(qs), single.density(qs))
    assert not np.array_equal(lc.density(qs), single.density(qs)), \
        "local clocks (max t ~ 50 < window) must over-retain"
    assert gc.steps == single.steps == 150
    assert all(w.steps == 150 for w in gc.workers)
    single.close(); gc.close(); lc.close()


def test_kde_advance_clock_wal_recovery(tmp_path):
    """`advance_clock` is WAL-logged (KIND_CLOCK) between chunk records
    and replays in order: recovery reproduces the advanced clock and the
    post-advance densities bitwise."""
    kw = dict(_GC_KW, snapshot_dir=str(tmp_path), snapshot_every=1000)
    data = _gauss(90, seed=8)
    qs = _gauss(12, seed=9)
    svc = KDEService(KDEServiceConfig(**kw))
    svc.ingest(data[:60])
    svc.advance_clock(130)                # expires most of the first 60
    svc.ingest(data[60:])
    ref, steps = svc.density(qs), svc.steps
    assert steps == 160                   # max-monotone: 130 + 30
    svc.close()

    rec = KDEService(KDEServiceConfig(**kw))
    assert rec.recover() > 0
    assert rec.steps == steps
    np.testing.assert_array_equal(rec.density(qs), ref)
    # monotone: advancing backwards is a no-op
    rec.advance_clock(10)
    assert rec.steps == steps
    rec.close()


def test_cluster_kde_global_clock_durable_recovery(tmp_path):
    """A durable global-clock cluster recovers bit-identically: per-worker
    WALs interleave chunk and KIND_CLOCK records, and the coordinator's
    logical clock is restored from the replayed worker clocks."""
    cfg = KDEServiceConfig(**dict(_GC_KW, snapshot_dir=str(tmp_path),
                                  snapshot_every=1000))
    data = _gauss(120, seed=10)
    qs = _gauss(10, seed=11)
    svc = ClusterKDEService(cfg, num_workers=2, merge_every=1,
                            global_clock=True)
    for i in range(0, 120, 10):
        svc.ingest(data[i:i + 10])
    before, steps = svc.density(qs), svc.steps
    svc.close()

    rec = ClusterKDEService(cfg, num_workers=2, merge_every=1,
                            global_clock=True)
    assert rec.recover() > 0
    assert rec.steps == steps and rec._global_steps == steps
    np.testing.assert_array_equal(rec.density(qs), before)
    rec.close()
