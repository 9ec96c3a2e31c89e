"""Network cluster (repro.net, DESIGN.md §16): multi-PROCESS sketch
workers behind the RPC front door, pinned bit-exact against the
in-process PR-5 cluster oracle:

  * for all three sketches, an RPC cluster (each worker its own spawned
    process + engine + WAL) answers ingest/query/delete exactly like the
    in-process `Cluster*Service` over the same stream;
  * a durable RPC cluster recovers bit-identically across a full
    stop/start of every worker process.

Killed workers, injected network faults and lifecycle hygiene are in
test_rpc_chaos.py.

Every test here spawns subprocesses (seconds of jax startup each on the
1-core dev shape), so the file is deselected from tier-1 and runs in the
CI ``rpc-cluster`` job — mirror of how test_distributed.py is handled.
"""
import multiprocessing

import numpy as np

from repro.net import (RPCClusterKDEService, RPCClusterRACEService,
                       RPCClusterRetrievalService)
from repro.serve.cluster import (ClusterKDEService, ClusterRACEService,
                                 ClusterRetrievalService)
from repro.serve.kde_service import KDEServiceConfig
from repro.serve.race_service import RACEServiceConfig
from repro.serve.retrieval import RetrievalConfig

_RACE_KW = dict(dim=8, L=6, W=32, ingest_chunk=64, seed=3)
_KDE_KW = dict(dim=8, L=6, W=32, window=100_000, eh_eps=0.2, ingest_chunk=50)
_SANN_KW = dict(dim=8, n_max=100, eta=0.0, r=0.4, c=2.0, w=1.0, L=6, k=3,
                ingest_chunk=64)
_FO = dict(max_retries=2, backoff_s=0.01)


def _data(n=500, d=8, seed=0):
    return np.random.default_rng(seed).uniform(0, 1, (n, d)).astype(
        np.float32)


def _states_equal(a, b):
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        bool((np.asarray(x) == np.asarray(y)).all())
        for x, y in zip(la, lb))


def _no_worker_orphans():
    return [p.name for p in multiprocessing.active_children()
            if p.name.startswith("sketch-worker")] == []


# ---------------------------------------------------------------------------
# Bit-exactness vs the in-process oracle
# ---------------------------------------------------------------------------

def test_rpc_race_bit_exact_vs_inprocess_oracle():
    data = _data(seed=2)
    qs = data[:7] + 0.01
    oracle = ClusterRACEService(RACEServiceConfig(**_RACE_KW),
                                num_workers=2, merge_every=4)
    oracle.ingest(data)
    svc = RPCClusterRACEService(RACEServiceConfig(**_RACE_KW),
                                num_workers=2, merge_every=4)
    try:
        assert svc.workers[0]._ch.engine_kind == "RACEService"
        # the coordinator picks the workers' platform: the host CPU
        assert [w["platform"] for w in svc.health()["workers"]] == ["cpu"] * 2
        svc.ingest(data)
        np.testing.assert_array_equal(svc.query(qs), oracle.query(qs))
        np.testing.assert_array_equal(svc.kde(qs), oracle.kde(qs))
        assert svc.count == oracle.count == len(data)
        svc.delete(data[:5])
        oracle.delete(data[:5])
        np.testing.assert_array_equal(svc.query(qs), oracle.query(qs))
        assert _states_equal(svc.merged_state(), oracle.merged_state())
    finally:
        svc.close()
        oracle.close()
    assert _no_worker_orphans()


def test_rpc_kde_bit_exact_vs_inprocess_oracle():
    data = _data(seed=3)
    qs = data[:7] + 0.01
    oracle = ClusterKDEService(KDEServiceConfig(**_KDE_KW),
                               num_workers=2, merge_every=4)
    oracle.ingest(data)
    svc = RPCClusterKDEService(KDEServiceConfig(**_KDE_KW),
                               num_workers=2, merge_every=4)
    try:
        svc.ingest(data)
        np.testing.assert_array_equal(svc.query(qs), oracle.query(qs))
        np.testing.assert_array_equal(svc.density(qs), oracle.density(qs))
        assert svc.steps == oracle.steps == len(data)
        assert _states_equal(svc.merged_state(), oracle.merged_state())
    finally:
        svc.close()
        oracle.close()
    assert _no_worker_orphans()


def test_rpc_sann_bit_exact_vs_inprocess_oracle():
    data = _data(n=300, seed=4)
    qs = np.asarray(data[:6] + 0.01, np.float32)
    oracle = ClusterRetrievalService(RetrievalConfig(**_SANN_KW),
                                     num_workers=2, merge_every=4)
    oracle.ingest(data)
    svc = RPCClusterRetrievalService(RetrievalConfig(**_SANN_KW),
                                     num_workers=2, merge_every=4)
    try:
        svc.ingest(data)
        for a, b in zip(svc.query(qs), oracle.query(qs)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ra, rb = svc.query_topk(qs), oracle.query_topk(qs)
        for a, b in zip(ra, rb):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert _states_equal(svc.merged_state(), oracle.merged_state())
    finally:
        svc.close()
        oracle.close()
    assert _no_worker_orphans()


def test_rpc_durable_recover_bit_exact(tmp_path):
    """Stop every worker process (graceful close), restart the cluster on
    the same directory, `recover()`: answers and state match the oracle
    that never stopped."""
    data = _data(n=400, seed=5)
    qs = data[:6] + 0.01
    oracle = ClusterRACEService(RACEServiceConfig(**_RACE_KW),
                                num_workers=2, merge_every=4)
    oracle.ingest(data)

    cfg = RACEServiceConfig(**_RACE_KW, snapshot_dir=str(tmp_path / "c"),
                            snapshot_every=3)
    a = RPCClusterRACEService(cfg, num_workers=2, merge_every=4)
    try:
        a.ingest(data)
    finally:
        a.close()
    assert _no_worker_orphans()

    b = RPCClusterRACEService(cfg, num_workers=2, merge_every=4)
    try:
        assert b.recover() > 0
        np.testing.assert_array_equal(b.query(qs), oracle.query(qs))
        assert _states_equal(b.merged_state(), oracle.merged_state())
    finally:
        b.close()
        oracle.close()
    assert _no_worker_orphans()
