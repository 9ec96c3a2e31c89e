"""Network cluster chaos and lifecycle (repro.net, DESIGN.md §16), against
the in-process oracles of test_rpc_cluster.py:

  * seeded chaos: SIGKILLing a worker process mid-stream drives the
    failover path — the coordinator respawns the process and
    `recover()`s it from its WAL (bit-exact), or, with respawn disabled,
    declares it DEAD and re-partitions its WAL tail to the survivors
    (RACE stays bit-identical to a single engine under any routing);
  * injected transient network faults (``net.send`` drop) retry in place
    with no recovery and identical state;
  * lifecycle hygiene: a constructor failing mid-startup reaps every
    already-spawned worker process (no orphan PIDs), and a query against
    a wedged worker resolves the batched-query future with an error
    instead of leaking it.

Like test_rpc_cluster.py, every test spawns worker processes, so the file
is deselected from tier-1 and runs in the CI ``rpc-cluster`` job.
"""
import os
import signal

import numpy as np
import pytest

from repro import persist
from repro.net import RPCClusterRACEService, RPCConfig
from repro.persist import faults
from repro.serve.cluster import ClusterRACEService, FailoverConfig
from repro.serve.race_service import RACEService, RACEServiceConfig

from test_rpc_cluster import (_FO, _RACE_KW, _data, _no_worker_orphans,
                              _states_equal)


# ---------------------------------------------------------------------------
# Chaos: killed worker processes, injected network faults
# ---------------------------------------------------------------------------

def test_rpc_worker_process_kill_failover_respawns_bit_exact(tmp_path):
    """SIGKILL worker 1's process mid-stream: the broken channel surfaces
    as a hard failure, failover respawns the process on the same
    durability directory and `recover()`s it from its WAL — every
    acknowledged chunk was logged (+flushed) before the OK, so the
    cluster converges bit-identically to a never-faulted oracle."""
    data = _data(seed=21)
    oracle = ClusterRACEService(RACEServiceConfig(**_RACE_KW),
                                num_workers=2, merge_every=4)
    oracle.ingest(data)
    svc = RPCClusterRACEService(
        RACEServiceConfig(**_RACE_KW, snapshot_dir=str(tmp_path / "c"),
                          snapshot_every=4),
        num_workers=2, merge_every=4, failover=FailoverConfig(**_FO))
    try:
        for i in range(0, len(data), 100):
            if i == 200:
                svc._procs[1].kill()        # SIGKILL, no goodbye
                svc._procs[1].join(10.0)
            svc.ingest(data[i:i + 100])
        h = svc.health()
        assert h["counters"]["recoveries"] >= 1
        assert h["dead_workers"] == [] and h["coverage"] == 1.0
        assert _states_equal(svc.merged_state(), oracle.merged_state())
    finally:
        svc.close()
        oracle.close()
    assert _no_worker_orphans()


def test_rpc_worker_kill_without_respawn_salvages_wal_tail(tmp_path):
    """Same kill, ``respawn=False``: the rebuild hook refuses, the worker
    is declared DEAD, and its WAL tail — read straight off the shared
    filesystem — is re-partitioned to the survivor.  RACE counter sums
    are routing-independent, so the cluster stays bit-identical to a
    single engine over the whole stream."""
    data = _data(seed=22)
    qs = data[:6] + 0.01
    single = RACEService(RACEServiceConfig(**_RACE_KW))
    single.ingest(data)
    # Huge snapshot cadence: the dead worker's WAL is never compacted, so
    # its whole history is salvageable (same setup as the in-process
    # dead-worker chaos tests).
    svc = RPCClusterRACEService(
        RACEServiceConfig(**_RACE_KW, snapshot_dir=str(tmp_path / "c"),
                          snapshot_every=10_000),
        num_workers=2, merge_every=4,
        failover=FailoverConfig(on_degraded="partial", **_FO),
        rpc=RPCConfig(respawn=False))
    try:
        for i in range(0, len(data), 100):
            if i == 200:
                svc._procs[1].kill()
                svc._procs[1].join(10.0)
            svc.ingest(data[i:i + 100])
        h = svc.health()
        assert h["dead_workers"] == [1]
        assert h["salvage_complete"] == [1]
        assert h["counters"]["repartitions"] >= 1
        np.testing.assert_array_equal(svc.query(qs), single.query(qs))
        assert svc.count == single.count == len(data)
    finally:
        svc.close()
        single.close()
    assert _no_worker_orphans()


def test_rpc_transient_net_drop_retries_in_place(tmp_path):
    """A seeded ``net.send`` drop (request lost before any bytes went
    out) is transient: the coordinator retries on the same channel — no
    recovery, no respawn, bit-identical state."""
    data = _data(n=300, seed=23)
    oracle = ClusterRACEService(RACEServiceConfig(**_RACE_KW),
                                num_workers=2, merge_every=4)
    oracle.ingest(data)
    svc = RPCClusterRACEService(
        RACEServiceConfig(**_RACE_KW), num_workers=2, merge_every=4,
        failover=FailoverConfig(**_FO))
    plan = persist.FaultPlan([persist.FaultSpec(
        site="worker_1/net.send", mode="drop", hit=2)])
    try:
        with faults.installed(plan):
            svc.ingest(data)
        assert plan.fired, "the injected send drop never fired"
        h = svc.health()
        assert h["counters"]["retries"] >= 1
        assert h["counters"]["recoveries"] == 0
        assert _states_equal(svc.merged_state(), oracle.merged_state())
    finally:
        svc.close()
        oracle.close()
    assert _no_worker_orphans()


# ---------------------------------------------------------------------------
# Lifecycle hygiene
# ---------------------------------------------------------------------------

def test_rpc_startup_connect_failure_reaps_spawned_workers():
    """Worker 0 spawns and connects fine; worker 1's connect is killed by
    an injected crash.  The constructor must reap BOTH processes before
    re-raising — no orphan PIDs from a half-built cluster."""
    plan = persist.FaultPlan([persist.FaultSpec(
        site="worker_1/net.connect", mode="crash", hit=1)])
    with faults.installed(plan):
        with pytest.raises(persist.FaultError):
            RPCClusterRACEService(RACEServiceConfig(**_RACE_KW),
                                  num_workers=2, merge_every=4)
    assert plan.fired
    assert _no_worker_orphans()


def test_rpc_query_timeout_resolves_batched_future(tmp_path):
    """SIGSTOP a worker and submit a batched query: the RPC timeout must
    propagate through the batch executor and resolve the future with an
    error — never leak it / hang the client."""
    data = _data(n=200, seed=24)
    svc = RPCClusterRACEService(
        RACEServiceConfig(**_RACE_KW, batch_queries=True),
        num_workers=2, merge_every=4)
    try:
        svc.ingest(data)
        _ = svc.query(data[:3])             # warm the jitted query path
        for w in svc.workers:               # then shrink the deadline
            w._ch._timeout = 1.5
            w._ch._sock.settimeout(1.5)
        os.kill(svc._procs[0].pid, signal.SIGSTOP)
        try:
            fut = svc.submit_query(data[:3])
            with pytest.raises(BaseException):
                fut.result(timeout=30.0)
        finally:
            os.kill(svc._procs[0].pid, signal.SIGCONT)
        assert svc.workers[0]._ch.broken is not None
    finally:
        svc.close()
    assert _no_worker_orphans()
