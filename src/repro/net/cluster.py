"""Coordinator side of the network cluster (DESIGN.md §16): the PR-5/PR-8
in-process cluster promoted to separate worker *processes* behind the RPC
front door.

`RPCClusterRetrievalService` / `RPCClusterKDEService` /
`RPCClusterRACEService` ARE the in-process coordinators — they subclass
`serve.cluster.Cluster*Service` and swap only the worker construction:
``make_worker(w)`` spawns (or connects to) a worker process and returns a
`RemoteEngine` proxy speaking the engine surface over one RPC channel.
Everything above the worker boundary is shared code, not a re-
implementation: the same splitmix64 content-hash partitioner routes
substreams (a pure function of row bytes — identical in every process),
the same merge-algebra fold combines worker snapshots, the same
LIVE/DEGRADED/DEAD failover machinery (DESIGN §14) retries transient RPC
faults in place, rebuilds a lost worker by **respawning its process** and
`recover()`-ing from its WAL, and — when respawn is impossible — declares
it DEAD and re-partitions its WAL tail to the survivors by reading the
dead worker's log straight off the shared filesystem.

Exactness (the PR-5 cluster stays the test oracle, tests/test_rpc_cluster
.py): the RPC cluster is *bit-exact* against the in-process cluster for
all three sketches because every divergence point is pinned —

  * partition: `hash_partition` hashes row bytes, not object identity;
  * per-worker configs: the identical `_worker_cfg(cfg, w, ...)` dict is
    shipped to the child and rebuilt, so worker w's engine (seed, salt,
    chunking, durability subdir) is the one the oracle builds in-process;
  * chunk schedule: the coordinator submits the same engine-chunk slices
    in the same round-robin order, and worker seq numbers (hence
    `fold_in(key, seq)` PRNG draws) are assigned in arrival order on one
    lockstep channel;
  * reads: worker snapshots travel as ``.npz`` leaves (dtype/byte exact)
    and are folded by the same jitted merge on the coordinator.

The coordinator keeps a local **template engine** (same config, no
durability, never ingested): it contributes the jitted query/merge
functions and sketch params the coordinator's read path needs (the
`_ref` hook of `ClusterService`) — params are a pure function of the
config seed, so the template's equal every worker's.

Lifecycle: workers spawn via the multiprocessing ``spawn`` context as
daemon children (a dying coordinator never leaves orphans), `close()`
SHUTDOWNs + reaps every process even when some fail, and a constructor
that fails mid-startup (e.g. worker 2 of 4 refuses connections) reaps the
already-spawned processes before re-raising — no leaked PIDs either way.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.net import protocol as P
from repro.net import worker as W
from repro.serve.cluster import (ClusterKDEService, ClusterRACEService,
                                 ClusterRetrievalService, FailoverConfig,
                                 _worker_cfg)
from repro.serve.kde_service import KDEService, KDEServiceConfig
from repro.serve.race_service import RACEService, RACEServiceConfig
from repro.serve.retrieval import RetrievalConfig, RetrievalService

# JAX platform of spawned workers.  The coordinator's template engine
# holds this process's device, and a chip belongs to one process at a
# time, so workers run on the host CPU (ROADMAP Queue 2, "Clusters on
# chips", would give each worker a chip of its own).
WORKER_PLATFORM = "cpu"

# `RemoteEngine._dur` sentinel: the failover layer only asks "is this
# worker durable?" (`old._dur is not None`) — the actual durability config
# lives in the worker process.
_REMOTE_DURABLE = object()


@dataclasses.dataclass(frozen=True)
class RPCConfig:
    """Network knobs for an RPC cluster.

    ``rpc_timeout_s`` bounds every request/reply round trip; a timed-out
    channel is *broken* (a late reply would desync the framing) and the
    worker goes through failover.  ``connect_retries``/``connect_backoff_s``
    retry the initial connect+handshake with exponential backoff.
    ``respawn`` — whether failover may restart a lost worker's process
    (False forces the DEAD + WAL-tail re-partition path).  ``peers`` —
    connect to externally-started workers (``run_worker`` in another
    terminal/host) instead of spawning: one ``(host, port)`` per worker.
    """
    host: str = "127.0.0.1"
    rpc_timeout_s: float = 300.0
    connect_retries: int = 3
    connect_backoff_s: float = 0.2
    spawn_timeout_s: float = 300.0
    respawn: bool = True
    peers: Optional[Sequence[Tuple[str, int]]] = None


class RemoteEngine:
    """Client-side proxy for one worker process, speaking the
    `SketchEngine` surface the cluster coordinator drives.

    Mutations (`ingest_async`, `flush`, `delete`, `advance_clock`,
    `recover`) are one RPC each; `snapshot()` pulls the worker's committed
    state as npz leaves and rebuilds the pytree against the template's
    treedef.  Worker-side failures arrive as `protocol.RemoteError`
    carrying the failover markers (``transient``, ``wal_accepted``), so
    `ClusterService._with_retries` / `_mutate_live` work unchanged.
    Channel-level failures mark the channel broken; the proxy then reads
    as poisoned and the coordinator's failover rebuilds it (respawn) or
    declares it dead (salvage)."""

    def __init__(self, channel: P.Channel, template, proc=None,
                 durable: bool = False):
        self._ch = channel
        self._tpl = template
        self.proc = proc
        self._chunk = template._chunk
        self._query_block = template._query_block
        self._dur = _REMOTE_DURABLE if durable else None
        self._closed = False
        self._last_health: Optional[dict] = None

    # --- engine surface -----------------------------------------------------

    def ingest_async(self, chunk) -> None:
        self._ch.call(P.K_INGEST,
                      arrays={"xs": np.asarray(chunk, np.float32)})

    def flush(self) -> None:
        self._ch.call(P.K_FLUSH)

    def delete(self, x) -> None:
        self._ch.call(P.K_DELETE, arrays={"x": np.asarray(x, np.float32)})

    def advance_clock(self, target: int) -> None:
        self._ch.call(P.K_ADVANCE_CLOCK, {"target": int(target)})

    def recover(self) -> int:
        meta, _ = self._ch.call(P.K_RECOVER)
        return int(meta["replayed"])

    def snapshot(self):
        meta, arrays = self._ch.call(P.K_SNAPSHOT)
        n = int(meta["num_leaves"])
        leaves = [jnp.asarray(arrays[f"l{i}"]) for i in range(n)]
        state = jax.tree.unflatten(jax.tree.structure(self._tpl.state),
                                   leaves)
        return state, int(meta["version"])

    def query(self, queries, kind: Optional[str] = None):
        """Direct worker-local query (not the merged cluster view) — the
        per-worker substream answer, mainly for tooling/tests."""
        meta, arrays = self._ch.call(
            P.K_QUERY, {"kind": kind},
            arrays={"qs": np.asarray(queries, np.float32)})
        return [arrays[f"l{i}"] for i in range(int(meta["num_leaves"]))]

    def _health_rpc(self) -> dict:
        meta, _ = self._ch.call(P.K_HEALTH)
        self._last_health = meta
        return meta

    def health(self) -> dict:
        """Worker health; a worker behind a broken channel reports itself
        poisoned (like an in-process poisoned engine still does) instead
        of raising — the coordinator's `health()` polls dead workers
        too."""
        if self._ch.broken is not None:
            return {"state": "poisoned",
                    "poison_reason": f"rpc channel broken: "
                                     f"{self._ch.broken}"}
        try:
            return self._health_rpc()
        except (P.ProtocolError, OSError) as e:
            return {"state": "poisoned",
                    "poison_reason": f"rpc health poll failed: {e!r}"}

    def stats(self) -> dict:
        meta, _ = self._ch.call(P.K_STATS)
        return meta

    def close(self) -> None:
        """Graceful SHUTDOWN + channel close + process reap.  Idempotent;
        the process is reaped even when the shutdown RPC fails, and a
        remote close failure re-raises afterwards (the cluster's close
        aggregates it)."""
        if self._closed:
            return
        self._closed = True
        err: Optional[BaseException] = None
        try:
            if self._ch.broken is None:
                self._ch.call(P.K_SHUTDOWN)
        except BaseException as e:
            err = e
        finally:
            self._ch.close()
            W.reap_process(self.proc)
        if err is not None and not isinstance(err, (P.ProtocolError,
                                                   OSError)):
            raise err

    # --- polled properties --------------------------------------------------

    @property
    def version(self) -> int:
        # Fail-stop read: a broken channel raises here (unlike health()).
        return int(self._health_rpc()["version"])

    @property
    def steps(self) -> int:
        return int(self._health_rpc().get("steps", 0))

    @property
    def count(self) -> int:
        return int(self._health_rpc().get("count", 0))

    @property
    def stored(self) -> int:
        return int(self._health_rpc().get("stored", 0))

    @property
    def sketch_bytes(self) -> int:
        return self._tpl.sketch_bytes      # same allocation, same config

    @property
    def _poisoned(self) -> bool:
        return self.health().get("state") == "poisoned"

    @property
    def _poison_reason(self) -> Optional[str]:
        if self._ch.broken is not None:
            return f"rpc channel broken: {self._ch.broken}"
        return (self._last_health or {}).get("poison_reason")


class _RPCClusterMixin:
    """Worker-construction override shared by the three RPC coordinators:
    spawn/connect with retry+backoff, template bookkeeping, startup and
    shutdown process reaping.  Subclasses set ``_service_kind`` and
    ``_worker_cfg_extra``."""

    _service_kind = ""

    def _rpc_setup(self, cfg, template, rpc: Optional[RPCConfig]) -> None:
        self._rpc = rpc or RPCConfig()
        if (self._rpc.peers is not None
                and getattr(cfg, "snapshot_dir", None) is None
                and not self._rpc.respawn):
            pass                           # nothing to validate further
        self._template = template
        self._base_cfg = cfg
        self._rpc_durable = getattr(cfg, "snapshot_dir", None) is not None
        self._procs: dict = {}
        self._remotes: dict = {}
        self._spawned_once: set = set()

    @property
    def _ref(self):
        return self._template

    def _worker_cfg_extra(self, w: int) -> dict:
        return dict(batch_queries=False)

    def _remote_worker(self, w: int) -> RemoteEngine:
        """``make_worker`` for the RPC cluster: start (or dial) worker
        ``w`` and return its proxy.  On a failover *rebuild* (the worker
        was built once already) this respawns the process — the old
        proxy's `close()` reaped the old one — unless ``respawn`` is off
        or the worker is an external peer, in which case the rebuild
        fails and the failover layer falls through to DEAD + salvage."""
        rc = self._rpc
        first = w not in self._spawned_once
        if not first and rc.peers is not None:
            raise RuntimeError(
                f"worker {w} is an external peer; the coordinator cannot "
                "respawn it")
        if not first and not rc.respawn:
            raise RuntimeError(
                f"worker {w} lost and respawn is disabled "
                "(RPCConfig.respawn=False)")
        self._spawned_once.add(w)
        wcfg = dataclasses.asdict(
            _worker_cfg(self._base_cfg, w, **self._worker_cfg_extra(w)))
        proc = None
        if rc.peers is not None:
            host, port = rc.peers[w]
        else:
            host = rc.host
            proc, port = W.spawn_worker(
                self._service_kind, wcfg, platform=WORKER_PLATFORM,
                host=host,
                spawn_timeout_s=rc.spawn_timeout_s)
        try:
            ch = self._connect(host, port, scope=f"worker_{w}/")
        except BaseException:
            W.reap_process(proc)
            self._procs.pop(w, None)
            raise
        self._procs[w] = proc
        eng = RemoteEngine(ch, self._template, proc=proc,
                           durable=self._rpc_durable)
        self._remotes[w] = eng
        return eng

    def _connect(self, host: str, port: int, scope: str) -> P.Channel:
        rc = self._rpc
        delay = rc.connect_backoff_s
        for attempt in range(rc.connect_retries + 1):
            try:
                return P.Channel(host, port, timeout_s=rc.rpc_timeout_s,
                                 fault_scope=scope)
            except (OSError, P.ProtocolError):
                if attempt == rc.connect_retries:
                    raise
                time.sleep(delay)
                delay *= 2

    def _reap_all(self) -> None:
        """Kill/collect every worker process and channel this coordinator
        ever created — the mid-startup failure path (satellite: no orphan
        PIDs when a connect fails after some workers spawned) and the
        close() backstop."""
        for eng in list(self._remotes.values()):
            try:
                eng._ch.close()
            except BaseException:
                pass
        for w, proc in list(self._procs.items()):
            W.reap_process(proc)
        self._procs.clear()

    def close(self) -> None:
        try:
            super().close()
        finally:
            self._reap_all()
            try:
                self._template.close()
            except BaseException:
                pass


class RPCClusterRetrievalService(_RPCClusterMixin, ClusterRetrievalService):
    """N-process S-ANN cluster behind the RPC front door (bit-exact vs
    `ClusterRetrievalService`)."""

    _service_kind = "retrieval"

    def __init__(self, cfg: RetrievalConfig, num_workers: int = 2,
                 merge_every: int = 8,
                 failover: Optional[FailoverConfig] = None,
                 rpc: Optional[RPCConfig] = None):
        self._rpc_setup(cfg, RetrievalService(dataclasses.replace(
            cfg, snapshot_dir=None, batch_queries=False)), rpc)
        try:
            super().__init__(cfg, num_workers, merge_every=merge_every,
                             failover=failover,
                             make_worker=self._remote_worker)
        except BaseException:
            self._reap_all()
            raise

    def _worker_cfg_extra(self, w: int) -> dict:
        return dict(ingest_salt=w, batch_queries=False)


class RPCClusterKDEService(_RPCClusterMixin, ClusterKDEService):
    """N-process SW-AKDE cluster behind the RPC front door (bit-exact vs
    `ClusterKDEService`, including the ``global_clock`` stream-time
    option — clock advances are one RPC per worker per ingest call)."""

    _service_kind = "kde"

    def __init__(self, cfg: KDEServiceConfig, num_workers: int = 2,
                 merge_every: int = 8,
                 failover: Optional[FailoverConfig] = None,
                 global_clock: bool = False,
                 rpc: Optional[RPCConfig] = None):
        self._rpc_setup(cfg, KDEService(dataclasses.replace(
            cfg, snapshot_dir=None, batch_queries=False)), rpc)
        try:
            super().__init__(cfg, num_workers, merge_every=merge_every,
                             failover=failover, global_clock=global_clock,
                             make_worker=self._remote_worker)
        except BaseException:
            self._reap_all()
            raise


class RPCClusterRACEService(_RPCClusterMixin, ClusterRACEService):
    """N-process RACE cluster behind the RPC front door (bit-exact vs
    `ClusterRACEService` — and therefore vs a single engine over the
    whole stream)."""

    _service_kind = "race"

    def __init__(self, cfg: RACEServiceConfig, num_workers: int = 2,
                 merge_every: int = 8,
                 failover: Optional[FailoverConfig] = None,
                 rpc: Optional[RPCConfig] = None):
        self._rpc_setup(cfg, RACEService(dataclasses.replace(
            cfg, snapshot_dir=None, batch_queries=False)), rpc)
        try:
            super().__init__(cfg, num_workers, merge_every=merge_every,
                             failover=failover,
                             make_worker=self._remote_worker)
        except BaseException:
            self._reap_all()
            raise


_SERVICES: dict[str, Callable] = {
    "retrieval": RPCClusterRetrievalService,
    "kde": RPCClusterKDEService,
    "race": RPCClusterRACEService,
}


def rpc_cluster(service_kind: str, cfg, **kwargs):
    """Factory by sketch name: ``rpc_cluster("race", cfg, num_workers=4)``."""
    try:
        cls = _SERVICES[service_kind]
    except KeyError:
        raise ValueError(f"unknown service kind {service_kind!r}; expected "
                         f"one of {sorted(_SERVICES)}") from None
    return cls(cfg, **kwargs)
