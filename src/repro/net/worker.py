"""Worker side of the network cluster: a `WorkerServer` wraps ONE sketch
service engine (`RetrievalService` / `KDEService` / `RACEService` —
unchanged) and speaks the `protocol` frames over a TCP socket, plus the
process entry points the coordinator spawns workers through.

Each worker process owns its engine outright: its commit worker + prepare
threads, its WAL + snapshots (under the cluster dir's ``worker_<w>``
subdirectory, exactly where the in-process cluster keeps them — so the
coordinator's WAL-tail salvage of a dead worker reads the same files),
and its own JAX runtime.  Ingest RPCs stream straight into the engine's
``ingest_async`` path, which WAL-logs at enqueue time *before* the OK
reply — an acknowledged chunk is replayable even if the process dies
immediately after (`wal.append` flushes per record).

The server is deliberately single-client/lockstep: the coordinator holds
one channel per worker and pipelines nothing, so request handling is a
simple read→dispatch→reply loop; a disconnected coordinator just drops
the connection and the server accepts the next one (a respawned
coordinator, or an operator poking at a worker).

Spawn path (`spawn_worker`): workers start via the multiprocessing
``spawn`` context — never ``fork``, which is unsafe once JAX has
initialised its runtime in the parent — as *daemon* children, so a dying
coordinator process can never leave orphan workers behind.  The child
binds an ephemeral port and hands it back over a pipe; service configs
travel as plain dicts (`dataclasses.asdict`) and are rebuilt in the
child, so the worker's engine is constructed from the exact same config
the in-process oracle would use.  The coordinator picks the child's JAX
platform and each worker reports the one it runs on in ``health()``.
"""
from __future__ import annotations

import os
import socket
import traceback
import uuid
from typing import Optional, Tuple

import numpy as np

from repro.net import protocol as P
from repro.persist import faults


def build_service(service_kind: str, cfg_dict: dict):
    """Rebuild a sketch service from its shipped config dict.  Imported
    lazily so the child process pays jax startup once, here."""
    import jax  # noqa: F401  (force backend init before the engine jits)

    if cfg_dict.get("mesh") is not None:
        raise ValueError("RPC workers are single-process engines; shard "
                         "inside the worker with num_shards, not mesh=")
    if service_kind == "retrieval":
        from repro.serve.retrieval import RetrievalConfig, RetrievalService
        return RetrievalService(RetrievalConfig(**cfg_dict))
    if service_kind == "kde":
        from repro.serve.kde_service import KDEService, KDEServiceConfig
        return KDEService(KDEServiceConfig(**cfg_dict))
    if service_kind == "race":
        from repro.serve.race_service import RACEService, RACEServiceConfig
        return RACEService(RACEServiceConfig(**cfg_dict))
    raise ValueError(f"unknown service kind {service_kind!r}")


class WorkerServer:
    """One engine behind one listening socket (see module docstring)."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 0):
        self.engine = engine
        self.session = uuid.uuid4().hex[:12]
        self._stop = False
        self._lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._lsock.bind((host, port))
        self._lsock.listen(4)
        self.host, self.port = self._lsock.getsockname()[:2]

    def serve_forever(self) -> None:
        """Accept coordinator connections until a SHUTDOWN request (one at
        a time — the protocol is lockstep and the coordinator is the only
        intended client)."""
        try:
            while not self._stop:
                try:
                    conn, _ = self._lsock.accept()
                except OSError:
                    break
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                try:
                    self._serve_conn(conn)
                finally:
                    try:
                        conn.close()
                    except OSError:
                        pass
        finally:
            self._lsock.close()

    def _serve_conn(self, conn: socket.socket) -> None:
        while not self._stop:
            try:
                mid, kind, body = P.recv_msg(conn)
            except (P.ProtocolError, OSError):
                return          # peer gone / garbage: drop the connection
            try:
                meta, arrays = P.decode_body(body)
                rmeta, rarrays = self._handle(kind, meta, arrays)
                P.send_msg(conn, mid, P.K_OK,
                           P.encode_body(rmeta, rarrays))
            except BaseException as e:
                err = {"error": f"{e!r}", "type": type(e).__name__,
                       "transient": faults.is_transient(e),
                       "wal_accepted": bool(getattr(e, "wal_accepted",
                                                    False))}
                try:
                    P.send_msg(conn, mid, P.K_ERR, P.encode_body(err))
                except OSError:
                    return
                if self._stop:          # shutdown failed but still stops
                    return

    # --- request dispatch ---------------------------------------------------

    def _handle(self, kind: int, meta: dict,
                arrays: dict) -> Tuple[dict, dict]:
        eng = self.engine
        if kind == P.K_HELLO:
            P.check_hello(meta)
            return {"version": P.PROTOCOL_VERSION, "session": self.session,
                    "engine": type(eng).__name__}, {}
        if kind == P.K_INGEST:
            eng.ingest_async(np.asarray(arrays["xs"], np.float32))
            return {}, {}
        if kind == P.K_FLUSH:
            eng.flush()
            return {}, {}
        if kind == P.K_QUERY:
            import jax
            qkind = meta.get("kind") or eng._default_query_kind
            fn = eng._kind_fn(qkind)
            res = fn(eng._query_snapshot_ctx(),
                     np.asarray(arrays["qs"], np.float32))
            leaves = [np.asarray(x) for x in jax.tree.leaves(res)]
            return ({"num_leaves": len(leaves)},
                    {f"l{i}": a for i, a in enumerate(leaves)})
        if kind == P.K_DELETE:
            eng.delete(np.asarray(arrays["x"], np.float32))
            return {}, {}
        if kind == P.K_HEALTH:
            return self._health_meta(), {}
        if kind == P.K_STATS:
            return dict(eng.stats()), {}
        if kind == P.K_SNAPSHOT:
            import jax
            state, version = eng.snapshot()
            leaves = [np.asarray(x) for x in jax.tree.leaves(state)]
            return ({"version": int(version), "num_leaves": len(leaves)},
                    {f"l{i}": a for i, a in enumerate(leaves)})
        if kind == P.K_RECOVER:
            return {"replayed": int(eng.recover())}, {}
        if kind == P.K_ADVANCE_CLOCK:
            eng.advance_clock(int(meta["target"]))
            return {}, {}
        if kind == P.K_SHUTDOWN:
            # Close the engine *before* the OK goes out: the coordinator's
            # shutdown call returns only once the WAL handle and threads
            # are down, so `close()` on the cluster is a real barrier.
            self._stop = True
            eng.close()
            return {}, {}
        raise P.ProtocolError(f"unknown request kind {kind}")

    def _health_meta(self) -> dict:
        import jax
        eng = self.engine
        out = dict(eng.health())
        out["version"] = int(eng.version)
        out["platform"] = jax.devices()[0].platform
        for extra in ("steps", "count", "stored"):
            try:
                v = getattr(eng, extra)
            except Exception:
                continue
            if isinstance(v, (int, np.integer)):
                out[extra] = int(v)
        return out


# --- process entry points ----------------------------------------------------

def run_worker(service_kind: str, cfg_dict: dict, host: str = "127.0.0.1",
               port: int = 0, announce=print) -> None:
    """Foreground worker (e.g. a second terminal via
    ``examples/serve_retrieval.py --rpc worker``): build the engine, bind,
    announce the port, serve until SHUTDOWN."""
    svc = build_service(service_kind, cfg_dict)
    srv = WorkerServer(svc, host=host, port=port)
    if announce is not None:
        announce(f"worker [{service_kind}] session {srv.session} "
                 f"listening on {srv.host}:{srv.port}")
    try:
        srv.serve_forever()
    finally:
        try:
            svc.close()
        except BaseException:
            pass


def _worker_main(conn, service_kind: str, cfg_dict: dict,
                 host: str, platform: str) -> None:
    """Spawned-child main: pin JAX to ``platform``, build engine, bind an
    ephemeral port, hand it back over the pipe, serve.  Any startup
    failure travels back as a traceback instead of a silent dead child."""
    os.environ["JAX_PLATFORMS"] = platform
    try:
        svc = build_service(service_kind, cfg_dict)
        srv = WorkerServer(svc, host=host, port=0)
    except BaseException:
        try:
            conn.send(("error", traceback.format_exc()))
        finally:
            conn.close()
        return
    conn.send(("ok", srv.port))
    conn.close()
    try:
        srv.serve_forever()
    finally:
        try:
            svc.close()
        except BaseException:
            pass


def spawn_worker(service_kind: str, cfg_dict: dict, *, platform: str,
                 host: str = "127.0.0.1",
                 spawn_timeout_s: float = 300.0):
    """Start a worker process (spawn context, daemon) and wait for its
    port.  ``platform`` is the child's ``JAX_PLATFORMS`` — the caller's
    choice, since a chip belongs to one process at a time.  Returns
    ``(process, port)``; on failure the child is reaped before the error
    propagates (no orphan PIDs)."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    rx, tx = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=_worker_main,
                       args=(tx, service_kind, cfg_dict, host, platform),
                       daemon=True, name=f"sketch-worker-{service_kind}")
    proc.start()
    tx.close()
    try:
        if not rx.poll(spawn_timeout_s):
            raise TimeoutError(
                f"worker [{service_kind}] did not report a port within "
                f"{spawn_timeout_s}s")
        status, payload = rx.recv()
    except BaseException:
        reap_process(proc)
        raise
    finally:
        rx.close()
    if status != "ok":
        reap_process(proc)
        raise RuntimeError(
            f"worker [{service_kind}] failed to start:\n{payload}")
    return proc, int(payload)


def reap_process(proc, timeout_s: float = 5.0) -> None:
    """Make sure a worker process is gone: join, then terminate, then
    kill.  Safe on already-dead processes; never raises."""
    if proc is None:
        return
    try:
        proc.join(timeout_s)
        if proc.is_alive():
            proc.terminate()
            proc.join(timeout_s)
        if proc.is_alive():
            proc.kill()
            proc.join(timeout_s)
    except BaseException:
        pass
