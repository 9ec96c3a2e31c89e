"""The general traffic generator: one driver per traffic ``kind``, each
read from a traffic file's parameters.

``prefill`` — set-up first commits this many points through
``ingest_async`` + ``flush``; a string names the configuration setting
that holds the count (``"window"``, ``"n_max"``).

``ingest`` — saturating stream ingest.  Set-up commits the prefill, at
least ``warm_chunks`` chunks, which also compiles the prepare and commit
programs.  The window's feeder thread then keeps the engine's queue
non-empty through ``ingest_async`` (its ``max_pending`` setting is the
backpressure).  The window opens at the
first commit completion and closes at the first commit completion
``seconds`` later; the rate is the rows committed in between over that
time.  Afterwards the feeder stops and every submitted chunk is flushed.
"""
from __future__ import annotations

import threading
import time

from chipbench import stats


def _prefill_chunks(run, traffic: dict) -> int:
    pre = traffic.get("prefill", 0)
    if isinstance(pre, str):
        pre = run.settings[pre]
    return -(-int(pre) // run.chunk)


def _prefill(run, n_chunks: int) -> None:
    for j in range(n_chunks):
        run.svc.ingest_async(run.stream.chunk_at(j))
    run.svc.flush()
    run.chunks_ingested = n_chunks


class IngestDriver:
    def __init__(self, run, traffic: dict):
        self.run, self.traffic = run, traffic
        self.window_stats: dict = {}

    def setup(self) -> None:
        run, tr = self.run, self.traffic
        _prefill(run, max(_prefill_chunks(run, tr),
                          int(tr.get("warm_chunks", 1))))

    def window(self, seconds: float) -> None:
        run = self.run
        stop = threading.Event()
        fed = [0]
        first = run.chunks_ingested

        def feed():
            j = first
            while not stop.is_set():
                run.svc.ingest_async(run.stream.chunk_at(j))
                j += 1
                fed[0] = j - first

        t_start = time.perf_counter()
        feeder = threading.Thread(target=feed, name="chipbench-feeder",
                                  daemon=True)
        feeder.start()
        try:
            opened = run.spans.wait_for("commit", t_start, timeout=600)
            if opened is None:
                raise RuntimeError("no commit completed within 600 s")
            closed = run.spans.wait_for("commit", opened[2] + seconds,
                                        timeout=seconds + 600)
            if closed is None:
                raise RuntimeError("no commit closed the window")
        finally:
            stop.set()
            feeder.join()
        run.svc.flush()
        run.chunks_ingested = first + fed[0]
        commits = run.spans.of("commit", opened[2] - 1e-9, closed[2])
        rate = stats.commit_window([c[2] for c in commits],
                                   [c[3] for c in commits], seconds)
        self.window_stats = {
            "t0": opened[2], "t1": closed[2],
            "rows": rate[0], "seconds": rate[1],
            "attempted": len(commits) - 1, "failed": 0}


DRIVERS = {"ingest": IngestDriver}
