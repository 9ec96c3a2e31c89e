"""Host spans around the service's layer calls, recorded from the
benchmark's side.

`Spans.wrap` replaces a bound method on the one service instance a run
built: each call is timed on the host clock and, in a traced run, also
wrapped in ``jax.profiler.TraceAnnotation`` so the span appears in the
profiler trace on the device's clock.  The wrapped calls block until
their result is ready (the engine's ``_prepare_ready`` and ``_commit_one``
do), so a span covers the device work.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

import jax


class Spans:
    def __init__(self, traced: bool):
        self.traced = traced
        self.records: list = []      # (name, t0, t1, rows)
        self._cv = threading.Condition()

    def wrap(self, obj, attr: str, name: str,
             rows: Optional[Callable] = None) -> None:
        fn = getattr(obj, attr)
        traced, records, cv = self.traced, self.records, self._cv

        def wrapped(*args, **kw):
            t0 = time.perf_counter()
            if traced:
                with jax.profiler.TraceAnnotation(name):
                    out = fn(*args, **kw)
            else:
                out = fn(*args, **kw)
            t1 = time.perf_counter()
            with cv:
                records.append((name, t0, t1, rows(args) if rows else 0))
                cv.notify_all()
            return out

        setattr(obj, attr, wrapped)

    def of(self, name: str, t0: float = float("-inf"),
           t1: float = float("inf")) -> list:
        """Spans named ``name`` that end in ``(t0, t1]``."""
        with self._cv:
            return [r for r in self.records
                    if r[0] == name and t0 < r[2] <= t1]

    def wait_for(self, name: str, after: float, timeout: float):
        """The first span ``name`` ending after ``after``; None on timeout."""
        deadline = time.perf_counter() + timeout
        with self._cv:
            while True:
                for r in self.records:
                    if r[0] == name and r[2] > after:
                        return r
                left = deadline - time.perf_counter()
                if left <= 0:
                    return None
                self._cv.wait(min(left, 0.5))
