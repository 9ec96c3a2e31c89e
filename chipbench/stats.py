"""Metric arithmetic shared by the readers."""
from __future__ import annotations

import numpy as np


def commit_window(done_times, rows, seconds: float):
    """Rate window over commit completions: it opens at the first
    completion and closes at the first completion at least ``seconds``
    later; the rows of the commits completed after the opening one, up to
    and including the closing one, over that time.  Returns ``(rows,
    seconds)`` or None when no commit closed the window."""
    t = np.asarray(done_times, np.float64)
    r = np.asarray(rows, np.int64)
    if t.size < 2:
        return None
    order = np.argsort(t, kind="stable")
    t, r = t[order], r[order]
    later = np.flatnonzero(t >= t[0] + seconds)
    if later.size == 0:
        return None
    end = later[0]
    return int(r[1:end + 1].sum()), float(t[end] - t[0])
