"""Plain SW-AKDE reference (paper §4, Alg. 2) on the host.

The exact windowed KDE of a query is the row mean of the number of the last
``N`` stream points that share its cell, ``count_l[code_l(q)]``, over
``min(t, N)``.  The sketch keeps each cell's count in an exponential
histogram (DGIM): buckets of sizes 2^level with the timestamp of their
newest point; the estimate at query clock ``t − 1`` is the total size of the
buckets whose timestamp lies in ``(t − 1 − N, t − 1]`` minus half the oldest
such bucket.  ``decode_cells`` reads that estimate straight from the
committed arrays ``ts (L, W, levels, slots)``, ``num (L, W, levels)``.
"""
from __future__ import annotations

import numpy as np


def window_counts(codes: np.ndarray, W: int) -> np.ndarray:
    """``(L, W)`` counts of the codes ``(n, L)`` of the window's points."""
    L = codes.shape[1]
    flat = (np.arange(L) * W + codes).reshape(-1)
    return np.bincount(flat, minlength=L * W).reshape(L, W)


def decode_cells(ts: np.ndarray, num: np.ndarray, t: int,
                 window: int) -> np.ndarray:
    """DGIM estimate of every cell at query clock ``t - 1`` → ``(L, W)``."""
    levels, slots = ts.shape[-2:]
    live = ((np.arange(slots) < num[..., None])
            & (ts > (t - 1) - window))
    sizes = (1 << np.arange(levels, dtype=np.int64))[:, None]
    total = np.where(live, sizes, 0).sum(axis=(-2, -1))
    has = live.any(axis=-1)
    top = np.where(has, np.arange(levels), -1).max(axis=-1)
    last = np.where(top >= 0, 1 << np.maximum(top, 0), 0)
    return np.maximum(total - last // 2, 0).astype(np.float64)


def densities(counts: np.ndarray, qcodes: np.ndarray,
              denom: int) -> np.ndarray:
    """Row mean of the query cells' counts over ``denom`` → ``(B,)``."""
    L = counts.shape[0]
    return counts[np.arange(L), qcodes].mean(axis=1) / float(denom)
