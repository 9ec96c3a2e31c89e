"""Stream and query generators, addressable by chunk so a reference can
regenerate any part of a run from its seed alone.

The distributions are those of ``benchmarks/common.py`` (``sift_like``: 64
Gaussian clusters in 128-d, the SIFT stand-in; ``gaussian_mixture_stream``:
the paper's Monte-Carlo protocol, a new Gaussian every ``segment`` points),
rewritten so that point ``i`` of the stream is a pure function of the seed
and ``i``: chunk ``j`` draws from its own ``numpy`` generator.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_SEED_MOD = 2**63


def rng(seed: int, *path: int) -> np.random.Generator:
    """Independent generator for ``(seed, *path)``; any integer seed."""
    return np.random.default_rng([int(seed) % _SEED_MOD, *path])


class SiftLike:
    """128-d clustered vectors: ``clusters`` centres ~ N(0, 1), each point a
    centre plus ``spread`` · N(0, 1) noise."""

    def __init__(self, seed: int, dim: int, chunk: int, clusters: int = 64,
                 spread: float = 0.35):
        self.seed, self.dim, self.chunk = seed, dim, chunk
        self.spread = spread
        self.centers = rng(seed, 0).normal(0.0, 1.0, (clusters, dim))

    def chunk_at(self, j: int) -> np.ndarray:
        r = rng(self.seed, 1, j)
        which = r.integers(0, len(self.centers), self.chunk)
        noise = r.normal(size=(self.chunk, self.dim))
        return (self.centers[which] + self.spread * noise).astype(np.float32)


class GaussianMixture:
    """The paper's Monte-Carlo KDE stream: every ``segment`` points switch
    to a new Gaussian, mean ~ N(0, mean_sd²) per coordinate, unit noise.

    With ``data_seed`` the means and the noise are drawn from it, the same
    for every run, and the run's ``seed`` only orders the points within
    each chunk: every seed then commits the same points per chunk, so the
    same work, in another order."""

    def __init__(self, seed: int, dim: int, chunk: int, segment: int,
                 mean_sd: float = 2.0, data_seed: int | None = None):
        if segment % chunk:
            raise ValueError("segment must be a multiple of the chunk")
        self.order_seed = None if data_seed is None else seed
        self.seed = seed if data_seed is None else data_seed
        self.dim, self.chunk = dim, chunk
        self.segment, self.mean_sd = segment, mean_sd
        self._means: dict = {}

    def _mean(self, s: int) -> np.ndarray:
        if s not in self._means:
            self._means[s] = rng(self.seed, 0, s).normal(
                0.0, self.mean_sd, self.dim)
        return self._means[s]

    def chunk_at(self, j: int) -> np.ndarray:
        mu = self._mean(j * self.chunk // self.segment)
        noise = rng(self.seed, 1, j).normal(size=(self.chunk, self.dim))
        x = (mu + noise).astype(np.float32)
        if self.order_seed is not None:
            x = x[rng(self.order_seed, 2, j).permutation(self.chunk)]
        return x


GENERATORS = {"sift_like": SiftLike, "gaussian_mixture": GaussianMixture}


def make_stream(seed: int, dim: int, chunk: int, spec: dict):
    """Build the stream a configuration names: ``spec["generator"]`` plus
    its keyword parameters."""
    kw = {k: v for k, v in spec.items() if k != "generator"}
    return GENERATORS[spec["generator"]](seed, dim, chunk, **kw)


def rows(stream, idx, workers: int = 8) -> np.ndarray:
    """Stream points ``idx`` (ascending or not), regenerated chunk by chunk
    on ``workers`` threads (numpy draws without the GIL; each chunk has its
    own generator, so the points do not depend on the order)."""
    idx = np.asarray(idx, np.int64)
    out = np.zeros((idx.size, stream.dim), np.float32)
    which = idx // stream.chunk
    order = np.argsort(which, kind="stable")
    js, first = np.unique(which[order], return_index=True)
    bounds = np.r_[first, idx.size]

    def fill(i: int) -> None:
        sel = order[bounds[i]:bounds[i + 1]]
        j = int(js[i])
        out[sel] = stream.chunk_at(j)[idx[sel] - j * stream.chunk]

    with ThreadPoolExecutor(workers) as ex:
        list(ex.map(fill, range(js.size)))
    return out


def perturbed(src: np.ndarray, noise: float, r: np.random.Generator):
    """Queries around ``src`` rows: N(0, noise²) per coordinate."""
    return (src + r.normal(0.0, noise, src.shape)).astype(np.float32)
