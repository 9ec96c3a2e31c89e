"""Plain S-ANN reference (paper §3, Alg. 1) on the host.

Sampling: point ``i`` of ingest chunk ``seq`` is kept iff
``bernoulli(fold_in(fold_in(fold_in(PRNGKey(seed + 1), salt), seq), i),
n_max^-eta)`` — the service's per-chunk key schedule, drawn here on the
host's CPU device.  The ``p``-th kept point takes point-store slot ``p mod
capacity``; a later point that takes the same slot replaces it, and every
table entry of the replaced point then holds -1.  Each kept point appends
its slot to the ring buffer (``bucket_cap`` entries) of bucket ``code_l``
in each of the L tables; a ring keeps the newest ``bucket_cap`` appends.
A top-k query scores the union of its L buckets' rings, each stored slot
once, and returns the k closest by float64 distance.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def keep_prob(n_max: int, eta: float) -> float:
    return n_max ** (-eta)


def capacity(n_max: int, eta: float, slack: float = 4.0) -> int:
    return max(64, int(slack * n_max ** (1.0 - eta)))


def n_buckets(n_max: int, eta: float) -> int:
    return max(64, int(4 * capacity(n_max, eta)))


@jax.jit
def _keep_chunk(base, seq, p, n_idx):
    key = jax.random.fold_in(base, seq)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(n_idx)
    return jax.vmap(lambda k: jax.random.bernoulli(k, p))(keys)


def keep_mask(seed: int, salt: int, first_seq: int, n_chunks: int,
              chunk: int, p: float) -> np.ndarray:
    """Keep decisions of chunks ``[first_seq, first_seq + n_chunks)``,
    flattened in stream order."""
    with jax.default_device(jax.devices("cpu")[0]):
        base = jax.random.fold_in(jax.random.PRNGKey(seed + 1), salt)
        idx = jnp.arange(chunk, dtype=jnp.int32)
        pf = jnp.float32(p)
        out = [np.asarray(_keep_chunk(base, jnp.int32(s), pf, idx))
               for s in range(first_seq, first_seq + n_chunks)]
    return (np.concatenate(out) if out else np.zeros(0, bool))


@dataclasses.dataclass
class Replay:
    """Tables of a host replay: for each surviving entry its row ``l``,
    table column ``col`` (= code · bucket_cap + ring position) and slot;
    plus, per bucket key ``l · n_buckets + code``, the ring contents in
    column order (slot -1 = empty) for queries."""
    l: np.ndarray
    col: np.ndarray
    slot: np.ndarray
    keys: np.ndarray            # sorted unique bucket keys
    rings: np.ndarray           # (n_keys, bucket_cap) slots, -1 = empty
    n_buckets: int
    bucket_cap: int
    appends: int                # total appends (Σ table_ptr)


def owners(n_kept: int, cap: int) -> np.ndarray:
    """Kept rank of each written slot's current point: slot ``s`` holds the
    last ``p < n_kept`` with ``p mod cap == s``."""
    n_slots = min(n_kept, cap)
    s = np.arange(n_slots, dtype=np.int64)
    return s + (n_kept - 1 - s) // cap * cap


def replay(codes: np.ndarray, n_buckets: int, bucket_cap: int,
           cap: int) -> Replay:
    """Ring-buffer appends of kept points ``0..n-1`` with hash codes
    ``codes (n, L)`` into a point store of ``cap`` slots."""
    n, L = codes.shape
    rank_p = np.repeat(np.arange(n, dtype=np.int64), L)
    row = np.tile(np.arange(L, dtype=np.int64), n)
    key = row * n_buckets + codes.reshape(-1)
    order = np.argsort(key, kind="stable")          # stream order per bucket
    key, rank_p, row = key[order], rank_p[order], row[order]
    # the point's slot while it is stored; -1 once a later point took it
    slot = np.where(rank_p >= n - cap, rank_p % cap, -1)
    start = np.r_[True, key[1:] != key[:-1]] if n else np.zeros(0, bool)
    seg = np.cumsum(start) - 1
    first = np.flatnonzero(start)
    rank = np.arange(key.size) - first[seg]
    total = np.diff(np.r_[first, key.size])[seg]
    live = rank >= total - bucket_cap
    code = key % n_buckets
    col = code * bucket_cap + rank % bucket_cap
    keys = key[first]
    rings = np.full((keys.size, bucket_cap), -1, np.int64)
    rings[seg[live], (rank % bucket_cap)[live]] = slot[live]
    return Replay(l=row[live], col=col[live], slot=slot[live], keys=keys,
                  rings=rings, n_buckets=n_buckets, bucket_cap=bucket_cap,
                  appends=int(key.size))


def topk(rep: Replay, stored: np.ndarray, qcodes: np.ndarray,
         qs: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Top-k over the union of each query's L bucket rings:
    ``(ids (B, k) -1-padded, dists (B, k) float64 inf-padded)``."""
    B, L = qcodes.shape
    qkey = np.arange(L) * rep.n_buckets + qcodes              # (B, L)
    pos = np.searchsorted(rep.keys, qkey)
    pos_c = np.minimum(pos, max(rep.keys.size - 1, 0))
    hit = (rep.keys.size > 0) & (rep.keys[pos_c] == qkey)
    cand = np.where(hit[..., None], rep.rings[pos_c], -1).reshape(B, -1)
    ids = np.full((B, k), -1, np.int64)
    dists = np.full((B, k), np.inf)
    x = stored.astype(np.float64)
    for b in range(B):
        c = np.unique(cand[b][cand[b] >= 0])
        if c.size == 0:
            continue
        d = np.sqrt(((x[c] - qs[b].astype(np.float64)) ** 2).sum(axis=1))
        o = np.argsort(d, kind="stable")[:k]
        ids[b, :o.size] = c[o]
        dists[b, :o.size] = d[o]
    return ids, dists
