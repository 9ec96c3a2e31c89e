"""Locality-sensitive hash families (paper §2.1).

Two families, exactly the ones the paper uses:
  * SRP-LSH (angular / sign-random-projection) [Cha02]
  * p-stable Euclidean LSH [DIIM04]

Both are expressed as pure-JAX pytrees + functions so they can live inside
`vmap`/`scan`/`pjit`.  Hashing is a matmul (MXU-friendly), and these
functions are the served path on every backend: ingest and query hash with
them directly.  The Pallas kernel `repro.kernels.srp_hash` (with its oracle
`repro.kernels.ref.srp_hash_ref`) is not called on that path; the TPU
compiler refuses it (`kernels.dispatch.TPU_ROUTE`).  On a TPU the matmul
runs at the backend's default float32 precision.

A "hash function" here is always the paper's concatenated hash
``g(x) = (h_1(x), ..., h_k(x))`` folded to a bounded integer range via a
multiply-shift universal hash — the paper's "rehashing" trick (§5.2
*Implementation*).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# Golden-ratio multiplicative constant for multiply-shift hashing.
_MIX = np.uint32(2654435761)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SRPParams:
    """L independent concatenations of k signed-random-projection bits."""

    proj: jax.Array      # (d, L*k) float32 — N(0,1) projections
    mix: jax.Array       # (L, k) uint32   — per-bit universal-hash multipliers
    L: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))
    n_buckets: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PStableParams:
    """L independent concatenations of k p-stable (Euclidean) hashes."""

    proj: jax.Array      # (d, L*k) float32 — N(0,1)
    bias: jax.Array      # (L*k,) float32   — U[0, w)
    mix: jax.Array       # (L, k) uint32
    w: float = dataclasses.field(metadata=dict(static=True))
    L: int = dataclasses.field(metadata=dict(static=True))
    k: int = dataclasses.field(metadata=dict(static=True))
    n_buckets: int = dataclasses.field(metadata=dict(static=True))


def init_srp(key: jax.Array, dim: int, L: int, k: int, n_buckets: int) -> SRPParams:
    kp, km = jax.random.split(key)
    proj = jax.random.normal(kp, (dim, L * k), dtype=jnp.float32)
    mix = jax.random.randint(km, (L, k), 1, 2**31 - 1, dtype=jnp.int32)
    mix = (mix.astype(jnp.uint32) << 1) | jnp.uint32(1)  # odd multipliers
    return SRPParams(proj=proj, mix=mix, L=L, k=k, n_buckets=n_buckets)


def init_pstable(
    key: jax.Array, dim: int, L: int, k: int, w: float, n_buckets: int
) -> PStableParams:
    kp, kb, km = jax.random.split(key, 3)
    proj = jax.random.normal(kp, (dim, L * k), dtype=jnp.float32)
    bias = jax.random.uniform(kb, (L * k,), minval=0.0, maxval=w, dtype=jnp.float32)
    mix = jax.random.randint(km, (L, k), 1, 2**31 - 1, dtype=jnp.int32)
    mix = (mix.astype(jnp.uint32) << 1) | jnp.uint32(1)
    return PStableParams(proj=proj, bias=bias, mix=mix, w=w, L=L, k=k, n_buckets=n_buckets)


def _fold(raw: jax.Array, mix: jax.Array, n_buckets: int) -> jax.Array:
    """Universal multiply-shift fold of (..., L, k) integer hashes → (..., L) buckets."""
    acc = (raw.astype(jnp.uint32) * mix).sum(axis=-1)  # wraps mod 2^32
    acc = acc * _MIX
    return (acc % jnp.uint32(n_buckets)).astype(jnp.int32)


def srp_hash(params: SRPParams, x: jax.Array) -> jax.Array:
    """x: (..., d) → bucket ids (..., L) in [0, n_buckets).

    Each of the L hashes is k sign bits packed to an integer (range 2^k),
    then folded to n_buckets.
    """
    y = x @ params.proj                                  # (..., L*k)
    bits = (y >= 0).astype(jnp.uint32)
    bits = bits.reshape(*x.shape[:-1], params.L, params.k)
    return _fold(bits, params.mix, params.n_buckets)


def pstable_hash(params: PStableParams, x: jax.Array) -> jax.Array:
    """x: (..., d) → bucket ids (..., L) via floor((a.x+b)/w), concatenated k times."""
    y = (x @ params.proj + params.bias) / params.w
    h = jnp.floor(y).astype(jnp.int32)
    h = h.reshape(*x.shape[:-1], params.L, params.k)
    return _fold(h, params.mix, params.n_buckets)


def code_range(params) -> int | None:
    """Distinct bucket ids one row can reach, or None if unbounded.

    SRP packs k sign bits, so a row has 2^k raw values and `_fold` maps them
    to at most 2^k buckets.  p-stable raw hashes are unbounded integers."""
    if isinstance(params, SRPParams):
        return 2 ** params.k
    if isinstance(params, PStableParams):
        return None
    raise TypeError(type(params))


def hash_points(params, x: jax.Array) -> jax.Array:
    if isinstance(params, SRPParams):
        return srp_hash(params, x)
    if isinstance(params, PStableParams):
        return pstable_hash(params, x)
    raise TypeError(type(params))


# ---------------------------------------------------------------------------
# Collision probabilities (analysis-side; used by theory.py and tests)
# ---------------------------------------------------------------------------

def srp_collision_prob(x: jax.Array, y: jax.Array, p: int = 1) -> jax.Array:
    """k(x,y)^p for SRP: (1 - theta/pi)^p  [Cha02]."""
    nx = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    ny = y / jnp.maximum(jnp.linalg.norm(y, axis=-1, keepdims=True), 1e-12)
    cos = jnp.clip((nx * ny).sum(-1), -1.0, 1.0)
    theta = jnp.arccos(cos)
    return (1.0 - theta / jnp.pi) ** p


def pstable_collision_prob(dist, w: float, p: int = 1):
    """k(x,y)^p for 2-stable LSH at Euclidean distance ``dist`` [DIIM04]:

        p(s) = 1 - 2*Phi(-w/s) - (2s/(sqrt(2*pi)*w)) * (1 - exp(-w^2/(2 s^2)))
    """
    dist = jnp.asarray(dist, jnp.float32)
    s = jnp.maximum(dist, 1e-12)
    t = w / s
    phi = 0.5 * (1.0 + jax.lax.erf(-t / jnp.sqrt(2.0)))
    prob = 1.0 - 2.0 * phi - (2.0 / (jnp.sqrt(2.0 * jnp.pi) * t)) * (
        1.0 - jnp.exp(-(t**2) / 2.0)
    )
    prob = jnp.where(dist <= 0.0, 1.0, prob)
    return jnp.clip(prob, 0.0, 1.0) ** p
