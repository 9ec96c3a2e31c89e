"""Plain LSH reference: the two hash families re-derived from the seed and
evaluated on the host in float64 (or, for the control, on int8-quantized
operands).

Parameter derivation follows the published construction the services use
(``jax.random`` draws from ``PRNGKey(seed)``): SRP takes
``kp, km = split(key)``; p-stable takes ``kp, kb, km = split(key, 3)``;
projections ``normal(kp, (d, L·k))``, p-stable offsets ``uniform(kb, (L·k,),
0, w)``, odd multipliers ``(randint(km, (L, k), 1, 2³¹−1) << 1) | 1``; each
point's ``k`` raw hashes are folded to ``n_buckets`` by the multiply-shift
``((Σ raw·mix) · 2654435761) mod n_buckets`` in uint32.  The draws run on
the host's CPU device, never on the chip.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = np.uint32(2654435761)


@dataclasses.dataclass(frozen=True)
class HashParams:
    proj: np.ndarray            # (d, L·k) float32
    mix: np.ndarray             # (L, k) uint32
    L: int
    k: int
    n_buckets: int
    bias: np.ndarray | None = None   # (L·k,) float32, p-stable only
    w: float = 0.0


def _cpu():
    return jax.default_device(jax.devices("cpu")[0])


def _mix(km, L: int, k: int) -> np.ndarray:
    m = jax.random.randint(km, (L, k), 1, 2**31 - 1, dtype=jnp.int32)
    return ((np.asarray(m).astype(np.uint32) << np.uint32(1))
            | np.uint32(1))


def srp_params(seed: int, dim: int, L: int, k: int,
               n_buckets: int) -> HashParams:
    with _cpu():
        kp, km = jax.random.split(jax.random.PRNGKey(seed))
        proj = np.asarray(jax.random.normal(kp, (dim, L * k), jnp.float32))
        return HashParams(proj=proj, mix=_mix(km, L, k), L=L, k=k,
                          n_buckets=n_buckets)


def pstable_params(seed: int, dim: int, L: int, k: int, w: float,
                   n_buckets: int) -> HashParams:
    with _cpu():
        kp, kb, km = jax.random.split(jax.random.PRNGKey(seed), 3)
        proj = np.asarray(jax.random.normal(kp, (dim, L * k), jnp.float32))
        bias = np.asarray(jax.random.uniform(kb, (L * k,), jnp.float32,
                                             0.0, w))
        return HashParams(proj=proj, mix=_mix(km, L, k), L=L, k=k,
                          n_buckets=n_buckets, bias=bias, w=w)


def quantize_int8(a: np.ndarray) -> np.ndarray:
    """Symmetric per-tensor int8 quantization, returned dequantized."""
    scale = float(np.abs(a).max()) / 127.0 or 1.0
    return np.clip(np.round(a / scale), -127, 127) * scale


def codes(p: HashParams, x: np.ndarray, precision: str = "float64",
          block: int = 65_536) -> np.ndarray:
    """Bucket ids ``(n, L)`` int64 of ``x (n, d)``.

    ``precision`` is ``"float64"`` (the reference) or ``"int8"`` (the
    control: projection on int8-quantized points and projections, one step
    below the one bfloat16 pass a TPU's default matmul precision takes)."""
    proj = p.proj.astype(np.float64)
    if precision == "int8":
        proj = quantize_int8(proj)
    elif precision != "float64":
        raise ValueError(precision)
    out = []
    for i in range(0, x.shape[0], block):
        xb = x[i:i + block].astype(np.float64)
        if precision == "int8":
            xb = quantize_int8(xb)
        y = xb @ proj
        if p.bias is None:
            raw = (y >= 0).astype(np.int64)
        else:
            raw = np.floor((y + p.bias.astype(np.float64)) / p.w)
            raw = raw.astype(np.int64)
        raw = raw.reshape(xb.shape[0], p.L, p.k).astype(np.uint32)
        acc = (raw * p.mix).sum(axis=-1, dtype=np.uint32)
        out.append(((acc * GOLDEN) % np.uint32(p.n_buckets)).astype(np.int64))
    if not out:
        return np.zeros((0, p.L), np.int64)
    return np.concatenate(out)
