"""Jitted public wrappers for the Pallas kernels.

Dispatch policy (single source of truth: `dispatch.py`):
  * on TPU backends → the op's entry in `dispatch.TPU_ROUTE`: the compiled
    Pallas kernel, or the jitted `ref.py` oracle for kernels the TPU
    compiler refuses;
  * on CPU → the pure-jnp oracle (`ref.py`) by default, because Pallas
    interpret mode is a Python-level emulator (correct but slow) — set
    ``REPRO_FORCE_PALLAS=1`` to route through interpret-mode kernels
    (this is what tests/test_kernels.py does when comparing vs ref).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import batch_score as _bs
from . import cand_score as _cs
from . import ingest_commit as _ic
from . import race_update as _ru
from . import ref
from . import sketch_decode_attn as _sda
from . import srp_hash as _sh
from .dispatch import resolve_interpret, use_pallas as _use_pallas


def _interpret() -> bool:
    return resolve_interpret(None)


def srp_hash(x: jax.Array, proj: jax.Array, mix: jax.Array, n_buckets: int) -> jax.Array:
    if _use_pallas("srp_hash"):
        return _sh.srp_hash(x, proj, mix, n_buckets, interpret=_interpret())
    return ref.srp_hash_ref(x, proj, mix, n_buckets)


def race_hist(codes: jax.Array, W: int) -> jax.Array:
    if _use_pallas("race_hist"):
        return _ru.race_hist(codes, W, interpret=_interpret())
    if W <= 128:
        # CPU mirror of the TPU kernel's one-hot compare + reduce (XLA CPU
        # scatters cost ~60ns/element; a fused compare-reduce over a small
        # W is several times faster).  Chunked over the batch so the
        # broadcast tile stays cache-resident.
        B, L = codes.shape
        cb = 128
        pad = (-B) % cb
        padded = jnp.pad(codes, ((0, pad), (0, 0)), constant_values=-1)
        iota = jnp.arange(W, dtype=codes.dtype)

        def step(acc, blk):
            return acc + (blk[:, :, None] == iota).sum(0, dtype=jnp.int32), None

        acc, _ = jax.lax.scan(step, jnp.zeros((L, W), jnp.int32),
                              padded.reshape(-1, cb, L))
        return acc
    return ref.race_update_ref(jnp.zeros((codes.shape[1], W), jnp.int32), codes)


def cand_score(q: jax.Array, cands: jax.Array) -> jax.Array:
    if _use_pallas("cand_score"):
        return _cs.cand_score(q, cands, interpret=_interpret())
    return ref.cand_score_ref(q, cands)


def batch_score_topk(qs: jax.Array, cands: jax.Array, ok: jax.Array,
                     k: int) -> tuple[jax.Array, jax.Array]:
    """Fused batched scorer: masked squared-L2 top-k of ``cands (B, M, d)``
    against ``qs (B, d)`` → ``(d2 (B, k) ascending, idx (B, k) int32)``.

    k = 1 is the argmin of the (c, r)-query path.  TPU: one Pallas pass
    (diff-based distances, running top-k across M tiles).  CPU: the
    diff-based oracle — bit-identical to the per-query `cand_score` path,
    which is what makes the fused engine exactly match the vmapped oracle
    in tests/test_query_batched.py."""
    if _use_pallas("batch_score_topk"):
        return _bs.batch_score_topk(qs, cands, ok, k, interpret=_interpret())
    return ref.batch_score_topk_ref(qs, cands, ok, k)


def sketch_decode_attn(q, k, v, block_ids, n_live, kv_len,
                       block_size: int = 512, softcap: float = 0.0) -> jax.Array:
    if _use_pallas("sketch_decode_attn"):
        return _sda.sketch_decode_attn(
            q, k, v, block_ids, n_live, kv_len,
            block_size=block_size, softcap=softcap, interpret=_interpret())
    nb = (k.shape[0] + block_size - 1) // block_size
    live = jnp.zeros((nb,), bool).at[jnp.maximum(block_ids, 0)].set(
        block_ids >= 0)
    return ref.sketch_decode_attn_ref(
        q, k, v, live, kv_len[0], block_size, softcap)


def swakde_segment_pass(cell_ts, cell_num, done, sorted_ts, seg_first,
                        seg_len, *, window: int, maxb: int, n_levels: int,
                        cap: int = 0):
    """One expiry-free closed-form commit pass over the prepared segments
    (ingest tentpole — see `ref.swakde_segment_pass_ref` for the contract).
    Both TPU (see `dispatch.TPU_ROUTE`) and CPU run the oracle: one fused
    pass replaces the per-add SumEH replay, independent of segment length;
    the tiled `ingest_commit.swakde_segment_pass` kernel runs only in
    interpret mode."""
    if _use_pallas("swakde_segment_pass"):
        return _ic.swakde_segment_pass(
            cell_ts, cell_num, done, sorted_ts, seg_first, seg_len,
            window=window, maxb=maxb, n_levels=n_levels, cap=cap,
            interpret=_interpret())
    return ref.swakde_segment_pass_ref(
        cell_ts, cell_num, done, sorted_ts, seg_first, seg_len,
        window=window, maxb=maxb, n_levels=n_levels, cap=cap)


def sann_table_scatter(tables, table_ptr, s_l, s_c, rank, val, mask):
    """Sorted-segment ring append into the S-ANN hash tables (entries are
    sorted by (row, code); see `ref.sann_table_scatter_ref`).  In place
    where that is cheaper than the oracle's whole-table scatter: a chunk's
    appends, not `sann_merge`'s replay of two whole sketches."""
    if (_use_pallas("sann_table_scatter")
            and _ic.append_in_place(s_l.shape[0], tables.size)):
        return _ic.sann_table_scatter(tables, table_ptr, s_l, s_c, rank, val,
                                      mask, interpret=_interpret())
    return ref.sann_table_scatter_ref(tables, table_ptr, s_l, s_c, rank,
                                      val, mask)


live_blocks_from_sketch = _sda.live_blocks_from_sketch
