"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the *semantic definition*; kernels must match it to
float tolerance across the shape/dtype sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_MIX = jnp.uint32(2654435761)


def srp_hash_ref(x: jax.Array, proj: jax.Array, mix: jax.Array, n_buckets: int) -> jax.Array:
    """x (B, d), proj (d, L*k), mix (L, k) → codes (B, L) int32 in [0, n_buckets)."""
    B = x.shape[0]
    L, k = mix.shape
    y = x.astype(jnp.float32) @ proj.astype(jnp.float32)       # (B, L*k)
    bits = (y >= 0).astype(jnp.uint32).reshape(B, L, k)
    acc = (bits * mix[None]).sum(axis=-1) * _MIX
    return (acc % jnp.uint32(n_buckets)).astype(jnp.int32)


def race_update_ref(counts: jax.Array, codes: jax.Array, sign: int = 1) -> jax.Array:
    """counts (L, W), codes (B, L) → counts + sign * histogram.

    Scatter-add (deterministic for integer counters) rather than a
    materialised (B, L, W) one-hot: O(B*L) memory instead of O(B*L*W)."""
    L, _ = counts.shape
    rows = jnp.broadcast_to(jnp.arange(L, dtype=codes.dtype), codes.shape)
    return counts.at[rows, codes].add(jnp.int32(sign))


def cand_score_ref(q: jax.Array, cands: jax.Array) -> jax.Array:
    """q (d,), cands (M, d) → squared L2 distances (M,) in fp32."""
    q = q.astype(jnp.float32)
    c = cands.astype(jnp.float32)
    return jnp.sum((c - q[None, :]) ** 2, axis=-1)


def batch_score_ref(qs: jax.Array, cands: jax.Array) -> jax.Array:
    """qs (B, d), cands (B, M, d) → squared L2 distances (B, M) in fp32.

    Diff-based (no matmul identity): the batched form of `cand_score_ref`,
    bit-identical to vmapping it over the B axis."""
    q = qs.astype(jnp.float32)
    c = cands.astype(jnp.float32)
    return jnp.sum((c - q[:, None, :]) ** 2, axis=-1)


def batch_score_topk_ref(qs: jax.Array, cands: jax.Array, ok: jax.Array,
                         k: int) -> tuple[jax.Array, jax.Array]:
    """Masked squared-L2 top-k per query (k = 1 ⇒ argmin): ``(d2 (B, k)
    ascending, idx (B, k) int32 into M)``.  Masked entries score inf; ties
    resolve to the lowest candidate index (`lax.top_k` semantics)."""
    d2 = jnp.where(ok, batch_score_ref(qs, cands), jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx.astype(jnp.int32)


def swakde_segment_pass_ref(
    cell_ts: jax.Array,    # (R, G, levels, S) int32 — gathered EH rings
    cell_num: jax.Array,   # (R, G, levels) int32 — live buckets per level
    done: jax.Array,       # (R, G) int32 — arrivals already committed
    sorted_ts: jax.Array,  # (R, C) int32 — per-row stamps in sorted order
    seg_first: jax.Array,  # (R, G) int32 — first sorted position of segment
    seg_len: jax.Array,    # (R, G) int32 — arrivals hitting each segment
    *,
    window: int,
    maxb: int,
    n_levels: int,
    cap: int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One closed-form sub-chunk commit pass over every (row, segment) pair.

    A segment is one cell's run of unit adds at ascending stamps
    ``sorted_ts[r, seg_first + done .. seg_first + seg_len)``.  Because the
    DGIM cascade's merge pattern depends only on per-level counts (stamps
    enter it solely through expiry), a run during which no bucket expires is
    binary-counter carry propagation — every level settles in one shot, like
    `core.eh.sum_eh_add` (Corollary 4.2), except the carried-up stamps are a
    bounded explicit prefix plus a stride-2^level window into ``sorted_ts``
    instead of a count of repeated ``t``s.

    The pass consumes the longest prefix of each segment's remaining
    arrivals that is expiry-free — every stamp live after expiring at the
    first arrival survives the last arrival's threshold, and the pass's own
    stamps span less than ``window`` — capped at ``cap`` arrivals when
    ``cap > 0`` (the heavy-cell cap: bounds per-pass work for hot cells).
    Segments with nothing to consume are left untouched (lazy expiry, like
    the per-point path).  Callers loop until ``done == seg_len``; the result
    is bit-identical (dead slots included) to replaying the arrivals through
    ``eh_add`` one by one.
    """
    R, G, LV, S = cell_ts.shape
    C = sorted_ts.shape[1]
    i32 = jnp.int32
    INT_MAX = jnp.iinfo(jnp.int32).max
    sidx = jnp.arange(S, dtype=i32)

    active = done < seg_len                                      # (R, G)
    start = seg_first + done                                     # (R, G)
    t_first = jnp.take_along_axis(
        sorted_ts, jnp.clip(start, 0, C - 1), axis=1)            # (R, G)

    # --- expire active cells at their first arrival ------------------------
    live = (sidx < cell_num[..., None]) & \
        (cell_ts > t_first[..., None, None] - window)            # (R,G,LV,S)
    cell_num = jnp.where(active[..., None],
                         live.sum(-1).astype(i32), cell_num)
    oldest = jnp.where(live, cell_ts, INT_MAX).min((-2, -1))     # (R, G)

    # --- expiry-free pass length -------------------------------------------
    # Arrivals are ascending per segment, so both conditions select a prefix:
    # no surviving bucket may expire (thr < oldest) and the pass's own first
    # stamp must outlive its last (thr < t_first).
    pos = jnp.arange(C, dtype=i32)[None, None, :]
    thr = sorted_ts[:, None, :] - window                         # (R, 1, C)
    in_seg = (pos >= start[..., None]) & \
        (pos < (seg_first + seg_len)[..., None])
    ok = (thr < oldest[..., None]) & (thr < t_first[..., None])
    p = (in_seg & ok).sum(-1).astype(i32)                        # (R, G)
    if cap:
        p = jnp.minimum(p, i32(cap))
    p = jnp.where(active, jnp.minimum(p, seg_len - done), 0)

    # --- the per-level closed form -----------------------------------------
    # Carried-up arrivals at each level: an explicit prefix P[:np] (old ring
    # stamps consumed by lower-level merges) followed by r stamps read from
    # sorted_ts at positions b, b+stride, b+2*stride, ...
    def queue(i, ts_l, m0, P, np_, b, stride):
        """Oldest-first queue lookup: reversed live ring ++ P ++ strided
        tail.  ``i`` (R, G, n) int32; out-of-range reads are clipped (the
        caller masks them)."""
        K = (m0 + np_)[..., None]
        ring = jnp.take_along_axis(
            ts_l, jnp.clip(m0[..., None] - 1 - i, 0, S - 1), -1)
        pre = jnp.take_along_axis(
            P, jnp.clip(i - m0[..., None], 0, S - 1), -1)
        tpos = jnp.clip(b[..., None] + (i - K) * stride[..., None], 0, C - 1)
        # Gather from the row's stamps directly: a (R, G, C) broadcast of
        # them would be materialised (1 GiB at G=1024, C=4096) on the TPU.
        tail = jnp.take_along_axis(
            sorted_ts, tpos.reshape(R, -1), axis=1).reshape(tpos.shape)
        return jnp.where(i < m0[..., None], ring,
                         jnp.where(i < K, pre, tail))

    def level_body(l, carry):
        cts, cnum, P, np_, b, stride, r = carry
        ts_l = jax.lax.dynamic_index_in_dim(cts, l, axis=2, keepdims=False)
        m0 = jax.lax.dynamic_index_in_dim(cnum, l, axis=2, keepdims=False)
        p_l = np_ + r                                            # arrivals
        K = m0 + np_
        total = m0 + p_l
        # Merge count: the level fills to maxb+1 once, then every second
        # arrival fires (the sum_eh_add saturation dynamics).  The top
        # level never merges.
        mu = jnp.where((total <= maxb) | (l == n_levels - 1),
                       0, 1 + (p_l - (maxb + 1 - m0)) // 2)
        new_num = total - 2 * mu
        # Final ring: the p_l arrivals newest-first, then the old ring
        # shifted — exactly the writes p_l sequential prepends perform.
        arr = queue(total[..., None] - 1 - sidx, ts_l, m0, P, np_, b, stride)
        old = jnp.take_along_axis(
            ts_l, jnp.clip(sidx - p_l[..., None], 0, S - 1), -1)
        new_ts = jnp.where(sidx < p_l[..., None], arr, old)
        # Carries up: merge j consumes queue[2j], queue[2j+1] and emits the
        # newer stamp queue[2j+1].  Those with 2j+1 < K become the explicit
        # prefix; the rest are a stride-doubled window into sorted_ts.
        np_n = jnp.minimum(mu, K // 2)
        r_n = mu - np_n
        P_n = queue(2 * sidx + 1, ts_l, m0, P, np_, b, stride)
        b_n = jnp.clip(b + (2 * np_n + 1 - K) * stride, 0, C - 1)
        cts = jax.lax.dynamic_update_index_in_dim(cts, new_ts, l, axis=2)
        cnum = jax.lax.dynamic_update_index_in_dim(cnum, new_num, l, axis=2)
        return (cts, cnum, P_n, np_n, b_n, stride * 2, r_n)

    init = (cell_ts, cell_num, jnp.zeros((R, G, S), i32),
            jnp.zeros((R, G), i32), jnp.clip(start, 0, C - 1),
            jnp.ones((R, G), i32), p)
    cell_ts, cell_num = jax.lax.fori_loop(0, LV, level_body, init)[:2]
    return cell_ts, cell_num, done + p


def sann_table_scatter_ref(
    tables: jax.Array,     # (L, n_buckets * bucket_cap) int32 (or 3-D)
    table_ptr: jax.Array,  # (L, n_buckets) int32 — per-bucket ring pointers
    s_l: jax.Array,        # (B*L,) int32 — row of each sorted append
    s_c: jax.Array,        # (B*L,) int32 — bucket code of each append
    rank: jax.Array,       # (B*L,) int32 — rank within its (row, code) run
    val: jax.Array,        # (B*L,) int32 — slot id to write (-1 tombstone)
    mask: jax.Array,       # (B*L,) bool — append lands in the final window
) -> jax.Array:
    """Sorted-segment ring append: entry i lands at ring position
    ``(table_ptr[s_l, s_c] + rank) % bucket_cap`` of its bucket; masked-out
    entries are dropped.  Entries are sorted by (row, code), so the writes
    of one bucket are contiguous — which is what the Pallas kernel tiles
    over.  ``tables`` may be flat per row or (L, n_buckets, bucket_cap):
    both lay the rings out in the same row-major order."""
    L, n_buckets = table_ptr.shape
    bucket_cap = tables.size // (L * n_buckets)
    ring_pos = (table_ptr[s_l, s_c] + rank) % bucket_cap
    flat = (s_l * n_buckets + s_c) * bucket_cap + ring_pos
    tsize = jnp.int32(tables.size)
    return tables.reshape(-1).at[
        jnp.where(mask, flat, tsize)].set(val, mode="drop").reshape(
        tables.shape)


def sketch_decode_attn_ref(
    q: jax.Array,            # (Hkv, G, dh)
    k: jax.Array,            # (S, Hkv, dh)
    v: jax.Array,            # (S, Hkv, dh)
    block_live: jax.Array,   # (num_blocks,) bool — sketch-pruned block mask
    kv_len: jax.Array,       # () int32 — #valid cache positions
    block_size: int,
    softcap: float = 0.0,
) -> jax.Array:
    """Masked decode attention: softmax over positions whose block survives
    the sketch pruning AND lie within kv_len.  Returns (Hkv, G, dh) fp32."""
    S = k.shape[0]
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    scores = jnp.einsum(
        "hgd,shd->hgs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if softcap and softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    pos = jnp.arange(S)
    live = block_live[pos // block_size] & (pos < kv_len)
    scores = jnp.where(live[None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)  # all-masked rows → zero output
    return jnp.einsum("hgs,shd->hgd", w, v.astype(jnp.float32))
