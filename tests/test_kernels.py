"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, strategies as st

from repro.kernels import cand_score as cs_k
from repro.kernels import ingest_commit as ic_k
from repro.kernels import race_update as ru_k
from repro.kernels import ref
from repro.kernels import sketch_decode_attn as sda_k
from repro.kernels import srp_hash as sh_k


# ---------------------------------------------------------------------------
# srp_hash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [1, 7, 128, 300])
@pytest.mark.parametrize("d", [32, 128])
@pytest.mark.parametrize("Lk", [(4, 4), (8, 6)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_srp_hash_matches_ref(B, d, Lk, dtype):
    L, k = Lk
    key = jax.random.PRNGKey(B * d + L)
    x = jax.random.normal(key, (B, d), dtype)
    proj = jax.random.normal(jax.random.PRNGKey(1), (d, L * k), jnp.float32)
    mix = jax.random.randint(jax.random.PRNGKey(2), (L, k), 1, 2**30).astype(jnp.uint32) | 1
    got = sh_k.srp_hash(x, proj, mix, n_buckets=97, interpret=True)
    want = ref.srp_hash_ref(x, proj, mix, n_buckets=97)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(B=st.integers(1, 64), d=st.sampled_from([16, 64]), L=st.integers(1, 6))
def test_srp_hash_property(B, d, L):
    k = 3
    x = jax.random.normal(jax.random.PRNGKey(B), (B, d))
    proj = jax.random.normal(jax.random.PRNGKey(d), (d, L * k))
    mix = jax.random.randint(jax.random.PRNGKey(L), (L, k), 1, 2**30).astype(jnp.uint32) | 1
    got = sh_k.srp_hash(x, proj, mix, n_buckets=64, interpret=True)
    want = ref.srp_hash_ref(x, proj, mix, n_buckets=64)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# race_hist
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B", [1, 37, 300])
@pytest.mark.parametrize("L", [1, 4])
@pytest.mark.parametrize("W", [64, 128])
def test_race_hist_matches_ref(B, L, W):
    codes = jax.random.randint(jax.random.PRNGKey(B + L + W), (B, L), 0, W, jnp.int32)
    got = ru_k.race_hist(codes, W, interpret=True)
    want = ref.race_update_ref(jnp.zeros((L, W), jnp.int32), codes)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).sum() == B * L


# ---------------------------------------------------------------------------
# cand_score
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M", [1, 24, 256, 777])
@pytest.mark.parametrize("d", [8, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cand_score_matches_ref(M, d, dtype):
    q = jax.random.normal(jax.random.PRNGKey(M), (d,), dtype)
    c = jax.random.normal(jax.random.PRNGKey(d), (M, d), dtype)
    got = cs_k.cand_score(q, c, interpret=True)
    want = ref.cand_score_ref(q, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=1e-4)


# ---------------------------------------------------------------------------
# sketch_decode_attn
# ---------------------------------------------------------------------------

def _attn_case(seed, Hkv, G, dh, S, bs, softcap, frac_live, kv_len):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (Hkv, G, dh), jnp.float32)
    k = jax.random.normal(ks[1], (S, Hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (S, Hkv, dh), jnp.float32)
    nb = S // bs
    live = jax.random.uniform(ks[3], (nb,)) < frac_live
    ids = np.full((nb,), -1, np.int32)
    lv = np.where(np.asarray(live))[0]
    ids[: len(lv)] = lv
    block_ids = jnp.asarray(ids)
    n_live = jnp.asarray([len(lv)], jnp.int32)
    kvl = jnp.asarray([kv_len], jnp.int32)

    got = sda_k.sketch_decode_attn(
        q, k, v, block_ids, n_live, kvl, block_size=bs, softcap=softcap,
        interpret=True)
    want = ref.sketch_decode_attn_ref(q, k, v, live, kvl[0], bs, softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("softcap", [0.0, 20.0])
@pytest.mark.parametrize("frac_live", [1.0, 0.5])
def test_sketch_decode_attn_matches_ref(softcap, frac_live):
    _attn_case(0, Hkv=2, G=4, dh=64, S=1024, bs=128, softcap=softcap,
               frac_live=frac_live, kv_len=900)


def test_sketch_decode_attn_partial_kv():
    _attn_case(1, Hkv=1, G=8, dh=32, S=512, bs=64, softcap=0.0,
               frac_live=1.0, kv_len=100)


def test_sketch_decode_attn_no_live_blocks():
    """All blocks pruned → zero output (matches oracle's nan→0)."""
    _attn_case(2, Hkv=1, G=2, dh=32, S=256, bs=64, softcap=0.0,
               frac_live=0.0, kv_len=256)


# ---------------------------------------------------------------------------
# ingest_commit (segment-reduce SumEH commit + S-ANN table scatter)
# ---------------------------------------------------------------------------

def _segment_case(seed, R=3, G=11, LV=6, S=5, C=64, window=37):
    rng = np.random.default_rng(seed)
    base_t = 1000
    cell_num = rng.integers(0, S, (R, G, LV)).astype(np.int32)
    cell_ts = (base_t - rng.integers(0, window, (R, G, LV, S))).astype(np.int32)
    sorted_ts = np.sort(
        rng.integers(base_t, base_t + 50, (R, C)), axis=1).astype(np.int32)
    seg_first = np.zeros((R, G), np.int32)
    seg_len = np.zeros((R, G), np.int32)
    for r in range(R):
        cuts = np.sort(rng.choice(np.arange(1, C), size=G - 1, replace=False))
        bounds = np.concatenate([[0], cuts, [C]])
        seg_first[r] = bounds[:G]
        seg_len[r] = np.diff(bounds)[:G]
    done = np.minimum(rng.integers(0, 3, (R, G)), seg_len).astype(np.int32)
    return tuple(jnp.asarray(a) for a in (
        cell_ts, cell_num, done, sorted_ts, seg_first, seg_len)), window


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("cap", [0, 2])
@pytest.mark.parametrize("block_g", [4, 8, 16])
def test_swakde_segment_pass_matches_ref(seed, cap, block_g):
    """Tiled kernel == oracle bit-for-bit, including non-divisible segment
    grids (padding segments are empty → identity)."""
    args, window = _segment_case(seed)
    want = ref.swakde_segment_pass_ref(
        *args, window=window, maxb=3, n_levels=6, cap=cap)
    got = ic_k.swakde_segment_pass(
        *args, window=window, maxb=3, n_levels=6, cap=cap,
        block_g=block_g, interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def _random_scatter_args(seed, mask_frac):
    """Random sorted appends into random flat-per-row tables."""
    rng = np.random.default_rng(seed)
    L, NB, cap, E = 4, 13, 6, 70
    tables = rng.integers(-1, 100, (L, NB * cap)).astype(np.int32)
    table_ptr = rng.integers(0, cap, (L, NB)).astype(np.int32)
    s_l = rng.integers(0, L, E).astype(np.int32)
    s_c = rng.integers(0, NB, E).astype(np.int32)
    order = np.lexsort((s_c, s_l))
    s_l, s_c = s_l[order], s_c[order]
    rank = np.zeros(E, np.int32)
    for i in range(1, E):
        same = s_l[i] == s_l[i - 1] and s_c[i] == s_c[i - 1]
        rank[i] = rank[i - 1] + 1 if same else 0
    val = rng.integers(0, 10_000, E).astype(np.int32)
    mask = rng.random(E) < mask_frac
    return tuple(jnp.asarray(x) for x in
                 (tables, table_ptr, s_l, s_c, rank, val, mask))


def _commit_scatter_args(case, monkeypatch):
    """The ring append's arguments as `sann_commit_chunk` builds them, from
    a real commit into a small sketch: ``case`` picks the chunk."""
    from repro.core import sann
    seen = []

    def record(*args):
        seen.append(args)
        return ref.sann_table_scatter_ref(*args)

    monkeypatch.setattr(sann.kernel_ops, "sann_table_scatter", record)
    # n_max 50,000: 13,374 point slots; the small ring (270 slots, every
    # point kept) is nearly full before the chunk, which laps it.
    small = case == "recycles_slots"
    cfg = sann.SANNConfig(dim=8, n_max=300 if small else 50_000,
                          eta=0.0 if small else 0.25, r=0.5, c=2.0, L=4, k=2,
                          capacity_slack=0.9 if small else 4.0)
    cfg, params, st = sann.sann_init(cfg, jax.random.PRNGKey(0))
    B = 16
    xs = jax.random.uniform(jax.random.PRNGKey(1), (B, 8))
    if case == "merge":
        fill = lambda k: sann.sann_insert_chunked(
            st, params, jax.random.uniform(jax.random.PRNGKey(k), (8192, 8)),
            jax.random.PRNGKey(k + 1), cfg, chunk=2048)
        a, b = fill(2), fill(4)
        seen.clear()
        sann.sann_merge(a, b, params, cfg)
        return seen[-1]
    if case == "recycles_slots":
        st = sann.sann_insert_chunked(
            st, params, jax.random.uniform(jax.random.PRNGKey(3),
                                           (cfg.capacity - 5, 8)),
            jax.random.PRNGKey(4), cfg, chunk=64)
        assert int(st.n_stored) == cfg.capacity - 5
        keep = jnp.ones((B,), bool)
    elif case == "bucket_overflow":
        # one point 3 x bucket_cap times: each row's bucket gets 48 appends
        xs = jnp.broadcast_to(xs[:1], (3 * cfg.bucket_cap, 8))
        keep = jnp.ones((xs.shape[0],), bool)
    else:
        keep = jnp.full((B,), case == "all_kept")
    seen.clear()
    sann.sann_commit_chunk(
        st, sann.sann_prepare_given_keep(params, xs, keep, cfg), cfg)
    return seen[-1]


SCATTER_CASES = ([f"random-{s}-{m}" for s in (0, 1) for m in (1.0, 0.7)]
                 + ["no_kept", "all_kept", "bucket_overflow",
                    "recycles_slots", "merge", "vmapped"])


@pytest.mark.parametrize("case", SCATTER_CASES)
def test_sann_table_scatter_matches_ref(case, monkeypatch):
    """The in-place ring append writes exactly the oracle's tables, for
    random appends and for the appends of real commits.  ``merge`` is
    `sann_merge`'s replay of two sketches: its entry count takes
    `ops.sann_table_scatter` to the oracle, while every chunk-sized case
    stays in place.  ``vmapped`` is the tenant fleet's form: the commit
    vmapped over stacked sketches."""
    if case == "vmapped":
        a = [jnp.stack(x) for x in zip(*(_random_scatter_args(s, 0.8)
                                         for s in (2, 3, 4)))]
        want = jax.vmap(ref.sann_table_scatter_ref)(*a)
        got = jax.vmap(functools.partial(ic_k.sann_table_scatter,
                                         interpret=True))(*a)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        return
    if case.startswith("random"):
        _, seed, frac = case.split("-")
        a = _random_scatter_args(int(seed), float(frac))
    else:
        a = _commit_scatter_args(case, monkeypatch)
        tables, mask = a[0], a[-1]
        assert ic_k.append_in_place(mask.shape[0], tables.size) == (
            case != "merge")
        if case == "bucket_overflow":
            assert int(mask.sum()) < mask.shape[0]    # shadowed appends
        if case == "no_kept":
            assert not bool(mask.any())
    want = ref.sann_table_scatter_ref(*a)
    got = ic_k.sann_table_scatter(*a, interpret=True)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_live_blocks_from_sketch_compaction():
    sigs = jnp.asarray([[1, 0, 0], [1, 1, 0], [0, 0, 1], [1, 1, 1]], bool)
    qsig = jnp.asarray([1, 1, 0], bool)
    ids, n_live = sda_k.live_blocks_from_sketch(
        qsig, sigs, kv_len=jnp.int32(4 * 16), block_size=16, min_match=2)
    ids = np.asarray(ids)
    assert int(n_live[0]) == 2
    assert set(ids[:2].tolist()) == {1, 3}
    assert (ids[2:] == -1).all()
