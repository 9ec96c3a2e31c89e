"""Two-phase ingest equivalence (the prepare/commit split, DESIGN.md §10).

The contract: for every sketch, ``*_commit_chunk(state, *_prepare_chunk(...))``
over a chunked stream is *bit-identical* to the fused batched path (and
therefore to the per-point reference path, which tests/test_batched_ingest.py
pins the fused path to).  Prepare is pure — it never reads sketch state —
so every chunk's prep can be computed *before any commit runs*; the
prepare-ahead tests fold prepared chunks in afterwards, which is exactly
the license for `repro.serve.engine` to overlap preparing chunk k+1 with
committing chunk k.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lsh, race, sann, swakde


def _states_equal(a, b):
    return all(
        bool((np.asarray(x) == np.asarray(y)).all())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# RACE
# ---------------------------------------------------------------------------

def test_race_two_phase_bit_identical():
    p = lsh.init_srp(jax.random.PRNGKey(0), 16, L=5, k=3, n_buckets=32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (203, 16))
    ref = race.race_update_batch(race.race_init(5, 32), p, xs)
    prep = race.race_prepare_chunk(p, xs, 32)
    st = race.race_commit_chunk(race.race_init(5, 32), prep)
    assert _states_equal(st, ref)
    # turnstile: committing the same prep with sign=-1 cancels exactly
    st = race.race_commit_chunk(st, prep, sign=-1)
    assert (np.asarray(st.counts) == 0).all()
    assert int(st.n) == 0


def test_race_prepare_ahead_of_commits():
    """All preps computed up front (no state in sight), commits folded in
    afterwards — equals the chunk-by-chunk fused path."""
    p = lsh.init_srp(jax.random.PRNGKey(2), 8, L=4, k=2, n_buckets=16)
    xs = jax.random.normal(jax.random.PRNGKey(3), (130, 8))
    chunks = [xs[i:i + 40] for i in range(0, 130, 40)]
    preps = [race.race_prepare_chunk(p, c, 16) for c in chunks]
    st = race.race_init(4, 16)
    ref = race.race_init(4, 16)
    for c, prep in zip(chunks, preps):
        ref = race.race_update_batch(ref, p, c)
        st = race.race_commit_chunk(st, prep)
    assert _states_equal(st, ref)


# ---------------------------------------------------------------------------
# SW-AKDE
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [17, 64, 250])
def test_swakde_two_phase_bit_identical(chunk):
    cfg = swakde.SWAKDEConfig(L=6, W=32, window=100, eh_eps=0.1)
    p = lsh.init_srp(jax.random.PRNGKey(0), 8, L=6, k=2, n_buckets=32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (250, 8))
    ref = swakde.swakde_stream(swakde.swakde_init(cfg), p, xs, cfg)
    st = swakde.swakde_init(cfg)
    for i in range(0, 250, chunk):
        prep = swakde.swakde_prepare_chunk(p, xs[i:i + chunk], cfg)
        st = swakde.swakde_commit_chunk(st, prep, cfg)
    assert _states_equal(st, ref)


@pytest.mark.parametrize("family,k,want", [
    ("srp", 2, 4), ("srp", 6, 32), ("pstable", 2, 32)])
def test_swakde_prepare_segment_width(family, k, want):
    """The segment axis holds the most cells one row can hit: min(C, W, 2^k)
    for SRP (k sign bits per row), min(C, W) for p-stable (unbounded raw
    hashes).  With 2^k >= W SRP keeps min(C, W) too.  Every point still
    lands in a segment."""
    L, C, W = 6, 64, 32
    cfg = swakde.SWAKDEConfig(L=L, W=W, window=100, eh_eps=0.1)
    key = jax.random.PRNGKey(0)
    if family == "srp":
        p = lsh.init_srp(key, 8, L=L, k=k, n_buckets=W)
    else:
        p = lsh.init_pstable(key, 8, L=L, k=k, w=1.0, n_buckets=W)
    assert want == swakde.segment_width(C, cfg, lsh.code_range(p))
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 8))
    prep = swakde.swakde_prepare_chunk(p, xs, cfg)
    for a in (prep.seg_code, prep.seg_len, prep.seg_first):
        assert a.shape == (L, want)
    assert (np.asarray(prep.seg_len).sum(axis=1) == C).all()


_commit_passes = jax.jit(swakde.swakde_commit_chunk_passes, static_argnums=2)


@pytest.mark.parametrize("k", [1, 2, 3])
def test_swakde_cut_commit_multipass_bit_identical(k):
    """SRP preps cut to 2^k segments commit bit-identically to the per-point
    path over a stream that fills and expires the window (window < chunk <
    stream), with commits that take more than one pass."""
    cfg = swakde.SWAKDEConfig(L=4, W=64, window=60, eh_eps=0.2)
    p = lsh.init_srp(jax.random.PRNGKey(3), 8, L=cfg.L, k=k, n_buckets=cfg.W)
    xs = jax.random.normal(jax.random.PRNGKey(4), (400, 8))
    ref = swakde.swakde_stream(swakde.swakde_init(cfg), p, xs, cfg)
    st = swakde.swakde_init(cfg)
    passes = []
    for i in range(0, 400, 100):
        prep = swakde.swakde_prepare_chunk(p, xs[i:i + 100], cfg)
        assert prep.seg_code.shape == (cfg.L, 2 ** k)
        st, n = _commit_passes(st, prep, cfg)
        passes.append(int(n))
    assert max(passes) > 1, passes
    assert _states_equal(st, ref)


def test_swakde_prepare_ahead_of_commits_skewed():
    """Prepare-ahead over the replay loop's worst case (all points in one
    bucket): preps carry only relative sort offsets, so committing them
    later against an advanced clock must still replay exactly."""
    cfg = swakde.SWAKDEConfig(L=4, W=16, window=40, eh_eps=0.2)
    p = lsh.init_srp(jax.random.PRNGKey(2), 4, L=4, k=2, n_buckets=16)
    xs = jnp.ones((96, 4))  # identical points → identical codes
    chunks = [xs[i:i + 32] for i in range(0, 96, 32)]
    preps = [swakde.swakde_prepare_chunk(p, c, cfg) for c in chunks]
    st = swakde.swakde_init(cfg)
    for prep in preps:
        st = swakde.swakde_commit_chunk(st, prep, cfg)
    ref = swakde.swakde_stream(swakde.swakde_init(cfg), p, xs, cfg)
    assert _states_equal(st, ref)


def _skew_stream(kind, n, dim, seed):
    """Synthetic streams that funnel mass into few (row, cell) segments."""
    rng = np.random.default_rng(seed)
    if kind == "hot":          # 100% of points in one (row, cell) per row
        return jnp.ones((n, dim), jnp.float32)
    if kind == "powerlaw":     # Zipf over a handful of distinct points
        base = rng.normal(size=(8, dim)).astype(np.float32)
        idx = np.minimum(rng.zipf(1.3, size=n) - 1, 7)
        return jnp.asarray(base[idx])
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["hot", "powerlaw"])
@pytest.mark.parametrize("cap", [0, 1, 3, 17])
def test_swakde_skewed_capped_commit_bit_identical(kind, cap):
    """Skew fuzz (DESIGN.md §12): heavy-cell-capped sub-chunk commits are
    bitwise identical to the uncapped per-point oracle — including EH
    expiry landing *at* a split boundary (window < chunk, so segments
    split both at expiry points and at the cap) and ring wrap inside one
    hot cell's cascade."""
    cfg = swakde.SWAKDEConfig(L=4, W=16, window=40, eh_eps=0.2,
                              heavy_cell_cap=cap)
    p = lsh.init_srp(jax.random.PRNGKey(2), 4, L=4, k=2, n_buckets=16)
    xs = _skew_stream(kind, 96, 4, seed=cap)
    ref = swakde.swakde_stream(swakde.swakde_init(cfg), p, xs, cfg)
    st = swakde.swakde_init(cfg)
    for i in range(0, 96, 64):                 # window (40) < chunk (64)
        prep = swakde.swakde_prepare_chunk(p, xs[i:i + 64], cfg)
        st = swakde.swakde_commit_chunk(st, prep, cfg)
    assert _states_equal(st, ref)


@pytest.mark.parametrize("seed", range(4))
def test_swakde_powerlaw_fuzz_cap_invariance(seed):
    """Every cap value (including uncapped) lands on the same bits: the cap
    only splits a segment's closed-form pass into shorter sub-chunk
    passes, never changes what is committed."""
    cfg0 = swakde.SWAKDEConfig(L=3, W=8, window=25, eh_eps=0.3)
    p = lsh.init_srp(jax.random.PRNGKey(5), 4, L=3, k=2, n_buckets=8)
    xs = _skew_stream("powerlaw", 150, 4, seed=100 + seed)
    states = []
    for cap in (0, 1, 2, 7):
        cfg = swakde.SWAKDEConfig(L=3, W=8, window=25, eh_eps=0.3,
                                  heavy_cell_cap=cap)
        st = swakde.swakde_init(cfg)
        for i in range(0, 150, 50):
            st = swakde.swakde_commit_chunk(
                st, swakde.swakde_prepare_chunk(p, xs[i:i + 50], cfg), cfg)
        states.append(st)
    ref = swakde.swakde_stream(swakde.swakde_init(cfg0), p, xs, cfg0)
    for st in states:
        assert _states_equal(st, ref)


# ---------------------------------------------------------------------------
# S-ANN
# ---------------------------------------------------------------------------

def _sann_setup(n_max=2000, eta=0.25, slack=4.0, dim=8, seed=0):
    cfg = sann.SANNConfig(dim=dim, n_max=n_max, eta=eta, r=0.5, c=2.0,
                          L=4, k=2, capacity_slack=slack)
    return sann.sann_init(cfg, jax.random.PRNGKey(seed))


def test_sann_two_phase_bit_identical():
    cfg, p, st0 = _sann_setup()
    xs = jax.random.uniform(jax.random.PRNGKey(1), (500, 8))
    key = jax.random.PRNGKey(2)
    ref = sann.sann_insert_batch(st0, p, xs, key, cfg)
    prep = sann.sann_prepare_chunk(p, xs, key, cfg)
    st = sann.sann_commit_chunk(st0, prep, cfg)
    assert _states_equal(st, ref)


def test_sann_prepare_ahead_under_ring_wrap():
    """Chunked prepare-ahead with the ring lapping several times inside and
    across chunks: relative slot ranks rebased on the live write pointer
    must replay the sequential eviction/tombstone semantics exactly."""
    cfg, p, st0 = _sann_setup(n_max=300, eta=0.0, slack=0.1)
    assert cfg.capacity == 64
    xs = jax.random.uniform(jax.random.PRNGKey(3), (300, 8))
    ckeys = jax.random.split(jax.random.PRNGKey(4), 4)
    chunks = [xs[i:i + 75] for i in range(0, 300, 75)]
    preps = [sann.sann_prepare_chunk(p, c, k, cfg)
             for c, k in zip(chunks, ckeys)]
    st = st0
    ref = st0
    for c, k, prep in zip(chunks, ckeys, preps):
        ref = sann.sann_insert_stream(ref, p, c, k, cfg)
        st = sann.sann_commit_chunk(st, prep, cfg)
    assert _states_equal(st, ref)
    assert int(st.n_stored) == int(st.valid.sum()) == 64


def test_sann_two_phase_eviction_tombstones_stale_entries():
    """Regression guard carried over to the split path: after ring-wrap via
    prepare→commit, every surviving table entry points at a vector that
    actually hashes into that bucket."""
    cfg, p, st0 = _sann_setup(n_max=300, eta=0.0, slack=0.1, dim=4, seed=7)
    xs = jax.random.uniform(jax.random.PRNGKey(8), (300, 4))
    st = st0
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    for i, k in zip(range(0, 300, 100), keys):
        st = sann.sann_commit_chunk(
            st, sann.sann_prepare_chunk(p, xs[i:i + 100], k, cfg), cfg)
    codes_all = np.asarray(lsh.hash_points(p, st.points))      # (capacity, L)
    tables = np.asarray(st.tables).reshape(cfg.L, cfg.n_buckets, -1)
    for l in range(cfg.L):
        tab = tables[l]                                        # (buckets, cap)
        mask = tab >= 0
        entry_codes = codes_all[np.maximum(tab, 0), l]
        expect = np.arange(tab.shape[0])[:, None]
        assert ((entry_codes == expect) | ~mask).all()
