"""Batched-ingest engine equivalence (the unified update contract).

The contract: for every sketch, the batched path must be *bit-identical* to
replaying the per-point reference path — RACE and SW-AKDE counters exactly,
S-ANN full state under a shared key schedule.  These tests are the license
for serve/ and benchmarks/ to use the batched engine unconditionally.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import eh, lsh, race, sann, swakde

import compiled


def _states_equal(a, b):
    return all(
        bool((np.asarray(x) == np.asarray(y)).all())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# RACE
# ---------------------------------------------------------------------------

def test_race_batch_bit_identical_to_scan():
    p = lsh.init_srp(jax.random.PRNGKey(0), 16, L=5, k=3, n_buckets=32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (203, 16))  # non-multiple of
    st_b = race.race_update_batch(race.race_init(5, 32), p, xs)  # the cb block

    def step(s, x):
        return race.race_update(s, p, x), None

    st_s, _ = jax.lax.scan(step, race.race_init(5, 32), xs)
    assert _states_equal(st_b, st_s)


def test_race_batch_turnstile_sign():
    p = lsh.init_pstable(jax.random.PRNGKey(2), 8, L=4, k=2, w=4.0,
                         n_buckets=16)
    xs = jax.random.normal(jax.random.PRNGKey(3), (60, 8))
    st = race.race_update_batch(race.race_init(4, 16), p, xs)
    st = race.race_update_batch(st, p, xs, sign=-1)
    assert (np.asarray(st.counts) == 0).all()
    assert int(st.n) == 0


def test_race_batch_wide_range_path():
    """W > 128 takes the scatter-add branch in kernels.ops.race_hist."""
    p = lsh.init_srp(jax.random.PRNGKey(4), 8, L=3, k=4, n_buckets=500)
    xs = jax.random.normal(jax.random.PRNGKey(5), (64, 8))
    st_b = race.race_update_batch(race.race_init(3, 500), p, xs)

    def step(s, x):
        return race.race_update(s, p, x), None

    st_s, _ = jax.lax.scan(step, race.race_init(3, 500), xs)
    assert _states_equal(st_b, st_s)


# ---------------------------------------------------------------------------
# SW-AKDE (exact per-point-timestamp chunk replay)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [1, 17, 64, 250])
def test_swakde_chunked_stream_bit_identical(chunk):
    cfg = swakde.SWAKDEConfig(L=6, W=32, window=100, eh_eps=0.1)
    p = lsh.init_srp(jax.random.PRNGKey(0), 8, L=6, k=2, n_buckets=32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (250, 8))
    st_seq = swakde.swakde_stream(swakde.swakde_init(cfg), p, xs, cfg)
    st_bat = swakde.swakde_stream_batched(swakde.swakde_init(cfg), p, xs, cfg,
                                          chunk=chunk)
    assert _states_equal(st_seq, st_bat)


def test_swakde_chunk_skewed_codes():
    """All points in one bucket — the replay loop's worst case."""
    cfg = swakde.SWAKDEConfig(L=4, W=16, window=40, eh_eps=0.2)
    p = lsh.init_srp(jax.random.PRNGKey(2), 4, L=4, k=2, n_buckets=16)
    xs = jnp.ones((96, 4))  # identical points → identical codes
    st_seq = swakde.swakde_stream(swakde.swakde_init(cfg), p, xs, cfg)
    st_bat = swakde.swakde_update_chunk(swakde.swakde_init(cfg), p, xs, cfg)
    assert _states_equal(st_seq, st_bat)


# ---------------------------------------------------------------------------
# SumEH closed form (the Corollary-4.2 batch cell)
# ---------------------------------------------------------------------------

def _live_masked(state, cfg):
    idx = np.arange(cfg.base.slots)[None, :]
    live = idx < np.asarray(state.num)[:, None]
    return np.where(live, np.asarray(state.ts), -7)


def test_sum_eh_closed_form_matches_ref():
    """Closed-form multi-increment add == `value` sequential unit adds:
    identical live buckets and identical queries at every step."""
    cfg = eh.SumEHConfig.create(window=20, eps=0.2, batch_max=64)
    rng = np.random.default_rng(0)
    st_ref = eh.sum_eh_init(cfg)
    st_new = eh.sum_eh_init(cfg)
    for t in range(40):
        v = int(rng.integers(0, 65))
        t_, v_ = jnp.int32(t), jnp.int32(v)
        st_ref = compiled.sum_eh_add_ref(st_ref, t_, v_, cfg)
        st_new = compiled.sum_eh_add(st_new, t_, v_, cfg)
        assert (np.asarray(st_ref.num) == np.asarray(st_new.num)).all(), t
        assert (_live_masked(st_ref, cfg) == _live_masked(st_new, cfg)).all(), t
        q_ref = float(compiled.sum_eh_query(st_ref, jnp.int32(t), cfg))
        q_new = float(compiled.sum_eh_query(st_new, jnp.int32(t), cfg))
        assert q_ref == q_new, (t, q_ref, q_new)


def test_batch_swakde_grid_matches_ref_cells():
    """batch_swakde_update (closed-form cells + kernel histogram) equals the
    reference per-cell sum_eh_add_ref grid, live-masked."""
    cfg = swakde.BatchSWAKDEConfig(L=4, W=16, window=6, eh_eps=0.2,
                                   batch_size=8)
    ehc = cfg.eh_config()
    p = lsh.init_srp(jax.random.PRNGKey(3), 8, L=4, k=2, n_buckets=16)
    st = swakde.batch_swakde_init(cfg)
    st_ref = swakde.batch_swakde_init(cfg)
    for i in range(10):
        batch = jax.random.normal(jax.random.PRNGKey(10 + i), (8, 8))
        st = swakde.batch_swakde_update(st, p, batch, cfg)
        codes = lsh.hash_points(p, batch)
        incr = jax.nn.one_hot(codes, cfg.W, dtype=jnp.int32).sum(0)

        def upd(ts, num, v, t=st_ref.t):
            s = eh.sum_eh_add_ref(eh.EHState(ts, num), t, v, ehc)
            return s.ts, s.num

        ts, num = jax.vmap(jax.vmap(upd))(st_ref.ts, st_ref.num, incr)
        st_ref = swakde.BatchSWAKDEState(ts=ts, num=num, t=st_ref.t + 1)
        assert (np.asarray(st.num) == np.asarray(st_ref.num)).all(), i
        idx = np.arange(ehc.base.slots)
        live = idx[None, None, None, :] < np.asarray(st.num)[..., None]
        assert (np.where(live, np.asarray(st.ts), -7)
                == np.where(live, np.asarray(st_ref.ts), -7)).all(), i
    assert int(st.t) == int(st_ref.t) == 10


# ---------------------------------------------------------------------------
# S-ANN
# ---------------------------------------------------------------------------

def _sann_setup(n_max=2000, eta=0.25, slack=4.0, dim=8, seed=0):
    cfg = sann.SANNConfig(dim=dim, n_max=n_max, eta=eta, r=0.5, c=2.0,
                          L=4, k=2, capacity_slack=slack)
    return sann.sann_init(cfg, jax.random.PRNGKey(seed))


def test_sann_batch_bit_identical_to_stream():
    cfg, p, st0 = _sann_setup()
    xs = jax.random.uniform(jax.random.PRNGKey(1), (500, 8))
    key = jax.random.PRNGKey(2)
    st_seq = sann.sann_insert_stream(st0, p, xs, key, cfg)
    st_bat = sann.sann_insert_batch(st0, p, xs, key, cfg)
    assert _states_equal(st_seq, st_bat)


def test_sann_batch_bit_identical_under_ring_wrap():
    """Chunk laps the ring several times: last-writer-wins + tombstones must
    still replay the sequential path exactly."""
    cfg, p, st0 = _sann_setup(n_max=300, eta=0.0, slack=0.1)
    assert cfg.capacity == 64
    xs = jax.random.uniform(jax.random.PRNGKey(3), (300, 8))
    key = jax.random.PRNGKey(4)
    st_seq = sann.sann_insert_stream(st0, p, xs, key, cfg)
    st_bat = sann.sann_insert_batch(st0, p, xs, key, cfg)
    assert _states_equal(st_seq, st_bat)
    assert int(st_seq.n_stored) == int(st_seq.valid.sum()) == 64


def test_sann_chunked_matches_sequential_build_and_queries():
    """sann_insert_chunked splits the key once per chunk; the sequential
    replay with the same per-chunk schedule must give identical state and
    identical query results."""
    cfg, p, st0 = _sann_setup(n_max=600, eta=0.1)
    xs = jax.random.uniform(jax.random.PRNGKey(5), (600, 8))
    key = jax.random.PRNGKey(6)
    chunk = 150
    ckeys = jax.random.split(key, 4)
    st_seq = st0
    for i in range(4):
        st_seq = sann.sann_insert_stream(
            st_seq, p, xs[i * chunk:(i + 1) * chunk], ckeys[i], cfg)
    st_bat = sann.sann_insert_chunked(st0, p, xs, key, cfg, chunk=chunk)
    assert _states_equal(st_seq, st_bat)
    qs = xs[:16] + 0.01
    r_seq = sann.sann_query_batch(st_seq, p, qs, cfg)
    r_bat = sann.sann_query_batch(st_bat, p, qs, cfg)
    assert (np.asarray(r_seq.index) == np.asarray(r_bat.index)).all()
    assert (np.asarray(r_seq.found) == np.asarray(r_bat.found)).all()
    assert (np.asarray(r_seq.distance) == np.asarray(r_bat.distance)).all()


def test_sann_ring_eviction_tombstones_stale_entries():
    """Regression (seed bug): streaming past capacity recycles slots; every
    surviving table entry must point at a vector that actually hashes into
    that bucket — stale references to evicted points must be tombstoned."""
    cfg, p, st0 = _sann_setup(n_max=300, eta=0.0, slack=0.1, dim=4, seed=7)
    xs = jax.random.uniform(jax.random.PRNGKey(8), (300, 4))
    for build in ("seq", "batch"):
        if build == "seq":
            st = sann.sann_insert_stream(st0, p, xs, jax.random.PRNGKey(9), cfg)
        else:
            st = sann.sann_insert_batch(st0, p, xs, jax.random.PRNGKey(9), cfg)
        codes_all = np.asarray(lsh.hash_points(p, st.points))  # (capacity, L)
        tables = np.asarray(st.tables).reshape(cfg.L, cfg.n_buckets, -1)
        for l in range(cfg.L):
            tab = tables[l]                                    # (buckets, cap)
            mask = tab >= 0
            entry_codes = codes_all[np.maximum(tab, 0), l]
            expect = np.arange(tab.shape[0])[:, None]
            assert ((entry_codes == expect) | ~mask).all(), build


# ---------------------------------------------------------------------------
# Serving layer
# ---------------------------------------------------------------------------

def test_retrieval_service_batched_ingest_partial_chunks():
    from repro.serve.retrieval import RetrievalConfig, RetrievalService
    svc = RetrievalService(RetrievalConfig(
        dim=8, n_max=1000, eta=0.2, r=0.4, c=2.0, w=1.0, L=6, k=3,
        ingest_chunk=64))
    rng = np.random.default_rng(0)
    data = rng.uniform(0, 1, (300, 8)).astype(np.float32)
    svc.ingest(data[:100])       # 1 full chunk + remainder
    svc.ingest(data[100:300])    # 3 full chunks + remainder
    assert svc.stored > 0
    res = svc.query(data[:8] + 0.01)
    assert np.asarray(res.found).any()
    assert int(svc.state.n_seen) == 300


def test_kde_service_matches_direct_stream():
    """The service's chunked ingest is bit-identical to one swakde_stream."""
    from repro.serve.kde_service import KDEService, KDEServiceConfig
    svc = KDEService(KDEServiceConfig(dim=8, L=6, W=32, window=80,
                                      eh_eps=0.2, ingest_chunk=50))
    rng = np.random.default_rng(1)
    data = rng.normal(0, 1, (230, 8)).astype(np.float32)
    svc.ingest(data[:120])
    svc.ingest(data[120:])
    assert svc.steps == 230
    direct = swakde.swakde_stream(
        swakde.swakde_init(svc.sketch_cfg), svc.params,
        jnp.asarray(data), svc.sketch_cfg)
    assert _states_equal(svc.state, direct)
    q = svc.query(data[:4])
    dq = np.asarray(swakde.swakde_query_batch(
        direct, svc.params, jnp.asarray(data[:4]), svc.sketch_cfg))
    np.testing.assert_allclose(q, dq)
    assert (svc.density(data[:4]) >= 0).all()
