"""Fused batched query engine == vmapped per-query oracles (all three
sketches).

PR 3 replaced the vmap-over-per-query *_batch entry points with batch-level
fused pipelines (one hash matmul + one gather + batch-wide truncation /
dedup / fused scoring).  The per-query functions (`sann_query`,
`sann_query_topk`, `race_query`, `swakde_query`) keep the original
semantics and serve as oracles; these tests pin the fused engine to them —
indices / found flags / counts exactly, distances to 1e-5, RACE counts and
SW-AKDE estimates exactly — including ring-wrapped and evicted stores,
duplicate-slot dedup, batch sizes not divisible by the service query
block.  The 8-device sharded query paths are in test_query_sharded.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lsh, race, sann, swakde
from repro.kernels import batch_score as bs_k
from repro.kernels import ref


# ---------------------------------------------------------------------------
# The fused scorer kernel vs its oracle (interpret mode).  These live here
# rather than tests/test_kernels.py so they are not gated behind that
# module's hypothesis importorskip — they must run everywhere.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,M,d", [(1, 1, 8), (7, 100, 24), (64, 300, 48)])
@pytest.mark.parametrize("k", [1, 5])
def test_batch_score_topk_matches_ref(B, M, d, k):
    k = min(k, M)
    qs = jax.random.normal(jax.random.PRNGKey(B + M), (B, d))
    cands = jax.random.normal(jax.random.PRNGKey(d + k), (B, M, d))
    ok = jax.random.uniform(jax.random.PRNGKey(3), (B, M)) < 0.8
    got_d, got_i = bs_k.batch_score_topk(qs, cands, ok, k, block_b=4,
                                         block_m=32, interpret=True)
    want_d, want_i = ref.batch_score_topk_ref(qs, cands, ok, k)
    got_d, want_d = np.asarray(got_d), np.asarray(want_d)
    finite = np.isfinite(want_d)
    np.testing.assert_array_equal(np.isfinite(got_d), finite)
    np.testing.assert_allclose(got_d[finite], want_d[finite],
                               rtol=2e-2, atol=1e-4)
    # identity-vs-diff numerics cannot flip generic (distinct-distance)
    # rankings; only compare indices where distances are finite
    np.testing.assert_array_equal(np.asarray(got_i)[finite],
                                  np.asarray(want_i)[finite])


def test_batch_score_topk_fully_masked_row():
    qs = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    cands = jax.random.normal(jax.random.PRNGKey(1), (4, 40, 16))
    ok = jnp.ones((4, 40), bool).at[2].set(False)
    got_d, _ = bs_k.batch_score_topk(qs, cands, ok, 3, block_b=2,
                                     block_m=16, interpret=True)
    assert np.isinf(np.asarray(got_d)[2]).all()
    assert np.isfinite(np.asarray(got_d)[[0, 1, 3]]).all()


def _assert_sann_results_equal(got: sann.SANNResult, want: sann.SANNResult):
    np.testing.assert_array_equal(np.asarray(got.index),
                                  np.asarray(want.index), err_msg="index")
    np.testing.assert_array_equal(np.asarray(got.found),
                                  np.asarray(want.found), err_msg="found")
    np.testing.assert_array_equal(np.asarray(got.n_candidates),
                                  np.asarray(want.n_candidates),
                                  err_msg="n_candidates")
    np.testing.assert_allclose(np.asarray(got.distance),
                               np.asarray(want.distance), atol=1e-5,
                               err_msg="distance")


def _wrapped_state(n=1200, d=10, extra_deletes=True):
    """A store that ring-wrapped several times (capacity_slack shrinks the
    ring far below the kept-point count) with tombstoned entries."""
    cfg = sann.SANNConfig(dim=d, n_max=n, eta=0.2, r=0.6, c=2.0, w=1.5,
                          L=8, k=3, capacity_slack=0.15)
    cfg, params, st = sann.sann_init(cfg, jax.random.PRNGKey(0))
    xs = jnp.asarray(np.random.default_rng(1).uniform(
        0, 1, (n, d)).astype(np.float32))
    st = sann.sann_insert_chunked(st, params, xs, jax.random.PRNGKey(2), cfg,
                                  chunk=256)
    assert int(st.n_stored) < n * cfg.keep_prob  # the ring really wrapped
    if extra_deletes:
        for i in (n - 3, n - 40):               # evict recent (live) points
            st = sann.sann_delete(st, params, xs[i], cfg)
    return cfg, params, st, xs


def test_sann_batch_matches_oracle_ring_wrapped():
    cfg, params, st, xs = _wrapped_state()
    # mix of: near-duplicates of stored points, exact stored points, and
    # far-away queries that must return NULL
    qs = jnp.concatenate([
        xs[-30:] + 0.01, xs[:5], jnp.full((4, xs.shape[1]), 25.0)])
    want = jax.vmap(lambda q: sann.sann_query(st, params, q, cfg))(qs)
    got = sann.sann_query_batch(st, params, qs, cfg)
    _assert_sann_results_equal(got, want)
    assert bool(np.asarray(want.found).any())       # test isn't vacuous
    assert not bool(np.asarray(want.found[-4:]).any())


def test_sann_topk_batch_matches_oracle_duplicate_slots():
    """Streams with many identical points put the same slot id in several
    buckets *and* several identical vectors in distinct slots — the dedup
    and tie paths of the top-k engine."""
    d = 8
    cfg = sann.SANNConfig(dim=d, n_max=400, eta=0.0, r=0.4, c=2.0, w=2.0,
                          L=4, k=2, bucket_cap=16)
    cfg, params, st = sann.sann_init(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(4)
    base = rng.uniform(0, 1, (40, d)).astype(np.float32)
    xs = jnp.asarray(np.repeat(base, 10, axis=0))   # every vector 10 times
    st = sann.sann_insert_batch(st, params, xs, jax.random.PRNGKey(5), cfg)
    qs = jnp.asarray(base[:12]) + 0.005
    want = jax.vmap(
        lambda q: sann.sann_query_topk(st, params, q, cfg, 10))(qs)
    got = sann.sann_query_topk_batch(st, params, qs, cfg, 10)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=1e-5)


def test_sann_topk_dedup_sort_fallback_branch():
    """A capacity above the scatter-dedup threshold exercises the batched
    argsort dedup branch of `_first_occurrence_mask`."""
    d = 6
    cfg = sann.SANNConfig(dim=d, n_max=1100, eta=0.0, r=0.5, c=2.0, w=2.0,
                          L=4, k=2, bucket_cap=8)
    cfg, params, st = sann.sann_init(cfg, jax.random.PRNGKey(6))
    assert cfg.capacity + 1 > max(4096, 8 * cfg.L * cfg.bucket_cap)
    xs = jnp.asarray(np.random.default_rng(7).uniform(
        0, 1, (1100, d)).astype(np.float32))
    st = sann.sann_insert_batch(st, params, xs, jax.random.PRNGKey(8), cfg)
    qs = xs[:7] + 0.01
    want = jax.vmap(
        lambda q: sann.sann_query_topk(st, params, q, cfg, 15))(qs)
    got = sann.sann_query_topk_batch(st, params, qs, cfg, 15)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=1e-5)


def test_race_batch_matches_oracle_exactly():
    d, L, W = 10, 12, 32
    params = lsh.init_srp(jax.random.PRNGKey(0), d, L=L, k=3, n_buckets=W)
    xs = jax.random.normal(jax.random.PRNGKey(1), (400, d))
    st = race.race_update_batch(race.race_init(L, W), params, xs)
    st = race.race_update_batch(st, params, xs[:60], sign=-1)  # turnstile
    qs = jax.random.normal(jax.random.PRNGKey(2), (33, d))
    for mom in (0, 4):
        want = jax.vmap(lambda q: race.race_query(st, params, q, mom))(qs)
        got = race.race_query_batch(st, params, qs, mom)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B", [5, 40])  # below / at-least the W threshold
def test_swakde_batch_matches_oracle_exactly(B):
    """Both fused branches (gather-then-query for B < W, grid precompute +
    one-hot read for B ≥ W) must equal the per-query oracle bitwise,
    including after window expiry."""
    d = 9
    cfg = swakde.SWAKDEConfig(L=6, W=24, window=90, eh_eps=0.15)
    params = lsh.init_srp(jax.random.PRNGKey(3), d, L=6, k=2, n_buckets=24)
    xs = jax.random.normal(jax.random.PRNGKey(4), (300, d))  # > window
    st = swakde.swakde_init(cfg)
    for i in range(0, 300, 100):
        st = swakde.swakde_update_chunk(st, params, xs[i:i + 100], cfg)
    qs = jax.random.normal(jax.random.PRNGKey(5), (B, d))
    want = jax.vmap(lambda q: swakde.swakde_query(st, params, q, cfg))(qs)
    got = swakde.swakde_query_batch(st, params, qs, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the row-estimate form feeding the sharded path is exact as well
    want_rows = jax.vmap(
        lambda q: swakde.swakde_row_estimates(st, params, q, cfg))(qs)
    got_rows = swakde.swakde_row_estimates_batch(st, params, qs, cfg)
    np.testing.assert_array_equal(np.asarray(got_rows), np.asarray(want_rows))


def test_service_query_block_not_divisible():
    """Serving through query_block chunks (B % block != 0) must equal one
    unblocked call for both services."""
    from repro.serve.kde_service import KDEService, KDEServiceConfig
    from repro.serve.retrieval import RetrievalConfig, RetrievalService

    rng = np.random.default_rng(0)
    emb = rng.normal(0, 1, (300, 12)).astype(np.float32)
    qs = np.concatenate([emb[:9] + 0.01, rng.normal(5, 1, (4, 12))]
                        ).astype(np.float32)                 # B=13

    kw = dict(dim=12, n_max=2000, eta=0.3, r=0.8, c=2.0, ingest_chunk=128)
    blocked = RetrievalService(RetrievalConfig(**kw, query_block=5))
    whole = RetrievalService(RetrievalConfig(**kw))
    blocked.ingest(emb)
    whole.ingest(emb)
    _assert_sann_results_equal(blocked.query(qs), whole.query(qs))

    kk = dict(dim=12, L=8, W=16, window=200, ingest_chunk=128)
    kb = KDEService(KDEServiceConfig(**kk, query_block=5))
    kww = KDEService(KDEServiceConfig(**kk))
    kb.ingest(emb)
    kww.ingest(emb)
    np.testing.assert_array_equal(kb.query(qs), kww.query(qs))
    np.testing.assert_array_equal(kb.density(qs), kww.density(qs))


def test_empty_query_batch():
    """B = 0 must return empty results everywhere (engine + services), as
    the pre-fusion vmapped path did."""
    from repro.serve.kde_service import KDEService, KDEServiceConfig
    from repro.serve.retrieval import RetrievalConfig, RetrievalService

    d = 8
    # the Pallas scorer itself (the TPU / REPRO_FORCE_PALLAS path) must not
    # try to launch a zero grid
    e_d2, e_idx = bs_k.batch_score_topk(
        jnp.zeros((0, d)), jnp.zeros((0, 6, d)), jnp.zeros((0, 6), bool), 2,
        interpret=True)
    assert e_d2.shape == (0, 2) and e_idx.shape == (0, 2)

    cfg = sann.SANNConfig(dim=d, n_max=200, eta=0.2, r=0.5, c=2.0, w=2.0,
                          L=4, k=2)
    cfg, params, st = sann.sann_init(cfg, jax.random.PRNGKey(0))
    empty = jnp.zeros((0, d), jnp.float32)
    res = sann.sann_query_batch(st, params, empty, cfg)
    assert res.index.shape == (0,)
    ids, dists = sann.sann_query_topk_batch(st, params, empty, cfg, 5)
    assert ids.shape[0] == 0 and dists.shape[0] == 0

    rparams = lsh.init_srp(jax.random.PRNGKey(1), d, L=4, k=2, n_buckets=16)
    assert race.race_query_batch(race.race_init(4, 16), rparams,
                                 empty).shape == (0,)
    scfg = swakde.SWAKDEConfig(L=4, W=16, window=50, eh_eps=0.2)
    assert swakde.swakde_query_batch(swakde.swakde_init(scfg), rparams,
                                     empty, scfg).shape == (0,)

    svc = RetrievalService(RetrievalConfig(dim=d, n_max=500))
    assert svc.query(np.zeros((0, d), np.float32)).index.shape == (0,)
    kde = KDEService(KDEServiceConfig(dim=d, L=4, W=16, window=50))
    assert kde.query(np.zeros((0, d), np.float32)).shape == (0,)
    assert kde.density(np.zeros((0, d), np.float32)).shape == (0,)
