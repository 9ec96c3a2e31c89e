"""Multi-tenant sketch fleets, core paths (repro.core.fleet):

  * tenant-routed vmapped ingest and fused queries are BIT-IDENTICAL to a
    per-tenant loop of the existing single-sketch paths, for all three
    sketches — including RACE counter saturation territory, S-ANN
    ring-wrap/eviction and EH expiry at tenant boundaries;
  * the `sann_row_keys` schedule is prefix-stable (the property the padded
    fleet Bernoulli draws rely on);
  * hypothesis fuzz over mixed-tenant chunk compositions, skewed
    (single-hot-tenant) included.

The `TenantFleet` service over these paths is tested in
test_tenant_fleet.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import fleet, race, sann, swakde
from repro.core.lsh import hash_points, init_pstable, init_srp

import compiled


def _mixed(T, n, d, seed=0, probs=None):
    """One mixed chunk: xs (n, d) and per-point tenant ids drawn from
    ``probs`` (uniform by default)."""
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n, d)).astype(np.float32)
    tids = rng.choice(T, size=n, p=probs).astype(np.int64)
    return xs, tids


def _leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# core.fleet vs per-tenant oracle loops
# --------------------------------------------------------------------------


def test_race_fleet_bitexact_vs_per_tenant_loop():
    T, d, L, W = 4, 6, 5, 32
    params = init_srp(jax.random.PRNGKey(0), d, L, 3, W)
    stacked = fleet.fleet_broadcast(race.race_init(L, W), T)
    oracle = [race.race_init(L, W) for _ in range(T)]
    for chunk in range(3):
        xs, tids = _mixed(T, 70, d, seed=chunk)
        stacked = compiled.race_fleet_ingest(stacked, params,
                                     jnp.asarray(xs),
                                     jnp.asarray(tids, jnp.int32))
        for t in range(T):
            sub = jnp.asarray(xs[tids == t])
            if sub.shape[0]:
                oracle[t] = compiled.race_commit_chunk(
                    oracle[t], compiled.race_prepare_chunk(params, sub, W))
    _leaves_equal(stacked, fleet.fleet_stack(oracle))

    qs, qt = _mixed(T, 25, d, seed=99)
    got = compiled.race_fleet_query(stacked, params, jnp.asarray(qs),
                            jnp.asarray(qt, jnp.int32))
    want = np.empty(25, np.float32)
    for t in range(T):
        m = qt == t
        if m.any():
            want[m] = np.asarray(compiled.race_query_batch(
                oracle[t], params, jnp.asarray(qs[m])))
    np.testing.assert_array_equal(np.asarray(got), want)

    kde = compiled.race_fleet_kde(stacked, params, jnp.asarray(qs),
                          jnp.asarray(qt, jnp.int32))
    for t in range(T):
        m = qt == t
        if m.any():
            want[m] = np.asarray(got)[m] / max(int(oracle[t].n), 1)
    np.testing.assert_array_equal(np.asarray(kde), want)


def test_swakde_fleet_bitexact_with_expiry_at_tenant_boundaries():
    """window < per-tenant stream: EH buckets expire at different clocks
    per tenant row; the vmapped commit must still match the per-tenant
    `swakde_update_chunk` loop bitwise."""
    T, d = 3, 5
    cfg = swakde.SWAKDEConfig(L=4, W=32, window=16, eh_eps=0.2)
    params = init_pstable(jax.random.PRNGKey(1), d, cfg.L, 2, 1.0, cfg.W)
    stacked = fleet.fleet_broadcast(swakde.swakde_init(cfg), T)
    oracle = [swakde.swakde_init(cfg) for _ in range(T)]
    # skew: tenant 0 hot — its window saturates and expires, tenant 2 cold
    for chunk in range(4):
        xs, tids = _mixed(T, 60, d, seed=10 + chunk,
                          probs=[0.7, 0.2, 0.1])
        cap = int(np.bincount(tids, minlength=T).max())
        stacked = compiled.swakde_fleet_ingest(
            stacked, params, jnp.asarray(xs), jnp.asarray(tids, jnp.int32),
            cfg, cap)
        for t in range(T):
            sub = jnp.asarray(xs[tids == t])
            if sub.shape[0]:
                oracle[t] = compiled.swakde_update_chunk(
                    oracle[t], params, sub, cfg)
    _leaves_equal(stacked, fleet.fleet_stack(oracle))
    assert int(oracle[0].t) > cfg.window, "tenant 0 must actually expire"

    qs, qt = _mixed(T, 20, d, seed=77)
    got = compiled.swakde_fleet_query(stacked, params, jnp.asarray(qs),
                              jnp.asarray(qt, jnp.int32), cfg)
    kde = compiled.swakde_fleet_kde(stacked, params, jnp.asarray(qs),
                            jnp.asarray(qt, jnp.int32), cfg)
    for t in range(T):
        m = qt == t
        if m.any():
            np.testing.assert_array_equal(
                np.asarray(got)[m],
                np.asarray(compiled.swakde_query_batch(
                    oracle[t], params, jnp.asarray(qs[m]), cfg)))
            denom = max(min(int(oracle[t].t), cfg.window), 1)
            np.testing.assert_array_equal(np.asarray(kde)[m],
                                          np.asarray(got)[m] / denom)


def test_swakde_fleet_srp_sentinel_past_cut_segment_axis():
    """SRP cuts the segment axis to 2^k.  A padded tenant whose rows hit
    all 2^k codes puts its pads' sentinel segment one past that axis, where
    the scatters drop it; the fleet stays bit-identical to the per-tenant
    loop, across expiring commits."""
    T, d, k = 2, 5, 2
    cfg = swakde.SWAKDEConfig(L=4, W=32, window=24, eh_eps=0.2)
    params = init_srp(jax.random.PRNGKey(12), d, cfg.L, k, cfg.W)
    stacked = fleet.fleet_broadcast(swakde.swakde_init(cfg), T)
    oracle = [swakde.swakde_init(cfg) for _ in range(T)]
    for chunk in range(3):
        xs, tids = _mixed(T, 80, d, seed=40 + chunk, probs=[0.7, 0.3])
        counts = np.bincount(tids, minlength=T)
        cap = int(counts.max())
        codes = np.asarray(hash_points(params, jnp.asarray(xs[tids == 1])))
        assert counts[1] < cap, "tenant 1 must carry pads"
        assert all(len(np.unique(codes[:, l])) == 2 ** k
                   for l in range(cfg.L)), "every row must hit all 2^k codes"
        stacked = compiled.swakde_fleet_ingest(
            stacked, params, jnp.asarray(xs), jnp.asarray(tids, jnp.int32),
            cfg, cap)
        for t in range(T):
            oracle[t] = compiled.swakde_update_chunk(
                oracle[t], params, jnp.asarray(xs[tids == t]), cfg)
    _leaves_equal(stacked, fleet.fleet_stack(oracle))
    assert int(oracle[1].t) > cfg.window, "tenant 1 must actually expire"


def test_sann_fleet_bitexact_with_ring_wrap():
    """eta > 0 (keys matter) and capacity 64 with ~90 kept points per
    tenant: the ring wraps and evicts.  The padded fleet draws must equal
    the unpadded per-tenant `sann_prepare_chunk` draws (prefix-stable
    `sann_row_keys`), so whole states — stamps, ring pointers, tables —
    match bitwise."""
    T, d = 3, 4
    base = sann.SANNConfig(dim=d, n_max=16, eta=0.3, r=0.5, c=2.0, w=1.0,
                           L=4, k=2)
    cfg, params, empty = sann.sann_init(base, jax.random.PRNGKey(2))
    stacked = fleet.fleet_broadcast(empty, T)
    oracle = [empty for _ in range(T)]
    key = jax.random.PRNGKey(3)
    for chunk in range(5):
        xs, tids = _mixed(T, 120, d, seed=20 + chunk)
        cap = int(np.bincount(tids, minlength=T).max())
        ck = jax.random.fold_in(key, chunk)
        keys = jnp.stack([jax.random.fold_in(ck, t) for t in range(T)])
        stacked = compiled.sann_fleet_ingest(
            stacked, params, jnp.asarray(xs), jnp.asarray(tids, jnp.int32),
            keys, cfg, cap)
        for t in range(T):
            sub = jnp.asarray(xs[tids == t])
            if sub.shape[0]:
                prep = compiled.sann_prepare_chunk(params, sub, keys[t], cfg)
                oracle[t] = compiled.sann_commit_chunk(oracle[t], prep, cfg)
    _leaves_equal(stacked, fleet.fleet_stack(oracle))
    assert any(int(s.n_seen) > cfg.capacity for s in oracle), \
        "stream must lap the ring"

    qs, qt = _mixed(T, 15, d, seed=55)
    res = compiled.sann_fleet_query(stacked, params, jnp.asarray(qs),
                            jnp.asarray(qt, jnp.int32), cfg)
    ids, dists = compiled.sann_fleet_query_topk(
        stacked, params, jnp.asarray(qs), jnp.asarray(qt, jnp.int32), cfg,
        topk=8)
    for t in range(T):
        m = qt == t
        if not m.any():
            continue
        want = compiled.sann_query_batch(oracle[t], params, jnp.asarray(qs[m]),
                                 cfg)
        for a, b in zip(res, want):
            np.testing.assert_array_equal(np.asarray(a)[m], np.asarray(b))
        wi, wd = compiled.sann_query_topk_batch(oracle[t], params,
                                        jnp.asarray(qs[m]), cfg, topk=8)
        np.testing.assert_array_equal(np.asarray(ids)[m], np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(dists)[m], np.asarray(wd))


def test_sann_row_keys_prefix_stable():
    """The property the cap-padded fleet draws rest on: the first b keys
    of an n-key schedule equal the b-key schedule (NOT true of
    `jax.random.split`, whose threefry counters depend on n)."""
    key = jax.random.PRNGKey(7)
    full = sann.sann_row_keys(key, 64)
    for b in (1, 5, 17, 64):
        np.testing.assert_array_equal(np.asarray(full[:b]),
                                      np.asarray(sann.sann_row_keys(key, b)))


def test_route_chunk_groups_in_stream_order():
    tids = jnp.asarray([2, 0, 2, 1, 0, 2, 5, -1], jnp.int32)  # 5/-1 dropped
    r = fleet.route_chunk(tids, 3, 4)
    np.testing.assert_array_equal(np.asarray(r.counts), [2, 1, 3])
    assert np.asarray(r.take)[0, :2].tolist() == [1, 4]     # tenant 0 rows
    assert np.asarray(r.take)[1, :1].tolist() == [3]
    assert np.asarray(r.take)[2, :3].tolist() == [0, 2, 5]  # stream order
    np.testing.assert_array_equal(
        np.asarray(r.valid),
        np.arange(4)[None, :] < np.asarray(r.counts)[:, None])


# --------------------------------------------------------------------------
# hypothesis fuzz over mixed-tenant compositions
# --------------------------------------------------------------------------

# Guarded import (NOT importorskip: that would skip the whole module,
# exactness tests above included) — the fuzz tests alone skip without it.
try:
    from hypothesis import given, strategies as st
except ImportError:                      # pragma: no cover
    given = None

_D, _T = 4, 3
_PARAMS = init_srp(jax.random.PRNGKey(29), _D, 4, 2, 16)
_SCFG = swakde.SWAKDEConfig(L=4, W=16, window=8, eh_eps=0.5)
_SPARAMS = init_pstable(jax.random.PRNGKey(31), _D, _SCFG.L, 2, 1.0,
                        _SCFG.W)

if given is not None:
    _comp = st.one_of(
        st.lists(st.integers(0, _T - 1), min_size=1, max_size=40),
        # skewed: one hot tenant + a trickle of others
        st.lists(st.sampled_from([0] * 8 + [1, 2]), min_size=1,
                 max_size=40),
        st.lists(st.just(1), min_size=1, max_size=40),  # single hot tenant
    )
else:                                    # pragma: no cover
    def test_fuzz_requires_hypothesis():
        pytest.skip("property tests need hypothesis")

    _comp = None


@pytest.mark.skipif(given is None, reason="needs hypothesis")
@(given(comp=_comp, seed=st.integers(0, 5)) if given else (lambda f: f))
def test_fuzz_race_fleet_matches_oracle(comp, seed):
    tids = np.asarray(comp, np.int64)
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(len(comp), _D)).astype(np.float32)
    stacked = fleet.fleet_broadcast(race.race_init(4, 16), _T)
    stacked = compiled.race_fleet_ingest(stacked, _PARAMS, jnp.asarray(xs),
                                 jnp.asarray(tids, jnp.int32))
    for t in range(_T):
        sub = xs[tids == t]
        st_o = race.race_init(4, 16)
        if sub.shape[0]:
            prep = compiled.race_prepare_chunk(_PARAMS, jnp.asarray(sub), 16)
            st_o = compiled.race_commit_chunk(st_o, prep)
        _leaves_equal(fleet.fleet_row(stacked, t), st_o)


@pytest.mark.skipif(given is None, reason="needs hypothesis")
@(given(comp=_comp, seed=st.integers(0, 5)) if given else (lambda f: f))
def test_fuzz_swakde_fleet_matches_oracle(comp, seed):
    tids = np.asarray(comp, np.int64)
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(len(comp), _D)).astype(np.float32)
    stacked = fleet.fleet_broadcast(swakde.swakde_init(_SCFG), _T)
    cap = int(np.bincount(tids, minlength=_T).max())
    stacked = compiled.swakde_fleet_ingest(
        stacked, _SPARAMS, jnp.asarray(xs), jnp.asarray(tids, jnp.int32),
        _SCFG, cap)
    for t in range(_T):
        sub = xs[tids == t]
        st_o = swakde.swakde_init(_SCFG)
        if sub.shape[0]:
            st_o = compiled.swakde_update_chunk(st_o, _SPARAMS,
                                        jnp.asarray(sub), _SCFG)
        _leaves_equal(fleet.fleet_row(stacked, t), st_o)
