"""Multi-tenant sketch fleets, service layer (repro.serve.tenant_fleet):
the `TenantFleet` LRU hot set — spill → reactivate round-trips are
bit-identical, a call touching more tenants than hot slots splits into
stream-order ops, and durable fleets recover bit-identically from
snapshot + tenant-tagged WAL + per-tenant spills.

The core fleet paths (repro.core.fleet) are tested in test_fleet.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import fleet, sann
from repro.serve.tenant_fleet import TenantFleet, TenantFleetConfig

import compiled


def _leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------
# serve.tenant_fleet: LRU hot set, spill/recover
# --------------------------------------------------------------------------


def _race_oracle(tf, streams):
    """Per-tenant single sketches built with the fleet's own params."""
    W = tf.cfg.W
    out = {}
    for t, chunks in streams.items():
        st = tf._empty
        for xs in chunks:
            prep = compiled.race_prepare_chunk(tf._params, jnp.asarray(xs), W)
            st = compiled.race_commit_chunk(st, prep)
        out[t] = st
    return out


def test_tenant_fleet_lru_spill_reactivate_bitexact():
    """6 tenants through 3 hot slots: every chunk evicts somebody; the
    reactivated rows must equal never-spilled single sketches bitwise."""
    d, T = 6, 6
    tf = TenantFleet(TenantFleetConfig(kind="race", dim=d, hot_slots=3,
                                       L=4, k=3, W=32, seed=11))
    streams = {t: [] for t in range(T)}
    rng = np.random.default_rng(42)
    for chunk in range(8):
        # rotate a window of 3 tenants so the hot set churns
        active = [(chunk + j) % T for j in range(3)]
        xs = rng.normal(size=(45, d)).astype(np.float32)
        tids = rng.choice(active, size=45)
        tf.ingest(xs, tids)
        for t in active:
            sub = xs[tids == t]
            if sub.shape[0]:
                streams[t].append(sub)
    assert tf.spills > 0, "LRU must actually spill"
    oracle = _race_oracle(tf, streams)
    qs = rng.normal(size=(30, d)).astype(np.float32)
    qt = rng.integers(0, T, size=30)
    got = np.asarray(tf.query(qs, qt))
    for t in range(T):
        m = qt == t
        if m.any():
            np.testing.assert_array_equal(
                got[m], np.asarray(compiled.race_query_batch(
                    oracle[t], tf._params, jnp.asarray(qs[m]))))
        # reactivate and compare the full row state
        tf._activate([t])
        _leaves_equal(fleet.fleet_row(tf._stacked, tf._slots[t]), oracle[t])
    tf.close()


def test_tenant_fleet_swakde_expiry_and_density():
    d, T = 5, 4
    tf = TenantFleet(TenantFleetConfig(kind="swakde", dim=d, hot_slots=2,
                                       L=4, k=2, W=32, window=16,
                                       eh_eps=0.2, seed=13))
    streams = {t: [] for t in range(T)}
    rng = np.random.default_rng(7)
    for chunk in range(6):
        active = [(chunk + j) % T for j in range(2)]
        xs = rng.normal(size=(40, d)).astype(np.float32)
        tids = rng.choice(active, size=40)
        tf.ingest(xs, tids)
        for t in active:
            sub = xs[tids == t]
            if sub.shape[0]:
                streams[t].append(sub)
    oracle = {}
    for t, chunks in streams.items():
        st = tf._empty
        for xs in chunks:
            st = compiled.swakde_update_chunk(st, tf._params,
                                      jnp.asarray(xs), tf._scfg)
        oracle[t] = st
    assert max(int(s.t) for s in oracle.values()) > tf._scfg.window
    qs = rng.normal(size=(20, d)).astype(np.float32)
    qt = rng.integers(0, T, size=20)
    got = np.asarray(tf.query(qs, qt))
    dens = np.asarray(tf.density(qs, qt))
    for t in range(T):
        m = qt == t
        if m.any():
            want = np.asarray(compiled.swakde_query_batch(
                oracle[t], tf._params, jnp.asarray(qs[m]), tf._scfg))
            np.testing.assert_array_equal(got[m], want)
            denom = max(min(int(oracle[t].t), tf._scfg.window), 1)
            np.testing.assert_array_equal(dens[m], want / denom)
    tf.close()


def test_tenant_fleet_sann_queries_and_split_ops():
    """A single ingest call touching more tenants than hot slots splits
    into multiple ops (stream-order) and still matches the oracle that
    replays the same op split with the fleet's key schedule."""
    d = 4
    tf = TenantFleet(TenantFleetConfig(kind="sann", dim=d, hot_slots=2,
                                       n_max=16, eta=0.3, r=0.5, c=2.0,
                                       w=1.0, L=4, k=2, seed=17))
    cfg = tf._sann_cfg
    rng = np.random.default_rng(3)
    oracle = {t: tf._empty for t in range(4)}
    for call in range(3):
        xs = rng.normal(size=(90, d)).astype(np.float32)
        tids = rng.integers(0, 4, size=90)
        # oracle replays the fleet's own op plan + key schedule
        seq0 = tf.seq
        plan = tf._plan_ops(tids)
        tf.ingest(xs, tids)
        for op, idx in enumerate(plan):
            ck = jax.random.fold_in(tf._base_key, seq0 + op)
            for t in np.unique(tids[idx]):
                sub = xs[idx][tids[idx] == t]
                key_t = jax.random.fold_in(ck, int(t))
                oracle[t] = sann.sann_commit_chunk(
                    oracle[t], sann.sann_prepare_chunk(
                        tf._params, jnp.asarray(sub), key_t, cfg), cfg)
    assert tf.splits > 0, "ops must actually split (4 tenants, 2 slots)"
    qs = rng.normal(size=(12, d)).astype(np.float32)
    qt = rng.integers(0, 4, size=12)
    res = tf.query(qs, qt)
    ids, dists = tf.query_topk(qs, qt, topk=6)
    for t in range(4):
        m = qt == t
        if not m.any():
            continue
        want = sann.sann_query_batch(oracle[t], tf._params,
                                     jnp.asarray(qs[m]), cfg)
        for a, b in zip(res, want):
            np.testing.assert_array_equal(np.asarray(a)[m], np.asarray(b))
        wi, wd = sann.sann_query_topk_batch(oracle[t], tf._params,
                                            jnp.asarray(qs[m]), cfg, topk=6)
        np.testing.assert_array_equal(np.asarray(ids)[m], np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(dists)[m], np.asarray(wd))
    tf.close()


def test_tenant_fleet_durable_recover_bitexact(tmp_path):
    """Crash-recover a durable fleet: snapshot + tenant-tagged WAL tail +
    per-tenant spills rebuild every tenant's state bit-identically —
    including tenants that were cold (spilled) at snapshot time."""
    d, T = 6, 6
    kw = dict(kind="race", dim=d, hot_slots=3, L=4, k=3, W=32, seed=19,
              snapshot_dir=str(tmp_path), snapshot_every=5)
    tf = TenantFleet(TenantFleetConfig(**kw))
    rng = np.random.default_rng(23)
    streams = {t: [] for t in range(T)}
    for chunk in range(11):
        active = [(chunk + j) % T for j in range(3)]
        xs = rng.normal(size=(30, d)).astype(np.float32)
        tids = rng.choice(active, size=30)
        tf.ingest(xs, tids)
        for t in active:
            sub = xs[tids == t]
            if sub.shape[0]:
                streams[t].append(sub)
    qs = rng.normal(size=(18, d)).astype(np.float32)
    qt = rng.integers(0, T, size=18)
    before = np.asarray(tf.query(qs, qt))
    seq = tf.seq
    tf.close()                                   # "crash" after close

    tf2 = TenantFleet(TenantFleetConfig(**kw))
    with pytest.raises(RuntimeError):
        tf2.ingest(qs, qt)                       # must recover first
    tf2.recover()
    assert tf2.seq == seq
    np.testing.assert_array_equal(np.asarray(tf2.query(qs, qt)), before)
    oracle = _race_oracle(tf2, streams)
    for t in range(T):
        tf2._activate([t])
        _leaves_equal(fleet.fleet_row(tf2._stacked, tf2._slots[t]),
                      oracle[t])
    assert tf2.known_tenants == set(range(T))
    tf2.close()
