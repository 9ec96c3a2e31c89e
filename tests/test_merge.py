"""Sketch merge APIs (multi-worker ingestion): single-device semantics,
no mesh.  RACE merges are exact and associative; SW-AKDE merges are the EH
bucket union (commutative bitwise, mass-exact, associative at the estimate
level); S-ANN merges keep the newest-capacity points of the stamp-interleaved
union; every merge saturates its stream counters.
"""
import compiled


def test_race_merge_associative_commutative():
    import jax
    import numpy as np
    from repro.core import lsh, race

    params = lsh.init_srp(jax.random.PRNGKey(0), 8, L=6, k=2, n_buckets=16)
    xs = jax.random.normal(jax.random.PRNGKey(1), (240, 8))
    parts = [race.race_update_batch(race.race_init(6, 16), params, xs[i::3])
             for i in range(3)]
    a, b, c = parts

    def eq(x, y):
        return all((np.asarray(u) == np.asarray(v)).all()
                   for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)))

    assert eq(race.race_merge(race.race_merge(a, b), c),
              race.race_merge(a, race.race_merge(b, c)))
    assert eq(race.race_merge(a, b), race.race_merge(b, a))
    # merge of partition == one sketch over the whole stream (any order)
    whole = race.race_update_batch(race.race_init(6, 16), params, xs)
    merged = race.race_merge(race.race_merge(a, b), c)
    assert (np.asarray(merged.counts) == np.asarray(whole.counts)).all()
    assert int(merged.n) == int(whole.n)


def test_swakde_merge_semantics():
    """swakde_merge is the exact EH bucket-union merge: commutative bitwise,
    mass-preserving, associative at the estimate level (bucket *structure*
    may differ by one cascade level between groupings), and a merge of
    sketches over disjoint sub-streams estimates the sum of their windows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import lsh, swakde

    d = 8
    # window < substream length, so cells hold buckets at the expiry
    # boundary (ts == t - 1 - window) — the case where the merge clock must
    # match the query clock (state.t - 1), not state.t.
    cfg = swakde.SWAKDEConfig(L=6, W=24, window=80, eh_eps=0.2)
    params = lsh.init_srp(jax.random.PRNGKey(0), d, L=6, k=2, n_buckets=24)
    xs = jax.random.normal(jax.random.PRNGKey(1), (300, d))
    qs = jax.random.normal(jax.random.PRNGKey(2), (5, d))

    # three workers over disjoint sub-streams, same shared clock length
    parts = [swakde.swakde_update_chunk(swakde.swakde_init(cfg), params,
                                        xs[i::3], cfg) for i in range(3)]
    a, b, c = parts

    ab = swakde.swakde_merge(a, b, cfg)
    ba = swakde.swakde_merge(b, a, cfg)
    for u, v in zip(jax.tree.leaves(ab), jax.tree.leaves(ba)):
        assert (np.asarray(u) == np.asarray(v)).all()   # commutative bitwise

    ab_c = swakde.swakde_merge(ab, c, cfg)
    a_bc = swakde.swakde_merge(a, swakde.swakde_merge(b, c, cfg), cfg)

    def mass(st):
        eh = cfg.eh_config()
        idx = np.arange(eh.slots)[None, :]
        ts, num = np.asarray(st.ts), np.asarray(st.num)
        # live at the query clock t - 1, the convention every reader uses
        live = (idx < num[..., None]) & (ts > int(st.t) - 1 - cfg.window)
        sizes = (1 << np.arange(eh.levels))[:, None]
        return (live * sizes).sum(axis=(-2, -1))

    np.testing.assert_array_equal(mass(ab_c), mass(a_bc))  # mass exact
    e1 = np.asarray(swakde.swakde_query_batch(ab_c, params, qs, cfg))
    e2 = np.asarray(swakde.swakde_query_batch(a_bc, params, qs, cfg))
    np.testing.assert_allclose(e1, e2, rtol=2 * cfg.kde_eps, atol=1.0)

    # identity: merging with an empty sketch changes no *live* state — the
    # merge may compact buckets `a` only expires lazily, so the invariant
    # is estimate equality (bitwise) + in-window mass, not raw num equality
    empty = swakde.swakde_init(cfg)._replace(t=a.t)
    ida = swakde.swakde_merge(a, empty, cfg)
    np.testing.assert_array_equal(
        np.asarray(swakde.swakde_query_batch(ida, params, qs, cfg)),
        np.asarray(swakde.swakde_query_batch(a, params, qs, cfg)))
    np.testing.assert_array_equal(mass(ida), mass(a))

    # disjoint-substream correctness: merged window ≈ sum of part windows
    est_m = np.asarray(swakde.swakde_query_batch(ab_c, params, qs, cfg))
    est_sum = sum(np.asarray(swakde.swakde_query_batch(p_, params, qs, cfg))
                  for p_ in parts)
    np.testing.assert_allclose(est_m, est_sum,
                               rtol=3 * cfg.kde_eps, atol=1.5)


def test_merge_fuzz_identity_and_associativity():
    """Property fuzz over randomized 3-way stream splits:

      * RACE — empty merge is the bitwise identity; associativity holds
        bitwise for every random split;
      * SW-AKDE — empty merge is a live-state identity (estimates + mass);
        associativity is bit-exact at the estimate level while nothing has
        expired (window >= stream), and mass-exact always.
    """
    import jax
    import numpy as np
    from repro.core import lsh, race, swakde

    d = 6
    params = lsh.init_srp(jax.random.PRNGKey(0), d, L=4, k=2, n_buckets=16)
    cfg = swakde.SWAKDEConfig(L=4, W=16, window=100_000, eh_eps=0.25)
    qs = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (4, d)))

    def eq(x, y):
        return all((np.asarray(u) == np.asarray(v)).all()
                   for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)))

    for seed in range(4):
        rng = np.random.default_rng(seed)
        xs = rng.normal(0, 1, (180, d)).astype(np.float32)
        pid = rng.integers(0, 3, len(xs))        # randomized 3-way split

        # --- RACE: bitwise identity + associativity ------------------------
        parts = [race.race_update_batch(race.race_init(4, 16), params,
                                        xs[pid == w]) for w in range(3)]
        a, b, c = parts
        assert eq(race.race_merge(a, race.race_init(4, 16)), a)
        assert eq(race.race_merge(race.race_init(4, 16), a), a)
        assert eq(race.race_merge(race.race_merge(a, b), c),
                  race.race_merge(a, race.race_merge(b, c)))

        # --- SW-AKDE: live-state identity + estimate associativity ---------
        sparts = [compiled.swakde_update_chunk(swakde.swakde_init(cfg),
                                               params, xs[pid == w], cfg)
                  for w in range(3)]
        sa, sb, sc = sparts
        est = lambda st: np.asarray(
            compiled.swakde_query_batch(st, params, qs, cfg))
        merge = lambda x, y: compiled.swakde_merge(x, y, cfg)
        empty = swakde.swakde_init(cfg)._replace(t=sa.t)
        np.testing.assert_array_equal(est(merge(sa, empty)), est(sa))
        lhs = merge(merge(sa, sb), sc)
        rhs = merge(sa, merge(sb, sc))
        # no expiry -> the bucket-union canonicalises: estimates bit-exact
        np.testing.assert_array_equal(est(lhs), est(rhs))
        # ... and equal to one sketch over the whole stream
        whole = compiled.swakde_update_chunk(swakde.swakde_init(cfg), params,
                                             xs, cfg)
        np.testing.assert_array_equal(est(lhs), est(whole))


def test_merge_counter_saturation():
    """Counter-overflow audit: every merge combines its
    stream counters through core.util.saturating_add — near-INT32_MAX
    inputs clamp instead of wrapping negative."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import lsh, race, sann
    from repro.core.util import _INT32_MAX

    big = int(_INT32_MAX) - 5
    a = race.RACEState(counts=jnp.zeros((2, 4), jnp.int32),
                       n=jnp.int32(big))
    b = race.RACEState(counts=jnp.ones((2, 4), jnp.int32),
                       n=jnp.int32(100))
    m = race.race_merge(a, b)
    assert int(m.n) == int(_INT32_MAX)          # clamped, not wrapped
    assert int(race.race_merge(b, a).n) == int(_INT32_MAX)

    cfg, params, empty = sann.sann_init(
        sann.SANNConfig(dim=4, n_max=100, eta=0.5, r=0.5, c=2.0, w=1.0,
                        L=2, k=2), jax.random.PRNGKey(0))
    xs = jax.random.normal(jax.random.PRNGKey(1), (32, 4))
    sa = sann.sann_insert_batch(empty, params, xs[:16],
                                jax.random.PRNGKey(2), cfg)
    sb = sann.sann_insert_batch(empty, params, xs[16:],
                                jax.random.PRNGKey(3), cfg)
    sa = sa._replace(n_seen=jnp.int32(big))
    merged = sann.sann_merge(sa, sb, params, cfg)
    assert int(merged.n_seen) == int(_INT32_MAX)
    # sanity: un-saturated counters still add exactly
    assert int(sann.sann_merge(
        sa._replace(n_seen=jnp.int32(16)), sb, params, cfg).n_seen) == 32


def test_sann_merge_disjoint_streams():
    """`sann_merge` semantics at the core level: two workers ingest
    disjoint halves of a stream under a shared per-point key schedule; the
    merged sketch equals a single sketch fed the canonical stamp
    interleaving — bitwise (except the stamp clocks) without eviction, and
    with eviction (union > capacity) the live point set, tombstone
    consistency and nearest-neighbor answers still match."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import sann

    def ingest_perpoint(state, params, rows, keys, cfg):
        for r, k in zip(rows, keys):
            state = compiled.sann_insert(state, params, jnp.asarray(r), k, cfg)
        return state

    def run_case(n_points, cfg_kw, expect_evict):
        cfg, params, empty = sann.sann_init(
            sann.SANNConfig(**cfg_kw), jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        data = rng.uniform(0, 1, (n_points, cfg.dim)).astype(np.float32)
        master = jax.random.PRNGKey(5)
        gkeys = [jax.random.fold_in(master, i) for i in range(n_points)]
        pid = np.arange(n_points) % 2            # round-robin split: the
        # canonical (local idx, worker) interleaving == original order
        wa = ingest_perpoint(empty, params, data[pid == 0],
                             [gkeys[i] for i in range(n_points)
                              if pid[i] == 0], cfg)
        wb = ingest_perpoint(empty, params, data[pid == 1],
                             [gkeys[i] for i in range(n_points)
                              if pid[i] == 1], cfg)
        ref = ingest_perpoint(empty, params, data, gkeys, cfg)
        m = sann.sann_merge(wa, wb, params, cfg)

        live = lambda st: sorted(
            map(tuple, np.asarray(st.points)[np.asarray(st.valid)].tolist()))
        assert live(m) == live(ref), "live point sets differ"
        # tombstone consistency: every table entry points at a live slot
        tb = np.asarray(m.tables)
        assert np.asarray(m.valid)[tb[tb >= 0]].all()
        assert int(m.n_stored) == int(np.asarray(m.valid).sum())
        assert int(m.n_seen) == n_points

        qs = jnp.asarray(data[:8] + 0.01)
        rm = sann.sann_query_batch(m, params, qs, cfg)
        rr = sann.sann_query_batch(ref, params, qs, cfg)
        np.testing.assert_allclose(np.asarray(rm.distance),
                                   np.asarray(rr.distance), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(rm.found),
                                      np.asarray(rr.found))
        if not expect_evict:
            # no eviction: full bitwise equality except the stamp clocks
            for name, (u, v) in zip(m._fields, zip(m, ref)):
                if name == "stamps":
                    continue
                np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                              err_msg=f"field {name!r}")
            np.testing.assert_array_equal(np.asarray(rm.index),
                                          np.asarray(rr.index))
        else:
            assert int(m.n_stored) == cfg.capacity, "union must evict"

    # eta=0, stream < capacity: exact case
    run_case(200, dict(dim=6, n_max=100, eta=0.0, r=0.4, c=2.0, w=1.0,
                       L=4, k=2, bucket_cap=4), expect_evict=False)
    # eta=0, stream > capacity: union eviction + tombstones (capacity =
    # max(64, 4 * 16^1.0) = 64 < 300 kept)
    run_case(300, dict(dim=6, n_max=16, eta=0.0, r=0.4, c=2.0, w=1.0,
                       L=4, k=2, bucket_cap=4, capacity_slack=1.0),
             expect_evict=True)


def test_sann_merge_fold_associative_stored_set():
    """K-way fold associativity at the stored-set level, *with* eviction:
    folding sann_merge in any grouping over 3 workers keeps the same live
    point set (the newest-capacity rule commutes with folding), matching
    the newest-capacity of the canonical interleaved union."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import sann

    cfg, params, empty = sann.sann_init(sann.SANNConfig(
        dim=6, n_max=16, eta=0.0, r=0.4, c=2.0, w=1.0, L=4, k=2,
        bucket_cap=4, capacity_slack=1.0), jax.random.PRNGKey(0))
    assert cfg.capacity == 64
    rng = np.random.default_rng(1)
    data = rng.uniform(0, 1, (270, 6)).astype(np.float32)   # > capacity
    key = jax.random.PRNGKey(7)
    parts = [sann.sann_insert_batch(empty, params, jnp.asarray(data[w::3]),
                                    key, cfg) for w in range(3)]
    a, b, c = parts
    m = lambda x, y: sann.sann_merge(x, y, params, cfg)
    lhs = m(m(a, b), c)
    rhs = m(a, m(b, c))
    live = lambda st: sorted(
        map(tuple, np.asarray(st.points)[np.asarray(st.valid)].tolist()))
    assert int(lhs.n_stored) == cfg.capacity        # eviction happened
    assert live(lhs) == live(rhs)
    # and both equal the newest-capacity of the stamp-interleaved union:
    # stamps are the per-worker local indices, ties broken worker-first,
    # so the newest 64 points of the (local_idx, worker) order survive.
    order = []
    for j in range(90):
        for w in range(3):
            order.append(data[w::3][j])
    expect = sorted(map(tuple, np.asarray(order[-cfg.capacity:],
                                          np.float32).tolist()))
    assert live(lhs) == expect
