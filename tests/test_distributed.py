"""Multi-device sketch semantics (subprocesses with 8 virtual CPU
devices): sketch sharding (repro.parallel.sketch_sharding) == single-device
sketches bit-for-bit, sharded S-ANN merge included.  The single-device merge
semantics are in test_merge.py, the multi-device training semantics in
test_distributed_train.py.
"""
from multidevice import run_script


# ---------------------------------------------------------------------------
# Sketch sharding (repro.parallel.sketch_sharding): 8 forced host devices,
# sharded state and query results must equal single-device bit-for-bit.
# ---------------------------------------------------------------------------

def test_sharded_race_matches_single_device():
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import lsh, race
        from repro.parallel import sketch_sharding as ss

        L, W, d = 16, 32, 12
        params = lsh.init_srp(jax.random.PRNGKey(0), d, L=L, k=3, n_buckets=W)
        xs = jax.random.normal(jax.random.PRNGKey(1), (300, d))
        qs = jax.random.normal(jax.random.PRNGKey(2), (7, d))

        ref = jitted(race.race_update_batch)(race.race_init(L, W), params, xs)
        ref = jitted(race.race_update_batch, sign=-1)(  # turnstile
            ref, params, xs[:50])

        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        st, p = ss.shard_race(race.race_init(L, W), params, ctx)
        st = jitted(ss.sharded_race_update_batch, ctx=ctx)(st, p, xs)
        st = jitted(ss.sharded_race_update_batch, ctx=ctx, sign=-1)(
            st, p, xs[:50])
        assert (np.asarray(st.counts) == np.asarray(ref.counts)).all()
        assert int(st.n) == int(ref.n)
        for mom in (0, 4):
            np.testing.assert_array_equal(
                np.asarray(jitted(ss.sharded_race_query_batch, ctx=ctx,
                                  median_of_means=mom)(st, p, qs)),
                np.asarray(jitted(race.race_query_batch,
                                  median_of_means=mom)(ref, params, qs)))
        print("RACE_SHARDED_OK")
    """)
    assert "RACE_SHARDED_OK" in out


def test_sharded_swakde_matches_single_device():
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import lsh, swakde
        from repro.parallel import sketch_sharding as ss

        d = 10
        cfg = swakde.SWAKDEConfig(L=8, W=32, window=120, eh_eps=0.15)
        params = lsh.init_srp(jax.random.PRNGKey(0), d, L=8, k=2, n_buckets=32)
        xs = jax.random.normal(jax.random.PRNGKey(1), (250, d))
        qs = jax.random.normal(jax.random.PRNGKey(2), (6, d))

        update = jitted(swakde.swakde_update_chunk, cfg=cfg)
        ref = swakde.swakde_init(cfg)
        for i in range(0, 250, 100):   # uneven final chunk on purpose
            ref = update(ref, params, xs[i:i+100])

        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        sharded_update = jitted(ss.sharded_swakde_update_chunk, cfg=cfg,
                                ctx=ctx)
        st, p = ss.shard_swakde(swakde.swakde_init(cfg), params, ctx)
        for i in range(0, 250, 100):
            st = sharded_update(st, p, xs[i:i+100])
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(ref)):
            assert (np.asarray(a) == np.asarray(b)).all()
        np.testing.assert_array_equal(
            np.asarray(jitted(ss.sharded_swakde_query_batch, cfg=cfg,
                              ctx=ctx)(st, p, qs)),
            np.asarray(swakde.swakde_query_batch(ref, params, qs, cfg)))
        print("SWAKDE_SHARDED_OK")
    """)
    assert "SWAKDE_SHARDED_OK" in out


def test_sharded_sann_matches_single_device():
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import sann
        from repro.parallel import sketch_sharding as ss

        d = 12
        cfg = sann.SANNConfig(dim=d, n_max=2000, eta=0.35, r=0.8, c=2.0,
                              w=1.6, L=16, k=4)
        cfg, params, st0 = sann.sann_init(cfg, jax.random.PRNGKey(0))
        stream = jnp.asarray(np.random.default_rng(1).uniform(
            0, 1, (600, d)).astype(np.float32))
        key = jax.random.PRNGKey(2)
        qs = stream[:9] + 0.01

        ref = jitted(sann.sann_insert_batch, cfg=cfg)(st0, params, stream,
                                                      key)
        ref = jitted(sann.sann_delete, cfg=cfg)(ref, params, stream[3])

        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        st, p = ss.shard_sann(st0, params, ctx)
        st = jitted(ss.sharded_sann_insert_batch, cfg=cfg, ctx=ctx)(
            st, p, stream, key)
        st = jitted(ss.sharded_sann_delete, cfg=cfg, ctx=ctx)(st, p,
                                                              stream[3])
        for nm, a, b in zip(ref._fields, st, ref):
            assert (np.asarray(a) == np.asarray(b)).all(), nm

        r_ref = jitted(sann.sann_query_batch, cfg=cfg)(ref, params, qs)
        r_sh = jitted(ss.sharded_sann_query_batch, cfg=cfg, ctx=ctx)(st, p,
                                                                     qs)
        for nm, a, b in zip(r_ref._fields, r_sh, r_ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=nm)
        tk_ref = jitted(sann.sann_query_topk_batch, cfg=cfg, topk=10)(
            ref, params, qs)
        tk_sh = jitted(ss.sharded_sann_query_topk_batch, cfg=cfg, ctx=ctx,
                       topk=10)(st, p, qs)
        np.testing.assert_array_equal(np.asarray(tk_sh[0]),
                                      np.asarray(tk_ref[0]))
        np.testing.assert_array_equal(np.asarray(tk_sh[1]),
                                      np.asarray(tk_ref[1]))
        print("SANN_SHARDED_OK")
    """)
    assert "SANN_SHARDED_OK" in out


def test_sharded_two_phase_matches_single_device():
    """Sharded prepare→commit (both phases under shard_map) is bit-identical
    to the fused sharded ingest and to single-device, for all three
    sketches; the sharded grid-estimate table (the KDE service's snapshot
    cache) matches the single-device table and its reads match the fused
    query path."""
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import lsh, race, sann, swakde
        from repro.parallel import sketch_sharding as ss

        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        d = 10
        xs = jax.random.normal(jax.random.PRNGKey(1), (230, d))

        # RACE: two chunks through sharded prepare -> commit
        L, W = 16, 32
        params = lsh.init_srp(jax.random.PRNGKey(0), d, L=L, k=2, n_buckets=W)
        ref = race.race_update_batch(race.race_init(L, W), params, xs[:130])
        ref = race.race_update_batch(ref, params, xs[130:], sign=-1)
        prepare = jitted(ss.sharded_race_prepare_chunk, n_buckets=W, ctx=ctx)
        st, p = ss.shard_race(race.race_init(L, W), params, ctx)
        st = jitted(ss.sharded_race_commit_chunk, ctx=ctx)(
            st, prepare(p, xs[:130]))
        st = jitted(ss.sharded_race_commit_chunk, ctx=ctx, sign=-1)(
            st, prepare(p, xs[130:]))
        assert (np.asarray(st.counts) == np.asarray(ref.counts)).all()
        assert int(st.n) == int(ref.n)

        # SW-AKDE: prepare-ahead (both preps before any commit), then the
        # sharded grid table vs single-device + fused query reads
        cfg = swakde.SWAKDEConfig(L=8, W=32, window=120, eh_eps=0.15)
        params = lsh.init_srp(jax.random.PRNGKey(2), d, L=8, k=2,
                              n_buckets=32)
        update = jitted(swakde.swakde_update_chunk, cfg=cfg)
        ref = swakde.swakde_init(cfg)
        for i in range(0, 230, 100):   # uneven final chunk on purpose
            ref = update(ref, params, xs[i:i+100])
        prepare = jitted(ss.sharded_swakde_prepare_chunk, cfg=cfg, ctx=ctx)
        commit = jitted(ss.sharded_swakde_commit_chunk, cfg=cfg, ctx=ctx)
        st, p = ss.shard_swakde(swakde.swakde_init(cfg), params, ctx)
        preps = [prepare(p, xs[i:i+100]) for i in range(0, 230, 100)]
        for prep in preps:
            st = commit(st, prep)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(ref)):
            assert (np.asarray(a) == np.asarray(b)).all()
        grid = jitted(ss.sharded_swakde_grid_estimates, cfg=cfg, ctx=ctx)(st)
        np.testing.assert_array_equal(
            np.asarray(grid), np.asarray(swakde.swakde_grid_estimates(ref,
                                                                      cfg)))
        qs = xs[:7]
        np.testing.assert_array_equal(
            np.asarray(jitted(ss.sharded_swakde_query_from_grid, cfg=cfg,
                              ctx=ctx)(grid, p, qs)),
            np.asarray(swakde.swakde_query_batch(ref, params, qs, cfg)))

        # S-ANN: sharded prepare -> commit under ring wrap vs single-device
        cfg = sann.SANNConfig(dim=d, n_max=600, eta=0.1, r=0.8, c=2.0,
                              w=1.6, L=16, k=4, capacity_slack=0.5)
        cfg, params, st0 = sann.sann_init(cfg, jax.random.PRNGKey(3))
        stream = jnp.asarray(np.random.default_rng(4).uniform(
            0, 1, (600, d)).astype(np.float32))
        ckeys = jax.random.split(jax.random.PRNGKey(5), 3)
        insert = jitted(sann.sann_insert_batch, cfg=cfg)
        ref = st0
        for i, k in zip(range(0, 600, 200), ckeys):
            ref = insert(ref, params, stream[i:i+200], k)
        prepare = jitted(ss.sharded_sann_prepare_chunk, cfg=cfg, ctx=ctx)
        commit = jitted(ss.sharded_sann_commit_chunk, cfg=cfg, ctx=ctx)
        st, p = ss.shard_sann(st0, params, ctx)
        for i, k in zip(range(0, 600, 200), ckeys):
            st = commit(st, prepare(p, stream[i:i+200], k))
        for nm, a, b in zip(ref._fields, st, ref):
            assert (np.asarray(a) == np.asarray(b)).all(), nm
        print("TWO_PHASE_SHARDED_OK")
    """)
    assert "TWO_PHASE_SHARDED_OK" in out


def test_sharded_services_match_single_device():
    out = run_script("""
        import numpy as np
        from repro.serve.retrieval import RetrievalConfig, RetrievalService
        from repro.serve.kde_service import KDEServiceConfig, KDEService

        rng = np.random.default_rng(0)
        emb = rng.normal(0, 1, (700, 24)).astype(np.float32)
        qs = emb[:5] + 0.01

        kw = dict(dim=24, n_max=5000, eta=0.4, r=0.6, c=2.0, ingest_chunk=256)
        r1 = RetrievalService(RetrievalConfig(**kw))
        r8 = RetrievalService(RetrievalConfig(**kw, num_shards=8))
        assert (r1.num_shards, r8.num_shards) == (1, 8)
        r1.ingest(emb); r8.ingest(emb)
        assert r1.stored == r8.stored
        q1, q8 = r1.query(qs), r8.query(qs)
        for nm, a, b in zip(q1._fields, q1, q8):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=nm)

        kk = dict(dim=24, L=16, W=64, window=500, ingest_chunk=256)
        k1 = KDEService(KDEServiceConfig(**kk))
        k8 = KDEService(KDEServiceConfig(**kk, num_shards=8))
        k1.ingest(emb); k8.ingest(emb)
        np.testing.assert_array_equal(k1.density(qs), k8.density(qs))
        print("SERVICES_SHARDED_OK")
    """)
    assert "SERVICES_SHARDED_OK" in out


def test_sharded_sann_merge_matches_single_device():
    out = run_script("""
        import dataclasses, jax, numpy as np
        from repro.core import sann
        from repro.parallel import sketch_sharding as ss

        cfg, params, empty = sann.sann_init(sann.SANNConfig(
            dim=8, n_max=500, eta=0.2, r=0.4, c=2.0, w=1.0, L=8, k=2,
            bucket_cap=4), jax.random.PRNGKey(0))
        xs = jax.random.normal(jax.random.PRNGKey(1), (256, 8))
        insert = jitted(sann.sann_insert_batch, cfg=cfg)
        a = insert(empty, params, xs[:128], jax.random.PRNGKey(2))
        b = insert(empty, params, xs[128:], jax.random.PRNGKey(3))
        single = jitted(sann.sann_merge, cfg=cfg)(a, b, params)

        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        a8, params8 = ss.shard_sann(a, params, ctx)
        b8, _ = ss.shard_sann(b, params, ctx)
        merged8 = jitted(ss.sharded_sann_merge, cfg=cfg, ctx=ctx)(a8, b8,
                                                                  params8)
        for name, (u, v) in zip(single._fields, zip(single, merged8)):
            np.testing.assert_array_equal(
                np.asarray(u), np.asarray(v), err_msg=name)
        print("SANN_MERGE_SHARDED_OK")
    """)
    assert "SANN_MERGE_SHARDED_OK" in out


def test_sharded_sann_recovery_matches_single_run():
    """Durability composes with sharding: a table-sharded durable service
    crash-recovers onto the mesh (`_place_state` re-shards the
    host-restored snapshot) bit-identically to the uninterrupted sharded
    run."""
    out = run_script("""
        import tempfile
        import numpy as np, jax
        from repro.serve.retrieval import RetrievalConfig, RetrievalService

        kw = dict(dim=8, n_max=1000, eta=0.2, r=0.4, c=2.0, w=1.0, L=8,
                  k=3, ingest_chunk=64, num_shards=8)
        data = np.random.default_rng(0).uniform(
            0, 1, (400, 8)).astype(np.float32)
        with tempfile.TemporaryDirectory() as d:
            ref = RetrievalService(RetrievalConfig(**kw))
            ref.ingest(data)
            a = RetrievalService(RetrievalConfig(
                **kw, snapshot_dir=d, snapshot_every=3))
            a.ingest(data)     # crash point: WAL + snapshots on disk
            a.close()
            b = RetrievalService(RetrievalConfig(
                **kw, snapshot_dir=d, snapshot_every=3))
            replayed = b.recover()
            assert replayed < -(-400 // 64), "snapshot must be used"
            for name, (u, v) in zip(ref.state._fields,
                                    zip(b.state, ref.state)):
                np.testing.assert_array_equal(
                    np.asarray(u), np.asarray(v), err_msg=name)
            qs = np.asarray(data[:6] + 0.01, np.float32)
            rb, rr = b.query(qs), ref.query(qs)
            for x, y in zip(rb, rr):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            b.close()
        print("SHARDED_RECOVERY_OK")
    """)
    assert "SHARDED_RECOVERY_OK" in out


def test_sharded_fleet_matches_single_device():
    """Tenant-axis fleet sharding: a stacked [T] fleet split over 8 shards
    (T/8 whole sketches per device, params replicated, routing local)
    ingests a mixed chunk and answers mixed-tenant queries bit-identically
    to the unsharded vmapped fleet."""
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import fleet, lsh, race, sann, swakde
        from repro.parallel import sketch_sharding as ss

        T, d = 16, 10
        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.normal(size=(200, d)).astype(np.float32))
        tids = jnp.asarray(rng.integers(0, T, size=200), jnp.int32)
        qs = jnp.asarray(rng.normal(size=(30, d)).astype(np.float32))
        qt = jnp.asarray(rng.integers(0, T, size=30), jnp.int32)

        # RACE fleet
        params = lsh.init_srp(jax.random.PRNGKey(0), d, L=6, k=3,
                              n_buckets=32)
        ref = jitted(fleet.race_fleet_ingest)(
            fleet.fleet_broadcast(race.race_init(6, 32), T), params, xs,
            tids)
        st, p = ss.shard_fleet(
            fleet.fleet_broadcast(race.race_init(6, 32), T), params, ctx)
        st = jitted(ss.sharded_race_fleet_ingest, ctx=ctx)(st, p, xs, tids)
        assert (np.asarray(st.counts) == np.asarray(ref.counts)).all()
        assert (np.asarray(st.n) == np.asarray(ref.n)).all()
        np.testing.assert_array_equal(
            np.asarray(jitted(ss.sharded_race_fleet_query, ctx=ctx)(
                st, p, qs, qt)),
            np.asarray(jitted(fleet.race_fleet_query)(ref, params, qs, qt)))

        # SW-AKDE fleet (window < per-tenant stream: expiry crosses shards)
        cfg = swakde.SWAKDEConfig(L=4, W=32, window=8, eh_eps=0.2)
        sp = lsh.init_pstable(jax.random.PRNGKey(1), d, 4, 2, 1.0, 32)
        cap = int(np.bincount(np.asarray(tids), minlength=T).max())
        sref = jitted(fleet.swakde_fleet_ingest, cfg=cfg, cap=cap)(
            fleet.fleet_broadcast(swakde.swakde_init(cfg), T), sp, xs,
            tids)
        sst, spp = ss.shard_fleet(
            fleet.fleet_broadcast(swakde.swakde_init(cfg), T), sp, ctx)
        sst = jitted(ss.sharded_swakde_fleet_ingest, cfg=cfg, cap=cap,
                     ctx=ctx)(sst, spp, xs, tids)
        for a, b in zip(jax.tree.leaves(sst), jax.tree.leaves(sref)):
            assert (np.asarray(a) == np.asarray(b)).all()
        np.testing.assert_array_equal(
            np.asarray(jitted(ss.sharded_swakde_fleet_query, cfg=cfg,
                              ctx=ctx)(sst, spp, qs, qt)),
            np.asarray(jitted(fleet.swakde_fleet_query, cfg=cfg)(
                sref, sp, qs, qt)))

        # S-ANN fleet (eta > 0: the per-tenant chunk keys shard with the
        # tenant axis; inf/-1 top-k padding must survive the psum combine)
        scfg = sann.SANNConfig(dim=d, n_max=16, eta=0.3, r=0.5, c=2.0,
                               w=1.0, L=4, k=2)
        scfg, spar, sempty = sann.sann_init(scfg, jax.random.PRNGKey(2))
        keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(5), t)
                          for t in range(T)])
        aref = jitted(fleet.sann_fleet_ingest, cfg=scfg, cap=cap)(
            fleet.fleet_broadcast(sempty, T), spar, xs, tids, keys)
        ast, app = ss.shard_fleet(fleet.fleet_broadcast(sempty, T), spar,
                                  ctx)
        ast = jitted(ss.sharded_sann_fleet_ingest, cfg=scfg, cap=cap,
                     ctx=ctx)(ast, app, xs, tids, keys)
        for a, b in zip(jax.tree.leaves(ast), jax.tree.leaves(aref)):
            assert (np.asarray(a) == np.asarray(b)).all()
        ids, dists = jitted(ss.sharded_sann_fleet_query_topk, cfg=scfg,
                            ctx=ctx, topk=8)(ast, app, qs, qt)
        wi, wd = jitted(fleet.sann_fleet_query_topk, cfg=scfg, topk=8)(
            aref, spar, qs, qt)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(dists), np.asarray(wd))
        res = jitted(ss.sharded_sann_fleet_query, cfg=scfg, ctx=ctx)(
            ast, app, qs, qt)
        want = jitted(fleet.sann_fleet_query, cfg=scfg)(aref, spar, qs, qt)
        for a, b in zip(res, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("FLEET_SHARDED_OK")
    """)
    assert "FLEET_SHARDED_OK" in out
