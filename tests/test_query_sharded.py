"""Sharded fused batched queries (8 virtual CPU devices, in subprocesses):
the sharded S-ANN, RACE and SW-AKDE query paths stay bitwise equal to the
single-device fused engine, which test_query_batched.py pins to the
per-query oracles.
"""
from multidevice import run_script


def test_sharded_fused_sann_matches_single_device():
    """The sharded S-ANN query paths reuse the fused batched reductions per
    table shard; results must stay bitwise equal to the single-device fused
    engine (and therefore to the per-query oracles) on 8 forced host
    devices."""
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import sann
        from repro.parallel import sketch_sharding as ss

        d = 12
        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        cfg = sann.SANNConfig(dim=d, n_max=2000, eta=0.35, r=0.8, c=2.0,
                              w=1.6, L=16, k=4)
        cfg, params, st0 = sann.sann_init(cfg, jax.random.PRNGKey(0))
        stream = jnp.asarray(np.random.default_rng(1).uniform(
            0, 1, (600, d)).astype(np.float32))
        key = jax.random.PRNGKey(2)
        qs = stream[:9] + 0.01
        ref = jitted(sann.sann_insert_batch, cfg=cfg)(st0, params, stream,
                                                      key)
        st, p = ss.shard_sann(st0, params, ctx)
        st = jitted(ss.sharded_sann_insert_batch, cfg=cfg, ctx=ctx)(
            st, p, stream, key)
        r1 = jitted(sann.sann_query_batch, cfg=cfg)(ref, params, qs)
        r8 = jitted(ss.sharded_sann_query_batch, cfg=cfg, ctx=ctx)(st, p, qs)
        for nm, a, b in zip(r1._fields, r8, r1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=nm)
        t1 = jitted(sann.sann_query_topk_batch, cfg=cfg, topk=10)(
            ref, params, qs)
        t8 = jitted(ss.sharded_sann_query_topk_batch, cfg=cfg, ctx=ctx,
                    topk=10)(st, p, qs)
        np.testing.assert_array_equal(np.asarray(t8[0]), np.asarray(t1[0]))
        np.testing.assert_array_equal(np.asarray(t8[1]), np.asarray(t1[1]))
        print("FUSED_SHARDED_SANN_OK")
    """)
    assert "FUSED_SHARDED_SANN_OK" in out


def test_sharded_fused_race_matches_single_device():
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import lsh, race
        from repro.parallel import sketch_sharding as ss

        d, L, W = 12, 16, 32
        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        stream = jnp.asarray(np.random.default_rng(1).uniform(
            0, 1, (600, d)).astype(np.float32))
        rp = lsh.init_srp(jax.random.PRNGKey(3), d, L=L, k=3, n_buckets=W)
        rref = jitted(race.race_update_batch)(race.race_init(L, W), rp,
                                              stream)
        rst, rpp = ss.shard_race(race.race_init(L, W), rp, ctx)
        rst = jitted(ss.sharded_race_update_batch, ctx=ctx)(rst, rpp, stream)
        qq = stream[:40]
        np.testing.assert_array_equal(
            np.asarray(jitted(ss.sharded_race_query_batch, ctx=ctx)(rst, rpp,
                                                                    qq)),
            np.asarray(jitted(race.race_query_batch)(rref, rp, qq)))
        print("FUSED_SHARDED_RACE_OK")
    """)
    assert "FUSED_SHARDED_RACE_OK" in out


def test_sharded_fused_swakde_matches_single_device():
    """Both fused branches (B < W gather / B ≥ W grid precompute) inside
    the shard_map body must stay bitwise equal to single-device."""
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import lsh, swakde
        from repro.parallel import sketch_sharding as ss

        d = 12
        ctx = ss.make_sketch_ctx(ss.make_sketch_mesh(8))
        stream = jnp.asarray(np.random.default_rng(1).uniform(
            0, 1, (250, d)).astype(np.float32))
        scfg = swakde.SWAKDEConfig(L=8, W=24, window=120, eh_eps=0.15)
        sp = lsh.init_srp(jax.random.PRNGKey(4), d, L=8, k=2, n_buckets=24)
        sref = jitted(swakde.swakde_update_chunk, cfg=scfg)(
            swakde.swakde_init(scfg), sp, stream)
        sst, spp = ss.shard_swakde(swakde.swakde_init(scfg), sp, ctx)
        sst = jitted(ss.sharded_swakde_update_chunk, cfg=scfg, ctx=ctx)(
            sst, spp, stream)
        for nq in (5, 40):
            qq = stream[:nq]
            np.testing.assert_array_equal(
                np.asarray(jitted(ss.sharded_swakde_query_batch, cfg=scfg,
                                  ctx=ctx)(sst, spp, qq)),
                np.asarray(swakde.swakde_query_batch(sref, sp, qq, scfg)))
        print("FUSED_SHARDED_SWAKDE_OK")
    """)
    assert "FUSED_SHARDED_SWAKDE_OK" in out
