"""Multi-device training semantics (subprocesses with 8 virtual CPU
devices): sharded step == single-device step; EP MoE == dense MoE;
compressed DP all-reduce ≈ exact with error feedback.
"""
from multidevice import run_script


def test_sharded_train_step_matches_single_device():
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import registry
        from repro.launch.inputs import make_batch
        from repro.launch.mesh import make_test_mesh
        from repro.optim.adam import adamw
        from repro.parallel import param_specs
        from repro.train.train_step import make_train_step
        import dataclasses

        cfg = dataclasses.replace(registry.get_smoke_config("qwen3-4b"),
                                  param_dtype="float32", seq_parallel=True)
        key = jax.random.PRNGKey(0)
        batch = make_batch(cfg, batch=8, seq=32, key=jax.random.PRNGKey(1))

        # single device
        step1, init1 = make_train_step(cfg, adamw(lr=1e-3), mesh=None)
        s1 = init1(key)
        s1, m1 = jax.jit(step1)(s1, batch)

        # 2x4 mesh with full sharding machinery
        mesh = make_test_mesh(2, 4)
        stepN, initN = make_train_step(cfg, adamw(lr=1e-3), mesh=mesh)
        sN = initN(key)
        p_sh = param_specs.param_shardings(sN.params, mesh)
        o_sh = param_specs.opt_state_shardings(sN.opt_state, p_sh, mesh)
        state_sh = type(sN)(params=p_sh, opt_state=o_sh,
                            step=NamedSharding(mesh, P()))
        b_sh = param_specs.batch_shardings(batch, mesh)
        sN = jax.device_put(sN, state_sh)
        batchN = jax.device_put(batch, b_sh)
        sN, mN = jax.jit(stepN, in_shardings=(state_sh, b_sh),
                         donate_argnums=(0,))(sN, batchN)

        print("loss1", float(m1["loss"]), "lossN", float(mN["loss"]))
        assert abs(float(m1["loss"]) - float(mN["loss"])) < 2e-4
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(sN.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        print("SHARDED_MATCH_OK")
    """)
    assert "SHARDED_MATCH_OK" in out


def test_moe_ep_matches_dense():
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.models import moe
        from repro.parallel.sharding import make_ctx

        key = jax.random.PRNGKey(0)
        d, E, k = 32, 8, 2
        p = moe.init_moe(key, d, E, n_shared=1, d_ff_expert=16,
                         dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, d))
        y_dense, aux_d = moe.moe_dense(p, x, topk=k, capacity_factor=64.0)

        mesh = make_test_mesh(2, 4)
        ctx = make_ctx(mesh)
        # place params per the EP layout (experts on model, FSDP on data)
        p_ep = dict(p)
        p_ep["w_gate"] = jax.device_put(p["w_gate"], NamedSharding(mesh, P("model", "data", None)))
        p_ep["w_up"] = jax.device_put(p["w_up"], NamedSharding(mesh, P("model", "data", None)))
        p_ep["w_down"] = jax.device_put(p["w_down"], NamedSharding(mesh, P("model", None, "data")))
        x_ep = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y_ep, aux_e = jax.jit(lambda p, x: moe.moe_ep(
            p, x, topk=k, capacity_factor=64.0, ctx=ctx))(p_ep, x_ep)

        # EP combines its psum in bf16 (§Perf hillclimb 2) — equivalence
        # holds to bf16 rounding of the routed-expert contribution
        np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_ep),
                                   rtol=2e-2, atol=2e-2)
        assert float(jnp.abs(jnp.asarray(y_dense) - jnp.asarray(y_ep)).mean()) < 5e-3
        # aux is a per-shard load-balance estimate (Switch-style): close but
        # not identical to the global product
        np.testing.assert_allclose(float(aux_d), float(aux_e), rtol=5e-2)
        print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out


def test_compressed_allreduce_error_feedback():
    out = run_script("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.optim import compress

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 130))

        def worker(g_local, e_local):
            (r, e2) = compress.compressed_pmean(
                {"w": g_local}, compress.ErrorState(err={"w": e_local}), "data")
            return r["w"], e2.err["w"]

        f = jax.jit(jax.shard_map(worker, mesh=mesh,
                                  in_specs=(P("data"), P("data")),
                                  out_specs=(P("data"), P("data")),
                                  check_vma=False))
        exact = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
        err = jnp.zeros_like(g)
        # accumulate over steps: error feedback keeps the running sum honest
        tot_comp = jnp.zeros_like(g[0])
        tot_exact = jnp.zeros_like(g[0])
        for i in range(20):
            red, err = f(g * (1 + 0.01 * i), err)
            tot_comp = tot_comp + red[0]
            tot_exact = tot_exact + (g * (1 + 0.01 * i)).mean(0)
        one_step_err = float(jnp.abs(red[0] - (g * 1.19).mean(0)).max())
        accum_err = float(jnp.abs(tot_comp - tot_exact).max())
        rel = accum_err / float(jnp.abs(tot_exact).max())
        print("one-step", one_step_err, "accum rel", rel)
        assert rel < 0.02  # error feedback keeps long-run bias tiny
        exact_b, comp_b = compress.bytes_saved_per_step({"w": g[0]})
        assert comp_b < 0.3 * exact_b
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out
