"""The TPU kernel route: every op that `kernels.dispatch` sends to Pallas on
a TPU compiles for a v5e chip at the widths the services run
(`chip_smoke.py`), and the route table names exactly the expected ops.
The S-ANN commit of the benchmark's cells compiles with its ring append in
place: at n_max = 1M on one chip, and sharded at n_max = 50M
(``num_shards=4``) on a v5e 2x2 host, where each chip holds only its share
of the index.

The compiles target a *described* chip (``v5e:2x2`` topology), so no TPU is
needed; nothing runs.  The topology is described inside a fixture, never at
import: one process at a time may load the TPU library, and every test
worker imports this module.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.core import lsh, sann
from repro.kernels import (batch_score, cand_score, dispatch, ingest_commit,
                           ops, race_update)
from repro.parallel import sketch_sharding as ss

PALLAS_OPS = {"race_hist", "cand_score", "batch_score_topk",
              "sann_table_scatter"}
XLA_OPS = {"srp_hash", "swakde_segment_pass", "sketch_decode_attn"}


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler / library held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (op, case id, kernel call, argument shapes): the smoke's widths, plus the
# services' default RACE width (W = 128).
CASES = [
    ("race_hist", "race-L512-W4096",
     lambda c: race_update.race_hist(c, 4096, interpret=False),
     [((4096, 512), jnp.int32)]),
    ("race_hist", "race-default-L32-W128",
     lambda c: race_update.race_hist(c, 128, interpret=False),
     [((1024, 32), jnp.int32)]),
    ("batch_score_topk", "sann-cr-M48-k1",
     lambda q, c, o: batch_score.batch_score_topk(q, c, o, 1,
                                                  interpret=False),
     [((1024, 128), jnp.float32), ((1024, 48, 128), jnp.float32),
      ((1024, 48), jnp.bool_)]),
    ("batch_score_topk", "sann-topk-M256-k10",
     lambda q, c, o: batch_score.batch_score_topk(q, c, o, 10,
                                                  interpret=False),
     [((1024, 128), jnp.float32), ((1024, 256, 128), jnp.float32),
      ((1024, 256), jnp.bool_)]),
    ("cand_score", "sann-oracle-M48",
     lambda q, c: cand_score.cand_score(q, c, interpret=False),
     [((128,), jnp.float32), ((48, 128), jnp.float32)]),
    # sann-sift128-1M: 16 tables of 505,964 buckets x 16 slots, a 4,096-point
    # chunk's 65,536 appends
    ("sann_table_scatter", "sann-sift128-1M-chunk4096",
     lambda *a: ingest_commit.sann_table_scatter(*a, interpret=False),
     [((16, 505964 * 16), jnp.int32), ((16, 505964), jnp.int32)]
     + [((65536,), jnp.int32)] * 4 + [((65536,), jnp.bool_)]),
]


@pytest.mark.parametrize("op,fn,args", [(c[0], c[2], c[3]) for c in CASES],
                         ids=[c[1] for c in CASES])
def test_pallas_op_compiles_for_v5e(one_chip, op, fn, args):
    assert dispatch.TPU_ROUTE[op] == "pallas"
    shapes = [_shape(one_chip, s, d) for s, d in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tpu_route_table():
    route = dispatch.TPU_ROUTE
    assert {op for op, r in route.items() if r == "pallas"} == PALLAS_OPS
    assert {op for op, r in route.items() if r == "xla"} == XLA_OPS
    # every routed op has a wrapper in ops.py, and every Pallas op has a
    # compile case above
    assert all(callable(getattr(ops, op)) for op in route)
    assert {c[0] for c in CASES} == PALLAS_OPS


def test_tpu_policy_never_interprets(monkeypatch):
    """On a TPU the route is the table, whatever REPRO_FORCE_PALLAS says,
    and a kernel wrapper's ``interpret=None`` resolves to compiled."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    assert dispatch.resolve_interpret(None) is False
    for op, r in dispatch.TPU_ROUTE.items():
        assert dispatch.route(op) == r
        assert dispatch.use_pallas(op) == (r == "pallas")
    monkeypatch.setattr(dispatch, "on_tpu", lambda: False)
    assert dispatch.resolve_interpret(None) is True
    assert all(dispatch.route(op) == "pallas" for op in dispatch.TPU_ROUTE)
    monkeypatch.delenv("REPRO_FORCE_PALLAS")
    assert all(dispatch.route(op) == "xla" for op in dispatch.TPU_ROUTE)


def _sann_cfg(n_max):
    """The knobs of the benchmark's S-ANN configurations."""
    return sann.SANNConfig(dim=128, n_max=n_max, eta=0.25, r=1.0, c=2.0,
                           w=12.0, L=16, k=8, bucket_cap=16).resolved()


def _sann_shapes(cfg):
    """Empty state, params and a 4,096-point chunk's prep, as shapes."""
    state = jax.eval_shape(lambda: sann.sann_empty_state(cfg))
    params = jax.eval_shape(lambda k: lsh.init_pstable(
        k, cfg.dim, cfg.L, cfg.k, cfg.w, cfg.n_buckets),
        jax.random.PRNGKey(0))
    xs = jax.ShapeDtypeStruct((4096, cfg.dim), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    prep = jax.eval_shape(lambda p, x, k: sann.sann_prepare_chunk(
        p, x, k, cfg), params, xs, key)
    return state, params, xs, key, prep


def _assert_append_in_place(compiled, row_entries):
    """The commit appends through the Pallas kernel, and no while loop of
    the compiled program carries a table row: XLA's flat scatter into the
    2-D tables relayouts them in two such loops."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    loops = [ln for ln in text.splitlines()
             if re.search(r"= .*\bwhile\(", ln)]
    assert not [ln for ln in loops if f",{row_entries}]" in ln]


def test_sann_1m_commit_appends_in_place_for_v5e(one_chip, monkeypatch):
    """`sann_commit_chunk` at `sann-sift128-1M`'s widths on one chip: the
    ring append runs in place (one Pallas call), so the commit holds less
    than one table (518,107,136 B) of temporaries and no while loop carries
    an ``s32[..., 8095424]`` table row."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cfg = _sann_cfg(1_000_000)
    state, _, _, _, prep = _sann_shapes(cfg)
    placed = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), t)
    compiled = jax.jit(lambda s, p: sann.sann_commit_chunk(s, p, cfg)).lower(
        placed(state), placed(prep)).compile()
    row = cfg.n_buckets * cfg.bucket_cap
    assert row == 8_095_424
    assert compiled.memory_analysis().temp_size_in_bytes < cfg.L * row * 4
    _assert_append_in_place(compiled, row)


def test_sann_50m_sharded_ingest_compiles_for_v5e_2x2(topo, no_compile_cache,
                                                      monkeypatch):
    """Empty state, prepare and commit of `RetrievalService` at the
    `sann-bigann128-50M-4chip` settings on a 2x2 mesh: every program
    compiles for the chip (its compiler refuses one that overflows HBM),
    and each chip's state is its 4 tables plus the replicated point store,
    not the whole 11.6 GB index."""
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    cfg = _sann_cfg(50_000_000)
    ctx = ss.make_sketch_ctx(Mesh(np.asarray(topo.devices).reshape(4),
                                  (ss.SHARD_AXIS,)))

    def placed(tree, specs):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(ctx.mesh, s)),
            tree, specs)

    state, params, xs, key, prep = _sann_shapes(cfg)
    state_specs = ss._sann_state_specs(ctx)
    st = placed(state, state_specs)
    rep = ctx.spec()

    empty = jax.jit(lambda: sann.sann_empty_state(cfg),
                    out_shardings=jax.tree.map(
                        lambda s: NamedSharding(ctx.mesh, s), state_specs))
    per_chip = empty.lower().compile().memory_analysis().output_size_in_bytes
    whole = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    replicated = sum(getattr(state, f).size * getattr(state, f).dtype.itemsize
                     for f in state._fields
                     if f not in ("tables", "table_ptr"))
    # the chip's layout pads the small arrays by a few KB
    assert abs(per_chip - ((whole - replicated) // 4 + replicated)) < 2**20

    jax.jit(lambda p, x, k: ss.sharded_sann_prepare_chunk(
        p, x, k, cfg, ctx)).lower(
        placed(params, ss._param_specs(params, ctx)),
        placed(xs, rep), placed(key, rep)).compile()
    commit = jax.jit(lambda s, p: ss.sharded_sann_commit_chunk(
        s, p, cfg, ctx)).lower(st, placed(prep, ss._sann_prep_specs(ctx))
                               ).compile()
    # per device: 4 tables of 152,218,496 entries; the ring append is in
    # place, so less than one table block of temporaries and no loop
    # carrying a table row
    row = cfg.n_buckets * cfg.bucket_cap
    assert row == 152_218_496
    assert commit.memory_analysis().temp_size_in_bytes < cfg.L // 4 * row * 4
    _assert_append_in_place(commit, row)
