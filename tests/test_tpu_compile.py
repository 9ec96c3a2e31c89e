"""The TPU kernel route: every op that `kernels.dispatch` sends to Pallas on
a TPU compiles for a v5e chip at the widths the services run
(`chip_smoke.py`), and the route table names exactly the expected ops.

The compiles target a *described* chip (``v5e:2x2`` topology), so no TPU is
needed; nothing runs.  The topology is described inside a fixture, never at
import: one process at a time may load the TPU library, and every test
worker imports this module.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import batch_score, cand_score, dispatch, ops, race_update

PALLAS_OPS = {"race_hist", "cand_score", "batch_score_topk"}
XLA_OPS = {"srp_hash", "swakde_segment_pass", "sann_table_scatter",
           "sketch_decode_attn"}


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
