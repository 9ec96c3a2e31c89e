"""The correctness check of the four-chip cell, ``sann.ingest.4chip``, at a
size a test run holds, on four virtual CPU devices: a sound run passes,
the control fails, and each fault of the timed path turns ``correct``
false — the faults of `test_checks` that a sharded index can have, and
two of the exchange between chips:

* one chip's table block left as it was before each commit;
* one chip's copy of the point store differing from the others';
* one shard's candidates dropped from the top-k merge.

Also the program counter the cell's ``replicated_state_share`` reads,
``RetrievalService.stats()["shard_state_bytes"]``, at one and four shards.

The device count is fixed when JAX starts, so each case runs in a fresh
Python process with ``--xla_force_host_platform_device_count=4``.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "sann.ingest.4chip"
TIMEOUT_S = 240

_PRELUDE = f"""\
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import json
import numpy as np
from chipbench import harness
from chipbench.tests import test_checks, test_checks_sharded as faults

SEED = test_checks.SEED
KW = dict(overrides=dict(dim=16, n_max=20_000, L=4, k=4, ingest_chunk=256,
                         max_pending=768, query_block=64, num_shards=4),
          traffic_overrides=dict(prefill=2048))


def run(tamper=None, with_control=False):
    return harness.run_cell({CELL!r}, SEED, 0.3, False, require_chip=False,
                            tamper=tamper, with_control=with_control, **KW)
"""


def run_script(body: str) -> str:
    """Run ``body`` after the prelude in a fresh process on four CPU
    devices; return its stdout, or fail with the tail of its output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run(
        [sys.executable, "-c", _PRELUDE + textwrap.dedent(body)],
        capture_output=True, text=True, timeout=TIMEOUT_S, env=env,
        cwd=ROOT)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    return r.stdout


def _result(body: str) -> dict:
    return json.loads(run_script(body).strip().splitlines()[-1])


def failed(out, key="compared"):
    return sorted(n for n, c in out[key].items() if c["value"] > c["limit"])


def test_sound_sharded_run_passes_and_control_fails():
    out = _result("""
        print(json.dumps(run(with_control=True), default=str))
    """)
    assert out["correct"], out["compared"]
    assert out["compared"]["shard_layout_mismatch"]["value"] == 0
    assert failed(out, "control"), out["control"]


def stale_table_block(svc):
    """The last chip's tables and pointers left as they were before each
    commit."""
    commit = svc._commit

    def stale(state, prep):
        new = commit(state, prep)
        n = new.tables.shape[0] // svc.num_shards
        return new._replace(
            tables=new.tables.at[-n:].set(state.tables[-n:]),
            table_ptr=new.table_ptr.at[-n:].set(state.table_ptr[-n:]))

    svc._commit = stale


def diverged_replica(svc):
    """One stored coordinate of the last chip's copy of the point store
    off by one after each commit; the other chips' copies are sound."""
    commit = svc._commit

    def diverged(state, prep):
        new = commit(state, prep)
        x = new.points
        bufs = [s.data for s in x.addressable_shards]
        bufs[-1] = bufs[-1].at[0, 0].add(1.0)
        return new._replace(points=jax.make_array_from_single_device_arrays(
            x.shape, x.sharding, bufs))

    svc._commit = diverged


def dropped_shard(svc):
    """The top-k merge sees no candidates of the last chip: its table
    block reads empty to the query."""
    kind_fns = svc._query_kind_fns

    def drop(fn):
        def dropped(ctx, qs):
            state, version = ctx
            n = state.tables.shape[0] // svc.num_shards
            return fn((state._replace(tables=state.tables.at[-n:].set(-1)),
                       version), qs)
        return dropped

    svc._query_kind_fns = lambda: {k: drop(f) for k, f in kind_fns().items()}


@pytest.mark.parametrize("fault", [
    "test_checks.unchanged_state", "test_checks.altered_answer",
    "faults.stale_table_block", "faults.diverged_replica",
    "faults.dropped_shard"])
def test_sharded_faults_turn_correct_false(fault):
    out = _result(f"""
        print(json.dumps(run(tamper={fault}), default=str))
    """)
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("shards", [1, 4])
def test_shard_state_bytes_reads_the_addressable_shards(shards):
    out = _result(f"""
        import jax
        from chipbench import spec
        from repro.serve.retrieval import RetrievalConfig, RetrievalService

        svc = RetrievalService(RetrievalConfig(
            dim=16, n_max=20_000, L=4, k=4, num_shards={shards}))
        svc.ingest(np.random.default_rng(0).normal(
            size=(512, 16)).astype(np.float32))
        b = svc.stats()["shard_state_bytes"]
        dev = jax.devices()[0]
        leaves = jax.tree.leaves(svc.snapshot()[0])
        on_dev = [s.data.nbytes for x in leaves
                  for s in x.addressable_shards if s.device == dev]
        whole = [x.addressable_shards[0].data.nbytes for x in leaves
                 if x.sharding.is_fully_replicated]
        reader = spec.load_module(spec.HERE / "metrics" /
                                  "replicated_state_share.py")

        class Ctx:
            class run:
                svc_stats = svc.stats()

        svc.close()
        print(json.dumps(dict(b, on_dev=sum(on_dev), whole=sum(whole),
                              share=reader.read(Ctx),
                              n_leaves=len(leaves))))
    """)
    assert out["per_chip"] == out["on_dev"] > 0
    assert out["replicated"] == out["whole"]
    assert out["share"] == 100.0 * out["replicated"] / out["per_chip"]
    if shards == 1:
        assert out["replicated"] == out["per_chip"]
    else:
        assert 0 < out["replicated"] < out["per_chip"]


def test_replicated_state_share_is_silent_without_the_counter():
    from chipbench import spec
    reader = spec.load_module(spec.HERE / "metrics" /
                              "replicated_state_share.py")

    class Ctx:
        class run:
            svc_stats = {"ticks": 0}

    assert reader.read(Ctx) is None
