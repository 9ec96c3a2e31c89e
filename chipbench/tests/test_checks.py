"""The correctness checks at a size a test run holds, on the CPU: a sound
run passes, the control (the reference one precision step down) fails, and
each fault of the timed path that a cell can have turns ``correct`` false.

The harness runs whole (set-up, measured window, check) with its look for
a chip skipped; the faults are planted in the service under test:

* a commit that returns the state unchanged;
* half of each chunk left out;
* an answer altered where it is produced.

(The exchange between chips has no fault here: every cell is one chip.)
"""
import numpy as np
import pytest

from chipbench import harness

SEED = 2**31 + 11
CELLS = {
    "swakde.ingest": dict(
        overrides=dict(dim=16, L=8, W=64, window=1024, ingest_chunk=256,
                       max_pending=768, stream={"segment": 512})),
    "sann.ingest": dict(
        overrides=dict(dim=16, n_max=20_000, L=4, k=4, ingest_chunk=256,
                       max_pending=768, query_block=64),
        traffic_overrides=dict(prefill=2048)),
}


def run(cell, tamper=None, with_control=False):
    return harness.run_cell(cell, SEED, 0.3, False, require_chip=False,
                            tamper=tamper, with_control=with_control,
                            **CELLS[cell])


def failed(out, key="compared"):
    return sorted(n for n, c in out[key].items() if c["value"] > c["limit"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_passes_and_control_fails(cell):
    out = run(cell, with_control=True)
    assert out["correct"], out["compared"]
    assert failed(out, "control"), out["control"]


def unchanged_state(svc):
    svc._commit = lambda state, prep: state


def half_of_each_chunk(svc):
    prepare = svc._prepare
    svc._prepare = lambda chunk, *a: prepare(chunk[:chunk.shape[0] // 2],
                                             *a)


def altered_answer(svc):
    kind_fns = svc._query_kind_fns

    def alter(fn):
        def altered(ctx, qs):
            out = fn(ctx, qs)
            if isinstance(out, tuple):               # S-ANN top-k
                ids, dists = (np.array(a) for a in out)
                dists[0, 0] *= 1.01
                return ids, dists
            out = np.array(out)
            out[0] *= 1.5
            return out
        return altered

    svc._query_kind_fns = lambda: {k: alter(f) for k, f in kind_fns().items()}


@pytest.mark.parametrize("fault", [unchanged_state, half_of_each_chunk,
                                   altered_answer])
@pytest.mark.parametrize("cell", ["swakde.ingest", "sann.ingest"])
def test_ingest_faults_turn_correct_false(cell, fault):
    out = run(cell, tamper=fault)
    assert not out["correct"], out["compared"]

