"""The vectorised S-ANN table replay against a point-by-point simulation
of the same rules: slot ``p mod capacity``, a replaced point's entries
tombstoned, rings keeping their newest ``bucket_cap`` appends."""
import numpy as np
import pytest

from chipbench.refs import sann as ref_sann


def simulate(codes, n_buckets, bucket_cap, cap):
    n, L = codes.shape
    tables = np.full((L, n_buckets * bucket_cap), -1)
    ptr = np.zeros((L, n_buckets), np.int64)
    for p in range(n):
        slot = p % cap
        if p >= cap:
            tables[tables == slot] = -1
        for l in range(L):
            c = codes[p, l]
            tables[l, c * bucket_cap + ptr[l, c] % bucket_cap] = slot
            ptr[l, c] += 1
    return tables


@pytest.mark.parametrize("n,cap", [(40, 64), (300, 64)])
def test_replay_matches_point_by_point_simulation(n, cap):
    codes = np.random.default_rng(n).integers(0, 6, (n, 3))
    want = simulate(codes, 8, 4, cap)
    rep = ref_sann.replay(codes, 8, 4, cap)
    got = np.full_like(want, -1)
    got[rep.l, rep.col] = rep.slot
    np.testing.assert_array_equal(got, want)
    assert rep.appends == n * 3
    owner = ref_sann.owners(n, cap)
    assert len(owner) == min(n, cap)
    assert np.all(owner % cap == np.arange(len(owner)))
    assert np.all(owner >= n - cap)
