"""The stream generators: a chunk regenerated by index equals the chunk
ingested, and a mixture with a fixed ``data_seed`` gives every run seed
the same points per chunk, in another order."""
import numpy as np

from chipbench import streams


def _rows_sorted(x):
    return x[np.lexsort(x.T[::-1])]


def test_fixed_data_seed_orders_the_same_points():
    a = streams.GaussianMixture(1, 8, 64, 128, data_seed=0)
    b = streams.GaussianMixture(2**31 + 5, 8, 64, 128, data_seed=0)
    for j in range(4):
        x, y = a.chunk_at(j), b.chunk_at(j)
        assert not np.array_equal(x, y)
        np.testing.assert_array_equal(_rows_sorted(x), _rows_sorted(y))


def test_rows_regenerate_the_chunks():
    st = streams.SiftLike(7, 8, 32)
    idx = np.random.default_rng(0).permutation(32 * 9)[:100]
    want = np.concatenate([st.chunk_at(j) for j in range(9)])[idx]
    np.testing.assert_array_equal(streams.rows(st, idx), want)
