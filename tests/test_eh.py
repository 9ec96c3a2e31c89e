"""Exponential Histogram invariants (paper §2.4, [DGIM02])."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import eh

import compiled


def _run_stream(bits, window, eps):
    cfg = eh.EHConfig.create(window=window, eps=eps)
    state = eh.eh_init(cfg)
    bits_arr = jnp.asarray(bits, jnp.int32)

    def step(s, tb):
        t, b = tb
        return eh.eh_step(s, t, b, cfg), None

    ts = jnp.arange(len(bits), dtype=jnp.int32)
    state, _ = jax.lax.scan(step, state, (ts, bits_arr))
    est = float(eh.eh_query(state, jnp.int32(len(bits) - 1), cfg))
    exact = int(np.asarray(bits)[max(0, len(bits) - window):].sum())
    return cfg, state, est, exact


@given(
    bits=st.lists(st.integers(0, 1), min_size=1, max_size=300),
    window=st.sampled_from([16, 64, 100]),
    eps=st.sampled_from([0.1, 0.2, 0.5]),
)
def test_eh_relative_error_bound(bits, window, eps):
    """DGIM guarantee: relative error <= eps (+1 for integer halving)."""
    _, _, est, exact = _run_stream(bits, window, eps)
    assert est <= (1 + eps) * exact + 1
    assert est >= (1 - eps) * exact - 1
    if exact == 0:
        assert est == 0


@given(
    bits=st.lists(st.integers(0, 1), min_size=50, max_size=300),
    window=st.sampled_from([32, 128]),
)
def test_eh_space_bound(bits, window):
    """Live buckets never exceed the paper's (k/2+1)(log(2N/k)+1)+1 bound."""
    cfg, state, _, _ = _run_stream(bits, window, 0.2)
    assert int(state.num.sum()) <= eh.eh_exact_upper(cfg)


def test_eh_all_ones_dense():
    bits = [1] * 250
    _, _, est, exact = _run_stream(bits, 100, 0.1)
    assert exact == 100
    assert abs(est - exact) <= 0.1 * exact + 1


def test_eh_expiry_to_zero():
    """A burst followed by silence must decay to zero estimate."""
    bits = [1] * 50 + [0] * 200
    _, _, est, exact = _run_stream(bits, 64, 0.1)
    assert exact == 0 and est == 0


def test_eh_timestamps_sorted_within_level():
    """Internal invariant: per-level rings are newest-first."""
    cfg, state, _, _ = _run_stream([1] * 200, 128, 0.1)
    ts, num = np.asarray(state.ts), np.asarray(state.num)
    for lvl in range(cfg.levels):
        live = ts[lvl, : num[lvl]]
        assert (np.diff(live) <= 0).all(), (lvl, live)


# ---------------------------------------------------------------------------
# Closed-form eh_add vs the scan-based oracle
# ---------------------------------------------------------------------------

@given(
    seed=st.integers(0, 2**31 - 1),
    window=st.sampled_from([16, 64, 100]),
    eps=st.sampled_from([0.1, 0.2, 0.5]),
)
@settings(max_examples=40, deadline=None)
def test_eh_add_closed_form_matches_ref(seed, window, eps):
    """`eh_add` (closed-form carry count) is bitwise identical to
    `eh_add_ref` (the scan cascade) — dead ring slots included — over
    gappy streams that exercise carries, ring wrap and expiry."""
    cfg = eh.EHConfig.create(window=window, eps=eps)
    rng = np.random.default_rng(seed)
    gaps = rng.integers(0, max(2, window // 4), 120)
    ts = np.cumsum(gaps).astype(np.int32)
    a = b = eh.eh_init(cfg)
    for t in ts:
        t = jnp.int32(t)
        a = compiled.eh_add(a, t, cfg)
        b = compiled.eh_add_ref(b, t, cfg)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# SumEH (batch updates, Corollary 4.2)
# ---------------------------------------------------------------------------

def _run_sum_stream(vals, window, eps, R):
    cfg = eh.SumEHConfig.create(window=window, eps=eps, batch_max=R)
    state = eh.sum_eh_init(cfg)

    def step(s, tv):
        t, v = tv
        return eh.sum_eh_add(s, t, v, cfg), None

    ts = jnp.arange(len(vals), dtype=jnp.int32)
    state, _ = jax.lax.scan(step, state, (ts, jnp.asarray(vals, jnp.int32)))
    est = float(eh.sum_eh_query(state, jnp.int32(len(vals) - 1), cfg))
    exact = int(np.asarray(vals)[max(0, len(vals) - window):].sum())
    return cfg, state, est, exact


@given(
    vals=st.lists(st.integers(0, 8), min_size=1, max_size=200),
    window=st.sampled_from([16, 64]),
    eps=st.sampled_from([0.1, 0.25]),
)
def test_sum_eh_error_bound(vals, window, eps):
    cfg, state, est, exact = _run_sum_stream(vals, window, eps, R=8)
    # Sum-EH guarantee is relative eps; allow +R slack for the boundary batch.
    assert est <= (1 + eps) * exact + 8
    assert est >= (1 - eps) * exact - 8
    assert int(state.num.sum()) <= cfg.max_buckets


def test_sum_eh_matches_binary_on_01():
    """On 0/1 streams SumEH and EH answer the same query."""
    rng = np.random.default_rng(0)
    bits = rng.integers(0, 2, 250).tolist()
    _, _, est_b, exact = _run_stream(bits, 64, 0.1)
    _, _, est_s, exact_s = _run_sum_stream(bits, 64, 0.1, R=1)
    assert exact == exact_s
    assert abs(est_b - exact) <= 0.1 * exact + 1
    assert abs(est_s - exact) <= 0.1 * exact + 1
