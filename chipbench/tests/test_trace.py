"""The trace reduction on a small trace recorded on the CPU, and the
metric arithmetic.

``data/cpu_trace.xplane.pb`` was recorded with ``jax.profiler`` on the
CPU: inside a `chipbench.trace.WINDOW` annotation, three rounds of a
jitted 192×192 matmul + tanh under a ``commit`` annotation, each followed
by a 4 ms sleep under a ``tick`` annotation.  The CPU has no device plane,
so the tests take the XLA executor threads of ``/host:CPU`` as the device.
"""
import pathlib

import numpy as np
import pytest

from chipbench import stats, trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


def cpu_ops(plane: str, line: str) -> bool:
    return plane == "/host:CPU" and line.startswith("tf_XLA")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(str(FIXTURE), ["commit", "tick"], cpu_ops)


def test_window_spans_and_busy(summary):
    assert summary is not None
    assert 0.012 < summary.window_s < 0.05
    assert sorted({s[0] for s in summary.spans}) == ["commit", "tick"]
    assert len(summary.spans) == 6
    assert 0 < summary.busy_s < summary.window_s


def test_kernel_time_by_name(summary):
    top = dict(summary.top_ops(10))
    assert top["dot_general.1"] > 0
    assert summary.op_seconds(lambda n: n == "dot_general.1") == \
        pytest.approx(top["dot_general.1"])


def test_idle_gaps_labelled_by_host_span(summary):
    gaps = summary.top_gaps(3)
    # the three 4 ms sleeps are the longest gaps, each inside its tick
    assert [g[0] for g in gaps] == ["tick"] * 3
    assert all(0.003 < g[1] < 0.006 for g in gaps)
    total_gap = sum(d for _, _, d in summary.gaps) / 1e9
    assert total_gap + summary.busy_s == pytest.approx(summary.window_s)


def test_union_and_complement():
    busy = trace.union([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert busy == [(0, 3), (5, 7)]
    assert trace.complement(busy, -1, 9) == [(-1, 0), (3, 5), (7, 9)]


def test_rate_closes_at_a_commit_completion():
    t = [10.0, 10.5, 11.2, 11.9, 12.4, 13.3]
    rows = [4096] * len(t)
    got = stats.commit_window(t, rows, 2.0)
    # opens at 10.0, closes at the first completion >= 12.0: 12.4;
    # the commits after the opening one up to the closing one count
    assert got == (4 * 4096, pytest.approx(2.4))
    assert stats.commit_window(t, rows, 10.0) is None
