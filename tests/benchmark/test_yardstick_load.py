"""The load generator: a seed fixes the schedule; seeds share the work
and differ in its order."""

import numpy as np
import pytest

from benchmark import load

TRAFFIC = {"loop": "open", "rate_per_s": 800.0, "one_row_share": 0.8,
           "rows_lo": 2, "rows_hi": 32, "query_pool": 10_000}


def _sizes(s):
    return np.array([len(r) for r in s.rows])


def test_same_seed_same_schedule():
    a = load.open_schedule(TRAFFIC, 2**31 + 5, 10)
    b = load.open_schedule(TRAFFIC, 2**31 + 5, 10)
    assert np.array_equal(a.due_s, b.due_s)
    assert all(np.array_equal(x, y) for x, y in zip(a.rows, b.rows))


def test_seeds_share_the_work_in_another_order():
    a = load.open_schedule(TRAFFIC, 1, 10)
    b = load.open_schedule(TRAFFIC, 2, 10)
    assert np.array_equal(np.sort(_sizes(a)), np.sort(_sizes(b)))
    # the gaps are one multiset of exponential quantiles, in two orders
    qa = np.percentile(np.diff(a.due_s), [10, 50, 90])
    qb = np.percentile(np.diff(b.due_s), [10, 50, 90])
    assert np.allclose(qa, qb, rtol=0.01)
    assert not np.array_equal(a.due_s, b.due_s)
    assert not np.array_equal(_sizes(a), _sizes(b))


def test_schedule_shape():
    s = load.open_schedule(TRAFFIC, 7, 10)
    sizes = _sizes(s)
    assert len(sizes) == 8000
    assert np.mean(sizes == 1) == pytest.approx(0.8)
    assert sizes[sizes > 1].min() == 2 and sizes.max() == 32
    assert s.due_s[0] >= 0 and s.due_s.max() < 10
    assert np.all(np.diff(s.due_s) >= 0)
    assert np.mean(np.diff(s.due_s)) == pytest.approx(1 / 800, rel=0.05)
    assert all(r.min() >= 0 and r.max() < 10_000 for r in s.rows)


def test_lateness_is_measured_from_the_due_time():
    late = load.lateness_ms(np.array([0.0, 0.001, 0.002, 0.010]))
    assert late["late_max_ms"] == pytest.approx(10.0)
    assert late["late_p50_ms"] == pytest.approx(1.5)
