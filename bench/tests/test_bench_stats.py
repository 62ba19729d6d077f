"""Latency percentiles from due times, and the Poisson schedule."""
import math

import numpy as np
import pytest

from bench import stats, traffic


def test_nearest_rank_percentile():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile(vals, 99) == 99
    assert stats.percentile(vals, 100) == 100
    assert stats.percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_stall_delays_every_request_behind_it():
    # due every 1 ms, served in 0.5 ms each, but the server stalls from
    # t = 10 ms to 60 ms: request 10 waits out the stall, and the queue
    # behind it drains by 0.5 ms per request
    due = [i * 1e-3 for i in range(1000)]
    done, free = [], 0.0
    for t in due:
        start = max(t, free)
        if 10e-3 <= start < 60e-3:
            start = 60e-3
        free = start + 0.5e-3
        done.append(free)
    lat = stats.latencies_from_due(due, done)
    assert lat[10] == pytest.approx(50.5e-3)
    assert lat[20] == pytest.approx(45.5e-3)
    assert lat[200] == pytest.approx(0.5e-3)
    assert stats.percentile(lat, 50) == pytest.approx(0.5e-3)
    # the 99th percentile is the 11th worst of 1000: request 20's wait
    assert stats.percentile(lat, 99) == pytest.approx(45.5e-3)


def test_a_request_that_never_finished_is_infinitely_late():
    lat = stats.latencies_from_due([0.0, 1.0], [0.5, None])
    assert lat[0] == 0.5 and math.isinf(lat[1])
    assert math.isinf(stats.percentile(lat, 99))


def test_every_seed_gets_the_same_arrivals_in_another_order():
    a = traffic.poisson_offsets(1000.0, 5.0, seed=1)
    b = traffic.poisson_offsets(1000.0, 5.0, seed=2**31 + 7)
    ga, gb = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    assert len(a) == len(b) == 5001
    assert not np.allclose(ga, gb)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert a[-1] == pytest.approx(b[-1])
    assert np.mean(ga) == pytest.approx(1e-3, rel=0.01)
