"""Window arithmetic on synthetic timestamps: both end-to-end metrics are
taken over the whole window, so a stall anywhere in it must move both."""

import numpy as np
import pytest

from benchmarks.chip import window


def window_metrics(times, t0, seconds):
    """The window of one env: every stamp is its own."""
    return window.window_metrics(times, t0, seconds, env=np.zeros(len(times), np.int64))


def _steady(start, stop, period):
    return np.arange(start, stop, period)


def test_steady_rate_and_gap():
    times = _steady(90.0, 140.0, 0.02)
    out = window_metrics(times, t0=100.0, seconds=30.0)
    assert out["env_steps_per_s"] == pytest.approx(50.0, rel=1e-3)
    assert out["action_gap_p95_ms"] == pytest.approx(20.0, rel=1e-6)
    assert out["steps"] == out["gaps"]


def test_a_stall_moves_both_metrics():
    steady = _steady(90.0, 140.0, 0.02)
    stalled = np.concatenate([steady[steady < 110.0], steady[steady >= 113.0]])  # 3 s with no step
    base = window_metrics(steady, 100.0, 30.0)
    hit = window_metrics(stalled, 100.0, 30.0)
    assert hit["env_steps_per_s"] < 0.95 * base["env_steps_per_s"]
    assert hit["steps_by_10s"] == [500, 350, 500] and sum(hit["steps_by_10s"]) == hit["steps"]
    # one long gap among ~1350 is under the 95th percentile: a tail of stalls is not
    many = steady[(np.floor(steady * 2) % 10 != 0) | (steady < 100.0)]  # a 0.5 s hole every 5 s
    tail = window_metrics(np.sort(many), 100.0, 30.0)
    assert tail["env_steps_per_s"] < base["env_steps_per_s"]
    slow = window_metrics(_steady(90.0, 140.0, 0.03), 100.0, 30.0)
    assert slow["action_gap_p95_ms"] > 1.4 * base["action_gap_p95_ms"]
    assert slow["env_steps_per_s"] < 0.7 * base["env_steps_per_s"]


def test_the_gap_open_at_the_start_counts_whole():
    times = np.array([99.0, 100.5, 100.6, 100.7])
    out = window_metrics(times, 100.0, 1.0)
    assert out["steps"] == 3 and out["gaps"] == 3
    assert out["action_gap_p95_ms"] > 1000.0


def test_steps_outside_the_window_do_not_count():
    times = np.concatenate([_steady(0.0, 100.0, 0.001), _steady(100.0, 110.0, 0.1), _steady(110.0, 120.0, 0.001)])
    out = window_metrics(times, 100.0, 10.0)
    assert out["env_steps_per_s"] == pytest.approx(10.0, abs=0.2)


@pytest.mark.parametrize("times,seconds", [(np.array([1.0, 2.0]), 0.0), (np.array([1.0]), 5.0), (np.array([]), 5.0)])
def test_nothing_to_measure_is_an_error(times, seconds):
    with pytest.raises(ValueError):
        window_metrics(times, 0.0, seconds)
