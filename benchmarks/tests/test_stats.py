"""The arithmetic from samples to end-to-end metrics, on made-up samples."""

import pytest

from benchmarks.harness import stats


def _client(first, last, lat=()):
    return {"t_first_send": first, "t_last_reply": last,
            "latency_ns": list(lat)}


def test_rate_is_all_work_over_the_whole_window():
    # two connections; the window runs from the earliest send to the latest
    # reply, not the mean of per-connection rates
    results = [_client(10.0, 20.0), _client(10.5, 22.0)]
    assert stats.rate(1200, results) == pytest.approx(100.0)


def test_a_stall_in_the_window_lowers_the_rate():
    steady = [_client(0.0, 10.0)]
    stalled = [_client(0.0, 10.0 + 2.5)]  # same work, one 2.5 s stall
    assert stats.rate(1000, stalled) < stats.rate(1000, steady)
    assert stats.rate(1000, stalled) == pytest.approx(80.0)


def test_percentiles_are_over_all_calls_not_medians_of_chunks():
    fast = [1_000_000] * 90          # 1 ms, connection A
    slow = [50_000_000] * 10         # 50 ms, connection B: a tail
    lat = stats.latencies_ms([_client(0, 1, fast), _client(0, 1, slow)])
    assert len(lat) == 100
    assert stats.percentile(lat, 50) == 1.0
    assert stats.percentile(lat, 95) == 50.0
    # the mean of the two connections' own p95s would say 25.5, and a median
    # of per-connection medians would say 25.5 for the p50 as well
    assert stats.percentile(lat, 90) == 1.0
    assert stats.percentile(lat, 91) == 50.0


def test_percentile_is_nearest_rank_and_exact():
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile([5], 95) == 5
    assert stats.percentile(list(range(1, 101)), 95) == 95
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        stats.rate(1, [_client(5.0, 5.0)])
