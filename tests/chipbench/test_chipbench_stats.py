"""Percentile and window-mark arithmetic on synthetic event lists."""
import numpy as np
import pytest

from chipbench import stats


@pytest.mark.parametrize("q", [0, 5, 50, 64, 95, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_is_numpys_linear(q, n):
    vals = list(np.random.default_rng(n).normal(size=n))
    assert stats.percentile(vals, q) == pytest.approx(
        float(np.percentile(vals, q)), abs=1e-12)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 50) is None
    assert stats.mean([]) is None


def test_window_is_open_at_its_start_and_closed_at_its_end():
    assert not stats.in_window(1.0, 1.0, 2.0)
    assert stats.in_window(2.0, 1.0, 2.0)
    assert stats.count_in_window([0.5, 1.0, 1.5, 2.0, 2.5], 1.0, 2.0) == 2


def test_tokens_count_by_their_own_timestamps_not_by_request():
    # one request straddles each mark: only its tokens inside count
    streams = [[0.8, 0.9, 1.1, 1.2], [1.9, 2.1, 2.2]]
    n = sum(stats.count_in_window(s, 1.0, 2.0) for s in streams)
    assert n == 3
    assert stats.rate(n, 1.0, 2.0) == pytest.approx(3.0)


def test_gaps_belong_to_the_window_their_end_falls_in():
    streams = [[0.9, 1.1, 1.4], [1.8, 2.3], [5.0]]
    gaps = stats.token_gaps(streams, 1.0, 2.0)
    assert gaps == pytest.approx([0.2, 0.3])


def test_gaps_never_join_two_streams():
    gaps = stats.token_gaps([[1.1, 1.2], [1.7, 1.9]], 1.0, 2.0)
    assert gaps == pytest.approx([0.1, 0.2])


def test_first_tokens_of_requests_submitted_inside_the_window():
    reqs = [(0.9, 1.1), (1.2, 1.5), (1.9, 2.4), (1.95, None), (2.1, 2.2)]
    assert stats.first_token_latencies(reqs, 1.0, 2.0) == \
        pytest.approx([0.3, 0.5])


def test_rate_needs_a_window():
    with pytest.raises(ValueError):
        stats.rate(3, 2.0, 2.0)


def test_spans_inside_subtracts_only_what_starts_inside():
    outer = [(0.0, 1.0), (2.0, 1.0)]
    inner = [(0.1, 0.2), (0.5, 0.3), (1.5, 0.1), (2.2, 0.5)]
    got = stats.spans_inside(outer, inner)
    assert [round(c, 6) for _, _, c in got] == [0.5, 0.5]
