"""Percentiles over every request due in the window."""
import types

import pytest

from measure import percentile, tpots, ttfts


def track(due, first=0.0, last=0.0, n=0):
    return types.SimpleNamespace(due=due, first=first, last=last, n=n)


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_a_request_without_a_token_counts_at_its_wait():
    # 19 requests served in 0.1 s, one shed (no token) due at t=1, the
    # run ending at t=11: its 10 s wait is the 95th percentile
    w = [track(due=0.0, first=0.1, last=0.5, n=5) for _ in range(19)]
    w.append(track(due=1.0))
    t = ttfts(w, t_end=11.0)
    assert len(t) == 20
    assert percentile(t, 95) == pytest.approx(0.1)
    assert percentile(t, 100) == pytest.approx(10.0)
    w.append(track(due=2.0))
    assert percentile(ttfts(w, 11.0), 95) == pytest.approx(9.0)


def test_tpot_needs_two_tokens():
    w = [track(0.0, 1.0, 2.0, 5), track(0.0, 1.0, 1.0, 1), track(0.0)]
    assert tpots(w) == [pytest.approx(0.25)]
