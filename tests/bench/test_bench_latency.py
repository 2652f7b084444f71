"""Latency arithmetic of an open-loop run (``bench/latency.py``)."""
import pytest

from bench import latency
from bench.latency import Stamps


def _recs():
    # window [10, 20): a due at 9 (pre-roll), b and c due inside, d after
    a = Stamps(due=9.0, submitted=9.0, prompt_len=4,
               tokens=[10.0, 11.0, 13.0, 21.0])
    b = Stamps(due=12.0, submitted=12.4, prompt_len=4,
               tokens=[14.0, 14.0, 16.5])
    c = Stamps(due=18.0, submitted=18.0, prompt_len=4)   # never served
    d = Stamps(due=20.0, submitted=20.0, prompt_len=4, tokens=[20.5])
    return [a, b, c, d]


def test_ttft_counts_from_due_time_and_censors_at_window_end():
    # b: first token 14.0 - due 12.0 (not its 12.4 submit time);
    # c: no token by 20.0 -> 20.0 - 18.0; a and d are not due in the window
    assert latency.ttfts(_recs(), 10.0, 20.0) == [2.0, 2.0]


def test_served_in_counts_requests_due_or_served_in_the_window():
    # a was due in the pre-roll and served in the window; b, c due in it;
    # d is due at the window's end
    recs = _recs()
    assert latency.served_in(recs, 10.0, 20.0) == recs[:3]
    assert latency.served_in(recs, 14.5, 20.0) == recs[1:3]


def test_itl_pools_every_gap_that_ends_in_the_window():
    # a: 11-10, 13-11 (21-13 ends outside); b: 14-14, 16.5-14
    assert sorted(latency.itls(_recs(), 10.0, 20.0)) == [0.0, 1.0, 2.0, 2.5]


def test_rate_is_tokens_in_window_over_the_whole_window():
    # a: 10, 11, 13; b: 14, 14, 16.5 -> 6 tokens over 10 s
    assert latency.rate(_recs(), 10.0, 20.0) == pytest.approx(0.6)


def test_percentile_of_nothing_is_none():
    assert latency.percentile([], 90) is None
    assert latency.percentile([1.0, 2.0, 3.0, 4.0, 5.0], 50) == 3.0
