"""The traffic generator (``bench/traffic.py``)."""
import json
from pathlib import Path

import numpy as np

from bench import traffic

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def test_seeds_permute_one_multiset_of_sizes_and_gaps():
    mix = _mix("chat_0.90rps")
    a = traffic.schedule(mix, 1.0, 30, 1, 1000)
    b = traffic.schedule(mix, 1.0, 30, 2**31 + 17, 1000)
    assert len(a) == len(b) == 72        # 70 due, in whole blocks of 8
    for f in (lambda r: len(r.prompt), lambda r: r.max_tokens):
        assert sorted(map(f, a)) == sorted(map(f, b))
        assert list(map(f, a)) != list(map(f, b))
    ga, gb = np.diff([0.0] + [r.due_s for r in a]), np.diff(
        [0.0] + [r.due_s for r in b])
    np.testing.assert_allclose(sorted(ga), sorted(gb))
    assert a[-1].due_s == np.float64(sum(ga))


def test_same_seed_same_requests():
    mix = _mix("chat_0.90rps")
    a = traffic.schedule(mix, 0.5, 20, 7, 1000)
    b = traffic.schedule(mix, 0.5, 20, 7, 1000)
    assert all(x.due_s == y.due_s and x.max_tokens == y.max_tokens
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_follow_the_mix_and_stay_in_bounds():
    mix = _mix("chat_0.90rps")
    s = traffic.schedule(mix, 2.0, 50, 3, 1000)
    plen = np.array([len(r.prompt) for r in s])
    out = np.array([r.max_tokens for r in s])
    assert plen.min() >= 32 and plen.max() <= 768
    assert out.min() >= 16 and out.max() <= 248
    assert abs(np.median(plen) - 384) <= 8
    assert abs(np.median(out) - 128) <= 4
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in s)


def test_backlog_is_due_at_the_window_start():
    mix = _mix("offline")
    s = traffic.schedule(mix, 0.0, 10, 5, 1000)
    assert len(s) == mix["backlog"]
    # due at the pre-roll's start, which the window's start follows
    assert {r.due_s for r in s} == {0.0} and mix["preroll_s"] > 0
    assert min(r.max_tokens for r in s) >= 256


def test_every_block_of_requests_holds_the_same_work():
    """Each block of BLOCK consecutive requests takes one length from each
    stratum, so any run of whole blocks from the start asks for nearly
    the same work whatever the seed."""
    mix = _mix("chat_0.90rps")
    n, B = 88, traffic.BLOCK
    strata = traffic.lengths(mix["prompt"], n).reshape(B, -1)
    for seed in (1, 2**31 + 17):
        s = traffic.schedule(mix, 0.9, 51, seed, 1000)
        assert len(s) == n
        plen = np.array([len(r.prompt) for r in s]).reshape(-1, B)
        for block in plen:
            got = sorted(block)
            assert all(lo <= v <= hi for v, lo, hi in
                       zip(got, strata.min(1), strata.max(1)))
