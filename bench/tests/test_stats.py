"""Latency arithmetic on hand-made stamps."""
import pytest

from stats import Served, percentile, queue_waits, tpots, ttfts


def served(due, stamps, finished=True, first_launch=None):
    return Served(rid=0, due=due, prompt=[1], out_len=len(stamps),
                  stamps=list(stamps), finished=finished,
                  first_launch=first_launch)


def test_unserved_ttft_is_its_age_at_close():
    reqs = [served(1.0, []), served(40.0, []), served(0.5, [0.9])]
    assert ttfts(reqs, close=51.0) == pytest.approx([50.0, 11.0, 0.4])


def test_unserved_ttft_moves_with_the_close():
    r = [served(3.0, [])]
    assert ttfts(r, 10.0) != ttfts(r, 20.0)


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9.1),
    ([5.0], 90, 5.0),
    ([0, 10], 50, 5.0),
])
def test_percentile_interpolates(values, q, want):
    assert percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_none():
    assert percentile([], 90) is None


def test_tpot_finished_and_unfinished():
    reqs = [served(0.0, [1.0, 1.1, 1.3]),               # (1.3-1.0)/2
            served(0.0, [2.0, 2.5], finished=False),    # runs to the close
            served(0.0, [3.0])]                         # one token: no TPOT
    assert tpots(reqs, close=4.0) == pytest.approx([0.15, 2.0])


def test_queue_wait_counts_unserved_at_close():
    reqs = [served(1.0, [2.0], first_launch=1.5), served(2.0, [])]
    assert queue_waits(reqs, close=5.0) == pytest.approx([0.5, 3.0])
