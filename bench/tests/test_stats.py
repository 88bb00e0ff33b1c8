"""End-to-end statistics are taken over all requests and the whole window."""
from types import SimpleNamespace as NS

import pytest

from bench import stats


def rec(due, times, rejected=False):
    return NS(due=due, times=times, rejected=rejected)


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 99) == 99
    assert stats.percentile([5.0], 99) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_ttft_counts_failed_requests_in_the_tail():
    recs = [rec(1.0, [1.5, 1.6]) for _ in range(8)]
    recs.append(rec(2.0, [], rejected=True))
    recs.append(rec(3.0, []))                     # never got a first token
    recs.append(rec(20.0, [20.1]))                # due after the window
    v = stats.ttft_values(recs, 0.0, 10.0, t_end=12.0)
    assert len(v) == 10
    assert sorted(v)[-2:] == [9.0, 10.0]          # t_end - due, in the tail
    assert stats.percentile(v, 90) == 9.0


def test_first_token_after_drain_counts_as_failed():
    v = stats.ttft_values([rec(1.0, [15.0])], 0.0, 10.0, t_end=12.0)
    assert v == [11.0]


def test_rate_is_over_the_whole_window():
    # 10 tokens inside a 10 s window, all in its first second: 1 token/s
    recs = [rec(0.0, [0.1 * i for i in range(10)] + [10.5])]
    assert stats.out_tok_s(recs, 0.0, 10.0) == pytest.approx(1.0)


def test_itl_takes_every_gap_of_every_request():
    recs = [rec(0.0, [0.0, 0.1, 0.2, 0.3]), rec(0.0, [1.0, 3.0])]
    gaps = stats.itl_gaps(recs, 0.0, 10.0)
    assert sorted(gaps) == pytest.approx([0.1, 0.1, 0.1, 2.0])
    assert stats.percentile(gaps, 99) == pytest.approx(2.0)
    # a gap counts where its later token lands
    assert stats.itl_gaps(recs, 0.15, 10.0) == pytest.approx([0.1, 0.1, 2.0])


def test_gateway_itl_reader_is_the_end_to_end_tail():
    from bench import harness
    read = harness.load_reader("gateway.itl_p99_ms")
    recs = {0: rec(0.0, [0.0, 0.1, 0.2, 0.3]), 1: rec(0.0, [1.0, 3.0])}
    run = NS(recs=recs, window=(0.0, 10.0))
    assert read(run) == pytest.approx(2000.0)
    assert read(NS(recs={0: rec(0.0, [5.0])}, window=(0.0, 10.0))) is None
