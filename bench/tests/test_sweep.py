"""The knee of a rate sweep: the highest rate at which neither the backlog
nor the median time to first token runs away, with every lower rate
sustained too."""
import pytest

from bench import sweep

BASE_MS = 5000.0     # the lowest rate's median time to first token


def row(rate, open_, close, ttft_ms):
    return {"rate": rate, "backlog_open": open_, "backlog_close": close,
            "ttft_p50_ms": ttft_ms}


@pytest.mark.parametrize("r, ok", [
    (row(0.5, 20, 24, 6000), True),            # steady
    (row(0.5, 20, 30, 6000), False),           # backlog grew by half
    (row(0.5, 20, 24, 11000), False),          # first tokens wait twice as long
    (row(0.5, 0, 4, 100), True),               # small absolute slack
])
def test_sustained(r, ok):
    assert sweep.sustained(r, BASE_MS) is ok


def test_knee_is_highest_rate_with_every_lower_rate_sustained():
    rows = [row(0.7, 30, 60, 4000), row(0.4, 10, 11, 3000),
            row(0.55, 20, 22, 3500), row(0.85, 30, 30, 4000)]
    assert sweep.knee(rows) == 0.55
    assert sweep.knee([row(0.4, 10, 40, 3000)]) == 0.0
    rows[0] = row(0.7, 30, 30, 7000)           # no backlog growth, slow TTFT
    assert sweep.knee(rows) == 0.55


def test_knee_of_the_chat_sweep_on_the_chip():
    """The sweep of qwen2.5-32b-l4.chat-bursty on one TPU v5e (75 s warm-up,
    51 s window per rate): backlog at open and close, median TTFT."""
    rows = [row(0.45, 10, 11, 6772.1), row(0.6, 24, 27, 10756.1),
            row(0.75, 29, 40, 9749.8), row(0.9, 46, 61, 28202.8),
            row(1.05, 56, 76, 54399.4)]
    assert sweep.knee(rows) == 0.75
