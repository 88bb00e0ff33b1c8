"""The correctness sample: finished requests packed into the reference's
rows, with the longest context and the longest answer always in it."""
from types import SimpleNamespace as NS

import pytest

from bench import harness


def run_of(recs):
    return NS(recs={r.rid: r for r in recs})


def rec(rid, prompt_len, n_tokens, finish="budget"):
    return harness.Rec(rid=rid, prompt=(1,) * prompt_len, gen_len=n_tokens,
                       tenant="t0", phase="window", tokens=[2] * n_tokens,
                       finish=finish)


def test_longest_context_and_longest_answer_are_in_the_sample():
    recs = [rec(i, 100, 10) for i in range(20)]
    recs += [rec(50, 900, 50), rec(51, 100, 400)]
    rows = harness.choose_sample(run_of(recs), seed=2**35 + 1, row_len=1024,
                                 rows=2)
    picked = [r.rid for row in rows for r in row]
    assert 50 in picked and 51 in picked
    assert len(picked) == len(set(picked))
    for row in rows:
        assert sum(len(r.prompt) + len(r.tokens) for r in row) <= 1024


@pytest.mark.parametrize("rows", [1, 2, 3])
def test_rows_fill_first_fit_and_leave_unfinished_out(rows):
    recs = [rec(i, 200, 56) for i in range(30)]
    recs.append(rec(99, 10, 5, finish=""))            # still running
    sample = harness.choose_sample(run_of(recs), seed=7, row_len=1024,
                                   rows=rows)
    assert len(sample) == rows
    assert all(len(row) == 4 for row in sample)       # 4 x 256 tokens a row
    assert 99 not in {r.rid for row in sample for r in row}


def test_the_seed_draws_the_rest():
    recs = [rec(i, 200, 56) for i in range(30)]
    pick = lambda s: [r.rid for row in harness.choose_sample(
        run_of(recs), seed=s, row_len=1024, rows=1) for r in row]
    assert pick(3) == pick(3)
    assert pick(3) != pick(4)


def test_no_finished_request_gives_no_sample():
    assert harness.choose_sample(run_of([rec(1, 10, 5, finish="")]), 1,
                                 1024) == []
