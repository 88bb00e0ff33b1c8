"""The traffic generator: one schedule for every seed, token ids from the
seed, and the shapes the mixes ask for."""
import numpy as np
import pytest

from bench import loadgen

CHAT = loadgen.load_mix("chat-bursty")
REASON = loadgen.load_mix("reason-closed")
BIG = 2**31 + 12345


def _window(items):
    return [it for it in items if it.phase == "window"]


def test_open_loop_is_seeded():
    a = loadgen.open_loop(CHAT, 40, BIG, 1000)
    b = loadgen.open_loop(CHAT, 40, BIG, 1000)
    assert [(x.due, x.gen_len, x.tenant) for x in a] == \
        [(x.due, x.gen_len, x.tenant) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("phase", ["window", "warmup", "tail"])
def test_seeds_change_tokens_not_work(phase):
    a = [it for it in loadgen.open_loop(CHAT, 40, 1, 1000) if it.phase == phase]
    b = [it for it in loadgen.open_loop(CHAT, 40, BIG, 1000) if it.phase == phase]
    assert len(a) == len(b) > 1
    assert [(x.due, len(x.prompt), x.gen_len, x.tenant) for x in a] == \
        [(x.due, len(x.prompt), x.gen_len, x.tenant) for x in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # the schedule still mixes long and short requests over the window
    assert len({x.gen_len for x in a}) > 1


@pytest.mark.parametrize("spec", [CHAT["prompt"], CHAT["output"],
                                  REASON["prompt"], REASON["output"]])
def test_lognormal_set_quantiles(spec):
    x = loadgen.lognormal_set(2000, spec)
    assert x.min() >= spec["min"] and x.max() <= spec["max"]
    assert abs(np.median(x) - spec["median"]) <= 0.01 * spec["median"] + 1
    # the quartiles of a lognormal sit at median * exp(-+0.674 sigma)
    for q, z in ((25, -0.6745), (75, 0.6745)):
        want = np.clip(spec["median"] * np.exp(z * spec["sigma"]),
                       spec["min"], spec["max"])
        assert abs(np.percentile(x, q) - want) <= 0.02 * want + 1


def test_chat_output_lengths_match_the_repository_trace_shape():
    x = loadgen.lognormal_set(4000, CHAT["output"])
    p50, p90, p99 = np.percentile(x, [50, 90, 99])
    assert 90 <= p50 <= 100 and 330 <= p90 <= 400 and 1000 <= p99 <= 1250


def test_burst_windows_concentrate_arrivals():
    w = np.sort(loadgen.pareto_window_weights(20, 3.0))[::-1]
    assert w.sum() == pytest.approx(1.0)
    # the top 10% of windows hold what the repository's random draws of
    # the same process give them on average (about 0.48 over 2000 draws)
    rng = np.random.default_rng(0)
    draws = []
    for _ in range(2000):
        x = rng.pareto(1.5, 20) + 0.1
        draws.append(np.sort(x / x.sum())[-2:].sum())
    assert abs(w[:2].sum() - np.mean(draws)) < 0.05
    t = loadgen.bursty_times(100, 40.0, 20, 3.0, np.random.default_rng(0))
    assert len(t) == 100 and t.min() >= 0 and t.max() < 40.0
    assert np.all(np.diff(t) >= 0)


def test_open_loop_rate_and_phases():
    items = loadgen.open_loop(CHAT, 40, 7, 1000)
    win = _window(items)
    assert len(win) == round(CHAT["rate_per_s"] * 40)
    assert all(0 <= x.due < 40 for x in win)
    warm = [x for x in items if x.phase == "warmup"]
    assert warm and all(-CHAT["warmup_s"] <= x.due < 0 for x in warm)
    tail = [x for x in items if x.phase == "tail"]
    assert tail and all(x.due >= 40 for x in tail)
    assert len({x.rid for x in items}) == len(items)
    assert all(0 <= int(x.prompt.max()) < 1000 for x in items)


def test_tenant_shares_are_zipf():
    t = loadgen.zipf_tenants(1000, 8, 1.0, np.random.default_rng(0))
    counts = [t.count(f"t{k}") for k in range(8)]
    h = sum(1 / k for k in range(1, 9))
    for k, c in enumerate(counts):
        assert abs(c - 1000 / (k + 1) / h) <= 1


def test_closed_loop_seeds_change_tokens_not_work():
    a = loadgen.closed_loop(REASON, 1, 64000)
    b = loadgen.closed_loop(REASON, BIG, 64000)
    assert [[(len(x.prompt), x.gen_len) for x in q] for q in a] == \
        [[(len(x.prompt), x.gen_len) for x in q] for q in b]
    assert not np.array_equal(a[0][0].prompt, b[0][0].prompt)


def test_closed_loop_histories_and_contexts():
    queues = loadgen.closed_loop(REASON, BIG, 64000)
    assert len(queues) == REASON["clients"]
    firsts = [q[0] for q in queues]
    assert all(len(q) == REASON["requests_per_client"] for q in queues)
    ctx = [len(it.prompt) + it.gen_len for q in queues for it in q]
    assert max(ctx) <= REASON["prompt"]["max"] + REASON["output"]["max"]
    # histories are spread over the whole output range
    hist = sorted(len(it.prompt) for it in firsts)
    assert hist[-1] > 4 * hist[0]
    assert all(it.phase == "first" for it in firsts)
    again = loadgen.closed_loop(REASON, BIG, 64000)
    assert [[it.gen_len for it in q] for q in queues] == \
        [[it.gen_len for it in q] for q in again]


def test_outputs_cut_to_the_context():
    free = loadgen.closed_loop(REASON, BIG, 64000)
    cut = loadgen.closed_loop(REASON, BIG, 64000, max_context=4096)
    ctx = [[len(x.prompt) + x.gen_len for x in q] for q in cut]
    assert max(map(max, ctx)) == 4096
    assert max(len(x.prompt) + x.gen_len for q in free for x in q) > 4096
    for qa, qb in zip(free, cut):
        for a, b in zip(qa, qb):
            assert b.gen_len == min(a.gen_len, 4096 - len(a.prompt)) \
                or len(b.prompt) < len(a.prompt)
    opened = loadgen.open_loop(CHAT, 40, BIG, 1000, max_context=6500)
    assert all(len(x.prompt) + x.gen_len <= 6500 for x in opened)
