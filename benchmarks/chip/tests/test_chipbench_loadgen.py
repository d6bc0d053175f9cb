"""The load generator: seeded, stratified, timed from due time."""
import asyncio
import statistics
import time

import numpy as np
import pytest

import tiny  # noqa: F401  (puts the harness on sys.path)
from harness import loadgen
from harness.vocab import Vocab

MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 50.0},
       "prompt_tokens": {"median": 256, "sigma": 0.7, "min": 32,
                         "max": 1024},
       "new_tokens": {"median": 48, "sigma": 0.7, "min": 8, "max": 256},
       "greedy_share": 0.5, "table": 256}


@pytest.fixture(scope="module")
def vocab():
    return Vocab(2048)


def test_same_seed_same_requests(vocab):
    a = loadgen.Traffic(MIX, 2**31 + 5, vocab)
    b = loadgen.Traffic(MIX, 2**31 + 5, vocab)
    c = loadgen.Traffic(MIX, 2**31 + 6, vocab)
    ra = [a.request(i) for i in range(20)]
    rb = [b.request(i) for i in range(20)]
    rc = [c.request(i) for i in range(20)]
    key = lambda r: (r.prompt, r.max_new, r.greedy, r.due)  # noqa: E731
    assert [key(r) for r in ra] == [key(r) for r in rb]
    assert [key(r) for r in ra] != [key(r) for r in rc]


def test_every_seed_draws_the_same_sizes_in_its_own_order(vocab):
    """A backlog's table, shuffled by the seed."""
    mix = dict(MIX, arrivals={"kind": "backlog", "waiting": 32,
                              "prepare": 256})
    a = loadgen.Traffic(mix, 1, vocab)
    b = loadgen.Traffic(mix, 2, vocab)
    assert sorted(a.plen) == sorted(b.plen)
    assert sorted(a.nnew) == sorted(b.nnew)
    assert list(a.plen) != list(b.plen)
    assert a.greedy.sum() == b.greedy.sum() == 128


def test_every_seed_shares_one_arrival_schedule(vocab):
    """An open mix: every seed sends the same sizes at the same times,
    with its own prompt tokens and its own greedy requests."""
    a = loadgen.Traffic(MIX, 1, vocab)
    b = loadgen.Traffic(MIX, 2**31 + 9, vocab)
    ra = [a.request(i) for i in range(40)]
    rb = [b.request(i) for i in range(40)]
    assert [(r.due, r.max_new, len(r.prompt_ids)) for r in ra] == \
        [(r.due, r.max_new, len(r.prompt_ids)) for r in rb]
    assert [r.prompt for r in ra] != [r.prompt for r in rb]
    assert list(a.greedy) != list(b.greedy)
    assert a.greedy.sum() == b.greedy.sum() == 128
    assert sorted(a.plen) != list(a.plen)       # shuffled, not sorted


def test_clips_and_distributions(vocab):
    t = loadgen.Traffic(MIX, 9, vocab)
    assert t.plen.min() >= 32 and t.plen.max() <= 1024
    assert t.nnew.min() >= 8 and t.nnew.max() <= 256
    full = loadgen.lognormal_table(MIX["prompt_tokens"], 1024)
    assert full.min() == 32 and full.max() == 1024     # both clips bite
    assert abs(statistics.median(t.plen) - 256) <= 2
    assert abs(statistics.median(t.nnew) - 48) <= 1
    # Poisson: the mean gap is 1 / rate
    assert abs(float(np.mean(t.gaps)) - 1 / 50.0) < 0.001
    r = t.request(3)
    assert len(r.prompt_ids) == t.plen[3]
    assert vocab.encode(r.prompt) == r.prompt_ids


def test_open_loop_is_timed_from_due_time():
    """A sender stalled for 0.2 s shows as lag, and the latency of the
    requests it delayed counts from when they were due."""
    reqs = [loadgen.Req(i=i, prompt=b"x", prompt_ids=[5], max_new=1,
                        greedy=True, due=0.01 * i) for i in range(10)]
    sent = []

    def submit(r):
        sent.append(r)
        if r.i == 2:
            time.sleep(0.2)           # the generator's thread is stuck

    async def go():
        t0 = time.perf_counter()
        await loadgen.open_loop(reqs, t0, submit)
        return t0
    t0 = asyncio.run(go())
    assert [r.i for r in sent] == list(range(10))
    lag = [r.sent_t - r.due_t for r in reqs]
    assert all(x >= -1e-3 for x in lag)
    assert lag[3] > 0.15 and lag[9] > 0.05
    assert reqs[5].due_t == pytest.approx(t0 + 0.05)


def test_backlog_keeps_its_depth_waiting():
    waiting = []
    made = []

    def make(i):
        r = loadgen.Req(i=i, prompt=b"x", prompt_ids=[5], max_new=1,
                        greedy=True)
        made.append(r)
        return r

    async def go():
        stop = asyncio.Event()
        task = asyncio.ensure_future(loadgen.backlog_loop(
            make, waiting.append, lambda: len(waiting), 32, stop))
        await asyncio.sleep(0.02)
        assert len(waiting) == 32
        del waiting[:10]                # the server admits ten
        await asyncio.sleep(0.02)
        assert len(waiting) == 32 and len(made) == 42
        stop.set()
        await task
    asyncio.run(go())
    assert all(r.due_t == r.sent_t for r in made)


def test_missing_requests_sort_above_every_value():
    vals = list(range(1, 91))
    assert loadgen.pct(vals, 90) == 81
    assert loadgen.pct(vals, 90, missing=10) == 90
    assert loadgen.pct(vals, 90, missing=11) == float("inf")
    assert loadgen.pct([], 50, missing=1) == float("inf")


def test_an_open_window_holds_the_whole_table(vocab):
    """A window of table / rate seconds holds every request of the table
    once, whatever the seed: seeds change the order, not the work."""
    mix = dict(MIX, table=90, arrivals={"kind": "poisson",
                                        "rate_per_s": 2.0})
    for seed in (1, 2, 2**31 + 3):
        t = loadgen.Traffic(mix, seed, vocab)
        assert t.due_before(45.0) == 90
        reqs = [t.request(i) for i in range(90)]
        assert sorted(r.max_new for r in reqs) == sorted(t.nnew)
        assert reqs[0].due == 0.0 and max(r.due for r in reqs) < 45.0
        assert t.request(90).due == pytest.approx(45.0)
