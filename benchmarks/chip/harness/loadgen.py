"""Seeded traffic from a mix's parameters.

A mix (traffic/<name>.json) gives distributions; a seed gives one run's
requests. Every run draws the SAME multiset of sizes: each distribution
is sampled at the midpoints of `table` equal-probability strata. An
open mix's gaps are scaled to sum to table / rate, and a window of that
length holds the whole table.

A backlog mix's table is shuffled by the seed. An open mix replays ONE
schedule for every seed: its gaps and sizes are shuffled once, by a
fixed key, so each request arrives at the same time with the same
prompt and output lengths in every run; the seed draws the prompts'
tokens, which requests are greedy, and the weights. With the schedule
shuffled by the seed, the seed changed the work: the bursts, and which
sizes fall in them, decide how long requests wait for the one admission
per step, and the 90th percentile of 72 TTFTs read 240-310 ms over
seeds while two runs of one seed agreed within 6%; one seed's order
queued six deep and read about 900 ms, the same in two runs of it.

Prompt content differs per request and seed: random vocabulary tokens,
re-encoded greedily and cut to the drawn token count (a cut at a token
boundary re-encodes to the same ids, so the program's tokenizer sees
exactly the drawn length).

Two arrival kinds:
  poisson  open loop at `rate_per_s`: request i is due at the sum of the
           first i gaps; it is timed from when it was due, not sent.
  backlog  keep `waiting` requests queued ahead of the slots, refilled
           as the server admits them; each is due when it is sent.
"""
from __future__ import annotations

import asyncio
import math
import statistics
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np

from .vocab import NUM_SPECIAL, Vocab

SCHEDULE_KEY = 0    # shuffles an open mix's schedule, one for every seed


def lognormal_table(d: dict, n: int) -> np.ndarray:
    """Stratified lognormal sizes: median, sigma, clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
    v = np.exp(math.log(d["median"]) + d["sigma"] * z)
    return np.clip(np.rint(v), d["min"], d["max"]).astype(np.int64)


def exponential_table(rate: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclass
class Req:
    """One request as the client sees it; times are perf_counter()."""
    i: int
    prompt: bytes
    prompt_ids: list
    max_new: int
    greedy: bool
    due: float = 0.0            # seconds after the window opens (open)
    due_t: Optional[float] = None
    sent_t: Optional[float] = None
    admit_t: Optional[float] = None
    token_t: list = field(default_factory=list)
    state: object = None        # the program's finished request state
    failed: bool = False

    @property
    def first_t(self):
        return self.token_t[0] if self.token_t else None


class Traffic:
    def __init__(self, mix: dict, seed: int, vocab: Vocab):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = vocab
        n = int(mix.get("table", 1024))
        arr = mix["arrivals"]
        self.kind = arr["kind"]
        rng = np.random.default_rng([self.seed, 1])
        order = np.random.default_rng(SCHEDULE_KEY) \
            if self.kind == "poisson" else rng
        self.plen = order.permutation(
            lognormal_table(mix["prompt_tokens"], n))
        self.nnew = order.permutation(lognormal_table(mix["new_tokens"], n))
        k = int(round(mix["greedy_share"] * n))
        self.greedy = rng.permutation(np.arange(n) < k)
        if self.kind == "poisson":
            gaps = exponential_table(arr["rate_per_s"], n)
            gaps *= n / arr["rate_per_s"] / gaps.sum()  # a cycle: n / rate
            self.gaps = order.permutation(gaps)
            # the first request is due at 0, the next cycle at n / rate
            self.due = np.concatenate([[0.0], np.cumsum(self.gaps)[:-1]])
            self.period = float(self.gaps.sum())
        elif self.kind != "backlog":
            raise ValueError(f"unknown arrival kind {self.kind!r}")
        self.n = n

    def prompt(self, i: int, n_tokens: int) -> tuple[bytes, list]:
        rng = np.random.default_rng([self.seed, 2, i])
        ids: list = []
        draw = 2 * n_tokens
        while len(ids) < n_tokens:
            pick = rng.integers(NUM_SPECIAL, self.vocab.size, size=draw)
            ids = self.vocab.encode(self.vocab.decode(pick))
            draw *= 2
        ids = ids[:n_tokens]
        return self.vocab.decode(ids), ids

    def request(self, i: int) -> Req:
        j = i % self.n
        text, ids = self.prompt(i, int(self.plen[j]))
        r = Req(i=i, prompt=text, prompt_ids=ids, max_new=int(self.nnew[j]),
                greedy=bool(self.greedy[j]))
        if self.kind == "poisson":
            r.due = float(self.due[j]) + (i // self.n) * self.period
        return r

    def due_before(self, seconds: float) -> int:
        """How many requests an open loop makes due in [0, seconds): the
        whole table when the window is one cycle (table / rate)."""
        i = 0
        while float(self.due[i % self.n]) + (i // self.n) * self.period \
                < seconds - 1e-9:
            i += 1
        return i


async def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        await asyncio.sleep(min(d, 0.05) if d > 0.002 else 0)


async def open_loop(reqs: list, t0: float, submit: Callable) -> None:
    """Send each request at t0 + due, stamping when it was due and sent;
    a late sender shows as sent_t - due_t, and the request is still
    timed from due_t."""
    for r in reqs:
        r.due_t = t0 + r.due
    for r in reqs:
        await _sleep_until(r.due_t)
        r.sent_t = time.perf_counter()
        submit(r)


async def backlog_loop(make: Callable, submit: Callable,
                       waiting: Callable, depth: int,
                       stop: asyncio.Event) -> None:
    """Keep `depth` requests waiting for a slot; each is due when sent."""
    i = 0
    while not stop.is_set():
        while waiting() < depth and not stop.is_set():
            r = make(i)
            r.due_t = r.sent_t = time.perf_counter()
            submit(r)
            i += 1
        await asyncio.sleep(0.002)


def pct(values, q: float, missing: int = 0) -> float:
    """q-th percentile (0..100) by the nearest-rank rule, with `missing`
    samples (failed or unanswered requests) sorting above every value."""
    vals = sorted(values)
    n = len(vals) + missing
    if n == 0:
        return float("nan")
    k = max(1, math.ceil(q / 100.0 * n))
    return vals[k - 1] if k <= len(vals) else float("inf")
