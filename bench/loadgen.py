"""Seeded traffic and the loop that feeds it to the engine.

One generator reads every traffic file (``bench/traffic/<mix>.json``):

    {"arrival": {"kind": "backlog", "requests": N}
              | {"kind": "poisson", "rate_rps": r, "lead_s": s},
     "prompt_len": {"median": m, "sigma": s, "min": a, "max": b},
     "output_len": {...same keys...},
     "check": {"requests": k}}

Lengths are lognormal with the given median and log-sigma, clipped to
[min, max]. Every seed gets the same lengths and the same inter-arrival
gaps, the quantiles (i + 1/2)/N of their distributions, in one fixed
order (a permutation drawn from ``ORDER``): the seed draws the prompt
tokens, not the amount or the order of the work. A fixed order is one
sample path of the arrival process; near an open loop's knee the order
alone moves the tail of time to first token by several times, which a
seeded order would put into every comparison. Prompt tokens are uniform
over the vocabulary. All requests are greedy.

The loop is single-threaded: before each ``step()`` it submits every
request whose arrival time has passed, and after it stamps each new token
with the host time ``step()`` returned, which is when a streaming client
would see it. An open-loop request is timed from its scheduled arrival,
so a late generator or a long step shows up in the latency it causes;
how late each submission came is recorded too.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time
from statistics import NormalDist
from typing import Callable, Deque, List, Tuple

import numpy as np


def lognormal_set(n: int, spec: dict) -> np.ndarray:
    """The n lengths every seed gets: quantiles of the clipped lognormal."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def exponential_set(n: int, rate: float) -> np.ndarray:
    """The n inter-arrival gaps every seed gets, in seconds."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


@dataclasses.dataclass
class Planned:
    """One request of the traffic: when it is due (seconds from the
    window's start; -inf for a backlog queued before the window) and its
    sizes and tokens."""
    idx: int
    due: float
    prompt: np.ndarray
    max_new: int


#: the seed of the one order of sizes and gaps that every run uses
ORDER = 0


def plan(traffic: dict, seed: int, vocab: int, seconds: float) -> List[Planned]:
    """The traffic's requests for ``seed``: sizes and gaps in the fixed
    order, prompt tokens drawn from the seed."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(ORDER)
    arr = traffic["arrival"]
    if arr["kind"] == "backlog":
        n = int(arr["requests"])
        dues = np.full(n, -math.inf)
    elif arr["kind"] == "poisson":
        rate, lead = float(arr["rate_rps"]), float(arr.get("lead_s", 0.0))
        n = int(math.ceil(rate * (lead + seconds)))
        dues = np.cumsum(order.permutation(exponential_set(n, rate))) - lead
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    plens = order.permutation(lognormal_set(n, traffic["prompt_len"]))
    outs = order.permutation(lognormal_set(n, traffic["output_len"]))
    return [Planned(i, float(dues[i]),
                    rng.integers(0, vocab, int(plens[i])).astype(np.int32),
                    int(outs[i])) for i in range(n)]


@dataclasses.dataclass
class Tracked:
    """The harness's view of one submitted request."""
    planned: Planned
    req: object                      # repro.serve.engine.Request
    due: float                       # absolute host time it was due
    submitted: float                 # absolute host time submit() ran
    times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRecord:
    """What one ``step()`` did, seen from the requests: decode tokens and
    the live context each decoded slot attended, and prompts completed."""
    t0: float
    t1: float
    decoded: int
    ctx: List[int]
    prefilled: int
    prompt_tokens: int


class Driver:
    """Feeds planned requests to an engine and records what came back."""

    def __init__(self, engine, make_request: Callable, annotate=None,
                 clock=time.perf_counter):
        self.eng = engine
        self.make_request = make_request
        self.clock = clock
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.pending: Deque[Tuple[float, Planned]] = collections.deque()
        self.flight: List[Tracked] = []
        self.tracked: List[Tracked] = []
        self.steps: List[StepRecord] = []

    def schedule(self, planned: List[Planned], t_zero: float) -> None:
        """Queue requests due at ``t_zero + p.due`` (a backlog is
        submitted right away)."""
        for p in sorted(planned, key=lambda p: p.due):
            due = t_zero + p.due if math.isfinite(p.due) else -math.inf
            self.pending.append((due, p))

    def submit_due(self, now: float) -> None:
        while self.pending and self.pending[0][0] <= now:
            due, p = self.pending.popleft()
            req = self.make_request(p)
            with self.annotate("bench.submit"):
                t = self.clock()
                self.eng.submit(req)
            tr = Tracked(p, req, due if math.isfinite(due) else t, t)
            self.tracked.append(tr)
            self.flight.append(tr)

    def step(self) -> int:
        before = [len(tr.req.out) for tr in self.flight]
        t0 = self.clock()
        with self.annotate("bench.step"):
            n = self.eng.step()
        t1 = self.clock()
        with self.annotate("bench.record"):
            ctx, prefilled, ptoks, keep = [], 0, 0, []
            for tr, b in zip(self.flight, before):
                got = len(tr.req.out) - b
                tr.times.extend([t1] * got)
                first = b == 0 and got > 0
                if first:
                    prefilled += 1
                    ptoks += len(tr.req.prompt)
                if got - first == 1:
                    # decoded this step: attended prompt + tokens fed so far
                    ctx.append(len(tr.req.prompt) + len(tr.req.out) - 1)
                if not tr.req.done:
                    keep.append(tr)
            self.flight = keep
            self.steps.append(StepRecord(t0, t1, len(ctx), ctx, prefilled,
                                         ptoks))
        return n

    def run_until(self, t_end: float) -> None:
        """Submit and step until the host clock passes ``t_end``. With
        nothing to do the loop sleeps until the next arrival."""
        while True:
            now = self.clock()
            if now >= t_end:
                return
            self.submit_due(now)
            if self.step() == 0:
                nxt = self.pending[0][0] if self.pending else t_end
                with self.annotate("bench.sleep"):
                    time.sleep(max(0.0, min(nxt, t_end) - self.clock()))

    def decoding(self) -> int:
        """Requests that have their first token and are not done."""
        return sum(1 for tr in self.flight if tr.req.out)

    def fill(self, slots: int, patience: int) -> None:
        """Step until ``slots`` requests decode, or ``patience`` steps in a
        row start no request (the KV pool admits no more of the backlog;
        a chunked prompt takes several steps to start)."""
        idle = 0
        while self.decoding() < slots and idle < patience:
            started = sum(1 for tr in self.tracked if tr.times)
            self.step()
            idle = (idle + 1 if sum(1 for tr in self.tracked if tr.times)
                    == started else 0)

    def lateness(self, lo: float, hi: float) -> List[float]:
        """Seconds between due and submit() of requests due in [lo, hi)."""
        return [tr.submitted - tr.due for tr in self.tracked
                if lo <= tr.due < hi]
