"""The traffic generator is seeded and deterministic, and every seed gets
the same work in the same order."""
import math

import numpy as np
import pytest

import loadgen

BACKLOG = {"arrival": {"kind": "backlog", "requests": 64},
           "prompt_len": {"median": 128, "sigma": 0.6, "min": 32, "max": 512},
           "output_len": {"median": 512, "sigma": 0.5, "min": 128,
                          "max": 1024}}
POISSON = {"arrival": {"kind": "poisson", "rate_rps": 5.0, "lead_s": 2.0},
           "prompt_len": {"median": 768, "sigma": 0.6, "min": 128,
                          "max": 1792},
           "output_len": {"median": 48, "sigma": 0.8, "min": 8, "max": 192}}


def _key(plan):
    return [(p.due, p.prompt.tolist(), p.max_new) for p in plan]


@pytest.mark.parametrize("traffic", [BACKLOG, POISSON])
def test_same_seed_same_plan(traffic):
    a = loadgen.plan(traffic, 2**31 + 77, 64000, 20.0)
    b = loadgen.plan(traffic, 2**31 + 77, 64000, 20.0)
    assert _key(a) == _key(b)


@pytest.mark.parametrize("traffic", [BACKLOG, POISSON])
def test_seeds_share_sizes_and_order_not_tokens(traffic):
    a = loadgen.plan(traffic, 1, 64000, 20.0)
    b = loadgen.plan(traffic, 2**31 + 3, 64000, 20.0)
    assert [(p.due, len(p.prompt), p.max_new) for p in a] == \
        [(p.due, len(p.prompt), p.max_new) for p in b]
    assert a[0].prompt.tolist() != b[0].prompt.tolist()


@pytest.mark.parametrize("traffic", [BACKLOG, POISSON])
def test_fixed_order_is_shuffled(traffic):
    # the one order mixes long and short requests; it is not sorted
    plens = [len(p.prompt) for p in loadgen.plan(traffic, 1, 64000, 20.0)]
    assert plens != sorted(plens) and plens != sorted(plens, reverse=True)


def test_lengths_follow_the_clipped_lognormal():
    x = loadgen.lognormal_set(1001, BACKLOG["prompt_len"])
    assert x.min() == 32 and x.max() == 512
    assert np.median(x) == 128


def test_poisson_covers_lead_and_window():
    plan = loadgen.plan(POISSON, 3, 64000, 20.0)
    assert len(plan) == math.ceil(5.0 * 22.0)
    assert plan[0].due > -2.0
    dues = [p.due for p in plan]
    assert dues == sorted(dues)


class _Req:
    def __init__(self, prompt, max_new):
        self.prompt, self.max_new_tokens = prompt, max_new
        self.out, self.done, self.error = [], False, None


class _Engine:
    """One request prefills per step (first token), every admitted one
    decodes one token per step, as ServeEngine.step does."""

    def __init__(self):
        self.queue, self.active = [], []

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        if self.queue:
            r = self.queue.pop(0)
            r.out.append(1)
            self.active.append(r)
        for r in list(self.active):
            r.out.append(2)
            if len(r.out) >= r.max_new_tokens:
                r.done = True
                self.active.remove(r)
        return len(self.active) + 1


def test_driver_stamps_tokens_and_contexts():
    clock = iter(range(1000)).__next__
    eng = _Engine()
    drv = loadgen.Driver(eng, lambda p: _Req(p.prompt, p.max_new),
                         clock=lambda: float(clock()))
    plan = [loadgen.Planned(0, -math.inf, np.zeros(5, np.int32), 3)]
    drv.schedule(plan, 0.0)
    drv.submit_due(0.0)
    drv.step()
    drv.step()
    tr = drv.tracked[0]
    assert tr.req.out == [1, 2, 2] and tr.req.done
    # both tokens of the first step carry the time that step returned
    assert tr.times[0] == tr.times[1] < tr.times[2]
    first, second = drv.steps
    assert (first.prefilled, first.prompt_tokens, first.decoded) == (1, 5, 1)
    # decode attended the prompt and every token fed before it
    assert first.ctx == [6] and second.ctx == [7]
    assert drv.flight == []


class _ChunkedEngine(_Engine):
    """A prompt takes three steps to start, as a prompt of three chunks
    does."""

    def __init__(self):
        super().__init__()
        self.n = 0

    def step(self):
        self.n += 1
        for r in self.active:
            r.out.append(2)
        if self.queue and self.n % 3 == 0:
            r = self.queue.pop(0)
            r.out.append(1)
            self.active.append(r)
        return 1


@pytest.mark.parametrize("patience,decoding", [(1, 0), (3, 2)])
def test_fill_waits_for_chunked_starts(patience, decoding):
    eng = _ChunkedEngine()
    drv = loadgen.Driver(eng, lambda p: _Req(p.prompt, p.max_new))
    drv.schedule([loadgen.Planned(i, -math.inf, np.zeros(5, np.int32), 100)
                  for i in range(4)], 0.0)
    drv.submit_due(0.0)
    drv.fill(2, patience)
    assert drv.decoding() == decoding
