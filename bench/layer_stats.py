"""Quantities the metric readers share: the records of the measured
window, and program and kernel time from the trace.

Each reader in ``bench/metrics/`` gets the run's context: ``drv`` (the
load driver, with its per-request and per-step records), ``t_open`` and
``t_close`` (the window on the host clock), ``m`` (the configuration's
sizes), ``tp``, ``chips``, ``peaks``, ``costs`` (the configuration's
counts module, bench/costs.py for the GQA configurations), and in a
traced run ``trace`` (see trace_reduce.py), ``planes`` (the device planes
of the chips in use) and ``obs`` (the engine's observability handle,
attached at the window's start). A reader returns None when it finds
nothing to read.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

import trace_reduce

#: programs of the serving engine, found by their jit function names
DECODE_PROGRAM = r"^jit_decode\b|jit\(decode\)"
PREFILL_PROGRAM = r"^jit_prefill\b|jit\(prefill\)"


def window_s(ctx) -> float:
    return ctx.t_close - ctx.t_open


def steps(ctx):
    """Step records whose step ended inside the window."""
    return [s for s in ctx.drv.steps if ctx.t_open <= s.t1 <= ctx.t_close]


def percentile(values: List[float], q: float) -> Optional[float]:
    """numpy's linear percentile, or None for no samples."""
    return float(np.percentile(values, q)) if values else None


def module_ms(ctx, pattern: str):
    """(summed device ms, executions) of the matching program on the
    first chip in use, or None when the trace holds none."""
    evs = trace_reduce.modules_matching(ctx.trace, ctx.planes[0], pattern)
    if not evs:
        return None
    return sum(e[2] for e in evs) * 1e-6, len(evs)


def kernel_s(ctx, pattern: str) -> Optional[float]:
    """Summed device seconds of the matching kernel on the first chip."""
    evs = trace_reduce.ops_matching(ctx.trace, ctx.planes[0], pattern)
    if not evs:
        return None
    return sum(e[2] for e in evs) * 1e-9


def idle_share(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    lo, hi = trace_reduce.window(ctx.trace)
    busy = np.mean([trace_reduce.busy_ns(ctx.trace, p) for p in ctx.planes])
    return 100.0 * (1.0 - busy / (hi - lo))


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of every token processed in the window, prompt and
    output (the configuration's counts module, ``ctx.costs``), over
    window x chips x bf16 peak, in %."""
    costs = ctx.costs
    flops = 0.0
    for s in steps(ctx):
        flops += sum(costs.token_flops(ctx.m, c, lm_head=True) for c in s.ctx)
    for tr in ctx.drv.tracked:
        if tr.times and ctx.t_open <= tr.times[0] <= ctx.t_close:
            flops += costs.prompt_flops(ctx.m, len(tr.req.prompt))
    if flops == 0:
        return None
    peak = ctx.peaks["bf16_flops_s"] * ctx.chips
    return 100.0 * flops / (window_s(ctx) * peak)


def phase_ms(ctx, name: str):
    """(sum ms, count) of the engine's host phase span ``name``."""
    if ctx.obs is None:
        return None
    snap = ctx.obs.metrics.snapshot().get(f"engine.phase.{name}_ms")
    if not snap or not snap.get("count"):
        return None
    return snap["sum"], snap["count"]
