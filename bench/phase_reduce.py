"""The engine's own phase spans and the collectives in a profiler trace.

This extends the plain structure of ``bench/trace_reduce.py`` with one
key, which older traces and fixtures lack:

    "program": [[name, start_ns, dur_ns]]

the serving engine's phase spans (``repro.obs`` ``phase()``: names
starting ``engine.``, such as ``engine.step``, ``engine.admit``,
``engine.plan``, ``engine.prefill_dispatch``, ``engine.dispatch``,
``engine.host_sync``, ``engine.sample_copy``). The engine writes them as
``jax.profiler.TraceAnnotation`` spans, on the profiler's clock, nested as
the calls nest. ``load_xplane`` reads them from an ``.xplane.pb``.

A benchmark run reduces its trace before the readers run and keeps only
the benchmark's own ``bench.*`` host spans, so ``program_spans`` also
takes the same spans from the engine's observability handle
(``Observability.spans``, host clock) and lays them over the trace: each
benchmark ``bench.step`` span of the window and the load generator's
record of the same step give the offset between the two clocks.

Idle time is split at span boundaries: each piece of an idle gap goes to
the innermost ``engine.*`` span covering it, else to the ``bench.*`` span
covering it, else to ``none``. Collectives are found by HLO opcode in the
op text (all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute, and their ``-start``/``-done`` halves).
"""
from __future__ import annotations

import json
import re
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce

PROGRAM_PREFIX = "engine."
STEP_SPAN = "engine.step"
#: the fused SwiGLU kernel by signature, for programs whose kernels carry
#: no name: two bf16 (rows, 1024) tiles in, a third out (kernels/ops.py)
SILU_MUL_SIGNATURE = (r"^%\S+ = bf16\[(\d+),1024\]\S* custom-call\("
                      r"bf16\[\1,1024\]\S* %\S+, bf16\[\1,1024\]\S* %\S+\), "
                      r'custom_call_target="tpu_custom_call"')
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start|-done)?$")

Span = Tuple[float, float, str]             # (start_ns, end_ns, name)


def load_xplane(path: str) -> dict:
    """``trace_reduce.load_xplane``, plus the engine's spans under
    ``program``. A span's arguments (``name#key=value#`` in a TraceMe)
    are dropped from its name."""
    from jax.profiler import ProfileData

    trace = trace_reduce.load_xplane(path)
    prog: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    prog.append([ev.name.split("#", 1)[0], ev.start_ns,
                                 ev.duration_ns])
    trace["program"] = prog
    return trace


def clock_offset_ns(ctx) -> float:
    """Trace time minus host ``time.perf_counter()`` time, in ns: the
    median over the window's steps of the start of each ``bench.step``
    span against the load generator's record of the same step, or, where
    the two do not pair up, the same from ``bench.window`` and the
    window's open."""
    traced = sorted(s for n, s, _ in ctx.trace["host"] if n == "bench.step")
    steps = [s.t0 for s in ctx.drv.steps if s.t0 >= ctx.t_open]
    if traced and len(traced) == len(steps):
        return statistics.median(a - b * 1e9 for a, b in zip(traced, steps))
    lo, _ = trace_reduce.window(ctx.trace)
    return lo - ctx.t_open * 1e9


def program_spans(ctx) -> Optional[List[list]]:
    """The engine's spans as ``[[name, start_ns, dur_ns]]`` on the trace's
    clock: the trace's own ``program`` spans where it keeps them, else the
    engine handle's span log laid over the trace. None for a program
    that keeps neither."""
    if ctx.trace.get("program"):
        return ctx.trace["program"]
    log = getattr(ctx.obs, "spans", None)
    if not log:
        return None
    off = clock_offset_ns(ctx) + ctx.obs.epoch * 1e9
    return [[PROGRAM_PREFIX + n, t0 * 1e9 + off, (t1 - t0) * 1e9]
            for n, t0, t1 in log]


def _as_spans(events) -> List[Span]:
    return sorted((s, s + d, n) for n, s, d in events)


class _Sweep:
    """The spans (sorted by start) that cover a point moving forward."""

    def __init__(self, spans: Sequence[Span]):
        self.spans, self.next, self.active = spans, 0, []

    def at(self, t: float) -> List[Span]:
        while self.next < len(self.spans) and self.spans[self.next][0] <= t:
            self.active.append(self.spans[self.next])
            self.next += 1
        self.active = [sp for sp in self.active if sp[1] > t]
        return self.active


def _labelled(lo: float, hi: float, inner: Sequence[Span],
              outer: Sequence[Span]) -> List[Span]:
    """[lo, hi] cut at every span boundary into (start, end, label)
    pieces: the innermost ``inner`` span covering a piece (the one that
    started last), else the ``outer`` span covering it, else ``none``."""
    cuts = {lo, hi}
    for s, e, _ in list(inner) + list(outer):
        cuts.update(t for t in (s, e) if lo < t < hi)
    pts = sorted(cuts)
    inner_at, outer_at = _Sweep(inner), _Sweep(outer)
    out: List[Span] = []
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        cover = inner_at.at(mid) or outer_at.at(mid)
        label = max(cover, key=lambda sp: (sp[0], -sp[1]))[2] if cover \
            else "none"
        out.append((a, b, label))
    return out


def _overlaps(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> List[float]:
    """For each interval of ``a``, how much of it ``b`` covers; both are
    sorted lists of disjoint intervals."""
    out, i = [], 0
    for s, e in a:
        while i < len(b) and b[i][1] <= s:
            i += 1
        j, got = i, 0.0
        while j < len(b) and b[j][0] < e:
            got += max(0.0, min(b[j][1], e) - max(b[j][0], s))
            j += 1
        out.append(got)
    return out


def idle_by_phase(trace: dict, plane: str,
                  program: Optional[Sequence[list]] = None
                  ) -> Dict[str, float]:
    """Idle nanoseconds of ``plane`` by the innermost span covering each
    piece of each idle gap: an ``engine.*`` span, else a ``bench.*`` span,
    else ``none``. ``program`` defaults to the trace's ``program`` key."""
    lo, hi = trace_reduce.window(trace)
    if program is None:
        program = trace.get("program", [])
    segs = _labelled(lo, hi, _as_spans(program),
                     trace_reduce.host_spans(trace))
    idle = _overlaps([(s, e) for s, e, _ in segs],
                     trace_reduce.idle_gaps(trace, plane))
    out: Dict[str, float] = {}
    for (_, _, label), v in zip(segs, idle):
        if v > 0:
            out[label] = out.get(label, 0.0) + v
    return out


def phase_summary(trace: dict, program: Optional[Sequence[list]] = None,
                  top: int = 10) -> dict:
    """``idle_by_phase`` of the first device, in s, the largest ``top``."""
    plane = trace_reduce.device_planes(trace)[0]
    got = sorted(idle_by_phase(trace, plane, program).items(),
                 key=lambda kv: -kv[1])
    return {"idle_by_phase": [[n, v * 1e-9] for n, v in got[:top]]}


def covered_idle_ns(trace: dict, plane: str, spans: Sequence[Span]) -> float:
    """Idle nanoseconds of ``plane`` inside the union of ``spans``."""
    return sum(_overlaps(trace_reduce.idle_gaps(trace, plane),
                         trace_reduce.merge((s, e) for s, e, _ in spans)))


def host_gap_ms_per_step(trace: dict, planes: Sequence[str],
                         steps: Sequence[list]) -> Optional[float]:
    """Device idle time inside the step spans ``steps`` ([[name, start_ns,
    dur_ns]]), mean over ``planes``, over the step spans that ended
    inside the window, in ms."""
    lo, hi = trace_reduce.window(trace)
    spans = _as_spans(steps)
    n = sum(1 for s, e, _ in spans if lo <= e <= hi)
    if not n or not planes:
        return None
    idle = statistics.fmean(covered_idle_ns(trace, p, spans) for p in planes)
    return idle * 1e-6 / n


def opcode(op: str) -> str:
    """The HLO opcode of an op's text (``fusion``, ``all-reduce-start``)."""
    return trace_reduce.short_name(op).split(" ", 1)[0].split("[", 1)[0]


def leaf_ops(trace: dict, plane: str) -> List[list]:
    """The plane's op events inside the window that hold no other op
    event: a while loop's event, which spans its body's ops, is left out."""
    lo, hi = trace_reduce.window(trace)
    evs = sorted((ev for ev in trace["devices"][plane]["ops"]
                  if ev[1] + ev[2] > lo and ev[1] < hi),
                 key=lambda ev: (ev[1], -ev[2]))
    holds = [False] * len(evs)
    stack: List[int] = []
    for k, ev in enumerate(evs):
        end = ev[1] + ev[2]
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= ev[1]:
            stack.pop()
        # the nearest open event that holds this one whole (an event that
        # only overlaps it, as an async collective may, holds nothing)
        for i in reversed(stack):
            if evs[i][1] + evs[i][2] >= end:
                holds[i] = True
                break
        stack.append(k)
    return [ev for ev, h in zip(evs, holds) if not h]


def collective_only_ns(trace: dict, plane: str) -> float:
    """Nanoseconds of the window in which a collective op runs on
    ``plane`` and no other op does."""
    lo, hi = trace_reduce.window(trace)
    coll, other = [], []
    is_coll: Dict[str, bool] = {}          # one regex per distinct op text
    for name, s, d, _ in leaf_ops(trace, plane):
        if name not in is_coll:
            is_coll[name] = bool(COLLECTIVE.match(opcode(name)))
        (coll if is_coll[name] else other).append((s, s + d))
    coll = trace_reduce.merge(trace_reduce._clipped(coll, lo, hi))
    busy = trace_reduce.merge(trace_reduce._clipped(other, lo, hi))
    return sum(e - s for s, e in coll) - sum(_overlaps(coll, busy))


def collective_share(trace: dict, planes: Sequence[str]) -> Optional[float]:
    """``collective_only_ns`` over the window, mean over ``planes``, in %;
    None when no plane ran a collective in the window."""
    lo, hi = trace_reduce.window(trace)
    per = [collective_only_ns(trace, p) for p in planes]
    if not any(per):
        return None
    return 100.0 * statistics.fmean(per) / (hi - lo)


def host_gap_metric(ctx) -> Optional[float]:
    """``host_gap_ms_per_step`` of a run over its ``engine.step`` spans,
    logging the first chip's ``idle_by_phase`` beside it. A program that
    keeps no engine spans is read by the ``bench.step`` spans instead."""
    prog = program_spans(ctx)
    if prog:
        steps = [ev for ev in prog if ev[0] == STEP_SPAN]
        ctx.log("idle by phase, s (first chip): " + json.dumps(
            phase_summary(ctx.trace, prog)["idle_by_phase"]))
    else:
        steps = [ev for ev in ctx.trace["host"] if ev[0] == "bench.step"]
        ctx.log("the program keeps no engine spans: bench.step stands in "
                "for engine.step")
    return host_gap_ms_per_step(ctx.trace, ctx.planes, steps)


def kernel_share(ctx, name: str, signature: str) -> Optional[float]:
    """Summed device time of the kernel ``name`` (a Pallas kernel's
    ``name``, the instruction name of its custom call) over the first
    chip's busy time, in %; by ``signature`` where no op carries the
    name."""
    plane = ctx.planes[0]
    evs = trace_reduce.ops_matching(ctx.trace, plane,
                                    rf"^%{re.escape(name)}(\.\d+)? = ")
    if not evs:
        evs = trace_reduce.ops_matching(ctx.trace, plane, signature)
        ctx.log(f"no op is named {name}: found by signature")
    if not evs:
        return None
    k = sum(ev[2] for ev in evs)
    ctx.log(f"{name}: {k * 1e-9:.6f} s over {len(evs)} calls")
    return 100.0 * k / trace_reduce.busy_ns(ctx.trace, plane)
