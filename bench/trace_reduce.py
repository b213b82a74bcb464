"""Reduction of a profiler trace to device busy time, idle gaps, program
and kernel time.

A trace is first read into a plain structure, which is also what the
recorded fixtures under ``bench/fixtures/`` hold:

    {"devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns, detail]],
                                   "modules": [[name, start_ns, dur_ns]]}},
     "host": [[name, start_ns, dur_ns]]}

``ops`` are the events of the device's "XLA Ops" line (one per operation
or kernel that ran), ``modules`` those of its "XLA Modules" line (one per
program execution), and ``host`` the benchmark's own
``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``), which
share the profiler's clock. An op's name is its whole HLO instruction
(``%fusion.12 = bf16[16,11008]{...} fusion(...)``); ``detail`` is free
text matched alongside it (empty in traces read from a TPU, whose op
stats are offsets and durations only). On TPU v5e with JAX 0.9 a Pallas
kernel appears as a ``tpu_custom_call`` with no kernel name, so the
readers find kernels by their signature.

Busy time is the union of op intervals, so overlapping or nested events
count once; the idle share is 1 - busy / window. Kernel and program time
are sums of event durations, matched by a regular expression.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PREFIX = "bench."


def load_xplane(path: str) -> dict:
    """Read one ``.xplane.pb`` into the plain structure above, keeping only
    device events that overlap the host span ``bench.window``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host: List[list] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIX):
                    host.append([ev.name, ev.start_ns, ev.duration_ns])
    win = [h for h in host if h[0] == "bench.window"]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    lo, hi = win[0][1], win[0][1] + win[0][2]
    devices: Dict[str, dict] = {}
    names: Dict[str, str] = {}      # one string per distinct op text
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s, d = ev.start_ns, ev.duration_ns
                if s + d < lo or s > hi:
                    continue
                name = names.setdefault(ev.name, ev.name)
                if line.name == "XLA Ops":
                    ops.append([name, s, d, ""])
                else:
                    mods.append([name, s, d])
        devices[plane.name] = {"ops": ops, "modules": mods}
    if not devices:
        raise RuntimeError("the trace holds no /device:TPU:N plane")
    return {"devices": devices, "host": host}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def window(trace: dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the measured window, from ``bench.window``."""
    for name, s, d in trace["host"]:
        if name == "bench.window":
            return s, s + d
    raise RuntimeError("the trace holds no bench.window span")


def device_planes(trace: dict) -> List[str]:
    return sorted(trace["devices"],
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def _clipped(intervals: Iterable[Tuple[float, float]], lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_intervals(trace: dict, plane: str) -> List[Tuple[float, float]]:
    """Union of the plane's op intervals inside the window."""
    lo, hi = window(trace)
    ops = trace["devices"][plane]["ops"]
    return merge(_clipped(((s, s + d) for _, s, d, _ in ops), lo, hi))


def busy_ns(trace: dict, plane: str) -> float:
    return sum(e - s for s, e in busy_intervals(trace, plane))


def idle_gaps(trace: dict, plane: str) -> List[Tuple[float, float]]:
    lo, hi = window(trace)
    gaps, t = [], lo
    for s, e in busy_intervals(trace, plane):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_spans(trace: dict) -> List[Tuple[float, float, str]]:
    """The benchmark's host spans other than the window, sorted by start.
    The driver's spans (submit, step, record, sleep) do not nest."""
    return sorted((s, s + d, n) for n, s, d in trace["host"]
                  if n != "bench.window")


def host_label(spans: List[Tuple[float, float, str]], t: float) -> str:
    """The host span (from ``host_spans``) covering t, or "none"."""
    i = bisect.bisect_right(spans, (t, float("inf"), "")) - 1
    if i >= 0 and spans[i][0] <= t <= spans[i][1]:
        return spans[i][2]
    return "none"


def idle_by_host(trace: dict, plane: str) -> Dict[str, float]:
    """Idle nanoseconds of ``plane`` grouped by what the host was doing at
    the middle of each gap."""
    spans = host_spans(trace)
    out: Dict[str, float] = {}
    for s, e in idle_gaps(trace, plane):
        label = host_label(spans, (s + e) / 2)
        out[label] = out.get(label, 0.0) + (e - s)
    return out


def _in_window(trace: dict, events):
    lo, hi = window(trace)
    return [ev for ev in events if lo <= ev[1] < hi]


def ops_matching(trace: dict, plane: str, pattern: str) -> List[list]:
    """Op events starting inside the window whose name or detail matches."""
    rx = re.compile(pattern)
    seen: Dict[Tuple[str, str], bool] = {}

    def hit(ev) -> bool:
        key = (ev[0], ev[3])
        if key not in seen:
            seen[key] = bool(rx.search(ev[0]) or rx.search(ev[3]))
        return seen[key]

    return [ev for ev in _in_window(trace, trace["devices"][plane]["ops"])
            if hit(ev)]


def modules_matching(trace: dict, plane: str, pattern: str) -> List[list]:
    rx = re.compile(pattern)
    return [ev for ev in _in_window(trace, trace["devices"][plane]["modules"])
            if rx.search(ev[0])]


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[16,11008]{...} fusion(...)`` -> ``fusion
    %fusion.12 bf16[16,11008]``: the opcode, the instruction and its
    result shape (``tuple`` for a tuple), without layouts and operands."""
    m = re.match(r"^(%\S+) = (.*)$", op, re.S)
    if not m:
        return op[:120]
    inst, rest = m.groups()
    if rest.startswith("("):
        k = re.search(r"\) ([a-z][\w\-]*)\(", rest)
        shape, kind = "tuple", (k.group(1) if k else "?")
    else:
        k = re.match(r"([^\s{]+)\S* ([a-z][\w\-]*)\(", rest)
        if not k:
            return op[:120]
        shape, kind = k.groups()
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target:
        kind += f"[{target.group(1)}]"
    return f"{kind} {inst} {shape}"[:160]


def self_time_by_name(trace: dict, plane: str) -> Dict[str, float]:
    """Nanoseconds per op, less the time of the ops nested inside it (a
    while loop's event spans its body's ops), keyed by ``short_name``."""
    evs = sorted(_in_window(trace, trace["devices"][plane]["ops"]),
                 key=lambda e: (e[1], -e[2]))
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name, self]

    def close(top):
        out[top[1]] = out.get(top[1], 0.0) + top[2]

    for name, s, d, _ in evs:
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, short_name(name), float(d)])
    while stack:
        close(stack.pop())
    return out


def summary(trace: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: device 0's ten longest ops by
    summed self time and its idle time by what the host was doing, in s."""
    plane = device_planes(trace)[0]
    ops = sorted(self_time_by_name(trace, plane).items(),
                 key=lambda kv: -kv[1])
    gaps = sorted(idle_by_host(trace, plane).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, v * 1e-9] for n, v in ops[:top]],
            "idle_gaps": [[n, v * 1e-9] for n, v in gaps[:top]]}
