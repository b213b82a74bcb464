"""Serving observability: metrics registry, request-lifecycle tracing, and
fixed-point saturation accounting — all host-side, all off by default.

``Observability`` is the handle an engine (or benchmark) is constructed
with. It bundles

* ``metrics`` — a :class:`repro.obs.metrics.MetricsRegistry` of counters /
  gauges / log-bucketed histograms with p50/p90/p99 readout and JSON
  snapshot export;
* ``trace`` — an optional :class:`repro.obs.trace.TraceRecorder` emitting
  Chrome-trace (Perfetto-loadable) request-lifecycle and engine-phase
  events against the same monotonic clock;
* ``phase(name)`` — a context manager timing one engine phase into the
  histogram ``engine.phase.<name>_ms``, an "X" span on the engine track
  (with tracing on), the handle's ``spans`` log, and a
  ``jax.profiler.TraceAnnotation`` named ``engine.<name>``, so that a
  profiler trace (``jax.profiler.trace(dir)``) shows the engine's phases
  on the same clock as the device's ops, nested as the calls nest.

``NULL`` is the disabled singleton: identical surface, no clock reads, no
annotation, no allocation — instrumented code writes through it
unconditionally, which is what keeps observability *off-by-default-cheap*
and the emitted tokens bit-identical with observability on or off
(nothing here touches a traced value; see tests/test_obs.py for the
enforced contract).

Saturation accounting closes the loop with the paper's overflow-free-Q2.14
claim: :func:`repro.core.fixed_point.set_saturation_observer` feeds every
*eager* quantize clip into the registry (tracer inputs are skipped — no
metric state is ever traced into a jitted function), and
:func:`saturation_audit` sweeps named tensors across the
``FORMAT_PROFILES`` ladder to report would-clip counts per format.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, Dict, Optional

from jax.profiler import TraceAnnotation

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               NULL_REGISTRY)
from repro.obs.trace import TraceRecorder, validate_chrome_trace

__all__ = [
    "Observability", "NULL", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "NULL_REGISTRY", "TraceRecorder",
    "validate_chrome_trace", "observe_saturation", "saturation_audit",
]


class _PhaseSpan:
    """Times one engine phase into a histogram, the handle's span log,
    (optionally) the Chrome trace, and a profiler annotation. Re-entered
    per use; allocation-free reuse is not worth the aliasing risk at one
    object per phase per step."""

    __slots__ = ("_obs", "_name", "_hist", "_t0", "_ann")

    def __init__(self, obs: "Observability", name: str, hist):
        self._obs = obs
        self._name = name
        self._hist = hist

    def __enter__(self):
        # a TraceMe: close to free when no profiler is collecting
        self._ann = TraceAnnotation(f"engine.{self._name}")
        self._ann.__enter__()
        self._t0 = self._obs.now()
        return self

    def annotate(self, **args) -> None:
        """Arguments of the profiler span (shown beside its name)."""
        self._ann.set_metadata(**args)

    def __exit__(self, exc_type, exc, tb):
        t1 = self._obs.now()
        self._ann.__exit__(exc_type, exc, tb)
        dt = t1 - self._t0
        self._hist.observe(dt * 1e3)
        self._obs.spans.append((self._name, self._t0, t1))
        if self._obs.trace is not None:
            self._obs.trace.complete(self._name, self._t0 * 1e6, dt * 1e6)
        return False


#: phase spans an enabled handle keeps in ``Observability.spans``, newest
#: last: about 18,000 serving steps at seven phases a step
SPAN_LOG = 1 << 17


class Observability:
    """Live observability handle: a metrics registry + optional tracer
    sharing one clock origin (``now()`` is seconds since construction)."""

    enabled = True

    def __init__(self, *, trace: bool = False,
                 process_name: str = "serve-engine"):
        self._clock = time.perf_counter
        self._t0 = self._clock()
        self.metrics = MetricsRegistry()
        self.trace: Optional[TraceRecorder] = (
            TraceRecorder(process_name) if trace else None)
        #: the newest ``SPAN_LOG`` phase spans, ``(name, start, end)`` in
        #: seconds on this handle's clock (``epoch`` + t is the absolute
        #: ``time.perf_counter()``): what a reader lays over a device trace
        self.spans: collections.deque = collections.deque(maxlen=SPAN_LOG)

    @property
    def epoch(self) -> float:
        """Absolute ``time.perf_counter()`` value of this handle's clock
        origin. The engine stamps request lifecycle times with the absolute
        clock (so stamps survive a later attach_obs); ``t_abs - epoch``
        converts one to this handle's trace timeline."""
        return self._t0

    def now(self) -> float:
        """Seconds since this handle was constructed (monotonic)."""
        return self._clock() - self._t0

    def now_us(self) -> float:
        return self.now() * 1e6

    def phase(self, name: str) -> _PhaseSpan:
        """``with obs.phase("dispatch"): ...`` — records the wall time into
        histogram ``engine.phase.<name>_ms``, ``spans``, an engine-track
        span, and the profiler span ``engine.<name>``."""
        return _PhaseSpan(
            self, name,
            self.metrics.histogram(f"engine.phase.{name}_ms", unit="ms"))

    def request_event(self, stage: str, rid: int,
                      args: Optional[dict] = None) -> None:
        """Lifecycle instant on the request's own trace track (no-op
        without tracing; the metric side of lifecycle events lives in the
        engine's histograms)."""
        if self.trace is not None:
            self.trace.instant(stage, self.now_us(), track=f"req {rid}",
                               args=args)

    def request_span(self, stage: str, rid: int, t0_s: float,
                     args: Optional[dict] = None) -> None:
        """Lifecycle span [t0_s, now] on the request's trace track."""
        if self.trace is not None:
            self.trace.complete(stage, t0_s * 1e6,
                                (self.now() - t0_s) * 1e6,
                                track=f"req {rid}", args=args)


class _NullPhase:
    def __enter__(self):
        return self

    def annotate(self, **args) -> None:
        pass

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_PHASE = _NullPhase()


class _NullObservability:
    """Disabled observability: same surface, zero work. ``metrics`` is the
    shared null registry, ``trace`` is None, clocks read 0.0."""

    enabled = False
    metrics = NULL_REGISTRY
    trace = None
    spans = ()
    epoch = 0.0

    def now(self) -> float:
        return 0.0

    def now_us(self) -> float:
        return 0.0

    def phase(self, name: str) -> _NullPhase:
        return _NULL_PHASE

    def request_event(self, stage: str, rid: int,
                      args: Optional[dict] = None) -> None:
        pass

    def request_span(self, stage: str, rid: int, t0_s: float,
                     args: Optional[dict] = None) -> None:
        pass


#: Shared disabled handle; `ServeEngine(obs=None)` resolves to this.
NULL = _NullObservability()


# --------------------------------------------------------------------------
# Fixed-point saturation accounting
# --------------------------------------------------------------------------
@contextlib.contextmanager
def observe_saturation(registry: MetricsRegistry):
    """While active, every *eager* ``fixed_point.quantize`` call feeds its
    clip count into ``registry``:

        fixed_point.saturation.clips{fmt=Q2.14}     (clipped elements)
        fixed_point.saturation.elements{fmt=Q2.14}  (elements quantized)

    Calls inside a jit trace are ignored by construction (the observer
    never sees tracers — see fixed_point._note_saturation), so attaching
    this changes neither compile counts nor any traced computation. The
    previous observer is restored on exit (scopes nest)."""
    from repro.core import fixed_point as fp

    def _observer(fmt: str, clipped: int, total: int) -> None:
        registry.counter(f"fixed_point.saturation.clips{{fmt={fmt}}}",
                         unit="elements").inc(clipped)
        registry.counter(f"fixed_point.saturation.elements{{fmt={fmt}}}",
                         unit="elements").inc(total)

    prev = fp.set_saturation_observer(_observer)
    try:
        yield registry
    finally:
        fp.set_saturation_observer(prev)


def saturation_audit(tensors: Dict[str, Any],
                     registry: Optional[MetricsRegistry] = None,
                     profiles: Optional[Dict[str, Any]] = None) -> dict:
    """Would-this-clip sweep: quantize every named tensor into every format
    profile's storage format (eagerly, on host) and report the clip counts

        {profile: {tensor: {"clipped": int, "total": int, "frac": float}}}

    — the software analogue of the paper's overflow-free-Q2.14 argument,
    and the telemetry ROADMAP item 5 (quantized KV formats) selects on.
    Counts are also fed into ``registry`` when one is given.
    """
    import numpy as np

    from repro.core import fixed_point as fp

    if profiles is None:
        from repro.cordic_engine.functions import FORMAT_PROFILES
        profiles = FORMAT_PROFILES

    out: Dict[str, Dict[str, dict]] = {}
    for pname, prof in sorted(profiles.items()):
        fmt = prof.cfg.fmt
        per = out[pname] = {}
        for tname, arr in sorted(tensors.items()):
            x = np.asarray(arr, np.float64).ravel()
            scaled = np.round(x * float(fmt.scale))
            clipped = int(np.sum((scaled > fmt.max_int)
                                 | (scaled < fmt.min_int)))
            total = int(x.size)
            per[tname] = {"clipped": clipped, "total": total,
                          "frac": clipped / total if total else 0.0}
            if registry is not None:
                registry.counter(
                    f"fixed_point.saturation.clips{{fmt={fmt}}}",
                    unit="elements").inc(clipped)
                registry.counter(
                    f"fixed_point.saturation.elements{{fmt={fmt}}}",
                    unit="elements").inc(total)
    return out
