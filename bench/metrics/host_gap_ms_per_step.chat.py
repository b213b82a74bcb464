"""Device idle milliseconds per engine step: the idle time of each chip in
use that falls inside the engine's ``engine.step`` spans (bench/
phase_reduce.py), mean over the chips, over the ``engine.step`` spans
that ended inside the window. The host time in ``step()`` that the chip
waits through: scheduling, prefill and decode dispatch, the wait for the
sampled tokens, the host's walk over the slots.

A program that keeps no engine spans (one older than them) is read by
the benchmark's own ``bench.step`` span, which wraps the same call."""
import phase_reduce


def read(ctx):
    return phase_reduce.host_gap_metric(ctx)
