"""The engine's phase spans on the profiler's clock (repro.obs ``phase``).

* under ``jax.profiler.trace`` a paged engine's steps leave ``engine.*``
  host events in the ``.xplane.pb``, each phase nested inside its
  ``engine.step``, and the tokens equal an unobserved engine's;
* the handle's ``spans`` log holds the same phases on the host clock;
* the disabled handle (``obs=None``) builds no annotation at all.
"""
import glob
import os

import jax
import numpy as np
import pytest

from repro import configs
from repro import obs as obs_lib
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine

PHASES = ("step", "admit", "plan", "prefill_dispatch", "dispatch",
          "host_sync", "sample_copy")


def _engine(obs=None):
    cfg = configs.get_smoke("yi-9b", act_impl="exact")
    params = tf.init(cfg, jax.random.PRNGKey(7))
    return cfg, ServeEngine(cfg, params, slots=2, max_len=64,
                            kv_impl="paged", obs=obs)


def _three_steps(cfg, eng):
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 5)
                    .astype(np.int32), max_new_tokens=4) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    return [list(r.out) for r in reqs]


def _engine_events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    out.append((ev.name.split("#", 1)[0], ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_phases_reach_the_profiler_trace_nested_in_step(tmp_path):
    cfg, eng_off = _engine()
    toks_off = _three_steps(cfg, eng_off)
    cfg, eng = _engine(obs_lib.Observability())
    with jax.profiler.trace(str(tmp_path)):
        toks_on = _three_steps(cfg, eng)
    assert toks_on == toks_off

    evs = _engine_events(str(tmp_path))
    names = {n for n, _, _ in evs}
    assert {f"engine.{p}" for p in PHASES} <= names, names
    steps = [(s, e) for n, s, e in evs if n == "engine.step"]
    assert len(steps) == 3
    for n, s, e in evs:
        if n != "engine.step":
            assert any(a <= s and e <= b for a, b in steps), (n, s, e)
    # plan and the prefill dispatches lie inside admit
    admits = [(s, e) for n, s, e in evs if n == "engine.admit"]
    for n, s, e in evs:
        if n in ("engine.plan", "engine.prefill_dispatch"):
            assert any(a <= s and e <= b for a, b in admits), (n, s, e)


def test_span_log_holds_each_phase_on_the_host_clock():
    ob = obs_lib.Observability()
    cfg, eng = _engine(ob)
    _three_steps(cfg, eng)
    log = list(ob.spans)
    assert {n for n, _, _ in log} == set(PHASES)
    assert sum(1 for n, _, _ in log if n == "step") == 3
    assert all(0.0 <= t0 <= t1 <= ob.now() for _, t0, t1 in log)
    # a phase closes before its step does: the log is in order of ends
    ends = [t1 for _, _, t1 in log]
    assert ends == sorted(ends)
    hist = ob.metrics.get("engine.phase.step_ms")
    assert hist.count == 3


def test_disabled_handle_builds_no_annotation(monkeypatch):
    built = []

    class Counting(obs_lib.TraceAnnotation):
        def __init__(self, *a, **k):
            built.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(obs_lib, "TraceAnnotation", Counting)
    cfg, eng = _engine()
    _three_steps(cfg, eng)
    assert built == []
    assert obs_lib.NULL.spans == ()
    cfg, eng = _engine(obs_lib.Observability())
    _three_steps(cfg, eng)
    assert {a[0] for a in built} == {f"engine.{p}" for p in PHASES}


@pytest.mark.parametrize("args", [{}, {"rows": 2, "tokens": 32}])
def test_phase_annotate_is_free_on_the_null_handle(args):
    with obs_lib.NULL.phase("prefill_dispatch") as span:
        span.annotate(**args)
    ob = obs_lib.Observability()
    with ob.phase("prefill_dispatch") as span:
        span.annotate(**args)
    assert [n for n, _, _ in ob.spans] == ["prefill_dispatch"]
