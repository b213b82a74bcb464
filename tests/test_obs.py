"""Observability-layer tests (repro.obs + the instrumented ServeEngine):

* metrics registry — counter/gauge semantics, log-bucketed histogram
  quantiles against numpy percentiles, snapshot/JSON export;
* Chrome-trace recorder — schema validity of exported traces;
* request lifecycle — per-request event ordering invariants
  (enqueue <= admit <= prefill <= first_token <= token* <= finish);
* saturation accounting — the eager-quantize observer fires on a
  deliberately overflowing Q2.14 input, never fires inside a jit trace,
  and the FORMAT_PROFILES audit reports per-format clip counts;
* the no-interference contract — an engine run with observability (and
  tracing) enabled emits bit-identical tokens and *identical compile
  counts* to an untraced run, and KVPager feeds pool gauges/counters.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro import obs as obs_lib
from repro.core import fixed_point as fp
from repro.models import transformer as tf
from repro.obs.metrics import Histogram, MetricsRegistry, NULL_REGISTRY
from repro.obs.trace import TraceRecorder, validate_chrome_trace
from repro.serve import kv_pager as kvp
from repro.serve.engine import Request, ServeEngine


def _cfg():
    return configs.get_smoke("yi-9b", act_impl="exact")


def _requests(cfg, n, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(3, 9))
                                        ).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 500
    return [list(r.out) for r in reqs]


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------
def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c", unit="tok")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    for v in (3.0, 7.0, 2.0):
        g.set(v)
    assert g.last == 2.0 and g.peak == 7.0
    assert g.mean == pytest.approx(4.0)
    # get-or-create returns the same instance; type conflicts raise
    assert reg.counter("c") is c
    with pytest.raises(TypeError):
        reg.gauge("c")


@pytest.mark.parametrize("dist", ["uniform", "lognormal", "bimodal"])
def test_histogram_quantiles_match_numpy(dist):
    rng = np.random.default_rng(42)
    if dist == "uniform":
        xs = rng.uniform(0.5, 50.0, 5000)
    elif dist == "lognormal":
        xs = rng.lognormal(1.0, 1.5, 5000)
    else:
        # asymmetric split so no tested quantile sits exactly on the mode
        # boundary (where numpy interpolates *between* modes and no
        # histogram estimate can agree)
        xs = np.concatenate([rng.normal(2.0, 0.1, 2000),
                             rng.normal(200.0, 5.0, 3000)])
        xs = np.abs(xs)
    h = Histogram("h", growth=1.07)
    for x in xs:
        h.observe(float(x))
    assert h.count == len(xs)
    assert h.min == pytest.approx(xs.min())
    assert h.max == pytest.approx(xs.max())
    assert h.sum == pytest.approx(xs.sum(), rel=1e-9)
    for q in (0.50, 0.90, 0.99):
        exact = np.percentile(xs, q * 100)
        got = h.quantile(q)
        # log-bucket growth 1.07 bounds the relative error by ~sqrt(1.07)
        # (plus discreteness at the very tail); 8% absorbs both
        assert got == pytest.approx(exact, rel=0.08), (q, got, exact)


def test_histogram_edge_cases():
    h = Histogram("h")
    assert np.isnan(h.quantile(0.5))
    h.observe(0.0)          # <= lo: bucket 0
    h.observe(-1.0)         # negative: clamped into bucket 0
    h.observe(5.0)
    assert h.count == 3
    assert h.quantile(0.0) == h.min == -1.0
    assert h.quantile(1.0) == h.max == 5.0
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_registry_snapshot_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a.count", unit="tok").inc(3)
    reg.gauge("b.depth").set(2.0)
    h = reg.histogram("c.lat_ms", unit="ms")
    for v in (1.0, 2.0, 10.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["a.count"]["value"] == 3
    assert snap["b.depth"]["peak"] == 2.0
    assert snap["c.lat_ms"]["count"] == 3
    assert set(snap["c.lat_ms"]) >= {"p50", "p90", "p99", "min", "max"}
    path = tmp_path / "metrics.json"
    reg.to_json(str(path))
    loaded = json.loads(path.read_text())
    assert loaded["metrics"] == json.loads(json.dumps(snap))


def test_null_registry_is_inert():
    c = NULL_REGISTRY.counter("x")
    c.inc(10)
    assert c.value == 0
    NULL_REGISTRY.gauge("y").set(5.0)
    NULL_REGISTRY.histogram("z").observe(1.0)
    assert NULL_REGISTRY.snapshot() == {}
    with pytest.raises(RuntimeError):
        NULL_REGISTRY.to_json("/dev/null")


# --------------------------------------------------------------------------
# chrome trace
# --------------------------------------------------------------------------
def test_trace_schema_valid(tmp_path):
    tr = TraceRecorder()
    tr.instant("enqueue", 10.0, track="req 0", args={"prompt_len": 4})
    tr.complete("prefill", 20.0, 15.0, track="req 0")
    tr.counter("engine.load", 30.0, {"queue_depth": 2})
    doc = tr.to_dict()
    validate_chrome_trace(doc)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    validate_chrome_trace(json.loads(path.read_text()))
    # every logical track got exactly one thread_name metadata record
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"
            and e["name"] == "thread_name"]
    assert {e["args"]["name"] for e in meta} == {"req 0", "engine"}


def test_trace_validator_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"name": "x", "ph": "X",
                                               "ts": 0.0, "pid": 1}]})
    with pytest.raises(ValueError):        # X without dur
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "ts": 0.0, "pid": 1, "tid": 0}]})
    with pytest.raises(ValueError):        # unknown phase
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "Q", "ts": 0.0, "pid": 1, "tid": 0}]})


# --------------------------------------------------------------------------
# saturation accounting
# --------------------------------------------------------------------------
def test_saturation_counter_fires_on_overflowing_q2_14():
    reg = MetricsRegistry()
    with obs_lib.observe_saturation(reg):
        # 3.0 > Q2.14 max (~1.99994): every element must clip
        fp.quantize(jnp.full((8,), 3.0), fp.Q2_14)
        # in-range values must not count as clips
        fp.quantize(jnp.full((4,), 0.5), fp.Q2_14)
    clips = reg.get("fixed_point.saturation.clips{fmt=Q2.14}")
    total = reg.get("fixed_point.saturation.elements{fmt=Q2.14}")
    assert clips.value == 8
    assert total.value == 12
    # observer detached on scope exit
    fp.quantize(jnp.full((8,), 3.0), fp.Q2_14)
    assert clips.value == 8


def test_saturation_observer_never_traces():
    """Inside jit the quantizer sees tracers: the observer must not fire
    (no Python metric state inside a compiled function) and must not
    change what the function compiles to."""
    reg = MetricsRegistry()

    def f(x):
        return fp.dequantize(fp.quantize(x, fp.Q2_14), fp.Q2_14)

    jf = jax.jit(f)
    with obs_lib.observe_saturation(reg):
        out = jf(jnp.full((8,), 3.0))
    assert reg.get("fixed_point.saturation.clips{fmt=Q2.14}") is None
    np.testing.assert_allclose(np.asarray(out), fp.Q2_14.max_int / 2**14)


@pytest.mark.parametrize("fmt,label", [("int8", "Q8.0"), ("q2_14", "Q2.14")])
def test_kv_quant_saturation_counters(fmt, label):
    """The quantized paged-KV write path rides the same eager-quantize
    observer: per-block amax scales map every element inside the code
    range (clips stay ZERO on in-range traces), while a deliberately
    pinned too-small scale pushes the tail out of range and the clip
    counter for the format's Q label moves."""
    from repro.core import kv_quant as kvq

    spec = kvq.spec_for(fmt)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 16, 2, 8)).astype(np.float32))
    good = kvq.block_scale(x, spec)
    reg = MetricsRegistry()
    with obs_lib.observe_saturation(reg):
        kvq.quantize(x, spec, good)
    clips = reg.get(f"fixed_point.saturation.clips{{fmt={label}}}")
    total = reg.get(f"fixed_point.saturation.elements{{fmt={label}}}")
    assert clips is not None and clips.value == 0
    assert total.value == x.size
    with obs_lib.observe_saturation(reg):
        # an eighth of the proper scale leaves everything past amax/8
        # outside the representable range — the counter must see it
        kvq.quantize(x, spec, good / 8.0)
    assert clips.value > 0
    assert total.value == 2 * x.size


def test_engine_kv_quant_gauges():
    """A quantized engine registers the kv.quant.* gauges (code width,
    derated bytes/token) and the pager's kv.pool.bytes_in_use follows
    alloc/release at the quantized block size."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(6))
    ob = obs_lib.Observability()
    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl="paged",
                      kv_quant="int8", obs=ob)
    _serve(eng, _requests(cfg, 3, max_new=3))
    m = ob.metrics
    assert m.get("kv.quant.code_bits").last == 8.0
    bpt = m.get("kv.quant.bytes_per_token").last
    assert bpt == eng.pager.block_bytes / eng.block_len > 0
    assert m.get("kv.pool.bytes_in_use").peak > 0
    assert m.get("kv.pool.bytes_in_use").last == 0.0    # all freed
    # the unquantized engine reports the f32 width through the same gauge
    ob32 = obs_lib.Observability()
    eng32 = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl="paged",
                        obs=ob32)
    assert ob32.metrics.get("kv.quant.code_bits").last == 32.0
    assert ob32.metrics.get("kv.quant.bytes_per_token").last > bpt


def test_saturation_audit_per_profile():
    audit = obs_lib.saturation_audit(
        {"inrange": np.linspace(-1.5, 1.5, 64),
         "logits": np.linspace(-20.0, 0.0, 64)})
    for prof in ("q2_14", "q2_20", "q2_29"):
        assert audit[prof]["inrange"]["clipped"] == 0
        assert audit[prof]["logits"]["clipped"] > 0
        assert audit[prof]["logits"]["total"] == 64
        assert 0 < audit[prof]["logits"]["frac"] <= 1


# --------------------------------------------------------------------------
# engine lifecycle + no-interference contract
# --------------------------------------------------------------------------
@pytest.mark.parametrize("kv_impl", ["dense", "paged"])
def test_engine_obs_no_interference(kv_impl):
    """The acceptance gate: identical tokens AND identical compile counts
    with observability (metrics + tracing) on vs off, plus a Perfetto-
    loadable trace out of the observed run."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(1))

    eng_off = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl=kv_impl)
    toks_off = _serve(eng_off, _requests(cfg, 5))

    ob = obs_lib.Observability(trace=True)
    eng_on = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl=kv_impl,
                         obs=ob)
    toks_on = _serve(eng_on, _requests(cfg, 5))

    assert toks_on == toks_off
    assert eng_on.compile_counts() == eng_off.compile_counts()
    validate_chrome_trace(ob.trace.to_dict())


def test_engine_lifecycle_event_ordering():
    """Per request: enqueue <= admit <= first_token <= token steps
    (monotone ts) <= finish, with per-token steps increasing by 1."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(2))
    ob = obs_lib.Observability(trace=True)
    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl="paged",
                      obs=ob)
    reqs = _requests(cfg, 5, max_new=5)
    _serve(eng, reqs)

    for r in reqs:
        evs = ob.trace.track_events(f"req {r.rid}")
        names = [e["name"] for e in evs]
        # prefill is a span starting at admit time; order the rest
        assert names[0] == "enqueue"
        assert names[1] == "admit"
        assert "first_token" in names
        assert names[-1] == "finish"
        ts = [e["ts"] for e in evs if e["ph"] == "i"]
        assert ts == sorted(ts), f"req {r.rid} events out of order"
        tok_steps = [e["args"]["step"] for e in evs if e["name"] == "token"]
        assert tok_steps == list(range(2, len(r.out) + 1))
        # timestamps mirrored onto the Request itself
        assert 0 <= r.t_enqueue <= r.t_admit <= r.t_first <= r.t_finish

    # engine-phase spans exist for every phase of every step
    phase_names = {e["name"] for e in ob.trace.track_events("engine")
                   if e["ph"] == "X"}
    assert {"admit", "dispatch", "host_sync",
            "sample_copy"} <= phase_names


def test_engine_metrics_populated():
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(3))
    ob = obs_lib.Observability()
    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl="paged",
                      obs=ob)
    reqs = _requests(cfg, 4, max_new=4)
    _serve(eng, reqs)

    m = ob.metrics
    assert m.get("engine.requests.submitted").value == 4
    assert m.get("engine.requests.finished").value == 4
    assert m.get("engine.tokens.emitted").value == sum(
        len(r.out) for r in reqs)
    assert m.get("engine.ttft_ms").count == 4
    assert m.get("engine.tpot_ms").count == 4     # max_new 4 > 1 token
    assert m.get("engine.e2e_ms").count == 4
    assert m.get("engine.batch_occupancy").peak == 2.0
    # cold engine: exactly the bucketed-prefill + decode compiles, seen
    # from the host via compile_counts() deltas
    assert m.get("engine.compiles.prefill").value >= 1
    assert m.get("engine.compiles.decode").value >= 1
    assert (m.get("engine.compiles.prefill").value
            + m.get("engine.compiles.decode").value
            == sum(eng.compile_counts().values()))
    # pool telemetry flowed through the same registry
    assert m.get("kv.pool.allocs").value == 4
    assert m.get("kv.pool.blocks_freed").value > 0
    assert m.get("kv.pool.blocks_in_use").peak > 0
    assert m.get("kv.pool.blocks_in_use").last == 0.0   # all freed
    # every phase histogram saw every decode step
    steps = m.get("engine.step_ms").count
    for ph in ("admit", "dispatch", "host_sync", "sample_copy"):
        assert m.get(f"engine.phase.{ph}_ms").count >= steps


def _device_lens(caches):
    """(slots,) lengths of the first attention layer's paged cache."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]:
        if getattr(path[-1], "key", None) == "lens":
            return np.asarray(leaf).reshape(-1, np.asarray(leaf).shape[-1])[0]
    raise AssertionError("no lens leaf in the paged cache")


@pytest.mark.parametrize("observed", [True, False])
def test_engine_decode_live_blocks(observed):
    """engine.decode.live_blocks records, once per decode dispatch, the
    live table entries sum(ceil(k_len / block_len)) of the decodable
    slots, k_len being the length the attend sees on the device (the
    cache's lens + 1); with observability off nothing is registered."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(6))
    ob = obs_lib.Observability() if observed else None
    eng = ServeEngine(cfg, params, slots=3, max_len=64, kv_impl="paged",
                      obs=ob)
    L = eng.block_len
    want = []
    decode = eng._decode

    def spy(params, caches, *rest):
        lens = _device_lens(caches)
        want.append(sum(-(-(int(lens[s]) + 1) // L)
                        for s in range(eng.slots)
                        if eng._active[s] is not None
                        and s not in eng.scheduler.chunking))
        return decode(params, caches, *rest)

    eng._decode = spy
    rng = np.random.default_rng(6)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n
                                               ).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(15, 4), (17, 9), (31, 6), (5, 3)])]
    _serve(eng, reqs)
    assert len(want) > 3 and max(want) > min(want)
    if not observed:
        assert eng.obs.metrics.get("engine.decode.live_blocks") is None
        return
    h = ob.metrics.get("engine.decode.live_blocks")
    assert h.unit == "blocks"
    assert h.count == len(want)
    assert h.sum == sum(want)
    assert (h.min, h.max) == (min(want), max(want))


def test_pager_backpressure_metric():
    ob = obs_lib.Observability()
    pager = kvp.KVPager(4, 16, 2, metrics=ob.metrics)
    assert pager.alloc(0, 3) is not None
    assert pager.alloc(1, 2) is None         # only 0 free: backpressure
    assert ob.metrics.get("kv.pool.alloc_failures").value == 1
    pager.free(0)
    assert ob.metrics.get("kv.pool.blocks_freed").value == 3
    assert ob.metrics.get("kv.pool.blocks_in_use").last == 0.0
    assert ob.metrics.get("kv.pool.blocks_in_use").peak == 3.0


def test_requests_submitted_before_attach_obs_keep_latency_stats():
    """The t_enqueue regression: submit() only stamped the enqueue time
    when observability was already attached, so requests queued before a
    post-warm-up attach_obs silently vanished from the TTFT and e2e
    histograms. Stamps are now unconditional: requests submitted *before*
    attach_obs still land in both histograms after it."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(5))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl="paged")
    reqs = _requests(cfg, 3, max_new=3)
    for r in reqs:
        eng.submit(r)                    # queued with NO obs attached
    ob = obs_lib.Observability(trace=True)
    eng.attach_obs(ob)
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 500
    assert all(r.done for r in reqs)
    m = ob.metrics
    assert m.get("engine.ttft_ms").count == 3
    assert m.get("engine.e2e_ms").count == 3
    for r in reqs:
        assert 0 < r.t_enqueue <= r.t_admit <= r.t_first <= r.t_finish


def test_attach_obs_after_warmup():
    """attach_obs swaps the handle mid-lifetime: the new registry sees
    only post-attach traffic and no compile events for warm shapes."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(4))
    eng = ServeEngine(cfg, params, slots=2, max_len=64, kv_impl="paged")
    _serve(eng, _requests(cfg, 2, max_new=2))          # warm, unobserved
    ob = obs_lib.Observability()
    eng.attach_obs(ob)
    reqs = _requests(cfg, 2, max_new=4, seed=1)
    _serve(eng, reqs)
    m = ob.metrics
    assert m.get("engine.requests.submitted").value == 2
    assert m.get("engine.compiles.prefill").value == 0
    assert m.get("engine.compiles.decode").value == 0
