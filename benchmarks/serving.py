"""Serving throughput benchmark + regression gate: decode tok/s vs slot
count — dense, paged-gather, and paged-pallas (the block-walking
paged-attention kernel) side by side.

The ServeEngine issues exactly one jitted decode per step, so slot count
should buy near-linear decode throughput on dispatch-bound hosts; the paged
engine must deliver the same tokens from a block pool instead of dense
per-slot buffers without giving that throughput back. This benchmark
measures all three decode planes and **fails the build** when they
regress: steady-state decode tok/s at slots in {1, 4, 8} per impl, every
configuration serving the same request workload per slot, written to
BENCH_serving.json:

    {"impls": {"dense": {"slots": {"1": {"tok_s": ...}, ...}, ...},
               "paged": {..., "pool": {"peak_blocks": ...}},
               "paged_pallas": {...}},
     "transient": {"64": {"gather": ..., "pallas": ...}, "128": {...}}}

``transient`` records the per-row decode-attend working set in bytes
(kernels.paged_attention.decode_transient_bytes, derived from the same
shapes the kernel's BlockSpecs are built from) at two max_len values: the
gather path must scale linearly with max_len, the pallas path must NOT
scale at all — that invariance is gated below, which is the benchmark's
teeth for the kernel (on this CPU container the kernel runs in interpret
mode, so its *absolute* tok/s measures the interpreter, not the datapath;
it is recorded for visibility but the gather-class tok/s gates are the
perf contract and the transient metric is the kernel's).

Like benchmarks/accuracy.py, the gate is a hard CI failure, not a record:
every metric in BASELINES must be present (a renamed metric must not
silently disable its gate) and must stay above
``max(FLOOR_TOK_S, baseline * (1 - TOLERANCE))``. Baselines are this
revision's smoke numbers on a dev host; the tolerance absorbs CI-runner
noise while still catching a serialized decode loop or a paged gather
going quadratic (both are >2x collapses, far past any plausible jitter).

Beyond steady-state tok/s, the benchmark drives the repro.obs
observability layer (this is the serving-latency entry point — the old
benchmarks/latency.py evaluator microbench lives here too, see run()):

``poisson``
    An *open-loop* Poisson arrival trace (exponential inter-arrival gaps,
    arrivals fire on schedule whether or not the engine keeps up — the
    correct load model for latency percentiles; a closed loop would let a
    slow engine throttle its own offered load). Reports p50/p99 TTFT and
    TPOT out of the engine's log-bucketed histograms, goodput (tokens of
    requests whose TTFT met the SLO, per wall second), queue-depth peak,
    batch-occupancy mean, pool-occupancy peak, and the compile counters
    (must stay 0 during the measured window — the engine is warmed
    first). These are *gated*: a missing or non-finite metric fails the
    build (PR-4 gate style); absolute latency is host-dependent and not
    thresholded.

``mixed_chunked``
    The chunked-prefill acceptance trace: ONE seeded open-loop arrival
    trace mixing long (~bucket-max) and short prompts, served twice on
    the paged engine — unchunked (legacy single-shot admission) and
    chunked (``prefill_chunk`` + multi-row batched prefill). Gated on
    both axes of the contract: the two runs' token streams must be
    bit-identical (scheduling must never change outputs), and the
    short-request p99 TTFT must improve by >= MIN_SHORT_TTFT_SPEEDUP
    (the point of chunking: a long prompt streams in across iterations
    instead of stalling every short request behind its full-width
    prefill). The speedup is a ratio of two runs on the same host in the
    same process, so it holds on any runner class.

``prefix_cache``
    The prefix-caching acceptance trace: a synthetic "N users, 5 system
    prompts" open-loop Poisson workload (every prompt = one of five
    112-token system prompts + a short unique user tail, greedy and
    seeded sampling mixed), served cache-off and cache-on
    (``prefix_cache=True``) on the paged engine. Gated on all three
    axes of ROADMAP item 2's contract: the two runs' token streams must
    be bit-identical, and both the prefill-token count and the pool
    peak-block occupancy must collapse by >= MIN_PREFIX_COLLAPSE (the
    point of the radix cache: the shared system prompt prefills once
    and its blocks are shared, not recomputed and duplicated, per
    user). Both runs first serve one priming request per system prompt
    to completion — production system prompts are long-lived, so the
    steady state measured is the warm-cache one; the priming tokens
    join the identity check. A TP=1-vs-TP=2 sub-trace (subprocess re-exec, like
    ``sharded``) additionally gates that cache-on tokens stay
    bit-identical under tensor parallelism — block sharing is
    host-side metadata, so the mesh must not see it.

``kv_quant``
    The quantized paged-KV acceptance trace (ROADMAP item 5): one
    seeded greedy request trace served from the unquantized f32 pool
    and from int8 / q2_14 block-scaled pools (K/V quantized at
    pool-write time against per-block-per-head scales, dequantized at
    every read via the CORDIC linear-rotation multiply —
    core/kv_quant.py). Gated per format on the resident-pool bytes
    collapse at matched block count (int8 >= 2x), the greedy token
    match rate vs the unquantized stream, and the tok/s floor; int8
    must additionally be bit-identical between the gather and pallas
    attends (the kernel dequantizes per-chunk in VMEM with the same
    CORDIC multiply) and across TP=1/TP=2 (scale pools shard on the
    kv-heads cut). All gates live in benchmarks/check_bench.py — the
    same checkers CI runs against the uploaded artifact.

``host_overhead_1slot``
    The per-step phase breakdown (admit / dispatch / host_sync /
    sample_copy mean ms) per impl at 1 slot — quantifying the carried
    63-vs-235 tok/s paged-vs-dense low-occupancy gap as dispatch-vs-sync
    host time, so the fix can be judged against a recorded baseline.

``saturation``
    Would-clip counts per FORMAT_PROFILES format (obs.saturation_audit)
    over the model weights and a served log-prob sample — the software
    analogue of the paper's overflow-free Q2.14 claim, and the telemetry
    the quantized-KV roadmap item selects formats with.

CLI: ``python benchmarks/serving.py --smoke [--out BENCH_serving.json]
[--no-check] [--trace-out TRACE.json] [--metrics-json METRICS.json]
[--evaluators]`` — smoke uses a smaller model + shorter generations for
CI; the nightly workflow runs the full (non-smoke) mode, uploads the
artifact, and exports the Poisson run's Chrome trace (Perfetto-loadable)
via --trace-out. Timing excludes compile: a warm-up pass on the *same*
engine compiles prefill + decode before the measured pass (jit caches are
per-engine, so a throwaway warm-up engine would not help), and the
observability handle is attached *after* warm-up so histograms hold only
steady-state samples.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, "src")
# the section gates live in benchmarks/check_bench.py (one source of
# truth shared with the CI belt-check step); make the import work from
# any cwd, not just repo root
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import numpy as np

from check_bench import (FLOOR_TOK_S, MIN_KVQ_BYTES_RATIO,
                         MIN_KVQ_MATCH_RATE, MIN_PREFIX_COLLAPSE,
                         MIN_SHORT_TTFT_SPEEDUP, check_kv_quant,
                         check_mixed_chunked, check_poisson,
                         check_prefix_cache, check_sharded)
from repro import obs as obs_lib
from repro.configs.base import ModelConfig
from repro.launch import compile_cache
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import SamplingParams

SLOT_COUNTS = (1, 4, 8)
#: result key -> (kv_impl, paged_attend_impl) engine configuration
IMPLS = {
    "dense": ("dense", "gather"),
    "paged": ("paged", "gather"),
    "paged_pallas": ("paged", "pallas"),
}
IMPL_KEYS = tuple(IMPLS)
#: max_len values the transient working-set metric is recorded at; the
#: pallas entry must be EQUAL at both (no max_len scaling), the gather
#: entry must grow with max_len. The kernel holds a fixed chunk of up to
#: 256 positions per pool, double-buffered, so the gather path's per-row
#: buffers pass it only at longer contexts; 2048 is the served max_len of
#: the on-chip benchmark's deployments.
TRANSIENT_MAX_LENS = (2048, 4096)

#: Smoke-mode tok/s baselines for this revision (idle dev host, CPU). The
#: gate fails a metric below max(FLOOR_TOK_S, baseline * (1 - TOLERANCE))
#: and fails outright when a metric disappears from the results. Absolute
#: tok/s scales with the runner, so the tolerance is wide; the
#: host-invariant teeth are the speedup ratios below.
BASELINES = {
    "dense/1": 168.0,
    "dense/4": 570.0,
    "dense/8": 615.0,
    "paged/1": 210.0,
    "paged/4": 484.0,
    "paged/8": 679.0,
    # interpret-mode kernel numbers: on CPU these measure the Pallas
    # interpreter, not the datapath (see module docstring) — on this dev
    # host the kernel lane still beats the gather lane (it skips the
    # max_len-sized gather materialization), and the wide tolerance below
    # absorbs the rest.
    "paged_pallas/1": 248.0,
    "paged_pallas/4": 513.0,
    "paged_pallas/8": 516.0,
}
TOLERANCE = 0.9         # absolute tok/s soaks up runner-class differences
                        # (a 2-vCPU CI box can be ~5x slower than the dev
                        # host); the collapse classes these still catch —
                        # compile-in-measurement, quadratic gathers — are
                        # >20x, and serialization is caught host-invariantly
                        # by the speedup-ratio gate below
#: (FLOOR_TOK_S — below which the serving loop is broken, not slow —
#: is imported from check_bench.py, shared with the kv_quant gate)
#: 8 slots must beat 1 slot by at least this factor per impl — a RATIO, so
#: it holds on any host speed. One decode dispatch per step buys ~3.5-4x
#: here; a relapse to per-slot dispatch (or a paged gather going quadratic
#: in slots) collapses it to ~1 and fails regardless of runner class.
MIN_SPEEDUP_8_OVER_1 = 1.5
#: the ratio gate applies to the gather-class impls; the interpret-mode
#: kernel's scaling reflects interpreter overhead (grid size grows with
#: slots), so its gates are the tok/s floor + the transient invariance.
SPEEDUP_IMPLS = ("dense", "paged")
#: (MIN_PREFIX_COLLAPSE, MIN_SHORT_TTFT_SPEEDUP, MIN_KVQ_* and the
#: section checkers themselves live in check_bench.py — the single
#: source of truth CI's belt-check step also runs)


def _cfg(smoke: bool) -> ModelConfig:
    if smoke:
        return ModelConfig(
            name="serve-bench-smoke", family="dense",
            num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
            d_ff=384, vocab_size=1024, act_impl="exact",
            rope_theta=1e4, dtype="float32",
        )
    return ModelConfig(
        name="serve-bench", family="dense",
        num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
        d_ff=768, vocab_size=4096, act_impl="cordic_fixed",
        rope_theta=1e4, dtype="float32",
    )


def _requests(cfg, n: int, max_new: int, plen: int = 8):
    # fixed prompt length: one prefill bucket, decode dominates the timing
    rng = np.random.default_rng(0)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def _serve_once(eng, cfg, *, requests_per_slot: int, max_new: int):
    """One timed serve pass on an existing engine. The warm-up and the
    measured pass MUST share the engine: each ServeEngine wraps its own
    jax.jit objects (that per-instance cache is what compile_counts()
    measures), so a throwaway warm-up engine would leave every compile
    inside the measured wall time."""
    reqs = _requests(cfg, eng.slots * requests_per_slot, max_new)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    steps = 0
    while eng.step():
        steps += 1
    wall = time.perf_counter() - t0
    toks = sum(len(r.out) for r in reqs)
    assert all(r.done for r in reqs)
    return toks, steps, wall


def bench(cfg, params, smoke: bool) -> dict:
    requests_per_slot = 2
    max_new = 8 if smoke else 32
    sampling = SamplingParams(greedy=True)

    impls = {}
    for impl_key, (kv_impl, attend_impl) in IMPLS.items():
        per_slots = {}
        pool = None
        for slots in SLOT_COUNTS:
            eng = ServeEngine(cfg, params, slots=slots, max_len=64,
                              sampling=sampling, kv_impl=kv_impl,
                              paged_attend_impl=attend_impl)
            # warm-up pass compiles prefill + the batched decode for this
            # slot count; the measured pass then times steady-state serving
            _serve_once(eng, cfg, requests_per_slot=1, max_new=2)
            toks, steps, wall = _serve_once(
                eng, cfg, requests_per_slot=requests_per_slot,
                max_new=max_new)
            per_slots[str(slots)] = {
                "tok_s": round(toks / wall, 2),
                "tokens": toks,
                "engine_steps": steps,
                "decode_dispatches": steps,
                "wall_s": round(wall, 3),
            }
            if eng.pager is not None:
                st = eng.pager.stats()
                pool = {"block_len": eng.block_len,
                        "num_blocks": st.num_blocks,
                        "peak_blocks": st.peak_in_use,
                        "dense_equiv_blocks": slots * eng.max_blocks}
            print(f"[serving] impl={impl_key} slots={slots}: {toks} tok / "
                  f"{steps} steps / {wall:.2f}s = {toks / wall:.1f} tok/s")

        rates = [per_slots[str(s)]["tok_s"] for s in SLOT_COUNTS]
        impls[impl_key] = {
            "slots": per_slots,
            "monotone": all(a < b for a, b in zip(rates, rates[1:])),
            "speedup_8_over_1": round(rates[-1] / rates[0], 2),
        }
        if pool is not None:
            impls[impl_key]["pool"] = pool

    # transient decode-attend working set per row (bytes), recorded at two
    # max_len values so the gate can assert the kernel path does not scale
    from repro.kernels import paged_attention as PA

    transient = {
        str(ml): {
            "gather": PA.decode_transient_bytes(cfg, max_len=ml,
                                                block_len=16, impl="gather"),
            "pallas": PA.decode_transient_bytes(cfg, max_len=ml,
                                                block_len=16, impl="pallas"),
        }
        for ml in TRANSIENT_MAX_LENS
    }

    return {
        "model": cfg.name,
        "mode": "smoke" if smoke else "full",
        "slot_counts": list(SLOT_COUNTS),
        "impl_configs": {k: {"kv_impl": kv, "paged_attend_impl": at}
                         for k, (kv, at) in IMPLS.items()},
        "impls": impls,
        "transient": transient,
    }


#: engine phases whose per-step means the host-overhead section records
#: (the poisson-section gated-key list is check_bench.POISSON_GATED)
PHASES = ("admit", "dispatch", "host_sync", "sample_copy")


def _poisson_params(smoke: bool) -> dict:
    # open-loop offered load: high enough that slots contend and the queue
    # builds (the percentiles must reflect queueing, not an idle engine),
    # low enough that the smoke trace stays a few seconds on a CI box
    return (dict(n=16, rate_req_s=8.0, max_new=8, slots=4, slo_ms=2000.0)
            if smoke else
            dict(n=64, rate_req_s=12.0, max_new=32, slots=8, slo_ms=1000.0))


def bench_poisson(cfg, params, smoke: bool, trace_out=None,
                  metrics_json=None) -> dict:
    """Open-loop Poisson arrival trace against the paged engine: arrivals
    fire at pre-drawn wall-clock offsets (exponential gaps, seeded), the
    engine steps continuously, and every latency number is read back out
    of the repro.obs histograms the engine filled. TTFT includes queueing
    (enqueue -> first token), which is the point of open-loop driving."""
    pp = _poisson_params(smoke)
    eng = ServeEngine(cfg, params, slots=pp["slots"], max_len=64,
                      sampling=SamplingParams(greedy=True), kv_impl="paged",
                      paged_attend_impl="gather")
    # warm every compile (prefill bucket + decode) on a NULL-obs engine,
    # then attach observability: the histograms see steady state only
    _serve_once(eng, cfg, requests_per_slot=1, max_new=2)
    ob = obs_lib.Observability(trace=trace_out is not None)
    eng.attach_obs(ob)

    rng = np.random.default_rng(7)
    arrivals = np.cumsum(rng.exponential(1.0 / pp["rate_req_s"], pp["n"]))
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 12))
                                        ).astype(np.int32),
                    max_new_tokens=pp["max_new"])
            for i in range(pp["n"])]

    # open loop: arrivals fire on schedule; an idle engine sleeps up to
    # the next arrival instead of spinning (the arrival time never moves)
    wall = _drive_open_loop(eng, reqs, arrivals)

    m = ob.metrics

    def _q(name):
        h = m.get(name)
        return {"p50": round(h.quantile(0.50), 3),
                "p90": round(h.quantile(0.90), 3),
                "p99": round(h.quantile(0.99), 3),
                "mean": round(h.mean, 3), "count": h.count}

    met = [r for r in reqs
           if (r.t_first - r.t_enqueue) * 1e3 <= pp["slo_ms"]]
    total_toks = sum(len(r.out) for r in reqs)
    st = eng.pager.stats()
    res = {
        **pp,
        "wall_s": round(wall, 3),
        "ttft_ms": _q("engine.ttft_ms"),
        "tpot_ms": _q("engine.tpot_ms"),
        "e2e_ms": _q("engine.e2e_ms"),
        "throughput_tok_s": round(total_toks / wall, 2),
        "goodput_tok_s": round(sum(len(r.out) for r in met) / wall, 2),
        "slo_met_requests": len(met),
        "queue_depth_peak": m.get("engine.queue_depth").peak,
        "batch_occupancy_mean": round(
            m.get("engine.batch_occupancy").mean, 3),
        "pool": {"peak_blocks": st.peak_in_use,
                 "num_blocks": st.num_blocks,
                 "alloc_failures": st.alloc_failures},
        # must be 0: the engine was warmed before obs attached, so any
        # compile here means a shape leaked into the measured window
        "compiles_measured": {
            k: c.value for k, c in
            (("prefill", m.get("engine.compiles.prefill")),
             ("decode", m.get("engine.compiles.decode")))},
    }
    print(f"[serving] poisson: {pp['n']} req @ {pp['rate_req_s']}/s -> "
          f"ttft p50/p99 {res['ttft_ms']['p50']}/{res['ttft_ms']['p99']} ms, "
          f"tpot p50 {res['tpot_ms']['p50']} ms, "
          f"goodput {res['goodput_tok_s']} tok/s ({len(met)}/{pp['n']} in "
          f"SLO), queue peak {res['queue_depth_peak']}")
    if trace_out:
        ob.trace.export(trace_out)
        print(f"[serving] wrote Chrome trace -> {trace_out} "
              f"({len(ob.trace.events)} events; load at ui.perfetto.dev)")
    if metrics_json:
        ob.metrics.to_json(metrics_json)
        print(f"[serving] wrote metrics snapshot -> {metrics_json}")
    return res


def _mixed_trace(cfg, smoke: bool):
    """Seeded mixed long/short request trace + open-loop arrival offsets.
    Longs sit near the engine's largest bucket (their single-shot prefill
    is the stall chunking removes); shorts are prompt-trivial and TTFT-
    sensitive. Deterministic: both engine runs serve identical requests
    at identical offsets."""
    n_long, n_short = (5, 10) if smoke else (8, 24)
    rate = 12.0 if smoke else 18.0
    rng = np.random.default_rng(11)
    kinds = [True] * n_long + [False] * n_short
    rng.shuffle(kinds)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, len(kinds)))

    def reqs():
        r = np.random.default_rng(13)
        out = []
        for i, is_long in enumerate(kinds):
            plen = (int(r.integers(900, 1001)) if is_long
                    else int(r.integers(4, 13)))
            out.append(Request(
                rid=i, prompt=r.integers(0, cfg.vocab_size,
                                         plen).astype(np.int32),
                max_new_tokens=2 if is_long else 8))
        return out

    return kinds, arrivals, reqs


def _drive_open_loop(eng, reqs, arrivals):
    """Open-loop replay: submit each request at its pre-drawn offset while
    stepping continuously (arrivals never wait for the engine)."""
    t0 = time.perf_counter()
    nxt = 0
    while not all(r.done for r in reqs):
        now = time.perf_counter() - t0
        while nxt < len(reqs) and arrivals[nxt] <= now:
            eng.submit(reqs[nxt])
            nxt += 1
        if not eng.step() and nxt < len(reqs):
            time.sleep(max(0.0, min(arrivals[nxt]
                                    - (time.perf_counter() - t0), 0.01)))
    return time.perf_counter() - t0


def bench_mixed_chunked(cfg, params, smoke: bool) -> dict:
    """Chunked vs unchunked on ONE mixed long/short trace (module
    docstring, ``mixed_chunked``): same seeded requests and arrival
    offsets, paged engine both times; report per-class TTFT percentiles,
    the short-request p99 speedup, and whether the token streams are
    bit-identical."""
    # the long prompts' single-shot prefill must dominate a decode step
    # for the stall to be visible: at max_len=1024 the full-width prefill
    # is ~two orders of magnitude over one decode dispatch on this model
    max_len, chunk = 1024, 64
    kinds, arrivals, mk_reqs = _mixed_trace(cfg, smoke)

    def serve(prefill_chunk):
        eng = ServeEngine(cfg, params, slots=4, max_len=max_len,
                          sampling=SamplingParams(greedy=True),
                          kv_impl="paged", prefill_chunk=prefill_chunk)
        # warm every measured shape before TTFT is measured: a burst pass
        # (all slots contended -> widest pow2 row groups compile) plus an
        # open-loop replay of the very trace (the admission cadence the
        # measured run will see, covering the remaining group shapes)
        for r in mk_reqs():
            eng.submit(r)
        eng.run()
        _drive_open_loop(eng, mk_reqs(), arrivals)
        reqs = mk_reqs()
        wall = _drive_open_loop(eng, reqs, arrivals)
        return eng, reqs, wall

    out = {}
    toks = {}
    for key, chunk_arg in (("unchunked", None), ("chunked", chunk)):
        eng, reqs, wall = serve(chunk_arg)
        toks[key] = [list(r.out) for r in reqs]
        ttft = {is_long: [(r.t_first - r.t_enqueue) * 1e3
                          for r, il in zip(reqs, kinds) if il == is_long]
                for is_long in (True, False)}
        out[key] = {
            "wall_s": round(wall, 3),
            "short_ttft_ms": {
                "p50": round(float(np.percentile(ttft[False], 50)), 3),
                "p99": round(float(np.percentile(ttft[False], 99)), 3)},
            "long_ttft_ms": {
                "p50": round(float(np.percentile(ttft[True], 50)), 3),
                "p99": round(float(np.percentile(ttft[True], 99)), 3)},
            "prefill_compiles": eng.compile_counts()["prefill"],
        }
    res = {
        "n_long": sum(kinds), "n_short": len(kinds) - sum(kinds),
        "max_len": max_len, "prefill_chunk": chunk,
        "tokens_identical": int(toks["chunked"] == toks["unchunked"]),
        "short_ttft_p99_speedup": round(
            out["unchunked"]["short_ttft_ms"]["p99"]
            / out["chunked"]["short_ttft_ms"]["p99"], 3),
        **out,
    }
    print(f"[serving] mixed_chunked: short p99 TTFT "
          f"{out['unchunked']['short_ttft_ms']['p99']}ms unchunked -> "
          f"{out['chunked']['short_ttft_ms']['p99']}ms chunked "
          f"(x{res['short_ttft_p99_speedup']}), tokens identical: "
          f"{bool(res['tokens_identical'])}")
    return res


def bench_host_overhead(cfg, params, smoke: bool) -> dict:
    """Per-step phase breakdown at 1 slot per impl — the carried
    63-vs-235 tok/s item made measurable: how much of a paged decode step
    is jit dispatch vs device->host sync vs host bookkeeping, recorded so
    the gap can be judged (and closed) against numbers, not vibes."""
    out = {}
    max_new = 16 if smoke else 64
    for impl_key, (kv_impl, attend_impl) in IMPLS.items():
        eng = ServeEngine(cfg, params, slots=1, max_len=64,
                          sampling=SamplingParams(greedy=True),
                          kv_impl=kv_impl, paged_attend_impl=attend_impl)
        _serve_once(eng, cfg, requests_per_slot=1, max_new=2)   # warm
        ob = obs_lib.Observability()
        eng.attach_obs(ob)
        toks, steps, wall = _serve_once(eng, cfg, requests_per_slot=2,
                                        max_new=max_new)
        entry = {"tok_s": round(toks / wall, 2), "steps": steps}
        for ph in PHASES:
            h = ob.metrics.get(f"engine.phase.{ph}_ms")
            entry[f"{ph}_ms_mean"] = round(h.mean, 4)
        entry["step_ms_mean"] = round(
            ob.metrics.get("engine.step_ms").mean, 4)
        out[impl_key] = entry
        print(f"[serving] host_overhead 1-slot {impl_key}: " +
              " ".join(f"{ph}={entry[f'{ph}_ms_mean']}ms" for ph in PHASES))
    out["paged_over_dense_step_ms"] = round(
        out["paged"]["step_ms_mean"] / out["dense"]["step_ms_mean"], 3)
    return out


def bench_saturation(cfg, params) -> dict:
    """FORMAT_PROFILES would-clip audit over (a) the model weights (the
    init's gaussian tail puts ~1% of elements past the Q2.x ±2 range —
    the number a per-tensor scale would have to absorb) and (b) a served
    teacher-forced log-prob row, which exceeds the range almost entirely
    (log-probs live far below -2): exactly the per-tensor telemetry a
    format-assignment sweep (ROADMAP item 5) consumes, and the serving-
    side analogue of the paper's overflow-free-Q2.14 domain argument."""
    cap = 1 << 16
    weights = np.concatenate([np.asarray(l).ravel()[:cap]
                              for l in jax.tree.leaves(params)])
    eng = ServeEngine(cfg, params, slots=1, max_len=64)
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, 16).astype(np.int32)
    logprobs = eng.score(prompt)
    reg = obs_lib.MetricsRegistry()
    audit = obs_lib.saturation_audit(
        {"weights": weights, "score_logprobs": logprobs}, registry=reg)
    clips = {name: m.value for name, m in
             ((n, reg.get(n)) for n in reg.names()) if "clips" in name}
    print(f"[serving] saturation: " + ", ".join(
        f"{p}: weights {audit[p]['weights']['clipped']}/"
        f"{audit[p]['weights']['total']}, logprobs "
        f"{audit[p]['score_logprobs']['clipped']}/"
        f"{audit[p]['score_logprobs']['total']}" for p in sorted(audit)))
    return {"profiles": audit, "clip_counters": clips}


def _bench_sharded_inner(smoke: bool) -> dict:
    """TP=1 vs TP=2 serve of the same fixed request trace. Must run in a
    process with >= 2 visible devices (bench_sharded arranges that); the
    gate downstream is token bit-identity + metrics-exist, NOT speedup —
    forced host-CPU shards time-share the same cores, so tok_s_tp2 is a
    topology record, not a performance claim."""
    cfg = _cfg(smoke)
    params = tf.init(cfg, jax.random.PRNGKey(0))
    max_new = 8 if smoke else 16

    def one(tp: int):
        eng = ServeEngine(cfg, params, slots=4, max_len=64, seed=0,
                          kv_impl="paged", block_len=16, tp=tp)
        _serve_once(eng, cfg, requests_per_slot=1, max_new=2)  # warm-up
        reqs = _requests(cfg, 8, max_new)
        for r in reqs[1::2]:  # greedy/sampled mix exercises both RNG paths
            r.sampling = SamplingParams(temperature=0.7, top_k=6)
        for r in reqs:
            eng.submit(r)
        t0 = time.perf_counter()
        while eng.step():
            pass
        wall = time.perf_counter() - t0
        toks = [list(map(int, r.out))
                for r in sorted(reqs, key=lambda r: r.rid)]
        n_tok = sum(len(t) for t in toks)
        axis = (dict(eng.mesh.shape) if eng.mesh is not None
                else {"data": jax.device_count(), "model": 1})
        return toks, round(n_tok / wall, 2), axis

    toks1, tok_s_tp1, _ = one(1)
    toks2, tok_s_tp2, axis2 = one(2)
    identical = int(toks1 == toks2)
    print(f"[serving] sharded: tp=1 {tok_s_tp1} tok/s, tp=2 {tok_s_tp2} "
          f"tok/s, tokens_identical={identical}")
    return {
        "device_count": jax.device_count(),
        "tp": 2,
        "axis_sizes": axis2,
        "tok_s_tp1": tok_s_tp1,
        "tok_s_tp2": tok_s_tp2,
        "tokens_identical": identical,
    }


def bench_sharded(smoke: bool) -> dict:
    """Tensor-parallel conformance section. jax freezes the device count
    at first backend init, so when this process sees a single device the
    measurement re-execs this file under
    XLA_FLAGS=--xla_force_host_platform_device_count=2 and parses the
    child's marker line; with >= 2 devices it runs in-process."""
    if jax.device_count() >= 2:
        return _bench_sharded_inner(smoke)
    return _cpu_child("--sharded-subprocess", smoke)


#: stdout marker a two-device child prints its JSON result after
_CHILD_MARKER = "CHILD_JSON:"


def _cpu_child(flag: str, smoke: bool) -> dict:
    """Re-exec this file with ``flag`` as a child on two forced host CPU
    devices and return the JSON it prints after _CHILD_MARKER. jax
    freezes the device count at first backend init, so a one-device
    parent cannot build a TP=2 mesh itself. The child is pinned to the
    CPU: where the parent runs on an accelerator it already holds it. A
    child that prints no result raises, so the benchmark exits non-zero
    instead of recording a partial section."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, os.path.abspath(__file__), flag]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=root)
    for line in proc.stdout.splitlines():
        if line.startswith(_CHILD_MARKER):
            return json.loads(line[len(_CHILD_MARKER):])
    raise RuntimeError(f"{flag} child (exit {proc.returncode}) produced no "
                       "result: " + (proc.stderr or proc.stdout)[-2000:])


def _prefix_trace(cfg, n_users: int, rate_req_s: float, seed: int = 21):
    """The "N users, 5 system prompts" workload: every prompt is one of
    five fixed 112-token (7-block) system prompts plus a 1..15-token
    unique user tail, arriving open-loop Poisson; every other request
    samples (seeded) instead of decoding greedily. Deterministic: both
    engine runs serve identical requests at identical offsets."""
    rng = np.random.default_rng(seed)
    sys_prompts = [rng.integers(0, cfg.vocab_size, 112).astype(np.int32)
                   for _ in range(5)]
    assign = rng.integers(0, 5, n_users)
    tails = rng.integers(1, 16, n_users)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_req_s, n_users))

    def reqs():
        r = np.random.default_rng(seed + 1)
        out = []
        for i in range(n_users):
            tail = r.integers(0, cfg.vocab_size,
                              int(tails[i])).astype(np.int32)
            out.append(Request(
                rid=i,
                prompt=np.concatenate([sys_prompts[int(assign[i])], tail]),
                max_new_tokens=2,
                sampling=(SamplingParams(temperature=0.7, top_k=6)
                          if i % 2 else None)))
        return out

    def prime():
        # one warm-up request per system prompt, served to completion
        # before the flood: production system prompts are long-lived, so
        # the steady state being measured is the warm-cache one. Both
        # engine runs serve them (identical work; the cache-off engine
        # just recomputes), and their tokens join the identity check.
        return [Request(rid=1_000_000 + j, prompt=sys_prompts[j],
                        max_new_tokens=2) for j in range(len(sys_prompts))]

    return arrivals, reqs, prime


def _serve_prefix(cfg, params, reqs, arrivals, *, prefix: bool,
                  tp=None, prime=None) -> tuple:
    """Priming pass (serve ``prime`` to completion — seeds the radix
    index when the cache is on) followed by one open-loop replay, with an
    attached metrics registry. Returns (section dict, sorted token
    streams incl. priming). Compile walls land in wall_s — recorded, not
    gated — keeping prefill-token and peak-block counts pure measures of
    the trace."""
    obs = obs_lib.Observability()
    eng = ServeEngine(cfg, params, slots=16, max_len=128, seed=0,
                      kv_impl="paged", block_len=16, prefix_cache=prefix,
                      tp=tp, obs=obs)
    prime = list(prime() if callable(prime) else prime or [])
    for r in prime:
        eng.submit(r)
    eng.run()
    wall = _drive_open_loop(eng, reqs, arrivals)
    st = eng.pager.stats()
    m = obs.metrics
    sec = {
        "wall_s": round(wall, 3),
        "prefill_tokens": int(m.get("engine.prefill.tokens").value),
        "prefix_hit_tokens": int(m.get("prefix.hit_tokens").value),
        "blocks_saved": int(m.get("kv.pool.blocks_saved").value),
        "pool_peak_blocks": int(st.peak_in_use),
        "pool_num_blocks": int(st.num_blocks),
    }
    toks = [list(map(int, r.out))
            for r in sorted(prime + list(reqs), key=lambda r: r.rid)]
    return sec, toks


def _bench_prefix_tp_inner(smoke: bool) -> dict:
    """Cache-on/off identity at TP=1 and TP=2 on a short slice of the
    prefix trace. Must run with >= 2 visible devices (bench_prefix_cache
    arranges that). The pager — and with it the radix cache's block
    sharing — is shard-agnostic host metadata, so the gate is pure token
    bit-identity, per tp and across tp."""
    cfg = _cfg(smoke)
    params = tf.init(cfg, jax.random.PRNGKey(0))
    arrivals, mk_reqs, mk_prime = _prefix_trace(cfg, 24, 40.0)
    toks = {}
    for tp in (1, 2):
        for prefix in (False, True):
            _, toks[tp, prefix] = _serve_prefix(cfg, params, mk_reqs(),
                                                arrivals, prefix=prefix,
                                                tp=tp, prime=mk_prime)
    out = {
        "device_count": jax.device_count(),
        "tokens_identical_tp1": int(toks[1, True] == toks[1, False]),
        "tokens_identical_tp2": int(toks[2, True] == toks[2, False]),
        "tokens_identical_across_tp": int(toks[1, True] == toks[2, True]),
    }
    print(f"[serving] prefix_cache tp: identical tp1="
          f"{out['tokens_identical_tp1']} tp2={out['tokens_identical_tp2']} "
          f"across={out['tokens_identical_across_tp']}")
    return out


def bench_prefix_cache(cfg, params, smoke: bool) -> dict:
    """Prefix-caching section (module docstring, ``prefix_cache``): the
    shared-system-prompt Poisson trace cache-off vs cache-on at TP=1,
    plus the TP=1/TP=2 identity sub-trace (re-execed with two forced
    host devices when this process only sees one, like bench_sharded)."""
    # the "1000 users" trace IS the claim being gated, so smoke keeps it:
    # max_new=2 and the shared prefill keep even 1000 users cheap
    n_users = 1000
    rate = 150.0
    arrivals, mk_reqs, mk_prime = _prefix_trace(cfg, n_users, rate)
    out = {}
    toks = {}
    for key, prefix in (("cache_off", False), ("cache_on", True)):
        reqs = mk_reqs()
        out[key], toks[key] = _serve_prefix(cfg, params, reqs, arrivals,
                                            prefix=prefix, prime=mk_prime)
        print(f"[serving] prefix_cache {key}: "
              f"{out[key]['prefill_tokens']} prefill tokens, pool peak "
              f"{out[key]['pool_peak_blocks']} blocks, "
              f"{out[key]['wall_s']}s")
    res = {
        "n_users": n_users,
        "n_system_prompts": 5,
        "system_prompt_len": 112,
        "eviction_policy": "lru",
        "tokens_identical": int(toks["cache_on"] == toks["cache_off"]),
        "prefill_tokens_ratio": round(
            out["cache_off"]["prefill_tokens"]
            / max(1, out["cache_on"]["prefill_tokens"]), 3),
        "peak_blocks_ratio": round(
            out["cache_off"]["pool_peak_blocks"]
            / max(1, out["cache_on"]["pool_peak_blocks"]), 3),
        **out,
    }
    print(f"[serving] prefix_cache: prefill tokens x"
          f"{res['prefill_tokens_ratio']} down, peak blocks x"
          f"{res['peak_blocks_ratio']} down, tokens identical: "
          f"{bool(res['tokens_identical'])}")
    res["tp"] = (_bench_prefix_tp_inner(smoke) if jax.device_count() >= 2
                 else _cpu_child("--prefix-subprocess", smoke))
    return res


def _kvq_serve(cfg, params, max_new: int, *, kv_quant, attend_impl="gather",
               tp=None):
    """One warmed serve of the fixed kv_quant request trace. Returns
    (sorted token streams, tok/s, resident pool bytes). Every call
    serves the identical seeded requests, so streams are comparable
    across storage formats, attend impls, and TP degrees."""
    eng = ServeEngine(cfg, params, slots=4, max_len=64, seed=0,
                      sampling=SamplingParams(greedy=True), kv_impl="paged",
                      paged_attend_impl=attend_impl, kv_quant=kv_quant,
                      tp=tp)
    _serve_once(eng, cfg, requests_per_slot=1, max_new=2)   # warm compiles
    reqs = _requests(cfg, 8, max_new)
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    while eng.step():
        pass
    wall = time.perf_counter() - t0
    toks = [list(map(int, r.out))
            for r in sorted(reqs, key=lambda r: r.rid)]
    n_tok = sum(len(t) for t in toks)
    return toks, round(n_tok / wall, 2), eng.kv_pool_bytes(), eng


def _bench_kvq_tp_inner(smoke: bool) -> dict:
    """int8 token identity at TP=1 vs TP=2 on the kv_quant trace. Must
    run with >= 2 visible devices (bench_kv_quant arranges that). The
    per-block-per-head scale pools shard on the same kv-heads cut as the
    code pools, so the mesh must not perturb a single emitted token."""
    cfg = _cfg(smoke)
    params = tf.init(cfg, jax.random.PRNGKey(0))
    max_new = 8 if smoke else 16
    toks = {tp: _kvq_serve(cfg, params, max_new, kv_quant="int8", tp=tp)[0]
            for tp in (1, 2)}
    out = {
        "device_count": jax.device_count(),
        "tokens_identical_across_tp": int(toks[1] == toks[2]),
    }
    print(f"[serving] kv_quant tp: int8 identical across tp1/tp2 = "
          f"{out['tokens_identical_across_tp']}")
    return out


def bench_kv_quant(cfg, params, smoke: bool) -> dict:
    """Quantized paged-KV acceptance section (ROADMAP item 5): the same
    seeded greedy trace served from an unquantized paged pool and from
    int8 / q2_14 block-scaled pools (quantize-at-write, CORDIC linear-
    rotation dequant at every read). Records, per format: the greedy
    token match rate vs the unquantized stream, resident pool bytes and
    the bytes collapse ratio at MATCHED block count, bytes/token, and
    tok/s. int8 additionally runs the pallas attend (in-kernel dequant
    must be bit-identical to the gather dequant) and a TP=1/TP=2
    identity sub-trace (subprocess re-exec with two forced host devices
    when needed, like ``sharded``). Gated by check_bench.check_kv_quant."""
    max_new = 8 if smoke else 16
    base_toks, base_tok_s, base_bytes, base_eng = _kvq_serve(
        cfg, params, max_new, kv_quant="none")
    total = sum(len(t) for t in base_toks)
    res = {
        "max_new": max_new,
        "n_requests": len(base_toks),
        "baseline": {"tok_s": base_tok_s, "pool_bytes": int(base_bytes),
                     "bytes_per_token": round(
                         base_eng.pager.block_bytes / base_eng.block_len, 2)},
        "formats": {},
    }
    toks_i8 = None
    for fmt in ("int8", "q2_14"):
        toks, tok_s, pool_bytes, eng = _kvq_serve(cfg, params, max_new,
                                                  kv_quant=fmt)
        if fmt == "int8":
            toks_i8 = toks
        matched = sum(a == b for s1, s2 in zip(base_toks, toks)
                      for a, b in zip(s1, s2))
        spec = eng._kv_quant_spec
        res["formats"][fmt] = {
            "match_rate": round(matched / max(1, total), 4),
            "matched_tokens": matched,
            "total_tokens": total,
            "tok_s": tok_s,
            "pool_bytes": int(pool_bytes),
            "pool_bytes_ratio": round(base_bytes / pool_bytes, 3),
            "bytes_per_token": round(
                eng.pager.block_bytes / eng.block_len, 2),
            "code_bits": spec.code_bits,
        }
        print(f"[serving] kv_quant {fmt}: match {matched}/{total} = "
              f"{res['formats'][fmt]['match_rate']}, pool bytes x"
              f"{res['formats'][fmt]['pool_bytes_ratio']} down, "
              f"{tok_s} tok/s")
    toks_pl, _, _, _ = _kvq_serve(cfg, params, max_new, kv_quant="int8",
                                  attend_impl="pallas")
    res["pallas_tokens_identical"] = int(toks_pl == toks_i8)
    print(f"[serving] kv_quant: int8 gather == int8 pallas: "
          f"{bool(res['pallas_tokens_identical'])}")
    res["tp"] = (_bench_kvq_tp_inner(smoke) if jax.device_count() >= 2
                 else _cpu_child("--kvq-subprocess", smoke))
    return res


def check_obs_sections(res: dict) -> list:
    """Presence/finiteness gate for the observability-driven sections —
    missing = failure, matching the tok/s gate's missing-metric rule.
    Latency magnitudes are host-dependent, so only existence + finiteness
    are enforced here. The poisson half is check_bench.check_poisson
    (shared with the CI belt-check); host-overhead and saturation shapes
    are benchmark-internal, so they stay here."""
    bad = list(check_poisson(res))

    def _finite(path: str) -> None:
        node = res
        try:
            for part in path.split("."):
                node = node[part]
        except (KeyError, TypeError):
            bad.append((path, float("nan"), "present"))
            return
        try:
            v = float(node)
        except (TypeError, ValueError):
            bad.append((path, float("nan"), "numeric"))
            return
        if not np.isfinite(v):
            bad.append((path, v, "finite"))

    for impl in IMPL_KEYS:
        for ph in PHASES:
            _finite(f"host_overhead_1slot.{impl}.{ph}_ms_mean")
    for prof in ("q2_14", "q2_20", "q2_29"):
        for tensor in ("weights", "score_logprobs"):
            _finite(f"saturation.profiles.{prof}.{tensor}.clipped")
    return bad


def check_thresholds(res: dict) -> list:
    """Returns [(metric, value, limit)] for every regressed metric; a
    BASELINES key missing from the results is itself a failure."""
    bad = []
    for key in sorted(BASELINES):
        impl, slots = key.split("/")
        limit = max(FLOOR_TOK_S, BASELINES[key] * (1.0 - TOLERANCE))
        try:
            value = res["impls"][impl]["slots"][slots]["tok_s"]
        except KeyError:
            bad.append((key, float("nan"), limit))
            continue
        if value < limit:
            bad.append((key, value, limit))
    for impl in SPEEDUP_IMPLS:
        key = f"{impl}/speedup_8_over_1"
        try:
            value = res["impls"][impl]["speedup_8_over_1"]
        except KeyError:
            bad.append((key, float("nan"), MIN_SPEEDUP_8_OVER_1))
            continue
        if value < MIN_SPEEDUP_8_OVER_1:
            bad.append((key, value, MIN_SPEEDUP_8_OVER_1))
    bad.extend(check_transient(res))
    bad.extend(check_obs_sections(res))
    bad.extend(check_mixed_chunked(res))
    bad.extend(check_sharded(res))
    bad.extend(check_prefix_cache(res))
    bad.extend(check_kv_quant(res))
    return bad


def check_transient(res: dict) -> list:
    """The kernel-path acceptance gate: the recorded per-row transient
    working set must be max_len-INVARIANT for the pallas attend, scale
    with max_len for the gather attend, and sit below gather at every
    recorded max_len. A missing entry is itself a failure."""
    bad = []
    try:
        tr = {ml: {im: float(res["transient"][str(ml)][im])
                   for im in ("gather", "pallas")}
              for ml in TRANSIENT_MAX_LENS}
    except KeyError:
        return [("transient/<missing>", float("nan"), float("nan"))]
    lo, hi = min(TRANSIENT_MAX_LENS), max(TRANSIENT_MAX_LENS)
    if tr[hi]["pallas"] != tr[lo]["pallas"]:
        bad.append((f"transient/pallas@{hi}==@{lo}", tr[hi]["pallas"],
                    tr[lo]["pallas"]))
    if tr[hi]["gather"] <= tr[lo]["gather"]:
        bad.append((f"transient/gather@{hi}>@{lo}", tr[hi]["gather"],
                    tr[lo]["gather"]))
    for ml in TRANSIENT_MAX_LENS:
        if tr[ml]["pallas"] >= tr[ml]["gather"]:
            bad.append((f"transient/pallas<gather@{ml}", tr[ml]["pallas"],
                        tr[ml]["gather"]))
    return bad


def run(csv_rows: list, n: int = 1_000_000, reps: int = 5) -> None:
    """Evaluator latency microbench (the benchmarks/run.py CSV protocol;
    formerly benchmarks/latency.py — serving.py is now the single
    latency-measurement entry point): us per call on an n-element tensor
    per sigmoid evaluator, host-CPU wall time. The CORDIC fixed path
    timing on CPU reflects the emulation (26 unrolled integer stages), not
    TPU VPU throughput — the structural VPU op count is in resources.py."""
    import jax.numpy as jnp

    from repro.core import sigmoid as S

    def _time(fn, x) -> float:
        fn(x).block_until_ready()  # compile+warm
        t0 = time.perf_counter()
        for _ in range(reps):
            fn(x).block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e6  # us

    x = jnp.asarray(np.random.default_rng(0).uniform(-1, 1, n), jnp.float32)
    cases = {
        "exact_jnp_sigmoid": jax.jit(S.sigmoid_exact),
        "cordic_float": jax.jit(lambda v: S.sigmoid_cordic_float(v)),
        "cordic_fixed_q2.14": jax.jit(lambda v: S.sigmoid_cordic_fixed(v)),
        "r2_cordic_fixed": jax.jit(lambda v: S.sigmoid_r2_cordic_fixed(v)),
        "pwl_16seg": jax.jit(lambda v: S.sigmoid_pwl_fixed(v, 16)),
        "lut_256": jax.jit(lambda v: S.sigmoid_lut_fixed(v, 256)),
    }
    for name, fn in cases.items():
        us = _time(fn, x)
        csv_rows.append((f"latency/{name}", round(us, 1),
                         f"{n / us:.0f} elem/us-e6; host-CPU measurement"))

    # integer end-to-end path (no float boundary) — the quantized-serving
    # mode
    xq = jnp.asarray(
        np.random.default_rng(1).integers(-(1 << 14), 1 << 14, n), jnp.int32)
    from repro.core.cordic import sigmoid_mr_q

    us = _time(jax.jit(sigmoid_mr_q), xq)
    csv_rows.append(("latency/cordic_fixed_int_io", round(us, 1),
                     "integer in/out (quantized pipeline)"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--no-check", action="store_true",
                    help="record only; skip the regression-threshold gate")
    ap.add_argument("--trace-out", default=None,
                    help="export the Poisson run's request-lifecycle + "
                         "engine-phase Chrome trace (Perfetto-loadable "
                         "JSON) to this path")
    ap.add_argument("--metrics-json", default=None,
                    help="export the Poisson run engine's full metrics-"
                         "registry snapshot to this path")
    ap.add_argument("--evaluators", action="store_true",
                    help="also run the evaluator latency microbench "
                         "(always on in full mode; ~1M-element tensors)")
    ap.add_argument("--sharded-subprocess", action="store_true",
                    help=argparse.SUPPRESS)  # internal: bench_sharded child
    ap.add_argument("--prefix-subprocess", action="store_true",
                    help=argparse.SUPPRESS)  # internal: prefix tp child
    ap.add_argument("--kvq-subprocess", action="store_true",
                    help=argparse.SUPPRESS)  # internal: kv_quant tp child
    args = ap.parse_args(argv)
    compile_cache.setup()

    child = (_bench_sharded_inner if args.sharded_subprocess
             else _bench_prefix_tp_inner if args.prefix_subprocess
             else _bench_kvq_tp_inner if args.kvq_subprocess else None)
    if child is not None:
        out = child(args.smoke)
        out["backend"] = jax.default_backend()
        print(_CHILD_MARKER + json.dumps(out))
        return 0

    cfg = _cfg(args.smoke)
    params = tf.init(cfg, jax.random.PRNGKey(0))
    res = bench(cfg, params, args.smoke)
    res["meta"] = {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        # the throughput benches above run the unsharded engine: one data
        # row per slot, no model-axis mesh
        "tp": 1,
        "axis_sizes": {"data": jax.device_count(), "model": 1},
        # ...and an unquantized f32 pool; the quantized-KV plane is
        # measured (and gated) in the dedicated kv_quant section below
        "kv_quant": "none",
    }
    res["poisson"] = bench_poisson(cfg, params, args.smoke,
                                   trace_out=args.trace_out,
                                   metrics_json=args.metrics_json)
    res["mixed_chunked"] = bench_mixed_chunked(cfg, params, args.smoke)
    res["host_overhead_1slot"] = bench_host_overhead(cfg, params, args.smoke)
    res["saturation"] = bench_saturation(cfg, params)
    res["sharded"] = bench_sharded(args.smoke)
    res["prefix_cache"] = bench_prefix_cache(cfg, params, args.smoke)
    res["kv_quant"] = bench_kv_quant(cfg, params, args.smoke)
    if args.evaluators or not args.smoke:
        rows: list = []
        run(rows, n=1 << 16 if args.smoke else 1_000_000,
            reps=3 if args.smoke else 5)
        res["evaluator_us"] = {name.split("/", 1)[1]: value
                               for name, value, _ in rows}
    with open(args.out, "w") as f:
        json.dump(res, f, indent=2, sort_keys=True)
    for impl in IMPL_KEYS:
        r = res["impls"][impl]
        print(f"[serving] {impl}: "
              f"{json.dumps({k: v['tok_s'] for k, v in r['slots'].items()})} "
              f"tok/s, x{r['speedup_8_over_1']} at 8 slots")
    print(f"[serving] wrote {args.out}")

    if not args.no_check and res["mode"] == "smoke":
        bad = check_thresholds(res)
        if bad:
            for name, value, limit in bad:
                # tok/s metrics gate on a lower bound; transient/* entries
                # are byte-valued relation checks — keep the message generic
                print(f"SERVING REGRESSION: {name} = {value:.6g} "
                      f"(limit {limit:.6g})", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
