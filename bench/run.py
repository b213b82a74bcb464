"""Serving benchmark: one cell of BENCHMARK.json, on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: sizes as
run, the engine settings of the deployment, source and cuts) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/loadgen.py``). Each metric
has a reader of its own, ``bench/metrics/<metric>.py``; the limits of the
correctness check are in ``bench/limits/<cell>.json``. The configuration
names, by path under ``bench/``, the three modules that know the model's
shape (bench/weights.py says what each provides): its ``layout`` (the
program's ModelConfig and the seeded weights), its plain ``reference`` and
its ``costs`` (the model's operations). Nothing here names a cell, a
configuration, a metric or a model family: a new one is new files and
entries.

A run:
  1. checks that JAX sees TPUs, as many as the cell asks for, and exits
     non-zero with no result otherwise;
  2. places JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
  3. loads the configuration's layout, reference and costs modules, checks
     that the layout's parameter tree is the program's own, draws the
     weights on the device from ``--seed`` in one jitted call (the
     layout's ``program_tree``), builds ``repro.serve.engine.ServeEngine``, and
     serves a fixed set of warm-up requests that compiles every prefill
     and decode shape the cell's traffic uses, and no other;
  4. queues the backlog, or starts the open-loop arrivals a few seconds
     early, then measures for ``--seconds``: every output token is stamped
     with the host time ``step()`` returned. A compile inside the window
     fails the run;
  5. with ``--trace 1``, records a profiler trace of the window and reduces
     it (bench/trace_reduce.py) to the per-layer metrics, device busy time
     and a breakdown; with ``--trace 0`` reports the end-to-end metrics;
  6. reads the peak device memory, frees the program's weights and caches,
     and checks a seeded sample of the finished requests, the longest among
     them, against the configuration's plain reference (its ``reference``
     module, given the layout's ``Source`` of the same weights): the widest
     gap by which a served token's reference logit lies below the
     reference's best must stay within the cell's limit. With
     ``control=True`` (bench/control.py) the reference's lower-precision
     control takes the program's place in that comparison: the tokens it
     puts first at the same positions are compared, against the same limit.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number the check compared,
beside its limit. The compared numbers are also the last lines of
standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WORK_DIR = os.path.join(ROOT, ".bench_work")


class RunFailed(Exception):
    """The run cannot report a result (no chip, a configuration without
    its model modules, a compile in the window, a metric the cell must
    report that could not be read)."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def resolve(cell_name: str) -> dict:
    """The cell, its configuration, traffic, limits and metric entries."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise RunFailed(f"no workload {cell_name!r} in BENCHMARK.json")
    cell = cells[cell_name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (cell_name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"cell": cell, "config": config,
            "traffic": load_json("traffic", cell["traffic"] + ".json"),
            "limits": load_json("limits", cell_name + ".json"),
            "end_to_end": e2e, "per_layer": layer}


#: the modules a configuration names, and what each has to provide
ROLES = {"layout": ("model_config", "abstract_tree", "program_tree",
                    "Source"),
         "reference": ("widest_gap",),
         "costs": ("token_flops", "prompt_flops")}


def load_module(path: str):
    """The module at ``path`` (a file under bench/, relative to the
    checkout), executed once per process."""
    full = os.path.realpath(os.path.join(ROOT, path))
    name = "bench_" + re.sub(r"\W", "_", os.path.relpath(full, BENCH))
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, full)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[name] = mod
        try:
            mod_spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def reader(name: str):
    return load_module(os.path.join("bench", "metrics", name + ".py")).read


def model_modules(config: dict) -> types.SimpleNamespace:
    """The ``layout``, ``reference`` and ``costs`` modules the
    configuration names (paths under bench/, see bench/weights.py)."""
    bench = os.path.realpath(BENCH)
    mods = {}
    for role, needs in ROLES.items():
        path = config.get(role)
        if not path:
            raise RunFailed(f"configuration {config.get('name')!r} names no "
                            f"{role!r} module")
        full = os.path.realpath(os.path.join(ROOT, path))
        if (os.path.commonpath([full, bench]) != bench
                or not os.path.isfile(full)):
            raise RunFailed(f"the {role!r} module {path!r} is no file under "
                            f"bench/")
        mod = load_module(path)
        missing = [f for f in needs if not hasattr(mod, f)]
        if missing:
            raise RunFailed(f"the {role!r} module {path!r} lacks {missing}")
        mods[role] = mod
    return types.SimpleNamespace(**mods)


def tpu_devices(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} device_kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise RunFailed(f"JAX found no TPU (platform {d.platform!r})")
    if len(devs) < chips:
        raise RunFailed(f"the cell needs {chips} chips, JAX found "
                        f"{len(devs)}")
    return devs


class CompileWatch:
    """Counts backend compiles and persistent-cache reads (jax.monitoring)."""

    def __init__(self):
        import jax

        self.events = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1
            self.seconds += secs

    def _ev(self, event, **_):
        if event in ("/jax/compilation_cache/cache_hits",
                     "/jax/compilation_cache/cache_misses"):
            self.events += 1


def check_layout(layout, cfg, m: dict, dtype) -> None:
    """The weights the layout module lays out must be the program's tree."""
    import jax

    from repro.models import transformer as tf

    want = jax.eval_shape(lambda: tf.init(cfg, jax.random.PRNGKey(0), dtype))
    got = layout.abstract_tree(m, dtype)
    flat = lambda t: {jax.tree_util.keystr(p): (tuple(x.shape), str(x.dtype))
                      for p, x in jax.tree_util.tree_leaves_with_path(t)}
    if flat(want) != flat(got):
        raise RunFailed(f"the program's parameter tree changed: {flat(want)} "
                        f"against {flat(got)}")


def warm_up(eng, planned, vocab: int) -> int:
    """Serve, to completion, one request per prefill width the traffic's
    prompts are admitted at, alone and (where the engine packs prefill
    rows) two at once: every prefill shape the window can dispatch, the
    decode step, first-token sampling and slot release. The same requests
    for every seed. Returns the number served."""
    import numpy as np

    from repro.serve.engine import Request

    widths = sorted({eng.scheduler.admission_width(len(p.prompt))
                     for p in planned})
    rid, n = 1 << 30, 0
    for w in widths:
        for together in sorted({1, min(2, eng.prefill_batch)}):
            for _ in range(together):
                eng.submit(Request(rid=rid, prompt=(np.arange(w) % vocab)
                                   .astype(np.int32), max_new_tokens=2))
                rid += 1
            done = eng.run()
            n += len(done)
            if any(r.error for r in done):
                raise RunFailed(f"a warm-up request was rejected: "
                                f"{[r.error for r in done if r.error]}")
    return n


def log_tails(drv, t_open: float, t_close: float) -> None:
    """Earlier lines, not metrics: the latency percentiles around the ones
    a cell reports, and the window's longest steps and longest gaps
    between steps, which say where a stall came from."""
    import numpy as np

    ttft = [1e3 * (tr.times[0] - tr.due) for tr in drv.tracked
            if tr.times and t_open <= tr.times[0] <= t_close]
    itl = [1e3 * (b - a) for tr in drv.tracked
           for a, b in zip(tr.times, tr.times[1:]) if t_open <= b <= t_close]
    for name, v in (("first token", ttft), ("token gap", itl)):
        if v:
            q = np.percentile(v, [50, 85, 90, 95, 99, 100])
            log(f"{name} ms over {len(v)}: p50/85/90/95/99/max "
                + " / ".join(f"{x:.1f}" for x in q))
    steps = [s for s in drv.steps if t_open <= s.t1 <= t_close]
    longest = sorted(steps, key=lambda s: s.t0 - s.t1)[:3]
    log("longest steps: " + ", ".join(
        f"{1e3 * (s.t1 - s.t0):.1f} ms ({s.decoded} decoded, "
        f"{s.prompt_tokens} prompt tokens done)" for s in longest))
    between = [1e3 * (b.t0 - a.t1) for a, b in zip(steps, steps[1:])]
    if between:
        log(f"longest gap between steps: {max(between):.1f} ms")


def free(tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


def choose_sample(tracked, k: int, seed: int):
    """The finished requests with the most served tokens, plus k-1 more
    drawn from the seed."""
    import numpy as np

    done = [tr for tr in tracked
            if tr.req.done and not tr.req.error and tr.req.out]
    if not done:
        return []
    done.sort(key=lambda tr: tr.planned.idx)
    longest = max(done, key=lambda tr: (len(tr.req.out), -tr.planned.idx))
    rest = [tr for tr in done if tr is not longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def run(argv=None, *, require_tpu: bool = True, override=None, fault=None,
        control: bool = False, check: bool = True):
    """One benchmark run; returns the result object (raises RunFailed).

    ``control=True`` puts the reference's control in the program's place in
    the comparison that decides ``correct`` (bench/control.py); the program's
    own reading is kept beside it. ``check=False`` leaves the reference out
    (bench/sweep.py, which reads load only). ``require_tpu=False``,
    ``override`` (a dict that replaces parts of the resolved cell) and
    ``fault`` (a callable given the engine before the window) are for the
    benchmark's own CPU tests."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    r = resolve(args.workload)
    if override:
        r.update(override)
    cell, config, traffic = r["cell"], r["config"], r["traffic"]
    m, eng_spec = config["model"], config["engine"]
    chips, tp = int(cell["chips"]), int(eng_spec.get("tp", 1))

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = tpu_devices(chips) if require_tpu else jax.devices()
    mods = model_modules(config)
    import loadgen
    import peaks
    import trace_reduce
    from repro.launch import compile_cache
    from repro.launch import mesh as mesh_lib
    from repro.distributed import sharding as shd
    from repro.serve.engine import Request, ServeEngine

    log(f"compilation cache: {compile_cache.setup()}")
    kind = devs[0].device_kind
    pk = peaks.peaks_for(kind) if require_tpu else peaks.PEAKS["TPU v5 lite"]
    watch = CompileWatch()
    t = {"start": T_START, "jax": time.perf_counter()}

    dtype = jnp.dtype(m["dtype"])
    cfg = mods.layout.model_config(m, eng_spec)
    check_layout(mods.layout, cfg, m, dtype)
    mesh = mesh_lib.mesh_or_none(tp)
    shardings = (None if mesh is None else shd.serve_param_shardings(
        cfg, mods.layout.abstract_tree(m, dtype), mesh))
    params = mods.layout.program_tree(m, args.seed, dtype, shardings)
    jax.block_until_ready(params)
    t["weights"] = time.perf_counter()
    eng = ServeEngine(
        cfg, params, slots=eng_spec["slots"], max_len=eng_spec["max_len"],
        kv_impl="paged", block_len=eng_spec["block_len"],
        num_blocks=eng_spec["num_blocks"],
        paged_attend_impl=eng_spec["paged_attend_impl"],
        prefill_chunk=eng_spec.get("prefill_chunk"),
        prefill_batch=eng_spec.get("prefill_batch"),
        max_prefill_tokens=eng_spec.get("max_prefill_tokens"),
        mesh=mesh, greedy=True)
    t["engine"] = time.perf_counter()
    planned = loadgen.plan(traffic, args.seed, m["vocab_size"], args.seconds)
    n_warm = warm_up(eng, planned, m["vocab_size"])
    t["warm"] = time.perf_counter()
    log(f"warm-up: {n_warm} requests, compile {watch.seconds:.3f} s over "
        f"{watch.events} compile/cache events, jit caches "
        f"{eng.compile_counts()}")
    if fault is not None:
        fault(eng)

    annotate = ((lambda name: jax.profiler.TraceAnnotation(name))
                if args.trace else (lambda name: contextlib.nullcontext()))
    drv = loadgen.Driver(
        eng, lambda p: Request(rid=p.idx, prompt=p.prompt,
                               max_new_tokens=p.max_new), annotate)
    clock = time.perf_counter
    arrival = traffic["arrival"]
    if arrival["kind"] == "backlog":
        drv.schedule(planned, clock())
        drv.submit_due(clock())
        chunk = eng_spec.get("prefill_chunk") or eng_spec["max_len"]
        drv.fill(eng_spec["slots"],
                 patience=1 + math.ceil(eng_spec["max_len"] / chunk))
        log(f"backlog: {len(planned)} requests queued, {drv.decoding()} "
            f"decoding when the window opens")
    else:
        t_zero = clock() + float(arrival.get("lead_s", 0.0))
        drv.schedule(planned, t_zero)
        drv.run_until(t_zero)

    events0, jits0 = watch.events, eng.compile_counts()
    obs = None
    trace_dir = os.path.join(WORK_DIR, "trace")
    if args.trace:
        from repro.obs import Observability

        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        obs = Observability()
        eng.attach_obs(obs)
        jax.profiler.start_trace(trace_dir)
    t_open = clock()
    setup_s = t_open - T_START
    with annotate("bench.window"):
        drv.run_until(t_open + args.seconds)
        t_close = clock()
    if args.trace:
        jax.effects_barrier()
        jax.profiler.stop_trace()
    if watch.events != events0 or eng.compile_counts() != jits0:
        raise RunFailed(
            f"compiles inside the window: {watch.events - events0} "
            f"compile/cache events, jit caches {jits0} -> "
            f"{eng.compile_counts()}")

    used = devs[:max(chips, tp)]
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in used]
    log(f"set-up {setup_s:.3f} s: jax {t['jax'] - T_START:.3f}, weights "
        f"{t['weights'] - t['jax']:.3f}, engine {t['engine'] - t['weights']:.3f},"
        f" warm-up {t['warm'] - t['engine']:.3f}, load before the window "
        f"{t_open - t['warm']:.3f}; backend compile {watch.seconds:.3f} s")
    late = drv.lateness(t_open, t_close)
    if late:
        log(f"generator lateness over {len(late)} arrivals: mean "
            f"{1e3 * float(np.mean(late)):.3f} ms, p99 "
            f"{1e3 * float(np.percentile(late, 99)):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms")
    else:
        log("generator lateness: no arrivals due in the window (backlog)")
    log_tails(drv, t_open, t_close)

    ctx = types.SimpleNamespace(
        args=args, cell=cell, config=config, m=m, traffic=traffic, tp=tp,
        chips=chips, peaks=pk, costs=mods.costs, drv=drv, t_open=t_open,
        t_close=t_close, setup_s=setup_s, obs=obs, trace=None, planes=None,
        log=log)
    result_device = {"platform": devs[0].platform, "kind": kind,
                     "count": len(devs),
                     "memory_peak_bytes": max((b for b in mem if b), default=0)}
    breakdown = None
    if args.trace:
        tr = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        planes = trace_reduce.device_planes(tr)[:max(chips, tp)]
        ctx.trace, ctx.planes = tr, planes
        lo, hi = trace_reduce.window(tr)
        busy = [trace_reduce.busy_ns(tr, p) for p in planes]
        result_device["busy_s"] = float(np.mean(busy)) * 1e-9
        result_device["window_s"] = (hi - lo) * 1e-9
        breakdown = trace_reduce.summary(tr)
        want = r["per_layer"]
    else:
        want = r["end_to_end"]
    metrics = {}
    for spec in want:
        v = reader(spec["name"])(ctx)
        if v is None:
            raise RunFailed(f"metric {spec['name']} found nothing to read in "
                            f"cell {cell['name']}")
        metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
        log(f"{spec['name']} = {float(v)!r} {spec['unit']}")

    def waiting(t):
        return sum(1 for tr in drv.tracked if tr.submitted <= t
                   and not 0 <= tr.req.t_admit <= t)

    load = {"due_in_window": sum(1 for tr in drv.tracked
                                 if t_open <= tr.due < t_close),
            "first_tokens_in_window": sum(
                1 for tr in drv.tracked
                if tr.times and t_open <= tr.times[0] <= t_close),
            "waiting_at_open": waiting(t_open),
            "waiting_at_close": waiting(t_close),
            "steps_in_window": sum(1 for s in drv.steps
                                   if t_open <= s.t1 <= t_close)}
    log(f"load: {json.dumps(load)}")

    # the check: program state freed first, so the reference fits
    free(eng._caches)
    free(params)
    free(eng.params)
    del eng, params
    ctx.drv.eng = None
    gc.collect()
    t_ref = clock()
    sample = choose_sample(drv.tracked, int(traffic["check"]["requests"]),
                           args.seed)
    bad = sum(1 for tr in drv.tracked
              if tr.req.error or any(not 0 <= x < m["vocab_size"]
                                     for x in tr.req.out))
    program_gap = None
    if sample and check:
        src = mods.layout.Source(m, args.seed, dtype)
        program_gap, control_gap, n_tok = mods.reference.widest_gap(
            src, m, [(tr.req.prompt, list(tr.req.out)) for tr in sample],
            control=control)
        gap = control_gap if control else program_gap
    else:
        gap, n_tok = None, 0
    log(f"check: {len(sample)} finished requests, {n_tok} served tokens "
        f"(longest {max((len(s.req.out) for s in sample), default=0)}), "
        f"reference {clock() - t_ref:.3f} s")
    lim = r["limits"]
    compared = {
        "widest_logit_gap": {"value": gap,
                             "limit": lim["widest_logit_gap"]["limit"]},
        "bad_requests": {"value": bad, "limit": 0},
        "tokens_checked": {"value": n_tok,
                           "limit": lim.get("min_tokens_checked", 1)},
    }
    correct = (gap is not None
               and gap <= compared["widest_logit_gap"]["limit"] and bad == 0
               and n_tok >= compared["tokens_checked"]["limit"])
    out = {"correct": bool(correct),
           "attempted": len(drv.tracked),
           "failed": sum(1 for tr in drv.tracked if tr.req.error),
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["load"] = load
    if control:
        out["program_widest_logit_gap"] = program_gap
    out["compared"] = compared
    return out


def main(argv=None) -> int:
    try:
        out = run(argv)
    except RunFailed as e:
        print(f"[bench] run failed: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["compared"].items():
        rel = "<=" if name != "tokens_checked" else ">="
        print(f"[bench] compared {name} = {c['value']!r} (limit {rel} "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
