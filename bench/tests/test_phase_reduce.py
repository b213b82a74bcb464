"""The engine-phase and collective reduction (phase_reduce.py) on
hand-made traces with known answers, and on recorded v5e traces."""
import json
import os
import types

import pytest

import phase_reduce as P
import trace_reduce as T

MS = 1_000_000  # ns
TPU0 = "/device:TPU:0"
FIXTURES = os.path.join(os.path.dirname(T.__file__), "fixtures")


def _phased():
    # window 0-100 ms; busy [0,10] [20,35] [60,65] [90,100], so idle
    # [10,20] [35,60] [65,90]; two engine steps with their phases nested,
    # inside the benchmark's own step spans
    ops = [["fusion.1", 0, 10 * MS, ""], ["fusion.2", 20 * MS, 15 * MS, ""],
           ["fusion.3", 60 * MS, 5 * MS, ""],
           ["fusion.4", 90 * MS, 10 * MS, ""]]
    host = [["bench.window", 0, 100 * MS], ["bench.step", 7 * MS, 32 * MS],
            ["bench.sleep", 40 * MS, 15 * MS],
            ["bench.step", 61 * MS, 35 * MS]]
    prog = [["engine.step", 8 * MS, 30 * MS],
            ["engine.admit", 8 * MS, 4 * MS],
            ["engine.plan", 9 * MS, 2 * MS],
            ["engine.dispatch", 12 * MS, 3 * MS],
            ["engine.host_sync", 15 * MS, 21 * MS],
            ["engine.sample_copy", 36 * MS, 2 * MS],
            ["engine.step", 62 * MS, 33 * MS],
            ["engine.admit", 62 * MS, 8 * MS],
            ["engine.dispatch", 70 * MS, 2 * MS],
            ["engine.host_sync", 72 * MS, 21 * MS],
            ["engine.sample_copy", 93 * MS, 2 * MS]]
    return {"devices": {TPU0: {"ops": ops, "modules": []}}, "host": host,
            "program": prog}


def test_idle_is_split_at_span_boundaries_to_the_innermost_span():
    got = P.idle_by_phase(_phased(), TPU0)
    assert got == {"engine.plan": 1 * MS, "engine.admit": 6 * MS,
                   "engine.dispatch": 5 * MS, "engine.host_sync": 24 * MS,
                   "engine.sample_copy": 2 * MS, "bench.step": 1 * MS,
                   "bench.sleep": 15 * MS, "none": 6 * MS}
    assert sum(got.values()) == 100 * MS - T.busy_ns(_phased(), TPU0)


def test_phase_summary_and_a_trace_without_program_spans():
    t = _phased()
    top = P.phase_summary(t, top=2)["idle_by_phase"]
    assert top == [["engine.host_sync", pytest.approx(0.024)],
                   ["bench.sleep", pytest.approx(0.015)]]
    # older traces have no "program" key: bench spans label everything
    del t["program"]
    assert P.idle_by_phase(t, TPU0) == {"bench.step": 39 * MS,
                                        "bench.sleep": 15 * MS, "none": 6 * MS}


def test_host_gap_per_step_reads_idle_inside_engine_steps():
    t = _phased()
    steps = [ev for ev in t["program"] if ev[0] == "engine.step"]
    # step 1 covers idle [10,20] and [35,38], step 2 [65,90]: 38 ms / 2
    assert P.host_gap_ms_per_step(t, [TPU0], steps) == pytest.approx(19.0)
    # a second chip idle the whole window: the mean over chips
    t["devices"]["/device:TPU:1"] = {"ops": [], "modules": []}
    assert P.host_gap_ms_per_step(t, [TPU0, "/device:TPU:1"], steps) == \
        pytest.approx((38 + 63) / 2 / 2)
    assert P.host_gap_ms_per_step(t, [TPU0], []) is None


def _op(inst, shape, kind, operands="%a"):
    return f"%{inst} = {shape}{{1,0}} {kind}({operands}), channel_id=1"


def _collectives():
    loop = "%while.2 = (s32[], bf16[16]{0}) while(%tuple.56), body=%b"
    ops = [[loop, 0, 50 * MS, ""],
           [_op("fusion.1", "bf16[16,7168]", "fusion"), 0, 10 * MS, ""],
           [_op("all-reduce.4", "bf16[16,7168]", "all-reduce"), 10 * MS,
            4 * MS, ""],
           [_op("fusion.2", "bf16[16,7168]", "fusion"), 12 * MS, 8 * MS, ""],
           [_op("all-gather-start.1", "f32[16,32256]", "all-gather-start"),
            30 * MS, 2 * MS, ""],
           [_op("all-gather-done.1", "f32[16,32256]", "all-gather-done"),
            32 * MS, 3 * MS, ""],
           [_op("gqa_decode.3", "f32[16,2,7,128]", "custom-call"), 40 * MS,
            5 * MS, ""],
           [_op("all-reduce.5", "bf16[16,7168]", "all-reduce"), 60 * MS,
            10 * MS, ""]]
    return {"devices": {TPU0: {"ops": ops, "modules": []},
                        "/device:TPU:1": {"ops": ops[:2], "modules": []}},
            "host": [["bench.window", 0, 100 * MS]]}


def test_collective_share_counts_collectives_alone_on_the_chip():
    t = _collectives()
    assert P.opcode(t["devices"][TPU0]["ops"][0][0]) == "while"
    assert P.opcode(t["devices"][TPU0]["ops"][4][0]) == "all-gather-start"
    # the loop holds the other ops and is left out; all-reduce.4 runs
    # alone for 2 ms, the all-gather halves 5 ms, all-reduce.5 10 ms
    assert P.collective_only_ns(t, TPU0) == 17 * MS
    assert P.collective_share(t, [TPU0]) == pytest.approx(17.0)
    assert P.collective_share(t, [TPU0, "/device:TPU:1"]) == \
        pytest.approx(8.5)
    assert P.collective_share(t, ["/device:TPU:1"]) is None


def _ctx(trace, obs, steps_t0, t_open=500.0):
    drv = types.SimpleNamespace(steps=[types.SimpleNamespace(t0=t)
                                       for t in steps_t0])
    return types.SimpleNamespace(trace=trace, obs=obs, drv=drv,
                                 t_open=t_open, planes=[TPU0],
                                 log=lambda msg: None)


def test_the_engine_span_log_is_laid_over_the_trace_by_the_step_spans():
    t = _phased()
    prog = t.pop("program")
    # host clock: the window opened at 500.0 s, trace time 0; each
    # bench.step span opens 2 us after the load generator's stamp
    t_open = 500.0
    steps_t0 = [t_open + (s - 2000) * 1e-9 for n, s, _ in t["host"]
                if n == "bench.step"]
    epoch = 480.0
    log = [(n[len("engine."):], t_open - epoch + s * 1e-9,
            t_open - epoch + (s + d) * 1e-9) for n, s, d in prog]
    obs = types.SimpleNamespace(spans=log, epoch=epoch)
    ctx = _ctx(t, obs, [t_open - 1.0] + steps_t0, t_open)
    got = P.program_spans(ctx)
    for (n, s, d), (n2, s2, d2) in zip(got, prog):
        assert n == n2
        assert s == pytest.approx(s2 + 2000, abs=1e-3)
        assert d == pytest.approx(d2, abs=1e-3)
    # the spans sit 2 us after the host stamps, like the step spans
    assert P.host_gap_metric(ctx) == pytest.approx(19.001, abs=1e-6)
    # unpaired steps: the window's open gives the offset instead
    ctx = _ctx(t, obs, steps_t0[:1], t_open)
    assert P.program_spans(ctx)[0][1] == pytest.approx(prog[0][1], abs=1e-3)


def test_a_program_without_engine_spans_is_read_by_bench_step():
    t = _phased()
    del t["program"]
    said = []
    ctx = _ctx(t, types.SimpleNamespace(), [])
    ctx.log = said.append
    # bench.step [7,39] covers idle [10,20] and [35,39], [61,96] [65,90]
    assert P.host_gap_metric(ctx) == pytest.approx((10 + 4 + 25) / 2)
    assert "bench.step stands in" in said[0]


def test_kernel_share_by_name_and_by_signature():
    named = ('%cordic_silu_mul.5 = bf16[32,1024]{1,0} custom-call('
             'bf16[32,1024]{1,0} %g, bf16[32,1024]{1,0} %u), '
             'custom_call_target="tpu_custom_call"')
    unnamed = named.replace("%cordic_silu_mul.5", "%closed_call.10")
    other = _op("fusion.1", "bf16[16,7168]", "fusion")
    for op in (named, unnamed):
        t = {"devices": {TPU0: {"ops": [[op, 0, 10 * MS, ""],
                                        [other, 10 * MS, 30 * MS, ""]],
                                "modules": []}},
             "host": [["bench.window", 0, 100 * MS]]}
        ctx = _ctx(t, None, [])
        assert P.kernel_share(ctx, "cordic_silu_mul",
                              P.SILU_MUL_SIGNATURE) == pytest.approx(25.0)


def test_cordic_share_reader_on_the_recorded_decode_trace():
    import run as bench_run

    with open(os.path.join(FIXTURES, "trace_v5e_decode3.json")) as f:
        t = json.load(f)
    ctx = _ctx(t, None, [])
    # the kernels of that trace carry no names: found by signature, the
    # same 72 calls silu_mul_roofline.chat reads
    got = bench_run.reader("cordic_share.chat")(ctx)
    assert got == pytest.approx(100 * 9295528 / 95742958)
    # its idle time, with no engine spans, split at the load generator's spans:
    # the same total as trace_reduce.idle_by_host, which labels each gap
    # whole by its middle
    assert P.idle_by_phase(t, TPU0) == {"bench.step": 10904080,
                                        "bench.record": 558889,
                                        "none": 135151}


# --- a recorded trace with the engine's spans: yi9b.decode_batch, v5e ---

def _phases_fixture():
    with open(os.path.join(FIXTURES, "trace_v5e_decode_phases.json")) as f:
        return json.load(f)


def test_recorded_phases_split_the_idle_time():
    t = _phases_fixture()
    assert T.window(t) == (1000000, 258968140)
    assert T.busy_ns(t, TPU0) == 234591297
    got = P.idle_by_phase(t, TPU0)
    assert sum(got.values()) == pytest.approx(258968140 - 1000000 - 234591297)
    # the decode steps' gaps: the wait for the sampled tokens after the
    # device is done, and the next dispatch before the device starts
    assert got["engine.host_sync"] == pytest.approx(7854133)
    assert got["engine.dispatch"] == pytest.approx(7158075)
    assert got["engine.prefill_dispatch"] == pytest.approx(5489445)
    assert got["bench.record"] == pytest.approx(1152810)
    inside = sum(v for k, v in got.items() if k.startswith("engine."))
    assert inside / (inside + got["bench.step"]) > 0.98
    steps = [ev for ev in t["program"] if ev[0] == "engine.step"]
    assert len(steps) == 3
    assert P.host_gap_ms_per_step(t, [TPU0], steps) == pytest.approx(
        7.1673876666666665)


def test_recorded_kernels_carry_their_names_and_signatures():
    import run as bench_run

    t = _phases_fixture()
    ctx = types.SimpleNamespace(
        trace=t, planes=[TPU0], tp=1, log=lambda msg: None,
        m={"num_kv_heads": 4, "num_heads": 32, "head_dim": 128},
        config={"engine": {"slots": 16}})
    # the signature readers find exactly the named ops
    sig = T.ops_matching(t, TPU0, _load_metric(
        "paged_attn_roofline.batch").kernel_pattern(ctx))
    named = T.ops_matching(t, TPU0, r"^%gqa_decode\.\d+ = ")
    assert len(sig) == len(named) == 72 and sig == named
    sig = T.ops_matching(t, TPU0, P.SILU_MUL_SIGNATURE)
    named = T.ops_matching(t, TPU0, r"^%cordic_silu_mul\.\d+ = ")
    assert len(sig) == 96 and sig == named
    k = sum(ev[2] for ev in named)
    assert bench_run.reader("cordic_share.chat")(ctx) == pytest.approx(
        100 * k / 234591297)


def _load_metric(name):
    import importlib.util

    path = os.path.join(os.path.dirname(T.__file__), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "pm_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
