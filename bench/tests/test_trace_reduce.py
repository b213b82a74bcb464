"""The trace reduction on a hand-made trace with known answers."""
import pytest

import costs
import peaks
import trace_reduce as T

MS = 1_000_000  # ns


def _trace():
    # window 0-100 ms; ops overlap (union counts once); one op starts
    # before the window and is clipped; host spans label the gaps
    ops = [["fusion.1", -5 * MS, 15 * MS, "tf_op=jit(decode)/dot"],
           ["fusion.2", 20 * MS, 10 * MS, ""],
           ["custom-call.3", 25 * MS, 10 * MS,
            "tf_op=jit(decode)/pallas_call name=_gqa_kernel"],
           ["all-reduce.4", 60 * MS, 5 * MS, ""],
           ["fusion.5", 90 * MS, 20 * MS, ""]]
    mods = [["jit_decode(7)", 20 * MS, 16 * MS],
            ["jit_prefill(9)", 60 * MS, 5 * MS],
            ["jit_decode(7)", 90 * MS, 10 * MS]]
    host = [["bench.window", 0, 100 * MS],
            ["bench.step", 12 * MS, 10 * MS],
            ["bench.sleep", 40 * MS, 15 * MS],
            ["bench.step", 66 * MS, 30 * MS]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "host": host}


def test_busy_is_the_union_inside_the_window():
    t = _trace()
    # [0,10] + [20,35] + [60,65] + [90,100] = 40 ms
    assert T.busy_ns(t, "/device:TPU:0") == 40 * MS


def test_idle_gaps_are_labelled_by_the_host_span():
    idle = T.idle_by_host(_trace(), "/device:TPU:0")
    # gaps [10,20] (mid 15: step), [35,60] (mid 47.5: sleep),
    # [65,90] (mid 77.5: step)
    assert idle == {"bench.step": 35 * MS, "bench.sleep": 25 * MS}


def test_kernels_and_programs_match_by_name_or_detail():
    t = _trace()
    assert [e[0] for e in T.ops_matching(t, "/device:TPU:0",
                                         r"_gqa_kernel")] == ["custom-call.3"]
    evs = T.modules_matching(t, "/device:TPU:0", r"^jit_decode\b")
    assert sum(e[2] for e in evs) == 26 * MS and len(evs) == 2


def test_breakdown_lists_ops_and_idle_in_seconds():
    b = T.summary(_trace())
    assert b["device_ops"][0] == ["fusion.5", 0.02]
    assert len(b["device_ops"]) == 4
    assert b["idle_gaps"][0] == ["bench.step", 0.035]


def test_roofline_takes_the_larger_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = costs.roofline_s(1e9, 819e9, pk)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = costs.roofline_s(197e12, 1.0, pk)
    assert bound == "flops" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_paged_attention_counts_live_bf16_kv():
    m = {"d_model": 4096, "num_heads": 32, "num_kv_heads": 4, "head_dim": 128,
         "d_ff": 11008, "vocab_size": 64000, "num_layers": 24}
    f, b = costs.paged_attn_step(m, [100, 300], tp=1)
    assert b == 2 * 400 * 4 * 128 * 2 + 2 * 2 * 32 * 128 * 2
    assert f == 4 * 32 * 128 * 400


def test_self_time_leaves_out_nested_ops():
    loop = ("%while.2 = (s32[], bf16[16,1,4096]{2,0,1:T(8,128)}) while("
            "%tuple.56), condition=%c, body=%b")
    kern = ('%closed_call.17 = f32[16,4,8,128]{3,2,1,0:T(8,128)S(1)} '
            'custom-call(s32[16,128]{1,0} %a, f32[1025,16,4,128]{3,2,1,0} %b),'
            ' custom_call_target="tpu_custom_call"')
    t = {"devices": {"/device:TPU:0": {
        "ops": [[loop, 0, 50 * MS, ""], [kern, 10 * MS, 20 * MS, ""],
                [kern, 35 * MS, 5 * MS, ""]], "modules": []}},
        "host": [["bench.window", 0, 100 * MS]]}
    got = T.self_time_by_name(t, "/device:TPU:0")
    assert got == {"while %while.2 tuple": 25 * MS,
                   "custom-call[tpu_custom_call] %closed_call.17 "
                   "f32[16,4,8,128]": 25 * MS}


# --- a recorded trace: three decode steps of yi9b.decode_batch on a v5e ---

def _recorded():
    import json
    import os

    path = os.path.join(os.path.dirname(T.__file__), "fixtures",
                        "trace_v5e_decode3.json")
    with open(path) as f:
        return json.load(f)


def _reader(name):
    import run as bench_run
    return bench_run.reader(name)


def test_recorded_trace_reduces_to_known_numbers():
    t, p = _recorded(), "/device:TPU:0"
    lo, hi = T.window(t)
    assert hi - lo == 107341078
    # checked against a 10 ns occupancy timeline of the same ops
    assert T.busy_ns(t, p) == 95742958
    assert T.idle_by_host(t, p) == {"bench.step": 11598120}
    dec = T.modules_matching(t, p, r"^jit_decode\b")
    assert len(dec) == 3 and sum(e[2] for e in dec) == 95745122


def test_recorded_trace_kernels_found_by_signature():
    import types

    t = _recorded()
    ctx = types.SimpleNamespace(
        trace=t, planes=["/device:TPU:0"], tp=1,
        m={"num_kv_heads": 4, "num_heads": 32, "head_dim": 128},
        config={"engine": {"slots": 16}})
    paged = _load_metric("paged_attn_roofline.batch")
    silu = _load_metric("silu_mul_roofline.chat")
    got = T.ops_matching(t, "/device:TPU:0", paged.kernel_pattern(ctx))
    assert (len(got), sum(e[2] for e in got)) == (72, 30889584)
    got = T.ops_matching(t, "/device:TPU:0", silu.KERNEL)
    assert (len(got), sum(e[2] for e in got)) == (72, 9295528)
    assert _reader("decode_step_ms.batch")(ctx) == pytest.approx(
        95.745122 / 3)
    assert _reader("idle_share.batch")(ctx) == pytest.approx(
        100 * (1 - 95742958 / 107341078))


def _load_metric(name):
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(T.__file__), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
