"""A whole benchmark run at a small size on the CPU, past the look for a
chip: a sound run is correct, and a broken timed path, or the float8
control put in the program's place, is not.

The faults a served-model cell can have: a decode step that hands back
its cache unchanged, and a token altered where the step produces it.
(Leaving half a training batch out has no counterpart in serving.) The
four-chip cell (``dscoder33b.tp4.decode_batch``) can also lose the
exchange between chips; its tests run the same path at tp=4 on the four
CPU devices that conftest.py asks for.
"""
import jax
import jax.numpy as jnp
import pytest

import run as bench_run

MODEL = {"num_layers": 4, "d_model": 128, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 32, "d_ff": 256, "vocab_size": 512, "rope_theta": 10000.0,
         "norm_eps": 1e-5, "dtype": "bfloat16", "act_impl": "exact",
         "softmax_impl": "exact"}
ENGINE = {"slots": 4, "max_len": 256, "block_len": 16, "num_blocks": 65,
          "paged_attend_impl": "pallas", "prefill_chunk": 32,
          "prefill_batch": 2, "max_prefill_tokens": 64, "tp": 1}
TRAFFIC = {"arrival": {"kind": "backlog", "requests": 16},
           "prompt_len": {"median": 24, "sigma": 0.6, "min": 8, "max": 64},
           "output_len": {"median": 16, "sigma": 0.5, "min": 8, "max": 32},
           "check": {"requests": 3}}
#: at this size a sound run reads a widest gap of a few thousandths
LIMIT = 0.05


#: the tp=4 cell's shape at the same size: heads and d_ff divide by 4
MODEL_TP4 = dict(MODEL, num_heads=8, num_kv_heads=4, head_dim=16)


def _run(seed=3, fault=None, control=False, cell="yi9b.decode_batch",
         model=MODEL, tp=1):
    r = bench_run.resolve(cell)
    r["config"] = dict(r["config"], model=model, engine=dict(ENGINE, tp=tp))
    r["traffic"] = TRAFFIC
    r["limits"] = {"widest_logit_gap": {"limit": LIMIT}}
    return bench_run.run(["--workload", cell, "--seed",
                          str(seed), "--seconds", "2", "--trace", "0"],
                         require_tpu=False, override=r, fault=fault,
                         control=control)


def _run_tp4(fault=None):
    return _run(fault=fault, cell="dscoder33b.tp4.decode_batch",
                model=MODEL_TP4, tp=4)


def _wrap_decode(eng, change):
    decode = eng._decode

    def broken(params, caches, *rest):
        nxt, new = decode(params, caches, *rest)
        return change(nxt, caches, new)
    eng._decode = broken


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["compared"]
    assert out["compared"]["tokens_checked"]["value"] > 20
    assert list(out)[-1] == "compared"


def test_step_returning_its_state_unchanged_is_not_correct():
    out = _run(fault=lambda eng: _wrap_decode(
        eng, lambda nxt, old, new: (nxt, old)))
    assert not out["correct"], out["compared"]


def test_token_altered_where_produced_is_not_correct():
    out = _run(fault=lambda eng: _wrap_decode(
        eng, lambda nxt, old, new: ((nxt + 1) % MODEL["vocab_size"], new)))
    assert not out["correct"], out["compared"]


def test_float8_control_is_not_correct():
    # the control in the program's place fails the run's own comparison,
    # while the program's reading on the same run stays within the limit
    out = _run(control=True)
    assert not out["correct"], out["compared"]
    ctrl = out["compared"]["widest_logit_gap"]["value"]
    prog = out["program_widest_logit_gap"]
    assert prog <= LIMIT < ctrl and ctrl > 3 * prog, (prog, ctrl)


def test_sound_tp4_run_is_correct():
    out = _run_tp4()
    assert out["correct"], out["compared"]
    assert out["compared"]["tokens_checked"]["value"] > 20


def test_exchange_between_chips_left_out_is_not_correct():
    # without the all-reduce after each row-parallel product, a chip sums
    # only its own heads' attention output and its own slice of d_ff: as
    # the first chip sees it, the other chips' rows of wo and w_down are 0
    def drop_exchange(eng):
        h = MODEL_TP4["num_heads"] // 4
        f = MODEL_TP4["d_ff"] // 4
        seg = eng.params["seg0"]
        part = {**eng.params, "seg0": {
            **seg,
            "attn": {**seg["attn"], "wo": seg["attn"]["wo"].at[:, h:].set(0)},
            "mlp": {**seg["mlp"],
                    "w_down": seg["mlp"]["w_down"].at[:, f:].set(0)}}}
        eng.params = jax.device_put(part, eng._param_sh)
    out = _run_tp4(fault=drop_exchange)
    assert not out["correct"], out["compared"]


def test_no_chip_no_result(capsys):
    # on this CPU-only machine the look for a chip must refuse the run
    assert bench_run.main(["--workload", "yi9b.decode_batch", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) != 0
    assert '"correct"' not in capsys.readouterr().out
