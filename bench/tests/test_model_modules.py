"""The modules a configuration names (``layout``, ``reference``,
``costs``): the GQA configurations name bench/weights.py,
bench/reference.py and bench/costs.py; a configuration that names none
fails the run; ``mfu`` takes its counts from the named module; and a model
that is not a GQA decoder, the registry's ``deepseek-v2-lite-16b`` smoke
preset (latent attention, routed and shared experts), is served through
bench/run.py by modules that its configuration names."""
import os
import types

import jax.numpy as jnp
import pytest

import run as bench_run

CONFIGS = ("yi-9b-24L", "dscoder-33b-31L-tp4")
FIXTURES = {"layout": "bench/tests/mla_moe_layout.py",
            "reference": "bench/tests/mla_moe_reference.py",
            "costs": "bench/tests/mla_moe_costs.py"}
MLA_MOE = {"preset": "deepseek-v2-lite-16b", "vocab_size": 512,
           "dtype": "float32", "act_impl": "exact", "softmax_impl": "exact"}
ENGINE = {"slots": 4, "max_len": 256, "block_len": 16, "num_blocks": 65,
          "paged_attend_impl": "pallas", "prefill_chunk": 32,
          "prefill_batch": 2, "max_prefill_tokens": 64, "tp": 1}
TRAFFIC = {"arrival": {"kind": "backlog", "requests": 12},
           "prompt_len": {"median": 24, "sigma": 0.6, "min": 8, "max": 64},
           "output_len": {"median": 12, "sigma": 0.5, "min": 6, "max": 24},
           "check": {"requests": 3}}


def _config(name):
    return bench_run.load_json("configs", name + ".json")


@pytest.mark.parametrize("name", CONFIGS)
def test_gqa_configurations_name_the_gqa_modules(name):
    config = _config(name)
    mods = bench_run.model_modules(config)
    for role, mod in vars(mods).items():
        assert (os.path.relpath(mod.__file__, bench_run.ROOT)
                == os.path.join("bench", {"layout": "weights.py",
                                          "reference": "reference.py",
                                          "costs": "costs.py"}[role]))
    m, dtype = config["model"], jnp.dtype(config["model"]["dtype"])
    cfg = mods.layout.model_config(m, config["engine"])
    bench_run.check_layout(mods.layout, cfg, m, dtype)


@pytest.mark.parametrize("role", sorted(bench_run.ROLES))
def test_configuration_without_a_module_fails(role):
    r = bench_run.resolve("yi9b.decode_batch")
    r["config"] = {k: v for k, v in r["config"].items() if k != role}
    with pytest.raises(bench_run.RunFailed, match=repr(role)):
        bench_run.run(["--workload", "yi9b.decode_batch", "--seed", "1",
                       "--seconds", "1", "--trace", "0"],
                      require_tpu=False, override=r)


def test_module_outside_the_benchmark_is_refused():
    config = dict(_config("yi-9b-24L"), costs="src/repro/configs/base.py")
    with pytest.raises(bench_run.RunFailed, match="'costs'"):
        bench_run.model_modules(config)


def test_mfu_takes_its_counts_from_the_configurations_module():
    import layer_stats

    step = types.SimpleNamespace(t1=1.0, ctx=[5, 7])
    req = types.SimpleNamespace(prompt=[0] * 9)
    ctx = types.SimpleNamespace(
        t_open=0.0, t_close=2.0, m={}, chips=1,
        peaks={"bf16_flops_s": 1e3},
        drv=types.SimpleNamespace(steps=[step], tracked=[
            types.SimpleNamespace(times=[1.5], req=req)]),
        costs=types.SimpleNamespace(
            token_flops=lambda m, c, lm_head: 10.0 * c,
            prompt_flops=lambda m, plen: 100.0 * plen))
    # (10 * (5 + 7) + 100 * 9) FLOPs over 2 s x 1e3 FLOP/s
    assert layer_stats.mfu(ctx) == pytest.approx(100.0 * 1020 / 2e3)


def test_mla_moe_preset_serves_through_its_named_modules(monkeypatch):
    called = []
    for role, path in FIXTURES.items():
        mod = bench_run.load_module(path)
        for fn in bench_run.ROLES[role]:
            orig = getattr(mod, fn)

            def spy(*a, _orig=orig, _name=f"{role}.{fn}", **k):
                called.append(_name)
                return _orig(*a, **k)
            monkeypatch.setattr(mod, fn, spy)

    import loadgen

    drivers = []

    class Driver(loadgen.Driver):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            drivers.append(self)
    monkeypatch.setattr(loadgen, "Driver", Driver)

    r = bench_run.resolve("yi9b.decode_batch")
    r["config"] = {"name": "deepseek-v2-lite-smoke", "model": MLA_MOE,
                   "engine": ENGINE, **FIXTURES}
    r["traffic"] = TRAFFIC
    r["limits"] = {"widest_logit_gap": {"limit": 1.0}}
    out = bench_run.run(["--workload", "yi9b.decode_batch", "--seed",
                         str(2**33 + 5), "--seconds", "2", "--trace", "0"],
                        require_tpu=False, override=r)

    assert {"layout.model_config", "layout.abstract_tree",
            "layout.program_tree", "layout.Source",
            "reference.widest_gap"} <= set(called), called
    # the layout matches the program's own latent-attention, expert tree
    layout = bench_run.load_module(FIXTURES["layout"])
    cfg = layout.model_config(MLA_MOE, ENGINE)
    assert cfg.mla is not None and cfg.moe is not None
    bench_run.check_layout(layout, cfg, MLA_MOE, jnp.float32)
    assert out["attempted"] == TRAFFIC["arrival"]["requests"]
    assert out["failed"] == 0
    reqs = [tr.req for tr in drivers[0].tracked]
    assert all(q.done and not q.error and q.out for q in reqs)
    assert all(0 <= t < MLA_MOE["vocab_size"] for q in reqs for t in q.out)
    assert out["compared"]["bad_requests"]["value"] == 0
    assert out["compared"]["tokens_checked"]["value"] > 0
    assert out["compared"]["widest_logit_gap"]["value"] is not None
