"""Mesh-sharded serving conformance: the tensor-parallel ServeEngine.

Three layers of coverage:

1. Device-count-independent unit tests for the sharding seeds —
   distributed.sharding.spec_for_axes (divisibility fallback, one-mesh-
   axis-per-tensor, rule-order precedence, all via AbstractMesh so no
   real devices are needed) and launch.mesh (make_host_mesh ValueError,
   mesh_or_none never building a trivial mesh). These always run, tier-1
   included.

2. The serving contract on a forced-multi-device CPU mesh: TP=2 and TP=4
   emit tokens bit-identical to the TP=1 single-device engine — greedy +
   seeded sampling mixed in one batch, GQA + MLA, dense + paged +
   paged-pallas, chunked + unchunked prefill — and decode stays exactly
   ONE dispatch per step regardless of tp. Skipped below 4 devices; CI's
   sharded-conformance job runs with
   XLA_FLAGS=--xla_force_host_platform_device_count=8.

3. The collective schedule, asserted on the compiled decode HLO via
   launch.hlo_analysis.collective_bytes: exactly one all-gather per
   decode step at the logits/vocab boundary (the partitioner may realize
   it on the logits or on the vocab-sharded lm_head table — both are the
   single vocab-boundary gather), NO collective inside the attention
   datapath (no all-to-all / collective-permute / reduce-scatter, and
   every all-reduce is an activation-sized Megatron row-parallel
   projection reduce, orders of magnitude below any KV-sized tensor).

Like tests/test_serving.py this file honors REPRO_TEST_BACKEND so the
sharded lane composes with the per-backend conformance matrix.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as PS

from repro import configs
from repro import obs as repro_obs
from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis
from repro.launch import mesh as mesh_lib
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import SamplingParams

_SOFTMAX_BY_BACKEND = {None: "exact", "jnp": "cordic_fixed",
                       "pallas_interpret": "cordic_pallas"}
_BACKEND = os.environ.get("REPRO_TEST_BACKEND")

_NDEV = jax.device_count()
multi_device = pytest.mark.skipif(
    _NDEV < 4,
    reason="needs >= 4 devices (XLA_FLAGS=--xla_force_host_platform_"
           "device_count=8)")


# ---------------------------------------------------------------------------
# 1a. spec_for_axes unit tests (AbstractMesh: no devices needed)
# ---------------------------------------------------------------------------
def _amesh(data=1, model=4):
    return AbstractMesh((data, model), ("data", "model"))


def test_spec_divisibility_fallback_replicates():
    # kv_heads=4 on a 16-way model axis: 4 % 16 != 0 -> that dim must
    # fall back to replicated instead of failing or splitting unevenly
    mesh = _amesh(model=16)
    spec = shd.spec_for_axes(("kv_heads", "embed"), (4, 64), mesh)
    assert spec == PS()
    # divisible case takes the axis
    spec = shd.spec_for_axes(("kv_heads", "embed"), (16, 64), mesh)
    assert spec == PS("model")


def test_spec_one_mesh_axis_per_tensor():
    # two logical axes both mapping to "model": only the first dim may
    # consume it (a mesh axis used twice in one PartitionSpec is illegal)
    mesh = _amesh(model=4)
    spec = shd.spec_for_axes(("heads", "mlp"), (8, 8), mesh)
    assert spec == PS("model")            # trailing None trimmed
    parts = tuple(spec) + (None,) * (2 - len(tuple(spec)))
    assert parts.count("model") == 1


def test_spec_rule_order_precedence():
    mesh = _amesh(model=4)
    # DEFAULT_RULES maps vocab->model and embed->None: position decides
    assert shd.spec_for_axes(("vocab", "embed"), (32, 8), mesh) == PS("model")
    assert shd.spec_for_axes(("embed", "vocab"), (8, 32), mesh) == \
        PS(None, "model")
    # a custom rule list can retarget a logical axis entirely
    rules = (("vocab", None), ("embed", "model"))
    assert shd.spec_for_axes(("vocab", "embed"), (32, 8), mesh,
                             rules=rules) == PS(None, "model")
    # unknown logical axes replicate
    assert shd.spec_for_axes(("nonesuch", None), (32, 8), mesh) == PS()


def test_kv_cache_shardings_shapes():
    # paged pool leaves shard dim -2 (the KH axis); tables/lens replicate
    mesh = _amesh(model=2)
    cache = {
        "k_pool": jax.ShapeDtypeStruct((9, 8, 4, 16), jnp.float32),
        "v_pool": jax.ShapeDtypeStruct((9, 8, 4, 16), jnp.float32),
        "tables": jax.ShapeDtypeStruct((4, 8), jnp.int32),
        "lens": jax.ShapeDtypeStruct((4,), jnp.int32),
        "c_kv_pool": jax.ShapeDtypeStruct((9, 8, 32), jnp.float32),
    }
    sh = shd.kv_cache_shardings(cache, mesh)
    assert sh["k_pool"].spec == PS(None, None, "model")
    assert sh["v_pool"].spec == PS(None, None, "model")
    assert sh["tables"].spec == PS()
    assert sh["lens"].spec == PS()
    assert sh["c_kv_pool"].spec == PS()     # MLA latent: head-less
    # non-divisible KH falls back to replicated, tokens still correct
    sh = shd.kv_cache_shardings(
        {"k_pool": jax.ShapeDtypeStruct((9, 8, 3, 16), jnp.float32)},
        mesh)
    assert sh["k_pool"].spec == PS()


# ---------------------------------------------------------------------------
# 1b. launch.mesh satellites
# ---------------------------------------------------------------------------
def test_make_host_mesh_raises_value_error():
    bad = _NDEV + 1 if _NDEV > 1 else 3   # never divides device_count
    with pytest.raises(ValueError, match=str(_NDEV)):
        mesh_lib.make_host_mesh(bad)
    with pytest.raises(ValueError):
        mesh_lib.make_host_mesh(0)


def test_mesh_or_none_single_device_is_none():
    assert mesh_lib.mesh_or_none(1) is None
    assert mesh_lib.mesh_or_none(None) is None


@multi_device
def test_mesh_or_none_builds_model_axis():
    mesh = mesh_lib.mesh_or_none(2)
    assert mesh is not None
    assert mesh.shape["model"] == 2
    assert mesh.shape["data"] == _NDEV // 2
    assert mesh.axis_names == ("data", "model")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_init_serve_params_in_cfg_dtype(dtype):
    cfg = dataclasses.replace(_gqa_cfg(), dtype=dtype)
    key = jax.random.PRNGKey(3)
    got = shd.init_serve_params(cfg, key)
    assert {x.dtype for x in jax.tree.leaves(got)} == {jnp.dtype(dtype)}
    # the same draws as an eager f32 init, up to the one jit's fusion
    # (f32) or the cast (bf16: half an ulp of its 8-bit mantissa)
    rtol = 1e-6 if dtype == "float32" else 2.0 ** -8
    jax.tree.map(lambda g, w: np.testing.assert_allclose(
        np.asarray(g, np.float32), w, rtol=rtol, atol=1e-7),
        got, tf.init(cfg, key, dtype=jnp.float32))


@multi_device
def test_init_serve_params_sharded_matches_unsharded():
    # each device draws only its own shard, and draws the same values
    cfg = dataclasses.replace(_gqa_cfg(), dtype="bfloat16")
    key = jax.random.PRNGKey(3)
    mesh = mesh_lib.mesh_or_none(4)
    got = shd.init_serve_params(cfg, key, mesh)
    want_sh = shd.serve_param_shardings(cfg, got, mesh)
    jax.tree.map(lambda x, s: x.sharding.is_equivalent_to(s, x.ndim)
                 or pytest.fail(f"{x.sharding} != {s}"), got, want_sh)
    jax.tree.map(np.testing.assert_array_equal, got,
                 shd.init_serve_params(cfg, key))


# ---------------------------------------------------------------------------
# 2. Token bit-identity per shard count
# ---------------------------------------------------------------------------
def _gqa_cfg():
    # KH=4 so pallas head-sharding divides at tp=2 and tp=4
    return ModelConfig(
        name="tp-gqa", family="dense", num_layers=2, d_model=64,
        num_heads=8, num_kv_heads=4, d_ff=192, vocab_size=512,
        rope_theta=1e4, dtype="float32",
        softmax_impl=_SOFTMAX_BY_BACKEND[_BACKEND])


def _mla_cfg():
    cfg = configs.get_smoke("deepseek-v2-lite-16b", act_impl="exact")
    return dataclasses.replace(cfg, input_mode="tokens",
                               softmax_impl=_SOFTMAX_BY_BACKEND[_BACKEND])


_PARAMS_CACHE = {}


def _params_for(kind):
    if kind not in _PARAMS_CACHE:
        cfg = _gqa_cfg() if kind == "gqa" else _mla_cfg()
        _PARAMS_CACHE[kind] = (cfg, tf.init(cfg, jax.random.PRNGKey(0)))
    return _PARAMS_CACHE[kind]


def _serve(cfg, params, *, tp, kv_impl, pai, chunk, obs=None):
    """Serve a fixed 6-request trace (greedy + seeded sampling mixed in
    one batch, prompt lengths spanning several buckets) and return the
    emitted token lists in rid order."""
    eng = ServeEngine(cfg, params, slots=3, max_len=64, seed=0,
                      kv_impl=kv_impl, block_len=8, paged_attend_impl=pai,
                      prefill_chunk=chunk, tp=tp, obs=obs)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(6):
        plen = int(rng.integers(4, 40))
        samp = (SamplingParams(greedy=True) if i % 2 == 0
                else SamplingParams(temperature=0.7, top_k=6))
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=8, sampling=samp))
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and r.error is None for r in reqs)
    return [r.out for r in sorted(reqs, key=lambda r: r.rid)], eng


@multi_device
@pytest.mark.parametrize("arch", ["gqa", "mla"])
@pytest.mark.parametrize("kv_impl,pai", [
    ("dense", "gather"), ("paged", "gather"), ("paged", "pallas")])
@pytest.mark.parametrize("chunk", [None, 16])
def test_tokens_bit_identical_per_tp(arch, kv_impl, pai, chunk):
    cfg, params = _params_for(arch)
    base, _ = _serve(cfg, params, tp=1, kv_impl=kv_impl, pai=pai,
                     chunk=chunk)
    assert any(len(o) > 1 for o in base)
    for tp in (2, 4):
        got, eng = _serve(cfg, params, tp=tp, kv_impl=kv_impl, pai=pai,
                          chunk=chunk)
        assert eng.tp == tp
        assert got == base, f"tp={tp} tokens diverged from tp=1"


def _serve_shared_prefix(cfg, params, *, tp, pai, prefix):
    """Six requests sharing a 3-block system prompt (greedy + seeded
    sampling mixed); with 3 slots the second wave admits after the first
    completes, so cache-on runs always exercise radix hits."""
    eng = ServeEngine(cfg, params, slots=3, max_len=64, seed=0,
                      kv_impl="paged", block_len=8, paged_attend_impl=pai,
                      prefix_cache=prefix, tp=tp)
    rng = np.random.default_rng(3)
    sys_prompt = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    reqs = []
    for i in range(6):
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, 13))).astype(np.int32)
        samp = (SamplingParams(greedy=True) if i % 2 == 0
                else SamplingParams(temperature=0.7, top_k=6))
        reqs.append(Request(rid=i,
                            prompt=np.concatenate([sys_prompt, tail]),
                            max_new_tokens=8, sampling=samp))
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done and r.error is None for r in reqs)
    return [r.out for r in sorted(reqs, key=lambda r: r.rid)], eng


@multi_device
@pytest.mark.parametrize("pai", ["gather", "pallas"])
def test_prefix_cache_bit_identical_per_tp(pai):
    """The refcounted pager + radix prefix cache are host-side metadata
    with one logical block id space, so block sharing must be invisible
    to sharding: cache-on tokens == cache-off tokens at every tp, and
    == the unsharded cache-on run."""
    cfg, params = _params_for("gqa")
    base, _ = _serve_shared_prefix(cfg, params, tp=1, pai=pai,
                                   prefix=False)
    assert any(len(o) > 1 for o in base)
    for tp in (1, 2, 4):
        got, eng = _serve_shared_prefix(cfg, params, tp=tp, pai=pai,
                                        prefix=True)
        assert eng.prefix is not None and eng.prefix.hits >= 1, \
            f"tp={tp}: trace never hit the radix index"
        assert eng.prefix.hit_blocks >= 1
        assert got == base, f"tp={tp} cache-on tokens diverged"


@multi_device
def test_prefix_shared_blocks_are_shard_local_slices():
    """A shared pool block is one logical id; every shard holds a
    head-slice of it. After a cache hit the lender's and borrower's
    tables reference the same ids — refcounts > 1 on the shared blocks —
    while the pool leaves stay sharded on the kv-heads dim."""
    cfg, params = _params_for("gqa")
    _, eng = _serve_shared_prefix(cfg, params, tp=2, pai="gather",
                                  prefix=True)
    assert eng.prefix.hit_blocks >= 3   # the 3-block system prompt reused
    # every finished slot dropped its references; the survivors are
    # exactly the radix index's blocks (>= the 3 system-prompt blocks),
    # each held by its single cache reference
    assert eng.pager.blocks_in_use == eng.prefix.num_blocks >= 3
    assert eng.pager.blocks_shared == 0


@multi_device
def test_decode_stays_one_dispatch_per_step():
    cfg, params = _params_for("gqa")
    eng = ServeEngine(cfg, params, slots=3, max_len=64, seed=0,
                      kv_impl="paged", block_len=8,
                      paged_attend_impl="pallas", tp=2)
    calls = []
    inner = eng._decode
    eng._decode = lambda *a: (calls.append(1), inner(*a))[1]
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
            max_new_tokens=6))
    decode_steps = 0
    while True:
        n = eng.step()
        if n == 0:
            break
        if len(calls) > decode_steps:
            decode_steps += 1
            assert len(calls) == decode_steps    # exactly one per step
    assert eng.compile_counts()["decode"] <= 2


@multi_device
def test_sharded_pool_layout_and_gauges():
    # the paged pool is physically head-parallel: each shard holds
    # (num_blocks, block_len, KH/tp, hd), and the mesh gauges + the
    # per-step host_sync span (one wait on the sampled tokens, as without
    # a mesh) land in the metrics snapshot
    cfg, params = _params_for("gqa")
    ob = repro_obs.Observability()
    _, eng = _serve(cfg, params, tp=2, kv_impl="paged", pai="pallas",
                    chunk=None, obs=ob)
    pool = eng._caches["seg0"]["k_pool"]
    kh = pool.shape[-2]
    # ([layers,] N, L, KH, hd) sharded on the KH axis (dim -2); the
    # trailing-None trim leaves "model" as the spec's last entry
    assert tuple(pool.sharding.spec) == (None,) * (pool.ndim - 2) + ("model",)
    shard_shapes = {s.data.shape for s in pool.addressable_shards}
    assert shard_shapes == {pool.shape[:-2] + (kh // 2, pool.shape[-1])}
    assert ob.metrics.get("engine.mesh.tp").last == 2
    assert ob.metrics.get("engine.mesh.devices").last == _NDEV
    assert ob.metrics.get("engine.phase.host_sync_ms").count > 0
    assert ob.metrics.get("engine.phase.collective_ms") is None


@multi_device
def test_score_matches_per_tp():
    cfg, params = _params_for("gqa")
    prompt = np.arange(1, 9, dtype=np.int32)
    eng1 = ServeEngine(cfg, params, slots=2, max_len=64, tp=1)
    eng2 = ServeEngine(cfg, params, slots=2, max_len=64, tp=2)
    s1, s2 = eng1.score(prompt), eng2.score(prompt)
    np.testing.assert_allclose(s1, s2, rtol=2e-5, atol=2e-5)


@multi_device
def test_pallas_head_divisibility_enforced_at_init():
    cfg, params = _params_for("gqa")     # KH=4
    bad_tp = 8 if _NDEV >= 8 else 4
    kh = 4
    if kh % bad_tp == 0:
        pytest.skip("no non-dividing tp available at this device count")
    with pytest.raises(ValueError, match="divisible"):
        ServeEngine(cfg, params, slots=2, max_len=64, kv_impl="paged",
                    block_len=8, paged_attend_impl="pallas", tp=bad_tp)


# ---------------------------------------------------------------------------
# 3. Collective schedule on the compiled decode HLO
# ---------------------------------------------------------------------------
@multi_device
@pytest.mark.parametrize("kv_impl,pai", [
    ("dense", "gather"), ("paged", "gather"), ("paged", "pallas")])
def test_decode_collective_schedule(kv_impl, pai):
    cfg, params = _params_for("gqa")
    slots = 3
    eng = ServeEngine(cfg, params, slots=slots, max_len=64, seed=0,
                      kv_impl=kv_impl, block_len=8, paged_attend_impl=pai,
                      tp=2)
    greedy_fn, _ = eng._decode_jits
    args = (eng.params, eng._caches,
            jnp.zeros((slots, 1), jnp.int32), jnp.zeros(slots, jnp.int32),
            jnp.zeros(slots, jnp.int32), jnp.ones(slots, jnp.float32),
            jnp.zeros(slots, jnp.int32), jnp.ones(slots, bool),
            eng._base_key)
    with shd.serving_mesh(eng.mesh):
        hlo = greedy_fn.lower(*args).compile().as_text()
    rep = hlo_analysis.collective_bytes(hlo)
    counts = rep["op_counts"]
    # exactly ONE all-gather per decode step, at the vocab boundary: the
    # partitioner realizes it either on the replicated logits
    # (slots*vocab*4) or on the vocab-sharded lm_head table
    # (vocab*d_model*4) — nothing else in the program is gatherable
    assert counts.get("all-gather", 0) == 1, counts
    vocab_boundary = {slots * cfg.vocab_size * 4.0,
                      cfg.vocab_size * cfg.d_model * 4.0}
    assert rep["per_kind_bytes"]["all-gather"] in vocab_boundary, rep
    # nothing reshards inside the datapath
    for kind in ("all-to-all", "collective-permute", "reduce-scatter"):
        assert counts.get(kind, 0) == 0, counts
    # all-reduces are the Megatron row-parallel projection reduces:
    # activation-sized (slots x d_model), orders of magnitude below any
    # KV/pool-sized tensor — i.e. no collective inside attention itself
    n_ar = counts.get("all-reduce", 0)
    if n_ar:
        per_op = rep["per_kind_bytes"]["all-reduce"] / n_ar
        assert per_op <= 4 * slots * cfg.d_model * 4, rep
