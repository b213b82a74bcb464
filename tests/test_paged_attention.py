"""Paged-attention kernel conformance: the block-walking Pallas decode
kernels (kernels/paged_attention.py) against the full-table *gather*
reference (kernels/ref.py, the PR-4 path in models/attention.py), plus the
serving-level contract — ``cfg.paged_attend_impl="pallas"`` must emit
token streams bit-identical to the gather path AND to the dense engine
(greedy and seeded sampling, GQA and MLA).

Kernel-level: attention outputs agree with the gather oracle to f32
round-off (the online/block-sequential accumulation reorders float
reductions) and the per-row argmax never moves.  Edge geometry is
exercised explicitly: lengths exactly on / one off block boundaries, a
slot with a single block, vacant slots (all-zero tables scribbling into
scratch block 0), and mixed-length batches.

CI runs this file once per datapath backend via REPRO_TEST_BACKEND in
{"jnp", "pallas_interpret"} (the kernel-conformance step of the
conformance matrix): the attention softmax follows the backend
(cordic_fixed / cordic_pallas), so a drift in one backend's block-walking
normalization is attributed there.  Unset (tier-1), the exact softmax runs.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops as kops
from repro.kernels import paged_attention as PA
from repro.kernels import ref as kref
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import SamplingParams

_SOFTMAX_BY_BACKEND = {None: "exact", "jnp": "cordic_fixed",
                       "pallas_interpret": "cordic_pallas"}
_BACKEND = os.environ.get("REPRO_TEST_BACKEND")
assert _BACKEND in _SOFTMAX_BY_BACKEND, \
    f"REPRO_TEST_BACKEND={_BACKEND!r} not in {sorted(filter(None, _SOFTMAX_BY_BACKEND))}"
SOFTMAX_IMPL = _SOFTMAX_BY_BACKEND[_BACKEND]

#: f32 contraction-order tolerance — probabilities are lane-exact vs the
#: reference (see kernels/paged_attention.py), only reduction order differs.
ATOL = 2e-5


def _cfg(arch: str = "yi-9b"):
    return dataclasses.replace(configs.get_smoke(arch, act_impl="exact"),
                               softmax_impl=SOFTMAX_IMPL)


# ---------------------------------------------------------------------------
# Kernel vs gather oracle (GQA)
# ---------------------------------------------------------------------------
def _gqa_case(klen_list, *, L=4, KH=2, G=2, hd=8, seed=0):
    """Pools/tables/lens for a batch of rows with the given live lengths.

    Rows with klen 0 are 'vacant': all-zero table (scratch block 0) and
    k_len pinned to 1, exactly how the engine drives inactive slots."""
    rng = np.random.default_rng(seed)
    B = len(klen_list)
    M = max(-(-k // L) for k in klen_list if k) if any(klen_list) else 1
    N = 1 + B * M
    q = jnp.asarray(rng.normal(size=(B, KH, G, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, L, KH, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, L, KH, hd)), jnp.float32)
    tables = np.zeros((B, M), np.int32)
    nxt = 1
    for b, klen in enumerate(klen_list):
        for c in range(-(-klen // L)):
            tables[b, c] = nxt
            nxt += 1
    k_len = jnp.asarray([max(k, 1) for k in klen_list], jnp.int32)
    return q, kp, vp, jnp.asarray(tables), k_len


def _assert_kernel_matches_ref(q, kp, vp, tables, k_len, scale=0.3):
    got = np.asarray(PA.gqa_decode(q, kp, vp, tables, k_len, scale=scale,
                                   softmax_impl=SOFTMAX_IMPL, interpret=True))
    want = np.asarray(kref.paged_attend_gqa_ref(q, kp, vp, tables, k_len,
                                                scale=scale,
                                                softmax_impl=SOFTMAX_IMPL))
    assert np.abs(got - want).max() < ATOL, np.abs(got - want).max()
    # token-decision identity at kernel granularity: the per-(kh,g) argmax
    # over the output features must never move
    np.testing.assert_array_equal(got.reshape(got.shape[0], -1).argmax(-1),
                                  want.reshape(want.shape[0], -1).argmax(-1))
    assert np.isfinite(got).all()


#: positions per chunk of the kernel's walk at _gqa_case's default
#: geometry (L 4, KH 2, hd 8, f32): 64 blocks, 256 positions
_CL = PA.chunk_blocks(4, 2, 8, 4) * 4


@pytest.mark.parametrize("klen", [1, 3, 4, 5, 7, 8, 9, 16,
                                  _CL - 1, _CL, _CL + 1, _CL + 4])
def test_gqa_kernel_block_boundary_lengths(klen):
    """Lengths exactly on (4, 8, 16) and one off (3, 5, 7, 9) the L=4
    block boundaries, plus the single-element and single-block cases;
    then one off and on the chunk boundary (_CL - 1, _CL, _CL + 1), and
    a full table whose block count is not a multiple of the chunk's
    (_CL + 4: 65 blocks, 64 a chunk)."""
    _assert_kernel_matches_ref(*_gqa_case([klen], seed=klen))


def test_gqa_kernel_single_block_slot():
    _assert_kernel_matches_ref(*_gqa_case([2], L=16))


@pytest.mark.parametrize("klens", [[1, 4, 5, 13, 16, 3],
                                   [_CL + 4, 4, 4, 4]],
                         ids=["one_to_four_blocks",
                              "full_table_beside_one_block"])
def test_gqa_kernel_mixed_length_batch(klens):
    """Rows at different lengths (spanning 1..4 live blocks) in one call;
    and one row at the full table (two chunks, the second one block)
    beside rows of one block each."""
    _assert_kernel_matches_ref(*_gqa_case(klens, seed=3))


@pytest.mark.parametrize("klens,vacant_len", [([5, 0, 9], 1),
                                              ([_CL + 1, 0, 5], 1),
                                              ([_CL + 1, 0, 5], 10_000)],
                         ids=["beside_short_rows", "beside_two_chunks",
                              "length_past_table"])
def test_gqa_kernel_vacant_slot_reads_scratch(klens, vacant_len):
    """A vacant row (all-zero table, len 0 -> k_len 1) rides along like an
    inactive engine slot: its output is finite garbage from scratch block
    0, and the live rows are bit-unaffected by its presence.  The engine
    keeps counting a vacant slot's length each decode step, so a length
    past the whole table must read as the table."""
    q, kp, vp, tables, k_len = _gqa_case(klens, seed=4)
    k_len = k_len.at[1].set(vacant_len)
    assert int(tables[1].max()) == 0            # vacant -> scratch only
    _assert_kernel_matches_ref(q, kp, vp, tables, k_len)
    # live rows identical with the vacant row removed from the batch
    keep = np.asarray([0, 2])
    full = np.asarray(PA.gqa_decode(q, kp, vp, tables, k_len, scale=0.3,
                                    softmax_impl=SOFTMAX_IMPL, interpret=True))
    sub = np.asarray(PA.gqa_decode(q[keep], kp, vp,
                                   jnp.asarray(np.asarray(tables)[keep]),
                                   jnp.asarray(np.asarray(k_len)[keep]),
                                   scale=0.3, softmax_impl=SOFTMAX_IMPL,
                                   interpret=True))
    np.testing.assert_array_equal(full[keep], sub)


def test_gqa_kernel_kv_dtype_rounding_matches_gather():
    """The gather path attends K/V cast to x.dtype (bf16 for bf16 models);
    the kernel must apply the same per-block rounding."""
    q, kp, vp, tables, k_len = _gqa_case([7, 12], seed=5)
    got = np.asarray(PA.gqa_decode(q, kp, vp, tables, k_len, scale=0.3,
                                   softmax_impl=SOFTMAX_IMPL,
                                   kv_dtype=jnp.bfloat16, interpret=True))
    want = np.asarray(kref.paged_attend_gqa_ref(q, kp, vp, tables, k_len,
                                                scale=0.3,
                                                softmax_impl=SOFTMAX_IMPL,
                                                kv_dtype=jnp.bfloat16))
    assert np.abs(got - want).max() < ATOL
    # and it differs from the unrounded attend (the cast is load-bearing)
    raw = np.asarray(PA.gqa_decode(q, kp, vp, tables, k_len, scale=0.3,
                                   softmax_impl=SOFTMAX_IMPL, interpret=True))
    assert np.abs(got - raw).max() > 0


# ---------------------------------------------------------------------------
# Kernel vs gather oracle (MLA)
# ---------------------------------------------------------------------------
def _mla_case(klen_list, *, L=4, H=4, R=16, P=8, seed=0):
    rng = np.random.default_rng(seed)
    B = len(klen_list)
    M = max(-(-k // L) for k in klen_list if k) if any(klen_list) else 1
    N = 1 + B * M
    qe = jnp.asarray(rng.normal(size=(B, H, R)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(B, H, P)), jnp.float32)
    cp = jnp.asarray(rng.normal(size=(N, L, R)), jnp.float32)
    rp = jnp.asarray(rng.normal(size=(N, L, P)), jnp.float32)
    tables = np.zeros((B, M), np.int32)
    nxt = 1
    for b, klen in enumerate(klen_list):
        for c in range(-(-klen // L)):
            tables[b, c] = nxt
            nxt += 1
    k_len = jnp.asarray([max(k, 1) for k in klen_list], jnp.int32)
    return qe, qr, cp, rp, jnp.asarray(tables), k_len


@pytest.mark.parametrize("klens", [[1], [4], [5], [8], [9],
                                   [3, 8, 1, 13, 16]])
def test_mla_kernel_matches_ref(klens):
    qe, qr, cp, rp, tables, k_len = _mla_case(klens, seed=len(klens))
    got = np.asarray(PA.mla_decode(qe, qr, cp, rp, tables, k_len, scale=0.2,
                                   softmax_impl=SOFTMAX_IMPL, interpret=True))
    want = np.asarray(kref.paged_attend_mla_ref(qe, qr, cp, rp, tables,
                                                k_len, scale=0.2,
                                                softmax_impl=SOFTMAX_IMPL))
    assert np.abs(got - want).max() < ATOL, np.abs(got - want).max()
    np.testing.assert_array_equal(got.reshape(got.shape[0], -1).argmax(-1),
                                  want.reshape(want.shape[0], -1).argmax(-1))


def test_mla_kernel_vacant_slot():
    qe, qr, cp, rp, tables, k_len = _mla_case([6, 0], seed=9)
    out = np.asarray(PA.mla_decode(qe, qr, cp, rp, tables, k_len, scale=0.2,
                                   softmax_impl=SOFTMAX_IMPL, interpret=True))
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# Serving-level token identity (the acceptance bar)
# ---------------------------------------------------------------------------
def _mk_varied(cfg, n, *, max_new=5, seed=7, sampling=None):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 3 + 2 * i),
                    max_new_tokens=max_new, sampling=sampling)
            for i in range(n)]


def _serve(cfg, params, reqs, **kw):
    eng = ServeEngine(cfg, params, slots=4, max_len=64, seed=0, **kw)
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [r.out for r in reqs]


@pytest.mark.parametrize("arch", ["yi-9b", "deepseek-v2-lite-16b"])
@pytest.mark.parametrize("sampling", [
    SamplingParams(greedy=True),
    SamplingParams(temperature=2.5, top_k=8),
])
def test_pallas_decode_tokens_bit_identical(arch, sampling):
    """cfg.paged_attend_impl='pallas' emits token streams bit-identical to
    the gather path AND to the dense engine — greedy and seeded sampling,
    GQA and MLA, across slot reuse and distinct prompt lengths (block
    boundaries, stale blocks, and vacant slots all land on the hot path)."""
    cfg = _cfg(arch)
    params = tf.init(cfg, jax.random.PRNGKey(3))
    dense = _serve(cfg, params, _mk_varied(cfg, 6, sampling=sampling),
                   kv_impl="dense")
    gather = _serve(cfg, params, _mk_varied(cfg, 6, sampling=sampling),
                    kv_impl="paged", paged_attend_impl="gather")
    pallas = _serve(cfg, params, _mk_varied(cfg, 6, sampling=sampling),
                    kv_impl="paged", paged_attend_impl="pallas")
    assert gather == dense
    assert pallas == gather


def test_pallas_decode_crosses_block_boundaries():
    """Prompt/decode lengths engineered so generation crosses block
    boundaries mid-stream (len 15->21 and 16->22 with L=16): tokens match
    the gather path at and across every boundary."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(5))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in (15, 16, 17)]

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]

    gather = _serve(cfg, params, reqs(), kv_impl="paged")
    pallas = _serve(cfg, params, reqs(), kv_impl="paged",
                    paged_attend_impl="pallas")
    assert pallas == gather


def test_pallas_engine_with_vacant_slots():
    """Fewer requests than slots: vacant slots decode against scratch
    block 0 every step; tokens still match the gather path."""
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(4))
    reqs = lambda: _mk_varied(cfg, 2, max_new=6)               # noqa: E731
    gather = _serve(cfg, params, reqs(), kv_impl="paged")
    pallas = _serve(cfg, params, reqs(), kv_impl="paged",
                    paged_attend_impl="pallas")
    assert pallas == gather


def test_pallas_requires_paged_plane():
    cfg = _cfg()
    params = tf.init(cfg, jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, slots=1, max_len=32, kv_impl="dense",
                    paged_attend_impl="pallas")
    with pytest.raises(ValueError, match="paged_attend_impl"):
        ServeEngine(cfg, params, slots=1, max_len=32, kv_impl="paged",
                    paged_attend_impl="nope")


def test_pallas_rejects_bf16_mxu_scoring():
    """The kernels score in f32 only; a bf16_mxu gather attend rounds
    differently, so the combination must fail loudly instead of silently
    breaking the token-identity contract."""
    from repro.models.attention import _paged_attend_impl

    cfg = dataclasses.replace(_cfg(), score_dtype="bf16_mxu",
                              paged_attend_impl="pallas")
    with pytest.raises(ValueError, match="score_dtype"):
        _paged_attend_impl(cfg)
    # and the engine fails fast at construction, not mid-serving
    params = tf.init(_cfg(), jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="score_dtype"):
        ServeEngine(dataclasses.replace(cfg, paged_attend_impl="gather"),
                    params, slots=1, max_len=32, kv_impl="paged",
                    paged_attend_impl="pallas")


# ---------------------------------------------------------------------------
# Transient working set: the metric benchmarks/serving.py gates
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["yi-9b", "deepseek-v2-lite-16b"])
def test_kernel_transient_independent_of_max_len(arch):
    """The point of the kernel: its per-step transient is a function of
    block_len only, while the gather path's scales linearly with max_len."""
    cfg = _cfg(arch)
    tr = lambda impl, ml: PA.decode_transient_bytes(                # noqa: E731
        cfg, max_len=ml, block_len=16, impl=impl)
    assert tr("pallas", 64) == tr("pallas", 1 << 20)
    assert tr("gather", 128) == 2 * tr("gather", 64)
    assert tr("pallas", 1 << 20) < tr("gather", 1 << 20)
