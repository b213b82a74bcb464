"""Block-walking paged-attention decode kernels (vLLM-style) in Pallas.

The paged KV plane (PR 4) stores decode K/V in a global pool of
``block_len``-position blocks with per-slot block tables.  The *gather*
attend path (`models.attention._pool_gather` + `_attend_rows`) assembles a
dense ``(slots, max_len, ...)`` buffer from the table before attending —
shape-identical to the dense path (which is what makes bit-identity
provable), but the per-step transient working set still scales with
``max_len`` even when every live sequence is short.

These kernels remove that transient: the grid runs over
``slots x table-chunks`` and each grid step DMAs exactly ONE pool block
into VMEM — the block id comes from the scalar-prefetched block table via
the BlockSpec index map (``tables[slot, chunk]``), so the pipeline walks
each row's *own* blocks in place and a ``max_len``-sized buffer is never
materialized.  Softmax is accumulated online in f32 scratch that persists
across the chunk axis (running max / running sum / weighted-value
accumulator, flash-decoding style), and the per-row length mask is applied
inside the loop; chunks at-or-past a row's live length skip their compute
(their table entries point at the reserved scratch block 0).

Selection (``cfg.paged_attend_impl``):

    "gather" — the PR-4 reference path: full-table gather, attend over
               dense shapes.  Transient = O(max_len) per row.  Exactly
               reproduces the dense path bit-for-bit.
    "pallas" — these kernels.  Transient = O(block_len) per grid step,
               independent of max_len.  The online-softmax accumulation
               reorders float reductions, so attention *outputs* agree
               with the gather path to f32 round-off (~1e-6 relative;
               ~1e-3 for the Q2.14 CORDIC softmax) — and the emitted
               *tokens* are bit-identical, which is the serving contract
               and what the per-backend CI conformance suite enforces
               (tests/test_paged_attention.py).

The softmax follows ``softmax_impl``:

    "exact"          — one sweep over the live blocks with the classic
                       online-softmax rescaling recurrence (jnp.exp).
    "cordic_fixed" / — three sweeps over the same blocks (a pass axis in
    "cordic_pallas"    the grid; blocks stay O(block_len) in VMEM): exact
                       row max, then the CORDIC-exp row sum, then a
                       per-lane normalization that replicates the selected
                       backend's softmax *bit-for-bit* given (max, sum) —
                       ``cordic_fixed`` via functions.exp_fixed +
                       divide_fixed (the jnp engine datapath),
                       ``cordic_pallas`` via the dyadic reduction +
                       ``_coshsinh_q`` rotation + frexp + ``_lvc_div_q``
                       stages of kernels/softmax_cordic.py.  Online
                       rescaling with quantized CORDIC exponentials would
                       drift ~1e-3 from the gather path — enough to flip a
                       sampled token — so the CORDIC impls trade one extra
                       block sweep for lane-exact probabilities.

Two kernels, one accumulation scheme:

    gqa_decode — grouped-query decode: q (B, KH, G, hd) against K/V pools
                 (N, L, KH, hd); returns (B, KH, G, hd) f32.
    mla_decode — absorbed-form MLA decode: q_eff (B, H, R) + q_rope
                 (B, H, P) against the compressed-latent pool (N, L, R)
                 and shared rope-key pool (N, L, P); returns the latent
                 output (B, H, R) f32 (the wv_b projection stays outside,
                 mirroring models.attention._mla_absorbed_decode).

CI exercises interpret mode (CPU); on TPU the same pallas_call compiles
via Mosaic with the block table scalar-prefetched into SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cordic import FixedConfig, MRSchedule, PAPER_FIXED, PAPER_SCHEDULE
from repro.kernels.cordic_act import (
    _I32,
    _coshsinh_q,
    _dequantize_f,
    _exp2_i32,
    _guard_drop,
    _lvc_div_q,
    _quantize_f,
    _shr,
    _wrap16,
)

NEG_INF = np.float32(-1e30)
_LN2 = np.float32(math.log(2.0))
_INV_LN2 = np.float32(1.0 / math.log(2.0))
#: same flush thresholds as kernels/softmax_cordic.py: lanes more than
#: ~e^-20 below the running max contribute exactly 0.
_DEAD_CUTOFF = np.float32(-20.0)
_MIN_K = np.float32(-30.0)


def _num_passes(impl: str) -> int:
    """Block sweeps per row: 1 (online rescaling) for the exact softmax,
    3 (max / sum / normalize) for the CORDIC impls — see module docstring."""
    if impl in (None, "exact"):
        return 1
    if impl in ("cordic_fixed", "cordic_pallas"):
        return 3
    raise ValueError(f"unknown softmax_impl {impl!r}")


def canonical_kv_dtype(kv_dtype):
    """Validate + canonicalize the ``kv_dtype`` cast seam (None passes
    through; the caller substitutes its pool-derived default).

    kv_dtype is the storage-rounding cast the gather path applies to K/V
    before scoring (x.dtype in models.attention); the kernels replay it
    per block so both paths attend identically-rounded values. It must be
    a *float* dtype — an unrecognized string or an integer dtype used to
    fall through silently and attend garbage-rounded scores; now it fails
    at call/init time, mirroring the _paged_attend_impl validation.
    Integer pool *storage* is selected with ``kv_quant``, not kv_dtype.
    """
    if kv_dtype is None:
        return None
    try:
        dt = jnp.dtype(kv_dtype)
    except TypeError:
        raise ValueError(
            f"unknown kv_dtype {kv_dtype!r}; expected a float dtype such "
            "as jnp.float32 / jnp.bfloat16") from None
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(
            f"kv_dtype {dt} is not a float dtype — kv_dtype is the "
            "storage-rounding cast applied to K/V before scoring; select "
            "integer pool storage with kv_quant instead")
    return dt


def _exp_codes(u, sched: MRSchedule, cfg: FixedConfig):
    """The exp stage of softmax_cordic's _softmax_kernel: dyadic reduction
    u = k ln2 + r and the Q-format cosh+sinh rotation. Returns the e^r
    codes, the dyadic exponents, and the dead-lane mask (u < -20) — the
    shared intermediates of the sum (pass 1) and normalize (pass 2) stages,
    so the two can never desynchronize."""
    fb = cfg.fmt.frac_bits
    bits = cfg.fmt.total_bits
    dead = u < _DEAD_CUTOFF
    k = jnp.maximum(jnp.floor(u * _INV_LN2 + 0.5), _MIN_K)
    r = jnp.where(dead, 0.0, u - k * _LN2)              # |r| <= ln2/2
    c, s = _coshsinh_q(_quantize_f(r, fb, bits), sched, cfg)
    eq = _wrap16(c + s, bits)                           # e^r codes
    return eq, k.astype(_I32), dead


def _lane_exp(u, impl: str, sched: MRSchedule, cfg: FixedConfig):
    """The selected backend's e^u per lane (u = score - row max <= 0).

    cordic_fixed replicates functions.exp_fixed (softmax_fixed's exp);
    cordic_pallas replicates the exp stage of softmax_cordic's
    _softmax_kernel: _exp_codes + exponent-field 2^k scale, lanes below
    e^-20 flushed to 0.
    """
    if impl == "cordic_fixed":
        from repro.cordic_engine import functions as F

        return F.exp_fixed(u, cfg=cfg)
    eq, ki, dead = _exp_codes(u, sched, cfg)
    ef = _dequantize_f(eq, cfg.fmt.frac_bits) * _exp2_i32(ki)
    return jnp.where(dead, 0.0, ef)


def _lane_probs(u, ssum, impl: str, sched: MRSchedule, cfg: FixedConfig):
    """Normalized probability per lane given the final (row max, row sum),
    replicating the selected backend's softmax bit-for-bit per lane.

    cordic_fixed: functions.divide_fixed(exp_fixed(u), S) — exactly what
    softmax_fixed does.  cordic_pallas: the normalization stage of
    _softmax_kernel — exponent-field frexp of S, Q-format mantissa, R2-LVC
    division, 2^(k - p + 1) scale, dead lanes exactly 0.
    """
    if impl == "cordic_fixed":
        from repro.cordic_engine import functions as F

        return F.divide_fixed(F.exp_fixed(u, cfg=cfg), ssum, cfg=cfg)
    fb = cfg.fmt.frac_bits
    bits = cfg.fmt.total_bits
    eq, ki, dead = _exp_codes(u, sched, cfg)
    p = (jax.lax.bitcast_convert_type(ssum, jnp.int32) >> 23) - 127
    ms = ssum * _exp2_i32(-p)
    mq = jnp.broadcast_to(_quantize_f(ms, fb, bits), eq.shape)
    t = _lvc_div_q(mq, _shr(eq, 1, bits), sched, cfg)
    tf = _dequantize_f(_guard_drop(t, cfg), fb)
    out = tf * _exp2_i32(ki - p + 1)
    return jnp.where(dead, 0.0, out)


def _pass_update(s, v, pass_idx, impl, sched, cfg, m_sc, l_sc, acc_sc,
                 contract):
    """One grid step of the softmax accumulation shared by both kernels.

    s: (..., L) masked scores for this block (masked lanes == NEG_INF);
    v: the block's values; contract(p, v) -> weighted-value partial sum.
    m_sc/l_sc keep a trailing singleton axis (s.shape[:-1] + (1,)) so
    they broadcast over both the score row and the accumulator's feature
    axis.  For the exact impl (single pass) this is the flash-decoding
    recurrence: rescale the running sum/accumulator by e^(m_old - m_new)
    whenever the running max moves.  For the CORDIC impls, pass 0 takes
    the exact row max, pass 1 the backend's e^u row sum, and pass 2
    accumulates lane-exact probabilities against the values.
    """
    if impl in (None, "exact"):
        m_old = m_sc[...]                                   # (..., 1)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        ef = jnp.exp(s - m_new)
        l_sc[...] = l_sc[...] * alpha + jnp.sum(ef, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + contract(ef, v)
        m_sc[...] = m_new
        return

    @pl.when(pass_idx == 0)
    def _():
        m_sc[...] = jnp.maximum(m_sc[...],
                                jnp.max(s, axis=-1, keepdims=True))

    @pl.when(pass_idx == 1)
    def _():
        ef = _lane_exp(s - m_sc[...], impl, sched, cfg)
        l_sc[...] = l_sc[...] + jnp.sum(ef, axis=-1, keepdims=True)

    @pl.when(pass_idx == 2)
    def _():
        pr = _lane_probs(s - m_sc[...], l_sc[...], impl, sched, cfg)
        acc_sc[...] = acc_sc[...] + contract(pr, v)


# ---------------------------------------------------------------------------
# GQA decode
# ---------------------------------------------------------------------------
def _gqa_kernel(tbl_ref, kl_ref, q_ref, k_ref, v_ref, *rest,
                block_len: int, scale: float, impl: str, sched: MRSchedule,
                cfg: FixedConfig, kv_dtype, kv_quant_spec=None):
    # quantized pools add two scale refs between the pools and the output
    if kv_quant_spec is not None:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = rest
    else:
        o_ref, m_sc, l_sc, acc_sc = rest
    b, p, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((p == 0) & (c == 0))
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    klen = kl_ref[b]
    base = c * block_len

    @pl.when(base < klen)                       # dead chunks: skip compute
    def _():
        q = q_ref[0].astype(jnp.float32)                        # (KH,G,hd)
        if kv_quant_spec is None:
            k = k_ref[0].astype(kv_dtype).astype(jnp.float32)   # (L,KH,hd)
            v = v_ref[0].astype(kv_dtype).astype(jnp.float32)
        else:
            # the kv_dtype cast seam as a real dequant stage: this block's
            # integer codes x its per-head scale, on the CORDIC linear-
            # rotation multiply — elementwise on exactly the (code, scale)
            # pairs the gather oracle dequantizes, so rounding matches
            from repro.core import kv_quant as kvq

            k = kvq.dequantize(k_ref[0], kv_quant_spec,
                               ks_ref[0]).astype(kv_dtype).astype(jnp.float32)
            v = kvq.dequantize(v_ref[0], kv_quant_spec,
                               vs_ref[0]).astype(kv_dtype).astype(jnp.float32)
        s = jnp.einsum("hgd,lhd->hgl", q, k,
                       preferred_element_type=jnp.float32) * scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < klen, s, NEG_INF)
        _pass_update(
            s, v, p, impl, sched, cfg, m_sc, l_sc, acc_sc,
            lambda pr, vb: jnp.einsum("hgl,lhd->hgd", pr, vb,
                                      preferred_element_type=jnp.float32))

    if impl in (None, "exact"):
        o_ref[...] = (acc_sc[...] / l_sc[...])[None]
    else:
        o_ref[...] = acc_sc[...][None]      # pass 2 accumulates normalized p


def gqa_decode(q, k_pool, v_pool, tables, k_len, *, scale: float,
               softmax_impl: str = "exact", kv_dtype=None,
               kv_quant: str = "none",
               k_scale_pool=None, v_scale_pool=None,
               sched: MRSchedule = PAPER_SCHEDULE,
               cfg: FixedConfig = PAPER_FIXED,
               interpret: bool = False) -> jax.Array:
    """Paged GQA decode attend: one query row per slot against its own
    live KV blocks, walked through the block table.

    q:       (B, KH, G, hd) post-RoPE grouped queries (any float dtype)
    k_pool:  (N, L, KH, hd) global key pool   (block 0 = scratch)
    v_pool:  (N, L, KH, hd) global value pool
    tables:  (B, M) int32 per-row block tables (entries past the live
             count point at scratch block 0)
    k_len:   (B,) int32 valid key count per row; must be >= 1 (the decode
             step writes its new element before attending)
    kv_dtype: storage dtype the gather path would cast K/V to (x.dtype in
             models.attention) — applied per block so both paths attend
             identically-rounded K/V.  Validated float (canonical_kv_dtype).
    kv_quant: "none" | "int8" | "q2_14" (core/kv_quant.py).  When set, the
             pools hold integer codes, ``k_scale_pool``/``v_scale_pool``
             carry the (N, 1, KH, 1) f32 per-block-per-head scales, and
             each grid step dequantizes its block in VMEM via the CORDIC
             linear-rotation multiply before scoring.

    Returns (B, KH, G, hd) f32 attention outputs.
    """
    from repro.core import kv_quant as kvq

    B, KH, G, hd = q.shape
    N, L = k_pool.shape[:2]
    M = tables.shape[1]
    spec = kvq.spec_for(kv_quant)
    if (spec is not None) != (k_scale_pool is not None
                              and v_scale_pool is not None):
        # checked before kv_dtype resolution: an integer pool with the
        # scale pools but no kv_quant should name the real mismatch, not
        # fall through to the float-kv_dtype error below
        raise ValueError(
            "kv_quant and the scale pools come together: kv_quant="
            f"{kv_quant!r} with k_scale_pool "
            f"{'set' if k_scale_pool is not None else 'missing'}")
    kv_dtype = canonical_kv_dtype(kv_dtype)
    if kv_dtype is None:
        kv_dtype = (jnp.dtype(jnp.float32) if spec is not None
                    else canonical_kv_dtype(k_pool.dtype))

    in_specs = [
        pl.BlockSpec((1, KH, G, hd),
                     lambda b, p, c, t, kl: (b, 0, 0, 0)),
        pl.BlockSpec((1, L, KH, hd),
                     lambda b, p, c, t, kl: (t[b, c], 0, 0, 0)),
        pl.BlockSpec((1, L, KH, hd),
                     lambda b, p, c, t, kl: (t[b, c], 0, 0, 0)),
    ]
    operands = (tables, k_len, q, k_pool, v_pool)
    if spec is not None:
        # per-block scales ride the same table walk as their code blocks
        in_specs += [
            pl.BlockSpec((1, 1, KH, 1),
                         lambda b, p, c, t, kl: (t[b, c], 0, 0, 0)),
            pl.BlockSpec((1, 1, KH, 1),
                         lambda b, p, c, t, kl: (t[b, c], 0, 0, 0)),
        ]
        operands += (k_scale_pool, v_scale_pool)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, _num_passes(softmax_impl), M),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KH, G, hd),
                               lambda b, p, c, t, kl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, G, 1), jnp.float32),    # running max
            pltpu.VMEM((KH, G, 1), jnp.float32),    # running sum
            pltpu.VMEM((KH, G, hd), jnp.float32),   # value accumulator
        ],
    )
    kern = functools.partial(_gqa_kernel, block_len=L, scale=float(scale),
                             impl=softmax_impl, sched=sched, cfg=cfg,
                             kv_dtype=kv_dtype, kv_quant_spec=spec)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, hd), jnp.float32),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# MLA decode (absorbed form)
# ---------------------------------------------------------------------------
def _mla_kernel(tbl_ref, kl_ref, qe_ref, qr_ref, c_ref, r_ref, o_ref,
                m_sc, l_sc, acc_sc, *, block_len: int, scale: float,
                impl: str, sched: MRSchedule, cfg: FixedConfig):
    b, p, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((p == 0) & (c == 0))
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    klen = kl_ref[b]
    base = c * block_len

    @pl.when(base < klen)
    def _():
        qe = qe_ref[0].astype(jnp.float32)                      # (H, R)
        qr = qr_ref[0].astype(jnp.float32)                      # (H, P)
        cc = c_ref[0].astype(jnp.float32)                       # (L, R)
        cr = r_ref[0].astype(jnp.float32)                       # (L, P)
        s = (jnp.einsum("hr,lr->hl", qe, cc,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("hp,lp->hl", qr, cr,
                          preferred_element_type=jnp.float32)) * scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < klen, s, NEG_INF)
        _pass_update(
            s, cc, p, impl, sched, cfg, m_sc, l_sc, acc_sc,
            lambda pr, vb: jnp.einsum("hl,lr->hr", pr, vb,
                                      preferred_element_type=jnp.float32))

    if impl in (None, "exact"):
        o_ref[...] = (acc_sc[...] / l_sc[...])[None]
    else:
        o_ref[...] = acc_sc[...][None]      # pass 2 accumulates normalized p


def mla_decode(q_eff, q_rope, c_pool, r_pool, tables, k_len, *, scale: float,
               softmax_impl: str = "exact",
               sched: MRSchedule = PAPER_SCHEDULE,
               cfg: FixedConfig = PAPER_FIXED,
               interpret: bool = False) -> jax.Array:
    """Paged absorbed-form MLA decode: scores against the compressed
    latent + shared rope key, output accumulated in the latent space.

    q_eff:  (B, H, R) absorbed queries (q_nope @ wk_b)
    q_rope: (B, H, P) rope-rotated query part
    c_pool: (N, L, R) compressed-latent pool    r_pool: (N, L, P) rope keys
    tables: (B, M) int32;  k_len: (B,) int32 (>= 1)

    Returns (B, H, R) f32 latent outputs (project with wv_b outside, as
    models.attention._mla_absorbed_decode does).
    """
    B, H, R = q_eff.shape
    P = q_rope.shape[-1]
    N, L = c_pool.shape[:2]
    M = tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, _num_passes(softmax_impl), M),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, p, c, t, kl: (b, 0, 0)),
            pl.BlockSpec((1, H, P), lambda b, p, c, t, kl: (b, 0, 0)),
            pl.BlockSpec((1, L, R), lambda b, p, c, t, kl: (t[b, c], 0, 0)),
            pl.BlockSpec((1, L, P), lambda b, p, c, t, kl: (t[b, c], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, p, c, t, kl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, R), jnp.float32),
        ],
    )
    kern = functools.partial(_mla_kernel, block_len=L, scale=float(scale),
                             impl=softmax_impl, sched=sched, cfg=cfg)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, R), jnp.float32),
        interpret=interpret,
    )(tables, k_len, q_eff, q_rope, c_pool, r_pool)
    return out


# ---------------------------------------------------------------------------
# Shard-local execution over a ("data","model") mesh
#
# pallas_call is opaque to the GSPMD partitioner — XLA cannot slice a
# kernel's grid or its scalar-prefetched block tables, so running these
# kernels on a sharded cache means wrapping them in shard_map over the
# model axis: each shard runs the SAME grid (slots x passes x table
# chunks) against its OWN head slice of the pools, with the block tables
# and lengths replicated (they are head-invariant host metadata — the
# whole point of KVPager staying shard-agnostic). The per-shard kernel is
# bitwise the single-device kernel on a narrower head axis, and head
# slices never interact inside attention, so no collective appears inside
# the wrapped region (check_vma=False: outputs are head-sharded, not
# replicated).
# ---------------------------------------------------------------------------
def shard_local_gqa(attend_fn, mesh, q, k_pool, v_pool, tables, k_len,
                    k_scale_pool=None, v_scale_pool=None):
    """Run a GQA paged-attend callable shard-locally over mesh axis "model".

    attend_fn: kernels.ops.paged_attend_gqa with kwargs bound (scale /
    softmax_impl / kv_dtype / kv_quant); q (B,KH,G,hd) and the pools
    (N,L,KH,hd) arrive KH-sharded, tables/k_len replicated; output is
    KH-sharded.  Quantized pools bring their (N,1,KH,1) scale pools, cut
    on the same KH dim — each shard dequantizes with exactly the scales
    the unsharded kernel would, so TP layouts stay token-identical.
    Caller guarantees KH % mesh.shape["model"] == 0.
    """
    from jax.sharding import PartitionSpec as PS

    in_specs = [PS(None, "model", None, None),        # q (B, KH, G, hd)
                PS(None, None, "model", None),        # k_pool (N, L, KH, hd)
                PS(None, None, "model", None),        # v_pool
                PS(None, None),                       # tables (B, M)
                PS(None)]                             # k_len (B,)
    args = (q, k_pool, v_pool, tables, k_len)
    if k_scale_pool is not None:
        in_specs += [PS(None, None, "model", None)] * 2  # scales (N,1,KH,1)
        args += (k_scale_pool, v_scale_pool)
        fn = lambda q_, kp_, vp_, t_, kl_, ks_, vs_: attend_fn(
            q_, kp_, vp_, t_, kl_, k_scale_pool=ks_, v_scale_pool=vs_)
    else:
        fn = attend_fn

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=PS(None, "model", None, None),
        check_vma=False,
    )(*args)


def shard_local_mla(attend_fn, mesh, q_eff, q_rope, c_pool, r_pool, tables,
                    k_len):
    """Run an MLA paged-attend callable shard-locally over mesh axis
    "model".

    MLA's latent/rope pools carry no head axis — they are replicated and
    each shard walks the full latent with its own H slice of q_eff/q_rope
    (head-parallel over the absorbed queries). Output (B,H,R) is
    H-sharded. Caller guarantees H % mesh.shape["model"] == 0.
    """
    from jax.sharding import PartitionSpec as PS

    return jax.shard_map(
        attend_fn, mesh=mesh,
        in_specs=(PS(None, "model", None),            # q_eff (B, H, R)
                  PS(None, "model", None),            # q_rope (B, H, P)
                  PS(None, None, None),               # c_pool (N, L, R)
                  PS(None, None, None),               # r_pool (N, L, P)
                  PS(None, None),                     # tables (B, M)
                  PS(None)),                          # k_len (B,)
        out_specs=PS(None, "model", None),
        check_vma=False,
    )(q_eff, q_rope, c_pool, r_pool, tables, k_len)


# ---------------------------------------------------------------------------
# Transient working-set accounting (the metric benchmarks/serving.py gates)
# ---------------------------------------------------------------------------
def _dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def decode_transient_bytes(cfg, *, max_len: int, block_len: int,
                           impl: str, pool_dtype=jnp.float32,
                           kv_quant: str = "none") -> int:
    """Per-row transient working set of one paged decode attend, in bytes.

    "gather" materializes the full table gather — two (max_len, heads,
    dim)-shaped buffers per row (K and V, or latent + rope for MLA) — so
    it scales with ``max_len``.  "pallas" holds exactly the VMEM blocks of
    one grid step (q + one K/V block pair + the f32 scratch accumulators):
    a function of ``block_len`` only.  Derived from the same shapes the
    BlockSpecs above are built from, so this metric cannot drift from the
    kernel silently.

    kv_quant != "none" (GQA only): gathered/streamed K/V are integer codes
    in the format's lane width plus per-block f32 scales, and every read
    also materializes the dequantized f32 buffer — the transient trades a
    narrower gather for the dequant copy; the *resident* pool is where
    quantization wins (kv.quant.bytes_per_token).
    """
    from repro.core import kv_quant as kvq

    spec = kvq.spec_for(kv_quant)
    ib = _dtype_bytes(pool_dtype)
    if getattr(cfg, "mla", None) is not None:
        if spec is not None:
            raise ValueError("kv_quant applies to GQA paged pools only")
        H, R, P = cfg.num_heads, cfg.mla.kv_lora_rank, cfg.mla.qk_rope_dim
        if impl == "gather":
            return max_len * (R + P) * ib
        if impl == "pallas":
            q = H * (R + P) * ib
            kv = block_len * (R + P) * ib
            scratch = (H * 1 * 2 + H * R) * 4
            return q + kv + H * R * 4 + scratch
    else:
        from repro.models.attention import _padded_heads

        H, KH = _padded_heads(cfg)
        G, hd = H // KH, cfg.head_dim
        kv_ib = _dtype_bytes(spec.code_dtype) if spec is not None else ib
        nblk = -(-max_len // block_len)
        if impl == "gather":
            codes = 2 * max_len * KH * hd * kv_ib
            if spec is None:
                return codes
            scales = 2 * nblk * KH * 4
            dequant = 2 * max_len * KH * hd * 4
            return codes + scales + dequant
        if impl == "pallas":
            q = KH * G * hd * ib
            kv = 2 * block_len * KH * hd * kv_ib
            scratch = (KH * G * 2 + KH * G * hd) * 4
            extra = (2 * KH * 4 + 2 * block_len * KH * hd * 4
                     if spec is not None else 0)
            return q + kv + KH * G * hd * 4 + scratch + extra
    raise ValueError(f"unknown paged_attend_impl {impl!r}")
