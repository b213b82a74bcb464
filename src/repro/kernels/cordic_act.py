"""Pallas TPU kernel for the MR-HRC CORDIC activation pipeline.

TPU mapping of the paper's fully-pipelined FPGA datapath:

* the 26-stage shift-add pipeline is fully unrolled inside one grid cell —
  straight-line VPU code over an (block_rows, block_cols) tile of int32
  lanes (8x128 VREG granularity);
* HBM -> VMEM movement is expressed with an explicit BlockSpec; each element
  is loaded once and stored once (the kernel is elementwise, so the memory
  term is the roofline floor and the VPU op count — which mixed radix
  minimizes — is the compute term);
* all arithmetic is integer add/sub/compare/select/shift on Q2.14 codes,
  plus a float quantize/dequantize at the boundary. No transcendentals,
  no division, no MXU involvement — the TPU analogue of "zero DSP".

Fused variants (`silu`, `silu_mul`) keep the elementwise epilogue of SwiGLU
MLPs inside the same VMEM tile, saving an HBM round-trip per activation —
this is the framework-level payoff of having the activation as a kernel.

Beyond the sigmoid/tanh family the same tile runs the generalized-engine
function kinds (`exp`, `log`, `softplus`, `elu`, `gelu_erf`): hyperbolic
rotation for e^r, hyperbolic vectoring for the atanh-based log, with dyadic
range reduction and the 2^k scale as an exponent-field bitcast. Each is
bit-identical to its jnp fixed-path twin in cordic_engine.functions, which
the golden-vector conformance suite enforces per backend.

Validated bit-exactly against kernels/ref.py (the pure-jnp Q2.14 oracle) in
interpret mode; compiled path is exercised by the dry-run on the TPU target.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.cordic import FixedConfig, MRSchedule, PAPER_FIXED, PAPER_SCHEDULE
from repro.cordic_engine.schedule import HYP_VECTORING, hyp_vectoring_for

# ---------------------------------------------------------------------------
# In-kernel fixed-point pipeline (explicit, Mosaic-friendly ops only)
# ---------------------------------------------------------------------------

_I32 = jnp.int32
_LN2 = np.float32(math.log(2.0))
_INV_LN2 = np.float32(1.0 / math.log(2.0))
#: exp clamp: keeps 2^k inside normal f32 exponent range (== functions._EXP_CLIP).
_EXP_CLIP = np.float32(80.0)
_ERF_A = np.float32(0.147)
#: hyperbolic-vectoring schedule for the in-kernel log leg (j=1..14 with the
#: textbook convergence repeats) — the same iteration list the jnp fixed path
#: uses, so the kernels stay bit-identical to cordic_engine.functions.
_HYP_VEC_JS = HYP_VECTORING.r2_js


def _wrap16(v, bits: int):
    """Mask an int32 lane to `bits`-bit two's complement (add/and/sub)."""
    mask = (1 << bits) - 1
    half = 1 << (bits - 1)
    return ((v + half) & mask) - half


def _shr(v, s: int, bits: int):
    """Arithmetic right shift with truncation, re-wrapped to the register width."""
    if s <= 0:
        return v
    return _wrap16(v >> s, bits)


def _coshsinh_q(zq, sched: MRSchedule, cfg: FixedConfig):
    """Q2.14 MR-HRC rotation stage: zq (cfg.fmt angle codes) -> (cosh, sinh)
    codes. Shared by the tanh pipeline and the fused softmax-exp kernel
    (e^r = cosh r + sinh r). Bit-identical to core.cordic.mr_hrc_q."""
    bits = cfg.fmt.total_bits
    fb = cfg.fmt.frac_bits
    zbits = cfg.zfmt.total_bits
    zfb = cfg.zfmt.frac_bits

    # --- extend angle register ---------------------------------------------
    z = zq
    if cfg.z_guard:
        z = _wrap16(z << cfg.z_guard, zbits)

    x = jnp.full_like(zq, _I32(int(round(sched.x0 * (1 << fb)))))
    y = jnp.zeros_like(zq)

    # --- radix-2 HRC stage -------------------------------------------------
    for j in sched.r2_js:
        a = _I32(int(round(math.atanh(2.0 ** -j) * (1 << zfb))))
        pos = z >= 0
        xs = _shr(x, j, bits)
        ys = _shr(y, j, bits)
        x_n = jnp.where(pos, _wrap16(x + ys, bits), _wrap16(x - ys, bits))
        y_n = jnp.where(pos, _wrap16(y + xs, bits), _wrap16(y - xs, bits))
        z = jnp.where(pos, _wrap16(z - a, zbits), _wrap16(z + a, zbits))
        x, y = x_n, y_n

    # --- radix-4 HRC stage (SRT digit set {-2..2}) -------------------------
    for j in sched.r4_js:
        t05 = _I32(int(round(0.5 * 4.0 ** -j * (1 << zfb))))
        t15 = _I32(int(round(1.5 * 4.0 ** -j * (1 << zfb))))
        a1 = _I32(int(round(math.atanh(1.0 * 4.0 ** -j) * (1 << zfb))))
        a2 = _I32(int(round(math.atanh(2.0 * 4.0 ** -j) * (1 << zfb))))
        pos = z >= 0
        mag2 = (z >= t15) | (z < -t15)
        mag0 = (z < t05) & (z >= -t05)
        xs1 = _shr(x, 2 * j, bits)
        ys1 = _shr(y, 2 * j, bits)
        xs2 = _shr(x, 2 * j - 1, bits)
        ys2 = _shr(y, 2 * j - 1, bits)
        zero = jnp.zeros_like(x)
        dx = jnp.where(mag0, zero, jnp.where(mag2, ys2, ys1))
        dy = jnp.where(mag0, zero, jnp.where(mag2, xs2, xs1))
        da = jnp.where(mag0, zero, jnp.where(mag2, a2, a1))
        x = jnp.where(pos, _wrap16(x + dx, bits), _wrap16(x - dx, bits))
        y = jnp.where(pos, _wrap16(y + dy, bits), _wrap16(y - dy, bits))
        z = jnp.where(pos, _wrap16(z - da, zbits), _wrap16(z + da, zbits))

    return x, y


def _lvc_div_q(x, y, sched: MRSchedule, cfg: FixedConfig):
    """Radix-2 linear vectoring: y/x in cfg.zfmt codes (no guard-bit drop).

    Shared by the tanh pipeline (t = sinh/cosh) and the softmax kernel's
    normalization (p = e_i / sum). Bit-identical to core.cordic.r2_lvc_q.
    """
    bits = cfg.fmt.total_bits
    zbits = cfg.zfmt.total_bits
    zfb = cfg.zfmt.frac_bits
    t = jnp.zeros_like(y)
    for j in sched.lvc_js:
        pos = y >= 0
        xs = _shr(x, j, bits)
        step = _I32(1 << max(zfb - j, 0))
        y = jnp.where(pos, _wrap16(y - xs, bits), _wrap16(y + xs, bits))
        t = jnp.where(pos, _wrap16(t + step, zbits), _wrap16(t - step, zbits))
    return t


def _guard_drop(t, cfg: FixedConfig):
    """Requantize zfmt -> fmt (out_round="nearest" on the guard-bit drop)."""
    if cfg.z_guard:
        t = _wrap16((t + (1 << (cfg.z_guard - 1))) >> cfg.z_guard,
                    cfg.fmt.total_bits)
    return t


def _cordic_tanh_q(zq, sched: MRSchedule, cfg: FixedConfig):
    """Q2.14 int32-lane tanh pipeline; bit-identical to core.cordic.tanh_mr_q.

    zq: int32 codes of the angle z in cfg.fmt, |z| <= 0.5. Returns int32
    codes of tanh(z) in cfg.fmt.
    """
    x, y = _coshsinh_q(zq, sched, cfg)
    return _guard_drop(_lvc_div_q(x, y, sched, cfg), cfg)


def _cordic_sigmoid_q(xq, sched: MRSchedule, cfg: FixedConfig):
    """Q2.14 sigmoid: input shift, tanh core, output scale+offset.

    Bit-identical to core.cordic.sigmoid_mr_q.
    """
    bits = cfg.fmt.total_bits
    fb = cfg.fmt.frac_bits
    t = _cordic_tanh_q(_shr(xq, 1, bits), sched, cfg)
    # --- output stage: sigma = 1/2 + t/2 (round-to-nearest half) -----------
    half = _I32(1 << (fb - 1))
    t2 = _wrap16((t + 1) >> 1, bits)
    return _wrap16(half + t2, bits)


def _quantize_f(xf, fb: int, bits: int = 16):
    """float32 -> Q codes, round-to-nearest, saturating (boundary op)."""
    scaled = xf * np.float32(1 << fb)
    q = jnp.round(scaled).astype(_I32)
    lim = (1 << (bits - 1)) - 1
    return jnp.clip(q, -lim - 1, lim)


def _dequantize_f(q, fb: int):
    return q.astype(jnp.float32) * np.float32(1.0 / (1 << fb))


def _exp2_i32(k):
    """2^k for int32 k in [-126, 127] via the f32 exponent field (no exp2)."""
    return jax.lax.bitcast_convert_type(((k + 127) << 23).astype(jnp.int32),
                                        jnp.float32)


def _frexp_f(v):
    """(m, p) with v = m * 2^p, m in [0.5, 1) — exponent-field frexp.

    Valid for positive normal f32 (callers floor at 1e-30 >> FLT_MIN).
    Matches jnp.frexp bit-for-bit on that domain, including exact powers
    of two (1.0 -> (0.5, 1)).
    """
    e = (jax.lax.bitcast_convert_type(v, jnp.int32) >> 23) - 127
    m = v * _exp2_i32(-e) * np.float32(0.5)            # [1,2) -> [0.5,1), exact
    return m, e + 1


def _hyp_vector_q(x, y, cfg: FixedConfig, js=_HYP_VEC_JS):
    """Radix-2 hyperbolic vectoring: drives y -> 0, returns atanh(y0/x0)
    codes in cfg.zfmt. Bit-identical to cordic_engine.core.vector_q with the
    HYP_VECTORING schedule (same shift order, same where/add/sub structure).
    """
    bits = cfg.fmt.total_bits
    zbits = cfg.zfmt.total_bits
    zfb = cfg.zfmt.frac_bits
    z = jnp.zeros_like(y)
    for j in js:
        a = _I32(int(round(math.atanh(2.0 ** -j) * (1 << zfb))))
        plus = y < 0                                   # e = +1 branch
        xs = _shr(x, j, bits)
        ys = _shr(y, j, bits)
        x_n = jnp.where(plus, _wrap16(x + ys, bits), _wrap16(x - ys, bits))
        y_n = jnp.where(plus, _wrap16(y + xs, bits), _wrap16(y - xs, bits))
        z = jnp.where(plus, _wrap16(z - a, zbits), _wrap16(z + a, zbits))
        x, y = x_n, y_n
    return z


def _exp_q(xf, sched: MRSchedule, cfg: FixedConfig):
    """e^x over (-80, 80): dyadic reduction + Q2.14 cosh+sinh rotation.

    Bit-identical to cordic_engine.functions.exp_fixed (the 2^k scale is an
    exponent-field bitcast of the same exact power of two jnp.exp2 yields).
    """
    fb = cfg.fmt.frac_bits
    bits = cfg.fmt.total_bits
    x = jnp.clip(xf, -_EXP_CLIP, _EXP_CLIP)
    k = jnp.round(x * _INV_LN2)
    r = x - k * _LN2                                   # |r| <= ln2/2 < 0.35
    c, s = _coshsinh_q(_quantize_f(r, fb, bits), sched, cfg)
    eq = _wrap16(c + s, bits)                          # e^r in (0.70, 1.42)
    return _dequantize_f(eq, fb) * _exp2_i32(k.astype(_I32))


def _log_q(v, cfg: FixedConfig):
    """ln v for v > 0: exponent-field mantissa split + atanh identity.

    Bit-identical to cordic_engine.functions.log_fixed: the vectoring runs
    on (m+1, m-1) with m in [0.5, 1) — both inside the Q format, no
    division. The vectoring depth is sized to the format's fraction bits
    (j=1..14 with repeats for Q2.14, deeper for the wider profiles).
    """
    fb = cfg.fmt.frac_bits
    bits = cfg.fmt.total_bits
    zfb = cfg.zfmt.frac_bits
    js = _HYP_VEC_JS if fb == 14 else hyp_vectoring_for(fb).r2_js
    v = jnp.maximum(v, np.float32(1e-30))
    m, p = _frexp_f(v)
    num = _quantize_f(m - 1.0, fb, bits)               # in [-0.5, 0)
    den = _quantize_f(m + 1.0, fb, bits)               # in [1.5, 2)
    at = _dequantize_f(_hyp_vector_q(den, num, cfg, js), zfb)
    return 2.0 * at + p.astype(jnp.float32) * _LN2


def _erf_q(u, sched: MRSchedule, cfg: FixedConfig):
    """Exponential erf approximation with the CORDIC exp core (|err|<2.5e-4).

    The rational prefactor and sqrt are float boundary ops, mirroring
    cordic_engine.functions._erf_from_exp op-for-op.
    """
    u2 = u * u
    g = u2 * (np.float32(4.0 / math.pi) + _ERF_A * u2) / (1.0 + _ERF_A * u2)
    return jnp.sign(u) * jnp.sqrt(jnp.maximum(1.0 - _exp_q(-g, sched, cfg), 0.0))


def _wide_sigmoid_f(xf, sched: MRSchedule, cfg: FixedConfig, max_doublings: int):
    """Dyadic range extension around the Q2.14 core (|x| <= 2^k)."""
    ax = jnp.abs(xf)
    # k = number of halvings, chosen by compares (shift-add spirit)
    k = jnp.zeros_like(xf, dtype=_I32)
    for i in range(max_doublings):
        k = k + (ax > np.float32(2.0 ** i)).astype(_I32)
    scale = jnp.exp2(-k.astype(jnp.float32))
    xs = jnp.clip(xf * scale, -1.0, 1.0)
    s = _dequantize_f(_cordic_sigmoid_q(
        _quantize_f(xs, cfg.fmt.frac_bits, cfg.fmt.total_bits), sched, cfg),
        cfg.fmt.frac_bits)
    for i in range(max_doublings):
        s2 = s * s
        denom = s2 + (1.0 - s) * (1.0 - s)
        doubled = s2 / jnp.maximum(denom, np.float32(1e-12))
        s = jnp.where(k > i, doubled, s)
    return s


# ---------------------------------------------------------------------------
# Kernel bodies
# ---------------------------------------------------------------------------
def _act_kernel(x_ref, o_ref, *, op: str, sched: MRSchedule, cfg: FixedConfig,
                max_doublings: int):
    xf = x_ref[...].astype(jnp.float32)
    fb = cfg.fmt.frac_bits
    if op == "sigmoid":
        xq = _quantize_f(jnp.clip(xf, -1.0, 1.0), fb, cfg.fmt.total_bits)
        out = _dequantize_f(_cordic_sigmoid_q(xq, sched, cfg), fb)
    elif op == "tanh":
        # tanh(z), |z| <= 0.5 clamp: direct angle feed (no halving round trip)
        zq = _quantize_f(jnp.clip(xf, -0.5, 0.5), fb, cfg.fmt.total_bits)
        out = _dequantize_f(_cordic_tanh_q(zq, sched, cfg), fb)
    elif op == "sigmoid_wide":
        out = _wide_sigmoid_f(xf, sched, cfg, max_doublings)
    elif op == "silu":
        out = xf * _wide_sigmoid_f(xf, sched, cfg, max_doublings)
    elif op == "exp":
        out = _exp_q(xf, sched, cfg)
    elif op == "log":
        out = _log_q(xf, cfg)
    elif op == "softplus":
        # log(1 + e^x) = relu(x) + log(1 + e^-|x|) — both CORDIC legs
        e = _exp_q(-jnp.abs(xf), sched, cfg)
        out = jnp.maximum(xf, 0.0) + _log_q(1.0 + e, cfg)
    elif op == "elu":
        em1 = _exp_q(jnp.minimum(xf, 0.0), sched, cfg) - 1.0
        out = jnp.where(xf > 0, xf, em1)
    elif op == "gelu_erf":
        # exact-form GELU 0.5 x (1 + erf(x/sqrt2)) with CORDIC-exp erf
        out = 0.5 * xf * (1.0 + _erf_q(xf * np.float32(1.0 / math.sqrt(2.0)),
                                       sched, cfg))
    else:
        raise ValueError(op)
    o_ref[...] = out.astype(o_ref.dtype)


def _act_q_kernel(x_ref, o_ref, *, sched: MRSchedule, cfg: FixedConfig):
    """Integer-in/integer-out sigmoid (int16 Q2.14 codes end-to-end)."""
    xq = x_ref[...].astype(_I32)
    o_ref[...] = _cordic_sigmoid_q(xq, sched, cfg).astype(o_ref.dtype)


def _silu_mul_kernel(g_ref, u_ref, o_ref, *, sched: MRSchedule, cfg: FixedConfig,
                     max_doublings: int):
    """Fused SwiGLU gate: out = u * g * sigmoid(g) in one VMEM pass."""
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    s = _wide_sigmoid_f(g, sched, cfg, max_doublings)
    o_ref[...] = (u * g * s).astype(o_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers with explicit VMEM BlockSpecs
# ---------------------------------------------------------------------------
#: Default VMEM tile: 256 sublane-groups x 1024 lanes of f32 = 1 MiB/tile;
#: with in/out + int32 x/y/z/t intermediates ~ 6 MiB live, comfortably inside
#: a v5e core's VMEM with double buffering.
DEFAULT_BLOCK = (256, 1024)


def _grid_and_specs(shape: Sequence[int], block):
    br = min(block[0], shape[0])
    bc = min(block[1], shape[1])
    # hardware alignment: sublane multiple of 8, lane multiple of 128
    br = max(8, (br // 8) * 8) if shape[0] >= 8 else shape[0]
    bc = max(128, (bc // 128) * 128) if shape[1] >= 128 else shape[1]
    grid = (pl.cdiv(shape[0], br), pl.cdiv(shape[1], bc))
    spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    return grid, spec


def act_2d(x: jax.Array, op: str, *, sched: MRSchedule = PAPER_SCHEDULE,
           cfg: FixedConfig = PAPER_FIXED, max_doublings: int = 3,
           block=DEFAULT_BLOCK, interpret: bool = False) -> jax.Array:
    """Run the activation kernel over a 2D array."""
    grid, spec = _grid_and_specs(x.shape, block)
    kern = functools.partial(_act_kernel, op=op, sched=sched, cfg=cfg,
                             max_doublings=max_doublings)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        interpret=interpret,
        name="cordic_act",
    )(x)


def act_q_2d(x_q: jax.Array, *, sched: MRSchedule = PAPER_SCHEDULE,
             cfg: FixedConfig = PAPER_FIXED, block=DEFAULT_BLOCK,
             interpret: bool = False) -> jax.Array:
    """Integer (Q2.14 int16/int32 codes) sigmoid over a 2D array."""
    grid, spec = _grid_and_specs(x_q.shape, block)
    kern = functools.partial(_act_q_kernel, sched=sched, cfg=cfg)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(x_q.shape, x_q.dtype),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        interpret=interpret,
        name="cordic_act_q",
    )(x_q)


def silu_mul_2d(gate: jax.Array, up: jax.Array, *,
                sched: MRSchedule = PAPER_SCHEDULE, cfg: FixedConfig = PAPER_FIXED,
                max_doublings: int = 3, block=DEFAULT_BLOCK,
                interpret: bool = False) -> jax.Array:
    """Fused `up * silu(gate)` over 2D arrays of identical shape."""
    assert gate.shape == up.shape, (gate.shape, up.shape)
    grid, spec = _grid_and_specs(gate.shape, block)
    kern = functools.partial(_silu_mul_kernel, sched=sched, cfg=cfg,
                             max_doublings=max_doublings)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(gate.shape, gate.dtype),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
        name="cordic_silu_mul",
    )(gate, up)
