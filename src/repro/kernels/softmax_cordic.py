"""Fused CORDIC softmax / log-softmax Pallas kernels: max-subtract +
CORDIC-exp + (linear-vectoring divide | hyperbolic-vectoring log) in a
single VMEM pass.

TPU mapping of softmax with the paper's shift-add arithmetic:

    u_i = x_i - max(x)                    (VPU max-reduce + subtract)
    u_i = k_i ln2 + r_i, |r_i| <= ln2/2   (dyadic reduction; k_i <= 0)
    e_i = (cosh r_i + sinh r_i) * 2^k_i   (MR-HRC rotation, Q2.14; the 2^k_i
                                           scale is an exponent-field bitcast,
                                           not a transcendental)
    S   = sum_i e_i = m * 2^p, m in [1,2) (exponent-field frexp)
    p_i = ((e_i/2) / m) * 2^(k_i - p + 1) (R2-LVC division, Q2.14)

The whole row lives in one VMEM block (the grid tiles rows only), so the
max/sum reductions and both CORDIC sweeps touch HBM exactly once per
element.  No transcendentals, no hardware divide: exp and the normalization
are the same shift-add stages as the sigmoid pipeline, reused from
``cordic_act`` (`_coshsinh_q`, `_lvc_div_q`).

Numerics: the Q2.14 core gives ~1e-3 pointwise error (validated against
jax.nn.softmax within 1e-2 max-abs in tests). Lanes below e^-20 of the max
(incl. -inf masked attention positions) flush to exactly 0.
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
    _log_q,
    _lvc_div_q,
    _quantize_f,
    _shr,
    _wrap16,
)

_LN2 = np.float32(math.log(2.0))
_INV_LN2 = np.float32(1.0 / math.log(2.0))
#: lanes more than ~e^-20 below the row max flush to exactly zero
#: (2^-29 < half a Q2.14 ULP relative to any row sum).
_DEAD_CUTOFF = np.float32(-20.0)
_MIN_K = np.float32(-30.0)


def _softmax_kernel(x_ref, o_ref, *, sched: MRSchedule, cfg: FixedConfig,
                    n_valid: int):
    fb = cfg.fmt.frac_bits
    bits = cfg.fmt.total_bits

    xf = x_ref[...].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1)
    live = col < n_valid
    xf = jnp.where(live, xf, np.float32(-1e30))

    # --- max-subtract + dyadic reduction -----------------------------------
    m = jnp.max(xf, axis=-1, keepdims=True)
    u = xf - m                                          # <= 0
    dead = (~live) | (u < _DEAD_CUTOFF)
    k = jnp.maximum(jnp.floor(u * _INV_LN2 + 0.5), _MIN_K)
    r = jnp.where(dead, 0.0, u - k * _LN2)              # |r| <= ln2/2

    # --- CORDIC exp: e^r = cosh r + sinh r (Q2.14 rotation stage) ----------
    c, s = _coshsinh_q(_quantize_f(r, fb, bits), sched, cfg)  # fmt registers
    eq = _wrap16(c + s, bits)                           # e^r in (0.70, 1.42)
    ki = k.astype(_I32)
    ef = jnp.where(dead, 0.0, _dequantize_f(eq, fb) * _exp2_i32(ki))

    # --- sum + exponent-field frexp: S = mS * 2^p, mS in [1, 2) ------------
    ssum = jnp.sum(ef, axis=-1, keepdims=True)
    p = (jax.lax.bitcast_convert_type(ssum, jnp.int32) >> 23) - 127
    ms = ssum * _exp2_i32(-p)
    mq = jnp.broadcast_to(_quantize_f(ms, fb, bits), eq.shape)

    # --- R2-LVC normalization: (e^r / 2) / mS, ratio in (0.175, 0.71) ------
    t = _lvc_div_q(mq, _shr(eq, 1, bits), sched, cfg)   # zfmt quotient codes
    tf = _dequantize_f(_guard_drop(t, cfg), fb)         # no-op when z_guard=0
    out = tf * _exp2_i32(ki - p + 1)
    o_ref[...] = jnp.where(dead, 0.0, out).astype(o_ref.dtype)


def _log_softmax_kernel(x_ref, o_ref, *, sched: MRSchedule, cfg: FixedConfig,
                        n_valid: int):
    """Fused CORDIC log-softmax: y_i = u_i - ln(sum_j e^{u_j}).

    Shares the max-subtract + CORDIC-exp pass with the softmax kernel; the
    normalization swaps the R2-LVC division for the hyperbolic-vectoring log
    leg (ln S = 2 atanh((m-1)/(m+1)) + p ln2 on the sum's mantissa). Masked
    lanes (-inf / -1e30) keep their hugely negative u, matching
    jax.nn.log_softmax semantics on padded attention rows.
    """
    fb = cfg.fmt.frac_bits
    bits = cfg.fmt.total_bits

    xf = x_ref[...].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1)
    live = col < n_valid
    xf = jnp.where(live, xf, np.float32(-1e30))

    # --- max-subtract + dyadic reduction (same pass as the softmax kernel) --
    m = jnp.max(xf, axis=-1, keepdims=True)
    u = xf - m                                          # <= 0
    dead = (~live) | (u < _DEAD_CUTOFF)
    k = jnp.maximum(jnp.floor(u * _INV_LN2 + 0.5), _MIN_K)
    r = jnp.where(dead, 0.0, u - k * _LN2)              # |r| <= ln2/2

    # --- CORDIC exp for the row sum ----------------------------------------
    c, s = _coshsinh_q(_quantize_f(r, fb, bits), sched, cfg)
    eq = _wrap16(c + s, bits)
    ef = jnp.where(dead, 0.0, _dequantize_f(eq, fb) * _exp2_i32(k.astype(_I32)))
    ssum = jnp.sum(ef, axis=-1, keepdims=True)          # in [1, cols)

    # --- hyperbolic-vectoring log of the sum -------------------------------
    lns = _log_q(ssum, cfg)
    o_ref[...] = (u - lns).astype(o_ref.dtype)


#: row-tile-sized f32 buffers live at once in a row kernel: the double-
#: buffered input and output blocks plus the CORDIC temporaries. Mosaic
#: asks 27.5 MiB of scoped VMEM for an (8, 64000) f32 tile on v5e, i.e.
#: ~14 tiles; 16 leaves headroom.
_LIVE_TILES = 16
#: working-set target per grid step, inside the 16 MiB default scoped
#: VMEM limit of v5e/v6e
_VMEM_BUDGET = 12 << 20
_DEFAULT_SCOPED_VMEM = 16 << 20


def _row_block(rows: int, cols_p: int) -> tuple:
    """Rows per block and the block's VMEM working set in bytes: whole
    rows only, sized so the working set stays near _VMEM_BUDGET. The
    sublane floor of 8 rows can overshoot it for very wide rows (a 64000-
    column vocabulary); the caller then raises the scoped VMEM limit."""
    row_bytes = 4 * cols_p * _LIVE_TILES
    br = min(max(1, _VMEM_BUDGET // row_bytes), rows)
    if rows >= 8:
        br = max(8, (br // 8) * 8)
    return br, br * row_bytes


def _rowwise_call(x: jax.Array, body, sched: MRSchedule, cfg: FixedConfig,
                  interpret: bool) -> jax.Array:
    """Pad columns to the 128-lane boundary and run a whole-row kernel."""
    rows, cols = x.shape
    cols_p = max(128, -(-cols // 128) * 128)
    if cols_p != cols:
        pad = jnp.full((rows, cols_p - cols), np.float32(-1e30), x.dtype)
        x = jnp.concatenate([x, pad], axis=1)
    br, vmem = _row_block(rows, cols_p)
    grid = (pl.cdiv(rows, br),)
    spec = pl.BlockSpec((br, cols_p), lambda i: (i, 0))
    kern = functools.partial(body, sched=sched, cfg=cfg, n_valid=cols)
    params = (pltpu.CompilerParams(vmem_limit_bytes=vmem)
              if vmem > _DEFAULT_SCOPED_VMEM else None)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((rows, cols_p), x.dtype),
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        compiler_params=params,
        interpret=interpret,
    )(x)
    return out[:, :cols]


def softmax_2d(x: jax.Array, *, sched: MRSchedule = PAPER_SCHEDULE,
               cfg: FixedConfig = PAPER_FIXED, interpret: bool = False) -> jax.Array:
    """Fused CORDIC softmax over the last axis of a 2D array.

    Columns are padded to the 128-lane boundary; padded lanes are masked
    inside the kernel (they contribute exactly 0 to the row sum).
    """
    return _rowwise_call(x, _softmax_kernel, sched, cfg, interpret)


def log_softmax_2d(x: jax.Array, *, sched: MRSchedule = PAPER_SCHEDULE,
                   cfg: FixedConfig = PAPER_FIXED,
                   interpret: bool = False) -> jax.Array:
    """Fused CORDIC log-softmax over the last axis of a 2D array."""
    return _rowwise_call(x, _log_softmax_kernel, sched, cfg, interpret)
