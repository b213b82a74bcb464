"""Public jit'd wrappers around the CORDIC Pallas kernels.

Shape-polymorphic (any rank), dtype-polymorphic (f32/bf16; int16/int32 for
the integer path), differentiable (custom_jvp from the primal output), and
backend-adaptive: on the CPU container the kernels run in interpret mode
(the kernel body executes in Python, bit-exactly); on TPU the same
pallas_call compiles via Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import cordic_act as K
from repro.core.cordic import FixedConfig, MRSchedule, PAPER_FIXED, PAPER_SCHEDULE

_COLS = 1024


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _to_2d(x: jax.Array):
    n = x.size
    cols = min(_COLS, max(128, n)) if n >= 128 else max(n, 1)
    rows = -(-n // cols)
    pad = rows * cols - n
    flat = jnp.ravel(x)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, cols), n


def _from_2d(y2: jax.Array, n: int, shape, dtype):
    return jnp.ravel(y2)[:n].reshape(shape).astype(dtype)


def _elementwise(x: jax.Array, op: str, sched, cfg, max_doublings: int) -> jax.Array:
    x2, n = _to_2d(x)
    y2 = K.act_2d(x2, op, sched=sched, cfg=cfg, max_doublings=max_doublings,
                  interpret=_use_interpret())
    return _from_2d(y2, n, x.shape, x.dtype)


def _make_unary(op: str, deriv):
    @functools.partial(jax.custom_jvp, nondiff_argnums=(1, 2, 3))
    def f(x, sched=PAPER_SCHEDULE, cfg=PAPER_FIXED, max_doublings=3):
        return _elementwise(x, op, sched, cfg, max_doublings)

    @f.defjvp
    def f_jvp(sched, cfg, max_doublings, primals, tangents):
        (x,), (dx,) = primals, tangents
        y = f(x, sched, cfg, max_doublings)
        return y, deriv(x, y) * dx

    return f


#: sigmoid with the paper's |x|<=1 clamp contract.
sigmoid = _make_unary("sigmoid", lambda x, s: s * (1.0 - s))
#: sigmoid with dyadic range extension to |x| <= 8.
sigmoid_wide = _make_unary("sigmoid_wide", lambda x, s: s * (1.0 - s))
#: tanh with the paper's |z|<=0.5 clamp contract.
tanh = _make_unary("tanh", lambda x, t: 1.0 - t * t)

# Engine-derived function kinds, each a dedicated kernel bit-identical to its
# jnp fixed-path twin in cordic_engine.functions; tangent coefficients come
# from the primal output (exp' = y; softplus' = sigma = 1 - e^-y;
# elu' = y + alpha = alpha e^x on the negative branch).
exp = _make_unary("exp", lambda x, y: y)
# log's forward floors x at 1e-30, so the primal is constant (flat) for
# x <= 0 — the tangent must be 0 there, not 1/clamp.
log = _make_unary("log", lambda x, y: jnp.where(x > 1e-30, 1.0 / x, 0.0))
softplus = _make_unary("softplus", lambda x, y: -jnp.expm1(-y))
elu = _make_unary("elu", lambda x, y: jnp.where(x > 0, 1.0, y + 1.0))
#: gelu'(x) = Phi(x) + x phi(x) — cheap closed form, exact to first order.
gelu_erf = _make_unary(
    "gelu_erf",
    lambda x, y: jax.scipy.stats.norm.cdf(x) + x * jax.scipy.stats.norm.pdf(x))


@functools.partial(jax.custom_jvp, nondiff_argnums=(1, 2, 3))
def silu(x, sched=PAPER_SCHEDULE, cfg=PAPER_FIXED, max_doublings=3):
    """x * sigmoid(x), wide-range, fused in one kernel pass."""
    return _elementwise(x, "silu", sched, cfg, max_doublings)


@silu.defjvp
def _silu_jvp(sched, cfg, max_doublings, primals, tangents):
    (x,), (dx,) = primals, tangents
    y = silu(x, sched, cfg, max_doublings)
    # silu'(x) = s(x) + x s'(x) = y/x + s(1-s)x ; use stable form via sigmoid
    s = sigmoid_wide(x, sched, cfg, max_doublings)
    return y, (s + x * s * (1.0 - s)) * dx


@functools.partial(jax.custom_jvp, nondiff_argnums=(2, 3, 4))
def silu_mul(gate, up, sched=PAPER_SCHEDULE, cfg=PAPER_FIXED, max_doublings=3):
    """Fused SwiGLU combiner: up * gate * sigmoid(gate) (one VMEM pass).

    gate/up must have identical shapes (the two MLP projections).
    """
    assert gate.shape == up.shape
    g2, n = _to_2d(gate)
    u2, _ = _to_2d(up)
    y2 = K.silu_mul_2d(g2, u2, sched=sched, cfg=cfg, max_doublings=max_doublings,
                       interpret=_use_interpret())
    return _from_2d(y2, n, gate.shape, gate.dtype)


@silu_mul.defjvp
def _silu_mul_jvp(sched, cfg, max_doublings, primals, tangents):
    (g, u), (dg, du) = primals, tangents
    s = sigmoid_wide(g, sched, cfg, max_doublings)
    sg = g * s
    y = u * sg
    dsg = s + g * s * (1.0 - s)
    return y, u * dsg * dg + sg * du


@functools.partial(jax.custom_jvp, nondiff_argnums=(1, 2, 3))
def softmax(x, axis: int = -1, sched=PAPER_SCHEDULE, cfg=PAPER_FIXED):
    """Fused CORDIC softmax (max-subtract + CORDIC-exp + LVC normalize).

    Any rank; reduces along `axis`. -inf/-1e30 masked lanes flush to 0,
    matching jax.nn.softmax semantics on padded attention rows.
    """
    from repro.kernels import softmax_cordic as SM

    xm = jnp.moveaxis(x, axis, -1)
    lead = xm.shape[:-1]
    c = xm.shape[-1]
    y2 = SM.softmax_2d(xm.reshape(-1, c).astype(jnp.float32),
                       sched=sched, cfg=cfg, interpret=_use_interpret())
    return jnp.moveaxis(y2.reshape(*lead, c).astype(x.dtype), -1, axis)


@softmax.defjvp
def _softmax_jvp(axis, sched, cfg, primals, tangents):
    (x,), (dx,) = primals, tangents
    y = softmax(x, axis, sched, cfg)
    dy = y * (dx - jnp.sum(y * dx, axis=axis, keepdims=True))
    return y, dy


@functools.partial(jax.custom_jvp, nondiff_argnums=(1, 2, 3))
def log_softmax(x, axis: int = -1, sched=PAPER_SCHEDULE, cfg=PAPER_FIXED):
    """Fused CORDIC log-softmax (max-subtract + CORDIC-exp + CORDIC-log).

    Any rank; reduces along `axis`. -inf/-1e30 masked lanes keep their
    hugely negative value, matching jax.nn.log_softmax on padded rows.
    This is the train-path kernel behind cfg.loss_impl="cordic_pallas".
    """
    from repro.kernels import softmax_cordic as SM

    xm = jnp.moveaxis(x, axis, -1)
    lead = xm.shape[:-1]
    c = xm.shape[-1]
    y2 = SM.log_softmax_2d(xm.reshape(-1, c).astype(jnp.float32),
                           sched=sched, cfg=cfg, interpret=_use_interpret())
    return jnp.moveaxis(y2.reshape(*lead, c).astype(x.dtype), -1, axis)


@log_softmax.defjvp
def _log_softmax_jvp(axis, sched, cfg, primals, tangents):
    (x,), (dx,) = primals, tangents
    y = log_softmax(x, axis, sched, cfg)
    p = jnp.exp(y)
    return y, dx - jnp.sum(p * dx, axis=axis, keepdims=True)


def paged_attend_gqa(q, k_pool, v_pool, tables, k_len, *, scale,
                     softmax_impl: str = "exact", kv_dtype=None,
                     kv_quant: str = "none",
                     k_scale_pool=None, v_scale_pool=None,
                     sched=PAPER_SCHEDULE, cfg=PAPER_FIXED) -> jax.Array:
    """Block-walking paged GQA decode attend (kernels/paged_attention.py).

    Walks each row's live KV blocks through its block table — a
    double-buffered chunk of blocks in VMEM at a time, online softmax in
    f32 scratch — instead of gathering the full (max_len)-sized buffer.
    Selected by ``cfg.paged_attend_impl="pallas"`` in models.attention.
    With ``kv_quant`` set, the pools hold integer codes and the per-block
    scales ride along: each chunk dequantizes in VMEM via the CORDIC
    linear-rotation multiply (core/kv_quant.py).
    """
    from repro.kernels import paged_attention as PA

    return PA.gqa_decode(q, k_pool, v_pool, tables, k_len, scale=scale,
                         softmax_impl=softmax_impl, kv_dtype=kv_dtype,
                         kv_quant=kv_quant, k_scale_pool=k_scale_pool,
                         v_scale_pool=v_scale_pool,
                         sched=sched, cfg=cfg, interpret=_use_interpret())


def paged_attend_mla(q_eff, q_rope, c_pool, r_pool, tables, k_len, *, scale,
                     softmax_impl: str = "exact",
                     sched=PAPER_SCHEDULE, cfg=PAPER_FIXED) -> jax.Array:
    """Block-walking paged MLA decode attend (absorbed form); see
    paged_attend_gqa.  Returns latent outputs (B,H,R) f32."""
    from repro.kernels import paged_attention as PA

    return PA.mla_decode(q_eff, q_rope, c_pool, r_pool, tables, k_len,
                         scale=scale, softmax_impl=softmax_impl,
                         sched=sched, cfg=cfg, interpret=_use_interpret())


def sigmoid_q(x_q: jax.Array, sched=PAPER_SCHEDULE, cfg=PAPER_FIXED) -> jax.Array:
    """Integer path: Q2.14 codes in (int16/int32), Q2.14 codes out.

    The quantized-inference entry point — activations never leave the
    integer domain (no dequant/requant round trip).
    """
    x2, n = _to_2d(x_q)
    y2 = K.act_q_2d(x2, sched=sched, cfg=cfg, interpret=_use_interpret())
    return _from_2d(y2, n, x_q.shape, x_q.dtype)
