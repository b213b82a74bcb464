"""Plain float32 reference of a dense GQA decoder, and its lower-precision
control.

The forward pass as the LLaMA-family papers describe it (Yi, arXiv:2403.04652;
DeepSeek-Coder, arXiv:2401.14196): token embedding; per layer
x += Wo·GQA(RoPE(Wq·n1), RoPE(Wk·n1), Wv·n1) with n1 = RMSNorm(x) and
x += W_down·(SiLU(W_gate·n2) * W_up·n2) with n2 = RMSNorm(x); a final
RMSNorm and an untied LM head. RoPE rotates the two halves of each head
(the "rotate_half" layout of the published checkpoints), with base
``rope_theta``. Every matrix product runs at ``Precision.HIGHEST``.

Departures from the published models, shared with the served program:
  * DeepSeek-Coder-33B publishes linear RoPE scaling by 4 (a 16K context);
    the served configuration and this reference use none. At the 2048
    positions served this changes the positions' angles, not the shapes
    or the work.
  * Weights are random (bench/weights.py), not the released checkpoints.

Nothing here imports the program. The weights come from bench/weights.py,
drawn again from the seed a layer at a time, and the reference runs layer
by layer over a batch of whole sequences, so that it fits beside nothing
else once the program's state is freed.

``control=True`` is the same pass with every matrix product's operands
rounded to float8 e4m3 (per-row scales for activations, per-output-channel
scales for weights, products and sums in float32): the next precision
below the configuration's bfloat16, which the benchmark's comparison has
to reject.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def fp8(a, axes):
    """Round ``a`` to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(a), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq: str, x, w, x_axes, w_axes, control: bool):
    if control:
        x, w = fp8(x, x_axes), fp8(w, w_axes)
    return jnp.einsum(eq, x, w, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (T, heads, hd), pos (T,): rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, control: bool):
    """One sequence, causal. q (T, H, hd); k, v (T, KH, hd)."""
    T, H, hd = q.shape
    KH = k.shape[1]
    qg = q.reshape(T, KH, H // KH, hd)
    if control:
        qg, k = fp8(qg, (-1,)), fp8(k, (-1,))
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) / np.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if control:
        p, v = fp8(p, (-1,)), fp8(v, (0,))
    o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)
    return o.reshape(T, H, hd)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _layer(x, w, *, m, control: bool):
    """x (B, T, d) f32 -> x after one decoder layer."""
    eps, theta = m.norm_eps, m.rope_theta
    T = x.shape[1]
    pos = jnp.arange(T)
    h = rmsnorm(x, w["ln1.scale"], eps)
    q = _mm("btd,dhk->bthk", h, w["attn.wq"], (-1,), (0,), control)
    k = _mm("btd,dhk->bthk", h, w["attn.wk"], (-1,), (0,), control)
    v = _mm("btd,dhk->bthk", h, w["attn.wv"], (-1,), (0,), control)
    q = jax.vmap(lambda a: rope(a, pos, theta))(q)
    k = jax.vmap(lambda a: rope(a, pos, theta))(k)
    o = jax.lax.map(lambda qkv: _attend(*qkv, control), (q, k, v))
    x = x + _mm("bthk,hkd->btd", o, w["attn.wo"], (-2, -1), (0, 1), control)
    h = rmsnorm(x, w["ln2.scale"], eps)
    g = _mm("btd,df->btf", h, w["mlp.w_gate"], (-1,), (0,), control)
    u = _mm("btd,df->btf", h, w["mlp.w_up"], (-1,), (0,), control)
    a = g * jax.nn.sigmoid(g) * u
    return x + _mm("btf,fd->btd", a, w["mlp.w_down"], (-1,), (0,), control)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _head(x, g, pos, *, m, control: bool):
    """Logits at positions ``pos`` (B, n) of x (B, T, d)."""
    xs = jnp.take_along_axis(x, pos[..., None], axis=1)
    xs = rmsnorm(xs, g["final_norm"], m.norm_eps)
    return _mm("bnd,vd->bnv", xs, g["lm_head"], (-1,), (-1,), control)


class Model(NamedTuple):
    """The sizes the reference needs, hashable so jit can take them static."""
    num_layers: int
    norm_eps: float
    rope_theta: float


def logits_at(source, m: dict, seqs: Sequence[np.ndarray],
              wanted: Sequence[np.ndarray], *, control: bool = False,
              pad_to: int = 256) -> List[jax.Array]:
    """Reference logits of each sequence at its ``wanted`` positions.

    ``source`` is a ``bench.weights.Source``. Sequences are padded to a
    common length (a multiple of ``pad_to``; causal attention hides the
    pad from real positions). Returns one (len(wanted[i]), vocab) float32
    array per sequence, on the device."""
    mm = Model(int(m["num_layers"]), float(m["norm_eps"]),
               float(m["rope_theta"]))
    B = len(seqs)
    T = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    toks = np.zeros((B, T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    n = max(len(w) for w in wanted)
    pos = np.zeros((B, n), np.int32)
    for i, w in enumerate(wanted):
        pos[i, :len(w)] = w
    g = source.globals()
    x = jnp.take(g["embed"], jnp.asarray(toks), axis=0)
    for l in range(mm.num_layers):
        x = _layer(x, source.layer(l), m=mm, control=control)
    out = _head(x, g, jnp.asarray(pos), m=mm, control=control)
    return [out[i, :len(w)] for i, w in enumerate(wanted)]


def served_positions(prompt_len: int, n_out: int) -> np.ndarray:
    """Positions whose logits chose served tokens 0..n_out-1: the last
    prompt position, then each served token but the last."""
    return prompt_len - 1 + np.arange(n_out)


def gaps(ref_logits, tokens) -> np.ndarray:
    """How far each chosen token's reference logit lies below the
    reference's best at the same position (0 where they agree)."""
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tok[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)


def widest_gap(source, m: dict, served: Sequence[Tuple[np.ndarray, List[int]]],
               *, control: bool = False):
    """Widest gap of the served tokens of ``served`` [(prompt, tokens)]
    against the reference, and, with ``control``, the widest gap of the
    tokens the float8 control puts first at the same positions (it need
    not decode: it reads the same prompts and served tokens).
    Returns (served gap, control gap or None, tokens compared)."""
    seqs = [np.concatenate([p, np.asarray(t, np.int32)])[:len(p) + len(t) - 1]
            for p, t in served]
    wanted = [served_positions(len(p), len(t)) for p, t in served]
    ref = logits_at(source, m, seqs, wanted)
    worst = max(float(gaps(r, t).max()) for r, (_, t) in zip(ref, served))
    ctrl = None
    if control:
        low = logits_at(source, m, seqs, wanted, control=True)
        ctrl = max(float(gaps(r, np.asarray(jnp.argmax(c, -1))).max())
                   for r, c in zip(ref, low))
    return worst, ctrl, sum(len(t) for _, t in served)
