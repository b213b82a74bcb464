"""Operations and bytes the algorithm needs, computed from the shapes of a
configuration (its ``model`` block), never from the compiled program.

A later change to how the program computes (a bf16 pool, skipped dead
table chunks, another CORDIC kernel) changes the time a kernel takes and
not these counts. Every count is for one chip: a tensor-parallel model
splits heads and the feed-forward width over ``tp`` chips.
"""
from __future__ import annotations

BF16 = 2  # bytes


def _dims(m: dict):
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return (m["d_model"], m["num_heads"], m["num_kv_heads"], hd, m["d_ff"],
            m["vocab_size"], m["num_layers"])


def layer_matmul_params(m: dict) -> int:
    """Weights of one dense GQA layer's projections (q, k, v, o, SwiGLU)."""
    d, H, KH, hd, F, _, _ = _dims(m)
    return d * hd * (H + 2 * KH) + H * hd * d + 3 * d * F


def token_flops(m: dict, ctx: int, lm_head: bool) -> float:
    """Forward FLOPs of one token that attends ``ctx`` positions (itself
    included): the layer projections, QK^T and PV over ``ctx`` keys in
    every layer, and the LM head when the token's logits are needed."""
    d, H, _, hd, _, V, L = _dims(m)
    f = 2.0 * layer_matmul_params(m) * L + 4.0 * H * hd * ctx * L
    return f + (2.0 * d * V if lm_head else 0.0)


def prompt_flops(m: dict, plen: int) -> float:
    """FLOPs of a causal prompt of ``plen`` tokens: position i attends i+1
    keys; only the last position needs the LM head (it yields the first
    output token)."""
    d, H, _, hd, _, V, L = _dims(m)
    return (2.0 * layer_matmul_params(m) * L * plen
            + 4.0 * H * hd * L * plen * (plen + 1) / 2.0 + 2.0 * d * V)


def paged_attn_step(m: dict, ctxs, tp: int = 1):
    """(flops, bytes) of one decode attend over one layer, on one chip,
    for slots whose live context lengths are ``ctxs``: the live K and V
    at bf16, plus the query and the output at bf16."""
    _, H, KH, hd, _, _, _ = _dims(m)
    h, kh = H / tp, KH / tp
    n = sum(ctxs)
    flops = 4.0 * h * hd * n
    nbytes = 2.0 * n * kh * hd * BF16 + 2.0 * len(ctxs) * h * hd * BF16
    return flops, nbytes


def silu_mul_call(m: dict, tokens: int, tp: int = 1):
    """(flops, bytes) of the SwiGLU gate h = u * g * sigmoid(g) over one
    layer for ``tokens`` tokens: read g and u, write h, at bf16. FLOPs
    count a sigmoid as four operations plus the two products."""
    F = m["d_ff"] / tp
    return 6.0 * tokens * F, 3.0 * tokens * F * BF16


def roofline_s(flops: float, nbytes: float, peaks: dict):
    """(least seconds, bound) of work on one chip at its published peaks."""
    t_f = flops / peaks["bf16_flops_s"]
    t_b = nbytes / peaks["hbm_bytes_s"]
    return (t_b, "bytes") if t_b >= t_f else (t_f, "flops")
