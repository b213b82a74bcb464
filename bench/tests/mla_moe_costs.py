"""Harness fixture: forward FLOPs of the smoke preset that
bench/tests/mla_moe_layout.py serves, from the preset's active parameters
(the experts a token is routed to, the shared experts, latent attention)
and its attention over ``ctx`` keys. A configuration of this model brings
its own counts, taken from its published shapes."""
from __future__ import annotations


def _preset(m: dict):
    from repro.configs import get_smoke

    return get_smoke(m["preset"])


def token_flops(m: dict, ctx: int, lm_head: bool) -> float:
    cfg = _preset(m)
    d, V, H, L = cfg.d_model, cfg.vocab_size, cfg.num_heads, cfg.num_layers
    body = cfg.param_counts()["active"] - 2 * V * d
    qk = cfg.mla.qk_nope_dim + cfg.mla.qk_rope_dim
    f = 2.0 * body + 2.0 * H * (qk + cfg.mla.v_dim) * ctx * L
    return f + (2.0 * d * V if lm_head else 0.0)


def prompt_flops(m: dict, plen: int) -> float:
    cfg = _preset(m)
    d, V = cfg.d_model, cfg.vocab_size
    return (sum(token_flops(m, i + 1, lm_head=False) for i in range(plen))
            + 2.0 * d * V)
