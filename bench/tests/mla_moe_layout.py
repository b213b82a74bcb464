"""Harness fixture: the layout of a model that is not a GQA decoder, for
the benchmark's CPU tests.

It serves the program registry's ``deepseek-v2-lite-16b`` smoke preset
(latent attention, routed and shared experts, a leading dense layer),
named by ``model["preset"]`` in a test's configuration, and draws every
leaf of the program's own parameter tree with bench/weights.py's per-leaf
key scheme. It is a fixture of the seam in bench/run.py, not a layout of
a served configuration: a configuration of this model brings its own.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

import weights


def _preset(m: dict):
    from repro.configs import get_smoke

    cfg = get_smoke(m["preset"], act_impl=m["act_impl"])
    return dataclasses.replace(cfg, softmax_impl=m["softmax_impl"],
                               dtype=m["dtype"])


def model_config(m: dict, engine: dict):
    return dataclasses.replace(
        _preset(m), kv_impl="paged", kv_block_len=engine["block_len"],
        paged_attend_impl=engine["paged_attend_impl"])


def leaves(m: dict):
    """(path, per-layer shape, stacked, kind, fan-in, layers) per leaf, in
    the order of the program's parameter tree."""
    from repro.models import transformer as tf
    from repro.models.common import P

    specs = tf.model_spec(_preset(m))
    out = []
    for path, p in jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda x: isinstance(x, P)):
        stacked = p.axes[0] == "layers"
        shape, axes = ((p.shape[1:], p.axes[1:]) if stacked
                       else (p.shape, p.axes))
        dims = [n for n, a in zip(shape, axes) if a != "experts"]
        if p.scale:
            fan_in = 1.0 / p.scale ** 2
        elif axes[-1] == "embed" and len(dims) >= 2:
            fan_in = math.prod(dims[:-1])
        else:
            fan_in = dims[0]
        out.append((tuple(k.key for k in path), shape, stacked,
                    "norm" if p.init == "ones" else "w", fan_in,
                    p.shape[0] if stacked else 1))
    return out


def program_tree(m: dict, seed: int, dtype, shardings=None):
    table = leaves(m)

    def make(base):
        return weights._nest({lf[0]: weights._leaf(base, i, lf[:5], lf[5],
                                                   dtype)
                              for i, lf in enumerate(table)})

    return jax.jit(make, out_shardings=shardings)(weights.seed_key(seed))


def abstract_tree(m: dict, dtype) -> dict:
    return weights._nest({lf[0]: jax.ShapeDtypeStruct(
        ((lf[5],) if lf[2] else ()) + lf[1], dtype) for lf in leaves(m)})


class Source:
    """The same weights again, in float32, whole (the model is small)."""

    def __init__(self, m: dict, seed: int, dtype):
        self.cfg = _preset(m)
        self.params = jax.tree.map(lambda a: a.astype(jnp.float32),
                                   program_tree(m, seed, dtype))
