"""Layout of a dense GQA decoder (Yi, DeepSeek-Coder): the program's model
configuration and its seeded weights, made by the benchmark.

A configuration (``bench/configs/<config>.json``) names three modules by
path, and ``bench/run.py`` loads them and nothing model-specific besides:

  ``layout``     ``model_config(m, engine)``, the program's ``ModelConfig``
                 for the configuration's ``model`` block, and its weights:
                 ``abstract_tree(m, dtype)``, ``program_tree(m, seed, dtype,
                 shardings)`` and ``Source(m, seed, dtype)``;
  ``reference``  ``widest_gap(source, m, served, control=)``, the plain
                 reference that decides ``correct`` (bench/reference.py);
  ``costs``      ``token_flops(m, ctx, lm_head)`` and ``prompt_flops(m,
                 plen)``, the model's operations for ``mfu`` (bench/costs.py).

This module is the layout of the GQA configurations. A model of another
shape joins the benchmark as new files: its configuration, a layout, a
reference and a counts module of its own, and its own roofline readers in
``bench/metrics/``.

The benchmark draws the weights itself, so that the reference can draw
the same ones again, layer by layer, after the program's copy is freed:
the reference takes nothing the program made. Each leaf, and each layer
of a stacked leaf, has its own key, ``fold_in(fold_in(seed_key, leaf),
layer)``, and is uniform with the leaf's standard deviation, computed
from the key's random bits by exact float steps (bit pattern of [1, 2),
minus 1.5, times a constant), so that any program that draws a layer
gets the same numbers. The values are rounded to the configuration's
dtype; the reference reads them back in float32.

Standard deviations: projections 1/sqrt(fan-in), the embedding 1, norm
scales uniform in [0.9, 1.1].

``program_tree`` lays the leaves out as the served program's parameter
tree: ``repro.models.transformer`` stacks the layers of a run of equal
blocks under ``seg0`` with a leading layer axis. ``run.py`` checks this
layout against the program's own before it serves.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: (path, per-layer shape, stacked over layers, kind, fan-in) per leaf
Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], bool, str, Optional[int]]


def model_config(m: dict, engine: dict):
    """The program's ModelConfig for a configuration's ``model`` block."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name="bench", family="dense", num_layers=m["num_layers"],
        d_model=m["d_model"], num_heads=m["num_heads"],
        num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
        d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["norm_eps"],
        act_impl=m["act_impl"], softmax_impl=m["softmax_impl"],
        dtype=m["dtype"], qkv_bias=False, tie_embeddings=False,
        kv_impl="paged", kv_block_len=engine["block_len"],
        paged_attend_impl=engine["paged_attend_impl"])


def dims(m: dict):
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return (m["d_model"], m["num_heads"], m["num_kv_heads"], hd, m["d_ff"],
            m["vocab_size"], m["num_layers"])


def leaves(m: dict) -> List[Leaf]:
    d, H, KH, hd, F, V, _ = dims(m)
    return [
        (("embed", "table"), (V, d), False, "w", 1),
        (("final_norm", "scale"), (d,), False, "norm", None),
        (("lm_head", "table"), (V, d), False, "w", d),
        (("seg0", "ln1", "scale"), (d,), True, "norm", None),
        (("seg0", "attn", "wq"), (d, H, hd), True, "w", d),
        (("seg0", "attn", "wk"), (d, KH, hd), True, "w", d),
        (("seg0", "attn", "wv"), (d, KH, hd), True, "w", d),
        (("seg0", "attn", "wo"), (H, hd, d), True, "w", H * hd),
        (("seg0", "ln2", "scale"), (d,), True, "norm", None),
        (("seg0", "mlp", "w_gate"), (d, F), True, "w", d),
        (("seg0", "mlp", "w_up"), (d, F), True, "w", d),
        (("seg0", "mlp", "w_down"), (F, d), True, "w", F),
    ]


def seed_key(seed: int):
    """A key for any whole seed up to 64 bits."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _draw(key, shape, kind: str, fan_in: Optional[int], dtype):
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = jax.lax.bitcast_convert_type((bits >> 9) | np.uint32(0x3F800000),
                                     jnp.float32) - 1.5       # [-0.5, 0.5)
    if kind == "norm":
        x = 1.0 + u * np.float32(0.2)
    else:
        x = u * np.float32(math.sqrt(12.0 / fan_in))
    return x.astype(dtype)


def _leaf(base, i: int, leaf: Leaf, num_layers: int, dtype, layer=None):
    _, shape, stacked, kind, fan_in = leaf
    k = jax.random.fold_in(jax.random.fold_in(base, 1), i)
    if not stacked:
        return _draw(k, shape, kind, fan_in, dtype)
    if layer is not None:
        return _draw(jax.random.fold_in(k, layer), shape, kind, fan_in, dtype)
    keys = jax.vmap(lambda l: jax.random.fold_in(k, l))(jnp.arange(num_layers))
    return jax.vmap(lambda kk: _draw(kk, shape, kind, fan_in, dtype))(keys)


def _nest(flat: Dict[Tuple[str, ...], object]) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


def program_tree(m: dict, seed: int, dtype, shardings=None):
    """Every weight in the program's tree layout, made on the device by one
    jitted call (``shardings``: the program's own, for a mesh)."""
    L = m["num_layers"]
    if L < 2:
        raise ValueError("the stacked layout needs at least two layers")
    table = leaves(m)

    def make(base):
        return _nest({lf[0]: _leaf(base, i, lf, L, dtype)
                      for i, lf in enumerate(table)})

    return jax.jit(make, out_shardings=shardings)(seed_key(seed))


def abstract_tree(m: dict, dtype) -> dict:
    """Shapes and dtypes of ``program_tree`` without making it."""
    L = m["num_layers"]
    return _nest({lf[0]: jax.ShapeDtypeStruct(
        ((L,) if lf[2] else ()) + lf[1], dtype) for lf in leaves(m)})


def _layer_fn(m: dict, dtype):
    table = leaves(m)
    L = m["num_layers"]

    @jax.jit
    def layer(base, l):
        return {lf[0][-2] + "." + lf[0][-1]:
                _leaf(base, i, lf, L, dtype, layer=l).astype(jnp.float32)
                for i, lf in enumerate(table) if lf[2]}
    return layer


def _global_fn(m: dict, dtype):
    table = leaves(m)
    L = m["num_layers"]

    @jax.jit
    def glob(base):
        return {lf[0][0]: _leaf(base, i, lf, L, dtype).astype(jnp.float32)
                for i, lf in enumerate(table) if not lf[2]}
    return glob


class Source:
    """The same weights again, in float32, a layer at a time (for the
    reference): ``layer(l)`` gives {"ln1.scale", "attn.wq", ...},
    ``globals()`` gives {"embed", "final_norm", "lm_head"}."""

    def __init__(self, m: dict, seed: int, dtype):
        self.base = seed_key(seed)
        self._layer = _layer_fn(m, dtype)
        self._glob = _global_fn(m, dtype)

    def layer(self, l: int) -> dict:
        return self._layer(self.base, jnp.int32(l))

    def globals(self) -> dict:
        return self._glob(self.base)
