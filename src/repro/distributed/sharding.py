"""Logical-axis -> mesh-axis sharding rules.

Model code annotates every parameter with logical axis names (models/common.P);
this module turns those into NamedShardings for a concrete mesh:

    RULES (ordered; first applicable wins, one mesh axis used at most once
    per tensor, divisibility checked — e.g. kv_heads=4 on a 16-way model
    axis falls back to replicated rather than failing):

        vocab    -> model        (embedding/logits vocab-parallel)
        mlp      -> model        (FFN tensor-parallel)
        heads    -> model        (attention head-parallel)
        kv_heads -> model        (when divisible)
        experts  -> model        (expert parallelism)
        embed    -> None         (d_model replicated across model axis)
        layers   -> None         (scan dim)

Batch/activation sharding: batch -> ("pod","data") when divisible; for
batch=1 long-context decode the *sequence* dim of activations/caches is
sharded over the data axis instead (sequence parallelism).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

#: logical axis -> candidate mesh axis (None = replicate)
DEFAULT_RULES: Tuple[Tuple[str, Optional[str]], ...] = (
    ("vocab", "model"),
    ("mlp", "model"),
    ("heads", "model"),
    ("kv_heads", "model"),
    ("experts", "model"),
    ("embed", None),
    ("layers", None),
)


def _mesh_axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def spec_for_axes(axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
                  mesh: Mesh, rules=DEFAULT_RULES) -> PS:
    """Build a PartitionSpec for one tensor, enforcing divisibility and
    one-use-per-mesh-axis."""
    rule_map = dict(rules)
    used = set()
    parts = []
    for dim, ax in zip(shape, axes):
        target = rule_map.get(ax) if ax is not None else None
        if (target is not None and target in mesh.shape and target not in used
                and dim % _mesh_axis_size(mesh, target) == 0):
            parts.append(target)
            used.add(target)
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return PS(*parts)


def tree_shardings(axes_tree, shape_tree, mesh: Mesh, rules=DEFAULT_RULES):
    """Map spec/axes trees -> NamedSharding tree (same structure)."""
    def one(axes, like):
        return NamedSharding(mesh, spec_for_axes(tuple(axes), tuple(like.shape),
                                                 mesh, rules))
    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def batch_spec(mesh: Mesh, global_batch: int, seq_len: int,
               extra_dims: int = 0) -> PS:
    """Sharding for (B, S, ...) activations/inputs.

    Prefers batch over ("pod","data"); falls back to sequence sharding
    (SP) for small batches (long-context decode with batch=1).
    """
    dp_axes = [a for a in ("pod", "data") if a in mesh.shape]
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    if global_batch % dp == 0 and global_batch >= dp:
        return PS(tuple(dp_axes), *([None] * (1 + extra_dims)))
    if seq_len % dp == 0:
        return PS(None, tuple(dp_axes), *([None] * extra_dims))
    return PS()


def cache_spec(mesh: Mesh, batch: int, seq_len: int, kv_heads: int) -> dict:
    """Shardings for KV-cache-like (B,S,KH,D) buffers: batch->data when
    divisible, else sequence->data (SP); kv heads->model when divisible."""
    dp_axes = [a for a in ("pod", "data") if a in mesh.shape]
    dp = int(np.prod([mesh.shape[a] for a in dp_axes])) if dp_axes else 1
    model = mesh.shape.get("model", 1)
    kh = "model" if (kv_heads % model == 0) else None
    if batch % dp == 0 and batch >= dp:
        return {"batch_axis": tuple(dp_axes), "seq_axis": None, "kv_axis": kh}
    return {"batch_axis": None, "seq_axis": tuple(dp_axes), "kv_axis": kh}


# ---------------------------------------------------------------------------
# Ambient serving mesh
#
# Model code (models/attention.py, models/transformer.py) is traced from
# inside ServeEngine's jits and must not take a mesh argument — the cfg
# dataclass is hashed into jit cache keys and a Mesh is not a config. The
# engine instead *enters* `serving_mesh(mesh)` around every trace/dispatch,
# and the model reads `active_serving_mesh()` at trace time to decide
# whether to emit sharding constraints / shard_map attention. Thread-local
# so concurrent engines on different meshes can't cross-talk.
# ---------------------------------------------------------------------------

_SERVING_MESH = threading.local()


def active_serving_mesh() -> Optional[Mesh]:
    """The mesh entered by the innermost `serving_mesh(...)`, or None."""
    return getattr(_SERVING_MESH, "mesh", None)


@contextlib.contextmanager
def serving_mesh(mesh: Optional[Mesh]):
    """Make `mesh` visible to model code traced inside this block."""
    prev = getattr(_SERVING_MESH, "mesh", None)
    _SERVING_MESH.mesh = mesh
    try:
        yield mesh
    finally:
        _SERVING_MESH.mesh = prev


def serve_param_shardings(cfg, params, mesh: Mesh):
    """NamedSharding tree for serving params on a ("data","model") mesh.

    The Megatron-style cut via DEFAULT_RULES (wq/wk/wv head-parallel,
    FFN column/row-parallel, untied lm_head vocab-parallel — the vocab
    cut is what makes the single logits all-gather the *only* gather in
    a decode step), with one serving-specific override: the embedding
    table is forced replicated. `jnp.take(table, tokens)` on a
    vocab-sharded table would lower to a collective inside the datapath;
    a replicated table keeps the embed lookup shard-local and costs only
    vocab*d_model bytes per device. Tied-embeddings models therefore
    replicate the head too (documented carve-out: zero all-gathers —
    logits are computed replicated from replicated weights).
    """
    from repro.models import common as cm
    from repro.models import transformer as _tf

    axes = cm.param_axes(_tf.model_spec(cfg))
    sh = tree_shardings(axes, params, mesh)
    repl = NamedSharding(mesh, PS())
    if "embed" in sh:
        sh["embed"] = jax.tree.map(lambda _: repl, sh["embed"])
    return sh


def init_serve_params(cfg, key, mesh: Optional[Mesh] = None):
    """Seeded random serving params in ``cfg.dtype``, built by one jit.

    With a mesh, the jit's out_shardings are ``serve_param_shardings``:
    each device draws only its own shard, so a sharded load never stages
    the whole tree on one device. The values do not depend on the mesh
    (jax's threefry PRNG is partitionable), so TP=N serves the same
    weights as TP=1."""
    from repro.models import transformer as _tf

    init = functools.partial(_tf.init, cfg, dtype=jnp.dtype(cfg.dtype))
    sh = (None if mesh is None else
          serve_param_shardings(cfg, jax.eval_shape(init, key), mesh))
    return jax.jit(init, out_shardings=sh)(key)


#: cache-tree leaf names whose dim -2 is the kv-head axis (dense stacked
#: (slots,1,S,KH,hd), per-slot (1,S,KH,hd), paged pools (N,L,KH,hd), and
#: the quantized pools' per-block scale tensors (N,1,KH,1) — with or
#: without a leading stacked-layer axis, -2 is always KH, so the scale
#: shards travel with the head slice whose codes they dequantize).
_KV_HEAD_LEAVES = ("k", "v", "k_pool", "v_pool",
                   "k_scale_pool", "v_scale_pool")


def kv_cache_shardings(cache, mesh: Mesh, rules=DEFAULT_RULES):
    """NamedSharding tree for a serve cache (dense or paged), same structure.

    k/v buffers and paged k/v pools shard their head axis (dim -2) over
    the model axis via the "kv_heads" rule — divisibility fallback to
    replicated comes for free from spec_for_axes. Everything else (block
    tables, lens, cur_idx, MLA latent `c_kv_pool`/`k_rope_pool`,
    recurrent state) is replicated: tables/lens are scalar-prefetched
    host metadata, and the MLA latent is per-slot-small + needed whole
    by every head shard.
    """
    repl = NamedSharding(mesh, PS())

    def one(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in _KV_HEAD_LEAVES and getattr(leaf, "ndim", 0) >= 2:
            axes = [None] * leaf.ndim
            axes[-2] = "kv_heads"
            return NamedSharding(
                mesh, spec_for_axes(tuple(axes), tuple(leaf.shape), mesh,
                                    rules))
        return repl

    return jax.tree_util.tree_map_with_path(one, cache)
