"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init;
smoke tests and benches see the real single device.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np


def _auto(n: int):
    # jax.make_mesh defaults to Explicit axes, which type-check shardings
    # at trace time; the serving and dry-run code is written for the
    # partitioner (sharding constraints + shard_map), i.e. Auto axes
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 ("data","model") single pod; 2x16x16 ("pod","data","model")
    for the two-pod (512-chip) dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """("data","model") mesh over whatever devices exist (CPU tests /
    examples; force more with XLA_FLAGS=--xla_force_host_platform_
    device_count=N). The model axis is ``model_parallel`` wide, the data
    axis soaks up the rest."""
    n = jax.device_count()
    if model_parallel < 1 or n % model_parallel != 0:
        raise ValueError(
            f"device count {n} is not divisible by model_parallel="
            f"{model_parallel}; pick a tensor-parallel degree that divides "
            "the devices visible to jax (force more CPU devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=_auto(2))


def mesh_or_none(model_parallel: int = 1) -> Optional[jax.sharding.Mesh]:
    """``make_host_mesh`` for multi-shard runs, ``None`` for TP=1.

    Single-device paths must never construct a trivial mesh: a 1-wide
    mesh still commits every array to an explicit sharding, changing jit
    cache keys and forcing device_put traffic for nothing. Callers treat
    ``None`` as "stay on the legacy single-device datapath"."""
    if model_parallel in (None, 0, 1):
        return None
    return make_host_mesh(model_parallel)
