"""Dry-run machinery tests.

The full 512-device dry-run needs a fresh process (XLA device count locks at
first jax init), so the production meshes are exercised via subprocess for
one representative cell; the sharding-rule logic is tested in-process.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as PS

from repro import configs
from repro.configs.base import SHAPES, shape_applicable
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis as hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# sharding rules (in-process, mesh over 1 device is fine for spec logic)
# ---------------------------------------------------------------------------
def _mesh_16x16_abstract():
    """AbstractMesh carries only names/shapes — perfect for spec logic."""
    from jax.sharding import AbstractMesh

    return AbstractMesh((16, 16), ("data", "model"))


def test_spec_divisibility_fallback():
    mesh = _mesh_16x16_abstract()
    # kv_heads = 4 on a 16-way model axis must fall back to replicated
    spec = shd.spec_for_axes(("embed", "kv_heads", None), (4096, 4, 128), mesh)
    assert spec == PS(None, None) or spec == PS()
    # divisible dims shard
    spec = shd.spec_for_axes(("embed", "heads", None), (4096, 32, 128), mesh)
    assert spec == PS(None, "model")


def test_spec_one_axis_use():
    mesh = _mesh_16x16_abstract()
    # experts and mlp both want "model": only the first gets it
    spec = shd.spec_for_axes(("experts", "embed", "mlp"), (64, 2048, 1408), mesh)
    assert spec == PS("model",)


def test_batch_spec_modes():
    mesh = _mesh_16x16_abstract()
    assert shd.batch_spec(mesh, 256, 4096) == PS(("data",), None)
    # batch=1 long-context: sequence sharding
    assert shd.batch_spec(mesh, 1, 524288) == PS(None, ("data",))
    # batch=1, seq=1: fully replicated
    assert shd.batch_spec(mesh, 1, 1) == PS()


def test_shape_applicability_rules():
    for arch in configs.ARCH_IDS:
        cfg = configs.get_config(arch)
        ok, why = shape_applicable(cfg, SHAPES["long_500k"])
        assert ok == cfg.sub_quadratic
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(cfg, SHAPES[s])[0]
    assert configs.get_config("xlstm-1.3b").sub_quadratic
    assert configs.get_config("zamba2-1.2b").sub_quadratic
    assert sum(configs.get_config(a).sub_quadratic for a in configs.ARCH_IDS) == 2


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
def test_collective_bytes_parser():
    text = """
  %ag = bf16[4,1024,128]{2,1,0} all-gather(bf16[4,64,128]{2,1,0} %p), dims={1}
  %ar.1 = f32[2048]{0} all-reduce(f32[2048]{0} %x), to_apply=%sum
  %a2a = f32[16,32]{1,0} all-to-all(f32[16,32]{1,0} %y), dimensions={0}
  %cp = u8[100]{0} collective-permute(u8[100]{0} %z)
  %ar-start = f32[8]{0} all-reduce-start(f32[8]{0} %w), to_apply=%sum
  %ar-done = f32[8]{0} all-reduce-done(f32[8]{0} %ar-start)
"""
    got = hlo.collective_bytes(text)
    assert got["op_counts"]["all-gather"] == 1
    assert got["op_counts"]["all-reduce"] == 2   # sync + async start
    ag = 4 * 1024 * 128 * 2
    ar = 2048 * 4 + 8 * 4
    a2a = 16 * 32 * 4
    cp = 100
    assert got["per_kind_bytes"]["all-gather"] == ag
    assert got["per_kind_bytes"]["all-reduce"] == ar
    assert got["weighted_bytes"] == pytest.approx(2 * ar + ag + a2a + cp)


def test_roofline_terms():
    t = hlo.roofline_terms(197e12, 819e9, 50e9)  # 1s each by construction
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(1.0)
    t2 = hlo.roofline_terms(1e12, 900e9, 1e9)
    assert t2["dominant"] == "memory_s"


# ---------------------------------------------------------------------------
# one real dry-run cell through the actual 512-device path (subprocess)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    out = tmp_path / "cell.json"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "internvl2-1b",
         "--shape", "decode_32k", "--multi-pod", "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    rec = json.load(open(out))[0]
    assert rec["status"] == "ok"
    assert rec["devices"] == 512
    assert rec["roofline"]["dominant"] in ("compute_s", "memory_s", "collective_s")
