"""Training-substrate tests: optimizer, data pipeline, checkpointing,
gradient compression, microbatch accumulation."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint import manager as ckpt
from repro.data.pipeline import DataConfig, DataIterator, SyntheticLMDataset
from repro.distributed import compression as comp
from repro.optim import adamw
from repro.train import step as step_lib


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------
def test_adamw_matches_reference():
    """One AdamW step vs a hand-rolled numpy reference."""
    cfg = adamw.AdamWConfig(lr=1e-2, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01,
                            grad_clip=1e9)
    p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    g = {"w": jnp.asarray([0.1, 0.2, -0.3])}
    st = adamw.init(p)
    p2, st2, _ = adamw.apply_updates(p, st, g, cfg)

    gn = np.asarray(g["w"], np.float64)
    m = 0.1 * gn
    v = 0.001 * gn * gn
    mh = m / (1 - 0.9)
    vh = v / (1 - 0.999)
    ref = np.asarray(p["w"], np.float64) - 1e-2 * (
        mh / (np.sqrt(vh) + 1e-8) + 0.01 * np.asarray(p["w"], np.float64))
    np.testing.assert_allclose(np.asarray(p2["w"]), ref, rtol=1e-5)
    assert int(st2.step) == 1


def test_grad_clipping():
    g = {"w": jnp.full((4,), 100.0)}
    clipped, gn = adamw.clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(200.0)
    assert float(adamw.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------
def test_data_determinism_and_resume():
    cfg = DataConfig(vocab_size=97, seq_len=16, global_batch=4, seed=7)
    ds = SyntheticLMDataset(cfg)
    a = ds.batch_at(12)
    b = ds.batch_at(12)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])

    it = DataIterator(ds)
    for _ in range(5):
        next(it)
    st = it.state()
    x = next(it)
    it2 = DataIterator(ds)
    it2.restore(st)
    y = next(it2)
    np.testing.assert_array_equal(x["tokens"], y["tokens"])


def test_data_labels_are_shifted_tokens():
    cfg = DataConfig(vocab_size=50, seq_len=8, global_batch=2, seed=1)
    b = SyntheticLMDataset(cfg).batch_at(0)
    # label[t] is the next token of tokens[t] — consistency of the stream
    assert b["tokens"].shape == b["labels"].shape == (2, 8)
    assert (b["tokens"][:, 1:] == b["labels"][:, :-1]).all()


def test_data_local_slice():
    cfg = DataConfig(vocab_size=50, seq_len=8, global_batch=8, seed=1)
    ds = SyntheticLMDataset(cfg)
    b = ds.batch_at(3)
    parts = [ds.local_slice(b, i, 4) for i in range(4)]
    np.testing.assert_array_equal(np.concatenate([p["tokens"] for p in parts]),
                                  b["tokens"])


def test_data_learnable_structure():
    """The synthetic stream must beat uniform entropy (it's learnable)."""
    cfg = DataConfig(vocab_size=64, seq_len=128, global_batch=8, seed=3)
    b = SyntheticLMDataset(cfg).batch_at(0)
    # bigram predictability: most mass concentrated on few successors
    from collections import Counter

    cnt = Counter(zip(b["tokens"].ravel()[:-1], b["tokens"].ravel()[1:]))
    uni = Counter(b["tokens"].ravel())
    top = sum(c for _, c in cnt.most_common(64 * 4))
    assert top / sum(cnt.values()) > 0.5  # structured, not uniform


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "nested": {"b": jnp.ones((4,), jnp.int32)}}
    ckpt.save(str(tmp_path), 5, state, extra={"data_step": 5})
    assert ckpt.latest_step(str(tmp_path)) == 5
    like = jax.tree.map(lambda a: jnp.zeros_like(a), state)
    rest, extra = ckpt.restore(str(tmp_path), 5, like)
    np.testing.assert_array_equal(np.asarray(rest["a"]), np.asarray(state["a"]))
    assert extra["data_step"] == 5


def test_checkpoint_atomic_commit(tmp_path):
    """Partial (uncommitted) checkpoints are invisible to latest_step."""
    state = {"a": jnp.ones((2,))}
    ckpt.save(str(tmp_path), 1, state)
    # simulate a crashed writer: directory without manifest
    os.makedirs(tmp_path / "step_00000002")
    (tmp_path / "step_00000002" / "arrays.npz").write_bytes(b"garbage")
    assert ckpt.latest_step(str(tmp_path)) == 1


def test_async_checkpointer(tmp_path):
    saver = ckpt.AsyncCheckpointer(str(tmp_path))
    state = {"w": jnp.full((128, 128), 3.0)}
    saver.save(7, state)
    saver.wait()
    rest, _ = ckpt.restore(str(tmp_path), 7, jax.tree.map(jnp.zeros_like, state))
    assert float(rest["w"][0, 0]) == 3.0


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------
def test_compression_error_feedback_unbiased():
    """Sum of (compressed grads + final error) == sum of raw grads."""
    rng = np.random.default_rng(0)
    g_seq = [{"w": jnp.asarray(rng.normal(0, 1, (64,)), jnp.float32)}
             for _ in range(20)]
    err = comp.init_error_state(g_seq[0])
    total_c = np.zeros(64)
    total_raw = np.zeros(64)
    for g in g_seq:
        gc, err = comp.compress_grads(g, err)
        total_c += np.asarray(gc["w"])
        total_raw += np.asarray(g["w"])
    resid = np.abs(total_c + np.asarray(err["w"]) - total_raw).max()
    assert resid < 1e-3


def test_compression_roundtrip_error_bounded():
    x = jnp.asarray(np.random.default_rng(1).normal(0, 2, (1000,)), jnp.float32)
    q, s = comp.quantize_int8(x)
    err = np.abs(np.asarray(comp.dequantize_int8(q, s)) - np.asarray(x)).max()
    assert err <= float(s) * 0.5 + 1e-6


def test_psum_compressed_matches_mean():
    """shard_map int8 psum ~ uncompressed mean within quantization error."""
    from jax.sharding import PartitionSpec as PS

    mesh = jax.make_mesh((1,), ("d",))
    x = jnp.asarray(np.random.default_rng(2).normal(0, 1, (8,)), jnp.float32)
    f = jax.shard_map(lambda v: comp.psum_compressed(v, "d"), mesh=mesh,
                      in_specs=PS(), out_specs=PS(), check_vma=False)
    got = np.asarray(f(x))
    assert np.abs(got - np.asarray(x)).max() < float(jnp.max(jnp.abs(x))) / 127 + 1e-6


# ---------------------------------------------------------------------------
# Microbatch accumulation
# ---------------------------------------------------------------------------
def test_accumulation_matches_full_batch():
    cfg = configs.get_smoke("yi-9b", act_impl="exact")
    opt = adamw.AdamWConfig(lr=1e-3)
    state = step_lib.init_state(cfg, jax.random.PRNGKey(0), opt)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (4, 16))
    batch = {"tokens": jnp.asarray(toks, jnp.int32),
             "labels": jnp.asarray(np.roll(toks, -1, 1), jnp.int32)}

    s1 = jax.jit(step_lib.make_train_step(cfg, opt, accum=1))
    s2 = jax.jit(step_lib.make_train_step(cfg, opt, accum=2))
    st1, m1 = s1(state, batch)
    st2, m2 = s2(state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    l1 = jax.tree.leaves(st1.params)
    l2 = jax.tree.leaves(st2.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
