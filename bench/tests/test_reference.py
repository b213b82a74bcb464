"""The plain reference against the program's own forward pass, at a small
size on the CPU, and the weights it draws again against the program's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import weights
import run as bench_run

SMALL = {"num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 96, "vocab_size": 128, "rope_theta": 10000.0,
         "norm_eps": 1e-5, "dtype": "float32", "act_impl": "exact",
         "softmax_impl": "exact"}
ENGINE = {"block_len": 16, "paged_attend_impl": "gather"}


def test_reference_matches_the_program_forward_pass():
    from repro.models import transformer as tf

    cfg = weights.model_config(SMALL, ENGINE)
    params = weights.program_tree(SMALL, 5, jnp.float32)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, 128, n).astype(np.int32) for n in (37, 20)]
    wanted = [np.arange(len(s)) for s in seqs]
    ref = reference.logits_at(weights.Source(SMALL, 5, jnp.float32), SMALL,
                              seqs, wanted, pad_to=16)
    with jax.default_matmul_precision("highest"):
        for s, r in zip(seqs, ref):
            prog = tf.apply(params, {"tokens": jnp.asarray(s[None])}, cfg)[0][0]
            np.testing.assert_allclose(np.asarray(r), np.asarray(prog),
                                       rtol=2e-4, atol=2e-4)


def test_layer_source_draws_the_program_weights_again():
    m = dict(SMALL, dtype="bfloat16")
    tree = weights.program_tree(m, 2**33 + 9, jnp.bfloat16)
    src = weights.Source(m, 2**33 + 9, jnp.bfloat16)
    stacked = [lf[0] for lf in weights.leaves(m) if lf[2]]
    for l in range(m["num_layers"]):
        lay = src.layer(l)
        for path in stacked:
            got = lay[path[-2] + "." + path[-1]]
            want = tree[path[0]][path[1]][path[2]][l].astype(jnp.float32)
            assert np.array_equal(np.asarray(got), np.asarray(want)), path
    g = src.globals()
    assert np.array_equal(np.asarray(g["lm_head"]),
                          np.asarray(tree["lm_head"]["table"], np.float32))


def test_layout_matches_the_program_tree():
    cfg = weights.model_config(SMALL, ENGINE)
    bench_run.check_layout(weights, cfg, SMALL, jnp.float32)


def test_gap_is_zero_for_the_reference_argmax():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(6, 50)),
                         jnp.float32)
    top = np.asarray(jnp.argmax(logits, -1))
    assert reference.gaps(logits, top).max() == 0.0
    other = (top + 1) % 50
    assert (reference.gaps(logits, other) > 0).all()


def test_fp8_rounding_is_coarser_than_bf16():
    x = jnp.asarray(np.random.default_rng(2).normal(size=(64, 256)),
                    jnp.float32)
    e8 = float(jnp.abs(reference.fp8(x, (-1,)) - x).mean())
    e16 = float(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).mean())
    assert e8 > 4 * e16 > 0
