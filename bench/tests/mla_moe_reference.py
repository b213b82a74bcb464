"""Harness fixture, not a plain reference: it scores served tokens with the
program's own unpaged forward pass (``repro.models.transformer.apply``
over each whole sequence, at ``highest`` matmul precision), so that the
benchmark's CPU tests can drive bench/run.py's check with a model that is
not a GQA decoder. It has no lower-precision control. The plain reference
of a served latent-attention, mixture-of-experts configuration imports
nothing of the program and comes with that configuration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference


def widest_gap(source, m: dict, served, *, control: bool = False):
    from repro.models import transformer as tf

    fwd = jax.jit(lambda p, t: tf.apply(p, {"tokens": t}, source.cfg)[0][0])
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for prompt, toks in served:
            seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
            logits = fwd(source.params, jnp.asarray(seq[None, :-1]))
            pos = reference.served_positions(len(prompt), len(toks))
            worst = max(worst, float(reference.gaps(logits[pos], toks).max()))
    return worst, None, sum(len(t) for _, t in served)
