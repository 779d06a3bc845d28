"""Weights from a seed: on the device, in one jitted call, in the type they
are served in. An architecture file gives the shapes; this makes the arrays."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def make(shapes, seed, dtype=jnp.bfloat16, shardings=None):
    """``shapes``: name -> (shape, std), std None for a norm gain. Matrices
    are N(0, std^2), gains 1 + 0.1 N(0, 1). The hardware generator (``rbg``)
    keeps it to seconds at several billion parameters."""
    def init(key):
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32 if std is None else dtype)
            out[name] = (1.0 + 0.1 * z).astype(dtype) if std is None \
                else z * jnp.asarray(std, dtype)
        return out

    key = jax.random.key(int(seed) % (2 ** 31 - 1), impl="rbg")
    return jax.jit(init, out_shardings=shardings)(key)
