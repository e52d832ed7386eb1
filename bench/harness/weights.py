"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights, not the program: the program's
``init_model`` is used only through ``jax.eval_shape`` for the names,
shapes and dtypes of its parameter tree.  So the plain reference, which
takes the same values, takes nothing that the program has made.

Rules, by leaf name (the published ``initializer_range`` of both BERT and
Qwen2 is 0.02):

* ``scale`` (a norm's gain): ones;
* LoRA ``a`` factors: normal with std ``1 / sqrt(d_in)``; ``b`` factors:
  zeros, so a fresh bank adds exactly nothing to the base;
* everything else, biases included: normal with std 0.02.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw threefry key from any non-negative seed (wider than 32 bits
    too) and a stream number."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _value(name: str, sds, key):
    if name == "scale":
        return jnp.ones(sds.shape, sds.dtype)
    if name == "b" and len(sds.shape) >= 2:
        return jnp.zeros(sds.shape, sds.dtype)
    std = (1.0 / sds.shape[-2]) ** 0.5 if name == "a" else 0.02
    return (std * jax.random.normal(key, sds.shape, jnp.float32)
            ).astype(sds.dtype)


def maker(template: Any):
    """``make(key) -> tree`` shaped like ``template`` (a tree of
    ShapeDtypeStructs); one program for the whole tree."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)

    @jax.jit
    def make(key):
        vals = [_value(_leaf_name(p), s, jax.random.fold_in(key, i))
                for i, (p, s) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, vals)

    return make
