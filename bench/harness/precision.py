"""Arithmetic of the plain references, by precision.

``f32`` is the reference itself: float32 operands at ``highest`` matmul
precision (on a TPU a float32 matmul otherwise runs as a single bfloat16
pass).  The lower precisions are the controls that the comparison has to
reject, one step below what a configuration states:

* ``bf16`` for a float32 configuration: weights, optimizer state and
  activations held in bfloat16, matmuls accumulated in float32;
* ``fp8`` for a bfloat16 configuration: every matmul operand rounded to
  float8 e4m3 with a per-tensor scale (gradients pass straight through),
  accumulated in float32.

``default`` is float32 at the TPU's default matmul precision: the
precision of the program's float32 path, used to plant faults cheaply.
Norms, softmax and the loss are computed in float32 in every mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

MODES = ("f32", "default", "bf16", "fp8")
_FP8_MAX = 448.0


def _fp8(t):
    t = t.astype(jnp.float32)
    s = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / _FP8_MAX)
    q = (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return t + jax.lax.stop_gradient(q - t)


class Num:
    def __init__(self, mode: str):
        if mode not in MODES:
            raise ValueError(f"unknown precision {mode!r}; have {MODES}")
        self.mode = mode
        self.act = jnp.bfloat16 if mode == "bf16" else jnp.float32

    def store(self, tree):
        """Trainable weights as this mode holds them."""
        if self.mode != "bf16":
            return tree
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), tree)

    def ein(self, spec: str, x, w):
        f32 = jnp.float32
        if self.mode == "f32":
            return jnp.einsum(spec, x.astype(f32), w.astype(f32),
                              precision=jax.lax.Precision.HIGHEST)
        if self.mode == "default":
            return jnp.einsum(spec, x.astype(f32), w.astype(f32))
        if self.mode == "bf16":
            return jnp.einsum(spec, x.astype(jnp.bfloat16),
                              w.astype(jnp.bfloat16),
                              preferred_element_type=f32
                              ).astype(jnp.bfloat16)
        return jnp.einsum(spec, _fp8(x), _fp8(w),
                          precision=jax.lax.Precision.HIGHEST)
