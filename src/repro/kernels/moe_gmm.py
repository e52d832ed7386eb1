"""MoE grouped-matmul Pallas TPU kernel: fused per-expert SwiGLU FFN.

Computes, for every expert e over its (C, d) capacity buffer:
    y_e = (silu(x_e @ Wg_e) * (x_e @ Wu_e)) @ Wo_e
as ONE kernel, so the (C, f) hidden activations never round-trip to HBM —
the fusion that makes expert-parallel MoE on TPU bandwidth-sane.

Grid: (experts, capacity-blocks, ffn-blocks); the ffn-block axis is innermost
(sequential), accumulating partial y in fp32 VMEM scratch.  Tiles: x (bc, d),
Wg/Wu (d, bf), Wo (bf, d) — with bc=bf=128 and d a multiple of 128 every
matmul hits the MXU at full shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wg_ref, wu_ref, wo_ref, y_ref, acc_ref, *, nf):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)          # (bc, d)
    wg = wg_ref[0].astype(jnp.float32)        # (d, bf)
    wu = wu_ref[0].astype(jnp.float32)
    g = jax.lax.dot(x, wg)
    u = jax.lax.dot(x, wu)
    h = (g * jax.lax.logistic(g)) * u         # silu(g) * u
    acc_ref[...] += jax.lax.dot(h, wo_ref[0].astype(jnp.float32))

    @pl.when(f == nf - 1)
    def _out():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "interpret"))
def moe_ffn(xe, wi_gate, wi_up, wo, *, block_c=128, block_f=128,
            interpret):
    """xe: (E,C,d); wi_gate/wi_up: (E,d,f); wo: (E,f,d) -> (E,C,d)."""
    E, C, d = xe.shape
    f = wi_gate.shape[-1]
    bc = min(block_c, max(C, 8))
    bf = min(block_f, max(f, 8))
    pc, pf = (-C) % bc, (-f) % bf
    if pc:
        xe = jnp.pad(xe, ((0, 0), (0, pc), (0, 0)))
    if pf:
        wi_gate = jnp.pad(wi_gate, ((0, 0), (0, 0), (0, pf)))
        wi_up = jnp.pad(wi_up, ((0, 0), (0, 0), (0, pf)))
        wo = jnp.pad(wo, ((0, 0), (0, pf), (0, 0)))
    Cp, fp = C + pc, f + pf
    nc, nf = Cp // bc, fp // bf

    y = pl.pallas_call(
        functools.partial(_kernel, nf=nf),
        grid=(E, nc, nf),
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e, c, f_: (e, c, 0)),
            pl.BlockSpec((1, d, bf), lambda e, c, f_: (e, 0, f_)),
            pl.BlockSpec((1, d, bf), lambda e, c, f_: (e, 0, f_)),
            pl.BlockSpec((1, bf, d), lambda e, c, f_: (e, f_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e, c, f_: (e, c, 0)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, d), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bc, d), jnp.float32)],
        interpret=interpret,
    )(xe, wi_gate, wi_up, wo)

    return y[:, :C]
