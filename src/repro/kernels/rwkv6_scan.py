"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The recurrence has no attention analogue: a per-head (D,D) state matrix with
*data-dependent per-channel decay* ``w_t``.  TPU adaptation: the state lives
in fp32 VMEM scratch and is carried across sequential grid steps along the
time-chunk axis; the grid's leading axis is (batch x heads), which is the
embarrassingly-parallel dim.  Inside a chunk the time loop is a
``lax.fori_loop`` over VMEM-resident (chunk, D) tiles — HBM traffic is one
read of r/k/v/w and one write of y per token, i.e. the kernel is
memory-bound by design (arithmetic intensity ~ D ops/byte).

The y_t contraction uses the algebraic split
    y_t = r_t @ S + (sum_i r_i u_i k_i) * v_t
which avoids materializing the (D,D) bonus outer product per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref, s_scr,
            r_scr, k_scr, v_scr, w_scr, y_scr, *, nt):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    # fp32 copies of the chunk: the time loop reads and writes one row per
    # step, and Mosaic slices rows at a traced offset only from 32-bit tiles
    for src, dst in ((r_ref, r_scr), (k_ref, k_scr), (v_ref, v_scr),
                     (w_ref, w_scr)):
        dst[...] = src[0].astype(jnp.float32)       # (chunk, D)
    u = u_ref[0].astype(jnp.float32)                # (1, D)

    def body(i, s):
        row = pl.ds(i, 1)
        rt, kt, vt, wt = r_scr[row], k_scr[row], v_scr[row], w_scr[row]
        ruk = jnp.sum(rt * u * kt, axis=-1, keepdims=True)   # (1, 1)
        y_scr[row] = jnp.dot(rt, s) + ruk * vt
        return wt.T * s + kt.T * vt

    s_scr[...] = jax.lax.fori_loop(0, r_scr.shape[0], body, s_scr[...])
    y_ref[0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(t == nt - 1)
    def _fin():
        sT_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(r, k, v, w, u, s0, *, chunk=128, interpret):
    """r,k,v,w: (B,T,H,D); u: (H,D); s0: (B,H,D,D) -> (y, sT). See ref.rwkv6_scan."""
    B, T, H, D = r.shape
    chunk = min(chunk, T)
    pad = (-T) % chunk
    BH = B * H

    def fold(x):  # (B,T,H,D) -> (BH, Tp, D)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(BH, T + pad, D)

    rf, kf, vf = fold(r), fold(k), fold(v)
    # padded decay=1, k=0: state passes through unchanged on padding steps.
    wf = fold(w)
    if pad:
        tmask = (jnp.arange(T + pad) < T)[None, :, None]
        wf = jnp.where(tmask, wf, 1.0)
    uf = jnp.broadcast_to(u[None], (B, H, D)).reshape(BH, 1, D)
    s0f = s0.reshape(BH, D, D)
    nt = (T + pad) // chunk

    y, sT = pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid=(BH, nt),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, 1, D), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((1, D, D), lambda i, t: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, D), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, D, D), lambda i, t: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T + pad, D), r.dtype),
            jax.ShapeDtypeStruct((BH, D, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)]
        + [pltpu.VMEM((chunk, D), jnp.float32)] * 5,
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0f)

    y = y[:, :T].reshape(B, H, T, D).transpose(0, 2, 1, 3)
    return y, sT.reshape(B, H, D, D)
