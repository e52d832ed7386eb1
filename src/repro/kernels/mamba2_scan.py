"""Mamba2 SSD recurrence as a Pallas TPU kernel.

Grid: (batch, time-chunks); the (H, P, N) fp32 state is VMEM scratch carried
across sequential time-chunk steps.  All heads of one batch element are
updated together so the per-step einsums have an MXU-friendly (H*P, N)
shape.  Like the RWKV kernel this is a memory-bound streaming kernel: one
HBM read of x/dt/B/C and one write of y per token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hT_ref, h_scr,
            dt_scr, b_scr, c_scr, *, nt):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    # fp32 copies of the (chunk, H|N) tiles: the time loop reads one row per
    # step, and Mosaic slices rows at a traced offset only from 32-bit tiles
    for src, dst in ((dt_ref, dt_scr), (b_ref, b_scr), (c_ref, c_scr)):
        dst[...] = src[0].astype(jnp.float32)
    a = -jnp.exp(a_ref[...].astype(jnp.float32))    # (1, H)

    def body(i, h):
        row = pl.ds(i, 1)
        dt = dt_scr[row].T                                  # (H, 1)
        decay = jnp.exp(dt * a.T)[:, :, None]               # (H, 1, 1)
        dx = dt * x_ref[0, i].astype(jnp.float32)           # (H, P)
        h = decay * h + dx[:, :, None] * b_scr[row][None]   # (H, P, N)
        y = jnp.sum(h * c_scr[row][None], axis=-1)          # (H, P)
        y_ref[0, i] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, dt_scr.shape[0], body, h_scr[...])

    @pl.when(t == nt - 1)
    def _fin():
        hT_ref[0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_scan(x, dt, a_log, b, c, h0, *, chunk=128, interpret):
    """See ref.mamba2_scan: x (B,T,H,P), dt (B,T,H), a_log (H,), b/c (B,T,N),
    h0 (B,H,P,N) -> (y (B,T,H,P), hT)."""
    B, T, H, Pd = x.shape
    N = b.shape[-1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))   # dt=0 -> decay=1, no input
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    Tp = T + pad
    nt = Tp // chunk

    y, hT = pl.pallas_call(
        functools.partial(_kernel, nt=nt),
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((1, chunk, H, Pd), lambda i, t: (i, t, 0, 0)),
            pl.BlockSpec((1, chunk, H), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, H), lambda i, t: (0, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, t: (i, t, 0)),
            pl.BlockSpec((1, H, Pd, N), lambda i, t: (i, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, H, Pd), lambda i, t: (i, t, 0, 0)),
            pl.BlockSpec((1, H, Pd, N), lambda i, t: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, H, Pd), x.dtype),
            jax.ShapeDtypeStruct((B, H, Pd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((H, Pd, N), jnp.float32),
                        pltpu.VMEM((chunk, H), jnp.float32),
                        pltpu.VMEM((chunk, N), jnp.float32),
                        pltpu.VMEM((chunk, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, a_log.reshape(1, H), b, c, h0)

    return y[:, :T], hT
