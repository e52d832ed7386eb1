"""Flash attention Pallas TPU kernel (GQA-aware, causal / sliding-window).

Layout: the wrapper folds (batch, kv_head) into the grid's first axis and
keeps the GQA group dim attached to the query block, so K/V are *not*
repeated in HBM (a Kv-head's K/V tile is loaded once and shared by its G
query heads — the point of GQA on a bandwidth-bound decode/prefill).

Tiling: q blocks (bq, G, D) x kv blocks (bk, D) with the classic online-
softmax accumulation in fp32 VMEM scratch; the kv-block grid axis is
innermost, i.e. sequential on TPU, which is what makes the scratch carry
legal.  Matmul shapes are (bq*G, D) @ (D, bk) — with bq=128, G>=1, D in
{64,128} both MXU dims are 128-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq, bk, G, D, causal, window, softcap, t_real, nk, scale):
    j = pl.program_id(1)          # q block
    kk = pl.program_id(2)         # kv block (sequential)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32).reshape(bq * G, D) * scale
    k = k_ref[0].astype(jnp.float32)                       # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq*G, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)

    rows = jax.lax.broadcasted_iota(jnp.int32, (bq * G, bk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq * G, bk), 1)
    pos_q = j * bq + rows // G
    pos_k = kk * bk + cols
    ok = pos_k < t_real                                    # mask kv padding
    if causal:
        ok &= pos_k <= pos_q
        if window:
            ok &= pos_k > pos_q - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p, v)

    @pl.when(kk == nk - 1)
    def _out():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                    # fully-masked rows
        out = (acc_ref[...] / l[:, None]).reshape(bq, G, D)
        o_ref[0] = out.astype(o_ref.dtype)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_k=128, interpret):
    """q: (B,S,H,D); k,v: (B,T,Kv,D) -> (B,S,H,D)."""
    B, S, H, D = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    bq, bk = min(block_q, max(S, 8)), min(block_k, max(T, 8))

    # fold kv-head into the leading grid axis; q rows ordered (seq, group).
    qf = q.reshape(B, S, Kv, G, D).transpose(0, 2, 1, 3, 4).reshape(B * Kv, S, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kv, T, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kv, T, D)
    qf = _pad_to(qf, bq, 1)
    kf = _pad_to(kf, bk, 1)
    vf = _pad_to(vf, bk, 1)
    Sp, Tp = qf.shape[1], kf.shape[1]
    nq, nk = Sp // bq, Tp // bk

    kern = functools.partial(
        _kernel, bq=bq, bk=bk, G=G, D=D, causal=causal, window=window,
        softcap=softcap, t_real=T, nk=nk, scale=1.0 / (D ** 0.5))

    out = pl.pallas_call(
        kern,
        grid=(B * Kv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, G, D), lambda i, j, kk: (i, j, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, bk, D), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, G, D), lambda i, j, kk: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Kv, Sp, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * G, D), jnp.float32),
            pltpu.VMEM((bq * G,), jnp.float32),
            pltpu.VMEM((bq * G,), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)

    out = out[:, :S].reshape(B, Kv, S, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, H, D)
