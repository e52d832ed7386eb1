"""Mamba2 (SSD) mixer for the hybrid architectures (zamba2, granite-4.0-h).

arXiv:2405.21060 structure: fused in-projection -> causal depthwise conv on
(x,B,C) -> selective scan with scalar-per-head A -> gated RMSNorm -> out
projection; one group of B/C, heads of 64.  The recurrence runs through
:mod:`repro.kernels` (``mamba2_scan``) under ``impl="pallas"``; otherwise
the shape decides (``ssd_chunk``): a sequence longer than the config's
chunk takes the chunked SSD, whose work is matmuls over chunks, and a
shorter one (decode, short prompts) the token-by-token scan.  Named scope
``ssd`` on the device trace: the scan alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn import param as P
from repro.nn.layers import project
from repro.nn.param import ParamCtx

HEAD_P = 64     # mamba2 head dim


def mamba_dims(d_model: int, d_state: int, expand: int = 2, conv_dim: int = 4):
    d_inner = expand * d_model
    n_heads = d_inner // HEAD_P
    conv_ch = d_inner + 2 * d_state           # x ++ B ++ C get convolved
    return d_inner, n_heads, conv_ch


def init_mamba2(ctx: ParamCtx, d_model: int, d_state: int, *, expand=2,
                conv_dim=4):
    d_inner, n_heads, conv_ch = mamba_dims(d_model, d_state, expand, conv_dim)
    proj_out = 2 * d_inner + 2 * d_state + n_heads   # z ++ xBC ++ dt
    return {
        "in_proj": ctx.param("in_proj", (d_model, proj_out), P.fan_in(),
                             (P.EMBED, P.FFN)),
        "conv_w": ctx.param("conv_w", (conv_dim, conv_ch), P.normal(0.1),
                            (P.DCONV, P.FFN)),
        "conv_b": ctx.param("conv_b", (conv_ch,), P.zeros(), (P.FFN,)),
        "a_log": ctx.param("a_log", (n_heads,), P.uniform(1.0), (None,)),
        "dt_bias": ctx.param("dt_bias", (n_heads,), P.normal(0.5), (None,)),
        "d_skip": ctx.param("d_skip", (n_heads,), P.ones(), (None,)),
        "norm": {"scale": ctx.param("norm_scale", (d_inner,), P.ones(),
                                    (P.FFN,))},
        "out_proj": ctx.param("out_proj", (d_inner, d_model), P.fan_in(),
                              (P.FFN, P.EMBED)),
    }


def _causal_conv(x, w, b, conv_state=None):
    """Depthwise causal conv.  x: (B,T,C); w: (W,C); conv_state: (B,W-1,C)
    carry-in (zeros at sequence start).  Returns (y, new_conv_state)."""
    B, T, C = x.shape
    W = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((B, W - 1, C), x.dtype)
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # (B,T+W-1,C)
    y = sum(xp[:, i:i + T, :] * w[i][None, None, :] for i in range(W))
    return y + b[None, None, :], xp[:, T:, :]      # last W-1 inputs


def ssd_chunk(cfg, seq_len: int) -> int:
    """The SSD chunk a ``seq_len``-token pass runs at: the config's chunk
    when the sequence is longer than it, else 0 (the token scan)."""
    return cfg.ssm_chunk if seq_len > cfg.ssm_chunk else 0


def apply_mamba2(params, x, cfg, *, conv_state=None, ssm_state=None,
                 impl="xla"):
    """x: (B,T,d) -> (out, new_conv_state, new_ssm_state)."""
    B, T, d = x.shape
    dt_ = x.dtype
    d_state = cfg.ssm_state
    d_inner, n_heads, conv_ch = mamba_dims(d, d_state, cfg.ssm_expand,
                                           cfg.conv_dim)

    zxbcdt = project("...d,de->...e", x, params["in_proj"])
    z, xBC, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_ch], axis=-1)
    xBC, new_conv = _causal_conv(xBC, params["conv_w"].astype(dt_),
                                 params["conv_b"].astype(dt_), conv_state)
    xBC = jax.nn.silu(xBC)
    xs, bmat, cmat = jnp.split(xBC, [d_inner, d_inner + d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))   # (B,T,H)

    xh = xs.reshape(B, T, n_heads, HEAD_P)
    if ssm_state is None:
        ssm_state = jnp.zeros((B, n_heads, HEAD_P, d_state), jnp.float32)

    chunk = ssd_chunk(cfg, T)
    with jax.named_scope("ssd"):
        if impl == "pallas":
            from repro.kernels import ops as kops
            y, new_ssm = kops.mamba2_scan(xh, dt.astype(dt_), params["a_log"],
                                          bmat, cmat, ssm_state)
        elif chunk:
            from repro.kernels import ref as kref
            y, new_ssm = kref.mamba2_scan_chunked(xh, dt, params["a_log"],
                                                  bmat, cmat, ssm_state,
                                                  chunk=chunk)
        else:
            from repro.kernels import ref as kref
            y, new_ssm = kref.mamba2_scan(xh, dt, params["a_log"], bmat, cmat,
                                          ssm_state)

    y = y + params["d_skip"].astype(dt_)[None, None, :, None] * xh
    y = y.reshape(B, T, d_inner)
    # gated RMSNorm (mamba2's norm-before-out-proj)
    yz = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(yz.astype(jnp.float32)), axis=-1, keepdims=True)
    yz = (yz.astype(jnp.float32) * jax.lax.rsqrt(var + cfg.norm_eps)
          * params["norm"]["scale"].astype(jnp.float32)).astype(dt_)
    return project("...e,ed->...d", yz, params["out_proj"]), new_conv, new_ssm
