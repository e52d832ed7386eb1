"""Per-family layer blocks assembled from repro.nn.

Each family exposes ``init_<family>_block(ctx, cfg)`` (one layer's boxed
params) and ``apply_<family>_block(params, x, cfg, layer_cache, **kw)``
returning ``(x, new_layer_cache)``.  Layer caches are dicts of per-layer
arrays — ``scan_stack`` scans over their stacked (leading-layers-dim) form.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn import param as P
from repro.nn.attention import _gqa_scores_combine, apply_attention, init_attention
from repro.nn.layers import apply_mlp, apply_norm, init_mlp, init_norm, project
from repro.nn.mamba import apply_mamba2, init_mamba2
from repro.nn.moe import apply_moe, init_moe
from repro.nn.param import ParamCtx
from repro.nn.rwkv import (apply_rwkv_channel_mix, apply_rwkv_time_mix,
                           init_rwkv_channel_mix, init_rwkv_time_mix,
                           rwkv_heads)


# ---------------------------------------------------------------------------
# Transformer block (dense / moe / mlm / whisper-enc / vlm-self)
# ---------------------------------------------------------------------------

def init_transformer_block(ctx: ParamCtx, cfg, *, cross: bool = False):
    p = {
        "ln1": init_norm(ctx.sub("ln1"), cfg.d_model, cfg.norm_type),
        "attn": init_attention(ctx.sub("attn"), cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.head_dim_,
                               qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm),
        "ln2": init_norm(ctx.sub("ln2"), cfg.d_model, cfg.norm_type),
    }
    if cfg.n_experts:
        p["moe"] = init_moe(ctx.sub("moe"), cfg.d_model, cfg.d_ff, cfg.n_experts)
    else:
        p["mlp"] = init_mlp(ctx.sub("mlp"), cfg.d_model, cfg.d_ff, cfg.mlp_type)
    if cross:
        # gated cross-attention (llama-3.2-vision style): tanh-gated residual
        p["xattn"] = init_attention(ctx.sub("xattn"), cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.head_dim_)
        p["lnx"] = init_norm(ctx.sub("lnx"), cfg.d_model, cfg.norm_type)
        p["gate_attn"] = ctx.param("gate_attn", (), P.zeros(), ())
        p["gate_mlp"] = ctx.param("gate_mlp", (), P.zeros(), ())
    return p


# Declarative PEFT target table (consumed by repro.peft.space).  Maps a
# target group to the projection leaves inside a block that are linear maps,
# with each leaf's dimension split ``(n_in_dims, n_out_dims)`` counted after
# stripping leading stack dims (layers / experts).  E.g. a stacked ``wq`` of
# shape (L, d, H, hd) factors as input (d,) -> output (H, hd).  Biases and
# norms are never adapted; MoE expert banks are deliberately excluded (their
# leading experts dim is a stack dim a rank-r factor would have to share).
PEFT_TARGETS = {
    "attn": {"wq": (1, 2), "wk": (1, 2), "wv": (1, 2), "wo": (2, 1)},
    "mlp": {"wi_gate": (1, 1), "wi_up": (1, 1), "wi": (1, 1), "wo": (1, 1)},
    "mamba": {"in_proj": (1, 1), "out_proj": (1, 1)},
}

# Path components under which each target group's leaves live.  "attn" covers
# both self-attention and the gated cross-attention of VLM/enc-dec blocks.
PEFT_GROUPS = {
    "attn": ("attn", "xattn"),
    "mlp": ("mlp",),
    "mamba": ("mamba",),
}


def _ffn(p, x, cfg, impl):
    if cfg.n_experts:
        groups = 0
        if cfg.moe_local_dispatch:
            from repro.sharding.ctx import data_parallel_size
            groups = data_parallel_size()
        return apply_moe(p["moe"], x, cfg.top_k,
                         capacity_factor=cfg.capacity_factor, impl=impl,
                         groups=groups)
    return apply_mlp(p["mlp"], x, cfg.mlp_type), jnp.zeros((), jnp.float32)


def apply_transformer_block(p, x, cfg, lc, *, mode, causal=True,
                            positions=None, cache_index=None, impl="xla"):
    """Self-attention transformer layer.  lc (layer cache): dict with
    k/v (B,C,Kv,D) or None in train mode; cache_index is the global scalar."""
    ck = lc.get("k") if lc else None
    cv = lc.get("v") if lc else None
    ci = cache_index
    if cfg.norm_position == "pre":
        with jax.named_scope("attn"):
            h = apply_norm(p["ln1"], x, cfg.norm_type, cfg.norm_eps)
            a, nk, nv = apply_attention(p["attn"], h, cfg, mode=mode,
                                        causal=causal, cache_k=ck,
                                        cache_v=cv, cache_index=ci,
                                        positions=positions, impl=impl)
            x = x + a
        with jax.named_scope("mlp"):
            h = apply_norm(p["ln2"], x, cfg.norm_type, cfg.norm_eps)
            m, aux = _ffn(p, h, cfg, impl)
            x = x + m
    else:  # post-norm (distilbert)
        with jax.named_scope("attn"):
            a, nk, nv = apply_attention(p["attn"], x, cfg, mode=mode,
                                        causal=causal, cache_k=ck,
                                        cache_v=cv, cache_index=ci,
                                        positions=positions, impl=impl)
            x = apply_norm(p["ln1"], x + a, cfg.norm_type, cfg.norm_eps)
        with jax.named_scope("mlp"):
            m, aux = _ffn(p, x, cfg, impl)
            x = apply_norm(p["ln2"], x + m, cfg.norm_type, cfg.norm_eps)
    nlc = {"k": nk, "v": nv} if lc else None
    return x, nlc, aux


def apply_cross_block(p, x, cfg, lc, *, mode, kv_embeds=None, positions=None,
                      impl="xla"):
    """Gated cross-attention layer (VLM).  kv_embeds: (B,Tkv,d) image/frame
    embeddings (prefill/train) — at decode the projected kv live in lc."""
    gate_a = jnp.tanh(p["gate_attn"]).astype(x.dtype)
    gate_m = jnp.tanh(p["gate_mlp"]).astype(x.dtype)
    with jax.named_scope("attn"):
        h = apply_norm(p["lnx"], x, cfg.norm_type, cfg.norm_eps)
        if mode == "decode" and lc and "xk" in lc:
            # reuse projected image kv from the cache
            dt = x.dtype
            q = project("...d,dhk->...hk", h, p["xattn"]["wq"])
            mask = jnp.zeros((1, 1, 1, lc["xk"].shape[1]), jnp.float32)
            out = _gqa_scores_combine(q, lc["xk"].astype(dt),
                                      lc["xv"].astype(dt), mask)
            a = project("...hk,hkd->...d", out, p["xattn"]["wo"])
            nxk, nxv = lc["xk"], lc["xv"]
        else:
            a, _, _ = apply_attention(p["xattn"], h, cfg, mode="train",
                                      causal=False, kv_x=kv_embeds, impl=impl)
            nxk = project("...d,dhk->...hk", kv_embeds, p["xattn"]["wk"])
            nxv = project("...d,dhk->...hk", kv_embeds, p["xattn"]["wv"])
        x = x + gate_a * a
    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg.norm_type, cfg.norm_eps)
        m, aux = _ffn(p, h, cfg, impl)
        x = x + gate_m * m
    nlc = {"xk": nxk, "xv": nxv} if lc is not None else None
    return x, nlc, aux


# ---------------------------------------------------------------------------
# Encoder-decoder block (whisper decoder: self + cross + mlp)
# ---------------------------------------------------------------------------

def init_encdec_block(ctx: ParamCtx, cfg):
    return {
        "ln1": init_norm(ctx.sub("ln1"), cfg.d_model, cfg.norm_type),
        "attn": init_attention(ctx.sub("attn"), cfg.d_model, cfg.n_heads,
                               cfg.n_kv_heads, cfg.head_dim_,
                               qkv_bias=cfg.qkv_bias),
        "lnx": init_norm(ctx.sub("lnx"), cfg.d_model, cfg.norm_type),
        "xattn": init_attention(ctx.sub("xattn"), cfg.d_model, cfg.n_heads,
                                cfg.n_kv_heads, cfg.head_dim_,
                                qkv_bias=cfg.qkv_bias),
        "ln2": init_norm(ctx.sub("ln2"), cfg.d_model, cfg.norm_type),
        "mlp": init_mlp(ctx.sub("mlp"), cfg.d_model, cfg.d_ff, cfg.mlp_type),
    }


def apply_encdec_block(p, x, cfg, lc, *, mode, enc_out=None, positions=None,
                       cache_index=None, impl="xla"):
    """Whisper decoder layer.  lc: {k, v, xk, xv}; cache_index global scalar."""
    ck = lc.get("k") if lc else None
    cv = lc.get("v") if lc else None
    ci = cache_index
    with jax.named_scope("attn"):
        h = apply_norm(p["ln1"], x, cfg.norm_type, cfg.norm_eps)
        a, nk, nv = apply_attention(p["attn"], h, cfg, mode=mode, causal=True,
                                    cache_k=ck, cache_v=cv, cache_index=ci,
                                    positions=positions, impl=impl)
        x = x + a
    with jax.named_scope("attn"):
        h = apply_norm(p["lnx"], x, cfg.norm_type, cfg.norm_eps)
        if mode == "decode" and lc and "xk" in lc:
            dt = x.dtype
            q = project("...d,dhk->...hk", h, p["xattn"]["wq"])
            if "bq" in p["xattn"]:
                q = q + p["xattn"]["bq"].astype(dt)
            mask = jnp.zeros((1, 1, 1, lc["xk"].shape[1]), jnp.float32)
            out = _gqa_scores_combine(q, lc["xk"].astype(dt),
                                      lc["xv"].astype(dt), mask)
            a = project("...hk,hkd->...d", out, p["xattn"]["wo"])
            nxk, nxv = lc["xk"], lc["xv"]
        else:
            a, _, _ = apply_attention(p["xattn"], h, cfg, mode="train",
                                      causal=False, kv_x=enc_out, impl=impl)
            dt = x.dtype
            nxk = project("...d,dhk->...hk", enc_out, p["xattn"]["wk"])
            nxv = project("...d,dhk->...hk", enc_out, p["xattn"]["wv"])
            if "bk" in p["xattn"]:
                nxk = nxk + p["xattn"]["bk"].astype(dt)
                nxv = nxv + p["xattn"]["bv"].astype(dt)
        x = x + a
    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg.norm_type, cfg.norm_eps)
        m = apply_mlp(p["mlp"], h, cfg.mlp_type)
        x = x + m
    nlc = None
    if lc is not None:
        nlc = {"k": nk, "v": nv, "xk": nxk, "xv": nxv}
    return x, nlc, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# RWKV6 block
# ---------------------------------------------------------------------------

def init_rwkv_block(ctx: ParamCtx, cfg):
    H = rwkv_heads(cfg.d_model, cfg.ssm_heads)
    return {
        "ln1": init_norm(ctx.sub("ln1"), cfg.d_model, "layernorm"),
        "tm": init_rwkv_time_mix(ctx.sub("tm"), cfg.d_model, H),
        "ln2": init_norm(ctx.sub("ln2"), cfg.d_model, "layernorm"),
        "cm": init_rwkv_channel_mix(ctx.sub("cm"), cfg.d_model, cfg.d_ff),
    }


def apply_rwkv_block(p, x, cfg, lc, *, impl="xla"):
    """lc: {tm_x (B,d), cm_x (B,d), wkv (B,H,hd,hd)} or None (train: zeros)."""
    B, T, d = x.shape
    H = rwkv_heads(cfg.d_model, cfg.ssm_heads)
    hd = d // H
    if lc is None:
        tm_x = jnp.zeros((B, d), x.dtype)
        cm_x = jnp.zeros((B, d), x.dtype)
        wkv = jnp.zeros((B, H, hd, hd), jnp.float32)
    else:
        tm_x, cm_x, wkv = lc["tm_x"].astype(x.dtype), lc["cm_x"].astype(x.dtype), lc["wkv"]
    h = apply_norm(p["ln1"], x, "layernorm", cfg.norm_eps)
    a, new_tm_x, new_wkv = apply_rwkv_time_mix(p["tm"], h, H, last_x=tm_x,
                                               state=wkv, impl=impl)
    x = x + a
    h = apply_norm(p["ln2"], x, "layernorm", cfg.norm_eps)
    m, new_cm_x = apply_rwkv_channel_mix(p["cm"], h, last_x=cm_x)
    x = x + m
    nlc = None
    if lc is not None:
        nlc = {"tm_x": new_tm_x, "cm_x": new_cm_x, "wkv": new_wkv}
    return x, nlc, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Mamba2 block (zamba2 main stack)
# ---------------------------------------------------------------------------

def init_mamba_block(ctx: ParamCtx, cfg):
    return {
        "ln": init_norm(ctx.sub("ln"), cfg.d_model, cfg.norm_type),
        "mamba": init_mamba2(ctx.sub("mamba"), cfg.d_model, cfg.ssm_state,
                             expand=cfg.ssm_expand, conv_dim=cfg.conv_dim),
    }


def apply_mamba_block(p, x, cfg, lc, *, impl="xla"):
    """lc: {conv (B,W-1,CC), ssm (B,H,P,N)} or None."""
    conv = lc["conv"] if lc else None
    ssm = lc["ssm"] if lc else None
    with jax.named_scope("mamba"):
        h = apply_norm(p["ln"], x, cfg.norm_type, cfg.norm_eps)
        y, nconv, nssm = apply_mamba2(p["mamba"], h, cfg, conv_state=conv,
                                      ssm_state=ssm, impl=impl)
        x = x + y
    nlc = {"conv": nconv, "ssm": nssm} if lc is not None else None
    return x, nlc, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Hybrid layer (granite-4.0-h): [Mamba2 or attention mixer -> MLP]
# ---------------------------------------------------------------------------

def init_hybrid_layer(ctx: ParamCtx, cfg, kind: str):
    """One layer of a ``hybrid_mlp`` stack; ``kind`` is "mamba" or
    "attention"."""
    p = {"ln1": init_norm(ctx.sub("ln1"), cfg.d_model, cfg.norm_type),
         "ln2": init_norm(ctx.sub("ln2"), cfg.d_model, cfg.norm_type),
         "mlp": init_mlp(ctx.sub("mlp"), cfg.d_model, cfg.d_ff, cfg.mlp_type)}
    if kind == "mamba":
        p["mamba"] = init_mamba2(ctx.sub("mamba"), cfg.d_model, cfg.ssm_state,
                                 expand=cfg.ssm_expand, conv_dim=cfg.conv_dim)
    else:
        p["attn"] = init_attention(ctx.sub("attn"), cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim_,
                                   qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm)
    return p


def apply_hybrid_layer(p, x, cfg, lc, kind: str, *, mode, positions=None,
                       cache_index=None, impl="xla"):
    """Pre-norm mixer then pre-norm MLP, each residual branch scaled by
    ``cfg.residual_multiplier``.  lc: {conv, ssm} (mamba) or {k, v}
    (attention), or None.  Named scopes ``mamba`` or ``attn``, then
    ``mlp``."""
    r = cfg.residual_multiplier
    if kind == "mamba":
        with jax.named_scope("mamba"):
            h = apply_norm(p["ln1"], x, cfg.norm_type, cfg.norm_eps)
            y, nconv, nssm = apply_mamba2(
                p["mamba"], h, cfg, conv_state=lc["conv"] if lc else None,
                ssm_state=lc["ssm"] if lc else None, impl=impl)
            x = x + r * y
        nlc = {"conv": nconv, "ssm": nssm} if lc is not None else None
    else:
        with jax.named_scope("attn"):
            h = apply_norm(p["ln1"], x, cfg.norm_type, cfg.norm_eps)
            a, nk, nv = apply_attention(
                p["attn"], h, cfg, mode=mode, causal=True,
                cache_k=lc["k"] if lc else None,
                cache_v=lc["v"] if lc else None, cache_index=cache_index,
                positions=positions, impl=impl)
            x = x + r * a
        nlc = {"k": nk, "v": nv} if lc is not None else None
    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg.norm_type, cfg.norm_eps)
        x = x + r * apply_mlp(p["mlp"], h, cfg.mlp_type)
    return x, nlc
