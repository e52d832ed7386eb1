"""Grouped-query attention with KV caches, sliding windows and cross-attention.

Three execution paths share one parameter layout:
  * ``mode="train"``    — full-sequence self-attention (causal or bidirectional),
  * ``mode="prefill"``  — causal self-attention that also fills a KV cache,
  * ``mode="decode"``   — one new token against an existing cache (ring-buffer
                          indexing when ``sliding_window`` is set).

``impl`` selects the attention-math backend: ``"xla"`` (einsum, used on CPU and
for the dry-run) or ``"pallas"`` (the flash-attention kernel in
``repro.kernels``; interpret-mode on CPU).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn import param as P
from repro.nn.layers import apply_rope, apply_rmsnorm, project
from repro.nn.param import ParamCtx
from repro.sharding.ctx import constrain

NEG_INF = -1e30


@dataclasses.dataclass
class KVCache:
    """Stacked-over-layers KV cache. k/v: (layers, batch, cache_len, n_kv, head_dim);
    ``index``: number of tokens already written (scalar int32)."""
    k: jax.Array
    v: jax.Array
    index: jax.Array

    def tree_flatten(self):
        return (self.k, self.v, self.index), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    KVCache, KVCache.tree_flatten, lambda aux, ch: KVCache(*ch))


def make_cache(n_layers, batch, cache_len, n_kv, head_dim, dtype):
    shape = (n_layers, batch, cache_len, n_kv, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   index=jnp.zeros((), jnp.int32))


def abstract_cache(n_layers, batch, cache_len, n_kv, head_dim, dtype):
    s = jax.ShapeDtypeStruct((n_layers, batch, cache_len, n_kv, head_dim), dtype)
    return KVCache(k=s, v=s, index=jax.ShapeDtypeStruct((), jnp.int32))


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(ctx: ParamCtx, d_model: int, n_heads: int, n_kv: int,
                   head_dim: int, *, qkv_bias=False, qk_norm=False):
    p = {
        "wq": ctx.param("wq", (d_model, n_heads, head_dim), P.fan_in(),
                        (P.EMBED, P.HEADS, P.HEAD_DIM)),
        "wk": ctx.param("wk", (d_model, n_kv, head_dim), P.fan_in(),
                        (P.EMBED, P.KV_HEADS, P.HEAD_DIM)),
        "wv": ctx.param("wv", (d_model, n_kv, head_dim), P.fan_in(),
                        (P.EMBED, P.KV_HEADS, P.HEAD_DIM)),
        "wo": ctx.param("wo", (n_heads, head_dim, d_model), P.fan_in(),
                        (P.HEADS, P.HEAD_DIM, P.EMBED)),
    }
    if qkv_bias:
        p["bq"] = ctx.param("bq", (n_heads, head_dim), P.zeros(), (P.HEADS, P.HEAD_DIM))
        p["bk"] = ctx.param("bk", (n_kv, head_dim), P.zeros(), (P.KV_HEADS, P.HEAD_DIM))
        p["bv"] = ctx.param("bv", (n_kv, head_dim), P.zeros(), (P.KV_HEADS, P.HEAD_DIM))
    if qk_norm:
        p["q_norm"] = {"scale": ctx.param("q_norm", (head_dim,), P.ones(), (P.HEAD_DIM,))}
        p["k_norm"] = {"scale": ctx.param("k_norm", (head_dim,), P.ones(), (P.HEAD_DIM,))}
    return p


def _project_qkv(params, x, kv_x, *, qk_norm, norm_eps):
    dt = x.dtype
    q = project("...d,dhk->...hk", x, params["wq"])
    k = project("...d,dhk->...hk", kv_x, params["wk"])
    v = project("...d,dhk->...hk", kv_x, params["wv"])
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    if qk_norm:
        q = apply_rmsnorm(params["q_norm"], q, norm_eps)
        k = apply_rmsnorm(params["k_norm"], k, norm_eps)
    return q, k, v


def _gqa_scores_combine(q, k, v, mask, *, softcap=0.0, scale=0.0):
    """q: (B,S,H,D); k/v: (B,T,Kv,D); mask: broadcastable (B,1,S,T) additive.
    ``scale`` multiplies the scores (Granite's attention multiplier); 0
    divides them by sqrt(D)."""
    B, S, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    qg = q.reshape(B, S, Kv, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    if scale:
        scores = scores * jnp.float32(scale)
    else:
        scores = scores / jnp.sqrt(D).astype(jnp.float32)
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = scores + mask[:, :, None, :, :] if mask.ndim == 4 else scores + mask
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(B, S, H, D)


def _causal_mask(S, T, offset=0, window=0):
    """Additive (S,T) mask: query i attends to keys j with j <= i+offset and,
    if window>0, j > i+offset-window."""
    qi = jnp.arange(S)[:, None] + offset
    kj = jnp.arange(T)[None, :]
    ok = kj <= qi
    if window:
        ok &= kj > qi - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def apply_attention(params, x, cfg, *, mode="train", causal=True,
                    cache_k=None, cache_v=None, cache_index=None,
                    positions=None, kv_x=None, impl="xla"):
    """Returns (out, new_cache_k, new_cache_v).

    train:   x (B,S,d); caches None.
    prefill: x (B,S,d); cache_(k,v) (B,C,Kv,D) zero-filled, C>=S; writes [0,S).
    decode:  x (B,1,d); cache holds `cache_index` tokens; writes 1 token
             (ring-indexed when cfg.sliding_window>0 and C==window).
    cross-attention: kv_x (B,Tkv,d) given, causal=False, caches None.
    """
    B, S, _ = x.shape
    cross = kv_x is not None
    q, k, v = _project_qkv(params, x, kv_x if cross else x,
                           qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    # activation shardings (no-ops outside a mesh context): queries may shard
    # their *sequence* dim over the model axis (ATTN_SEQ rule) when the head
    # count does not divide it — context-parallel attention instead of
    # replication.  K/V replicate over model (GQA kv heads are few).
    q = constrain(q, (P.BATCH, P.ATTN_SEQ, P.HEADS, P.HEAD_DIM))
    k = constrain(k, (P.BATCH, None, P.KV_HEADS, P.HEAD_DIM))
    v = constrain(v, (P.BATCH, None, P.KV_HEADS, P.HEAD_DIM))
    hd = cfg.head_dim_
    if positions is None:
        positions = jnp.arange(S)[None, :]

    if cfg.use_rope and not cfg.nope and not cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    window = cfg.sliding_window
    scale = cfg.attention_multiplier
    if mode == "train" or (mode == "prefill" and cache_k is None):
        if cross:
            mask = jnp.zeros((S, k.shape[1]), jnp.float32)
        elif causal:
            mask = _causal_mask(S, S, window=window)
        else:
            mask = jnp.zeros((S, S), jnp.float32)
        if impl == "pallas" and not cross:
            from repro.kernels import ops as kops
            if scale:       # the kernel divides by sqrt(D)
                q = q * jnp.asarray(scale * hd ** 0.5, q.dtype)
            out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                       softcap=cfg.attn_logit_softcap)
        else:
            out = _gqa_scores_combine(q, k, v, mask, softcap=cfg.attn_logit_softcap,
                                      scale=scale)
        new_k, new_v = cache_k, cache_v

    elif mode == "prefill":
        C = cache_k.shape[1]
        if window and C == window:
            # keep last `window` tokens of the prompt in the ring
            sl = jax.lax.dynamic_slice_in_dim(k, max(0, S - window), min(S, window), axis=1)
            sv = jax.lax.dynamic_slice_in_dim(v, max(0, S - window), min(S, window), axis=1)
            new_k = jax.lax.dynamic_update_slice_in_dim(cache_k, sl, 0, axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache_v, sv, 0, axis=1)
        else:
            new_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, 0, axis=1)
            new_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, 0, axis=1)
        mask = _causal_mask(S, S, window=window)
        out = _gqa_scores_combine(q, k, v, mask, softcap=cfg.attn_logit_softcap,
                                  scale=scale)

    elif mode == "decode":
        C = cache_k.shape[1]
        idx = cache_index
        if window and C == window:
            slot = jnp.mod(idx, window)
        else:
            slot = idx
        new_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
        new_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
        kj = jnp.arange(C)
        if window and C == window:
            valid = kj < jnp.minimum(idx + 1, window)       # ring: all written slots valid
        else:
            valid = kj <= idx
        mask = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)  # (C,)
        mask = mask[None, None, None, :]                    # (1,1,1,C): bcast B, heads, S
        out = _gqa_scores_combine(q, new_k, new_v, mask,
                                  softcap=cfg.attn_logit_softcap, scale=scale)
    else:
        raise ValueError(mode)

    out = constrain(out, (P.BATCH, P.ATTN_SEQ, P.HEADS, P.HEAD_DIM))
    out = project("...hk,hkd->...d", out, params["wo"])
    out = constrain(out, (P.BATCH, P.SEQ, P.EMBED))
    return out, new_k, new_v
