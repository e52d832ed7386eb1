"""Core layers: norms, RoPE, MLPs, embeddings.

Every ``init_*`` takes a :class:`~repro.nn.param.ParamCtx` and returns a boxed
pytree; every ``apply_*`` takes the *unboxed* params.  All apply functions are
shape-polymorphic over leading batch/seq dims where possible.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.nn import param as P
from repro.nn.param import Box, ParamCtx
from repro.sharding.ctx import constrain


# ---------------------------------------------------------------------------
# Projections, with a low-rank bank applied unmerged
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LowRank:
    """A frozen projection ``w`` with its trainable low-rank factors, as
    ``repro.peft.ParamSpace.unmerged`` puts them in place of the weight.
    ``w`` is (stack..., in dims..., out dims...) with ``n_in`` input and
    ``n_out`` output dims; ``a`` is (stack..., d_in | d_out, r) and ``b``
    (stack..., r, d_out), so slicing a layer stack slices all three.
    ``kind`` "lora" adds ``scale * (x A) B``; "adapter" adds
    ``((x W) D) U``.  The factors never meet ``w``: no merged weight is
    formed, so a delta far below ``w``'s rounding still reaches the
    output and the gradient reaches only ``a`` and ``b``."""

    w: Any
    a: Any
    b: Any
    scale: float = 1.0
    kind: str = "lora"
    n_in: int = 1
    n_out: int = 1

    def tree_flatten(self):
        return (self.w, self.a, self.b), (self.scale, self.kind, self.n_in,
                                          self.n_out)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def project(spec: str, x, w):
    """``einsum(spec, x, w)`` in ``x``'s dtype, the one call every adaptable
    projection makes.  A :class:`LowRank` ``w`` adds its bank's term,
    computed in float32 (named scope ``lora``) and added to the frozen
    projection before the one rounding to ``x``'s dtype."""
    if not isinstance(w, LowRank):
        return jnp.einsum(spec, x, w.astype(x.dtype))
    # the frozen projection stays in float32 until the bank's term is in:
    # rounded first, it would sit on x's grid, and a term under half its
    # spacing would round away entirely
    y = jnp.einsum(spec, x, w.w.astype(x.dtype),
                   preferred_element_type=jnp.float32)
    lead = y.shape[:y.ndim - w.n_out]
    with jax.named_scope("lora"):
        if w.kind == "adapter":
            src = y.reshape(lead + (-1,))
            low = (src @ w.a.astype(jnp.float32)) @ w.b.astype(jnp.float32)
        else:
            src = x.reshape(x.shape[:x.ndim - w.n_in] + (-1,))
            low = ((src.astype(jnp.float32) @ w.a.astype(jnp.float32))
                   @ w.b.astype(jnp.float32)) * w.scale
        out = y + low.reshape(y.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(ctx: ParamCtx, d: int):
    return {"scale": ctx.param("scale", (d,), P.ones(), (P.EMBED,))}


def apply_rmsnorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(ctx: ParamCtx, d: int):
    return {
        "scale": ctx.param("scale", (d,), P.ones(), (P.EMBED,)),
        "bias": ctx.param("bias", (d,), P.zeros(), (P.EMBED,)),
    }


def apply_layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(dtype)


def init_norm(ctx: ParamCtx, d: int, kind: str):
    return init_rmsnorm(ctx, d) if kind == "rmsnorm" else init_layernorm(ctx, d)


def apply_norm(params, x, kind: str, eps: float = 1e-5):
    return (apply_rmsnorm if kind == "rmsnorm" else apply_layernorm)(params, x, eps)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs   # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]                    # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(ctx: ParamCtx, d_model: int, d_ff: int, kind: str):
    p = {}
    if kind == "swiglu":
        p["wi_gate"] = ctx.param("wi_gate", (d_model, d_ff), P.fan_in(), (P.EMBED, P.FFN))
        p["wi_up"] = ctx.param("wi_up", (d_model, d_ff), P.fan_in(), (P.EMBED, P.FFN))
        p["wo"] = ctx.param("wo", (d_ff, d_model), P.fan_in(), (P.FFN, P.EMBED))
    elif kind in ("gelu", "relu2"):
        p["wi"] = ctx.param("wi", (d_model, d_ff), P.fan_in(), (P.EMBED, P.FFN))
        p["bi"] = ctx.param("bi", (d_ff,), P.zeros(), (P.FFN,))
        p["wo"] = ctx.param("wo", (d_ff, d_model), P.fan_in(), (P.FFN, P.EMBED))
        p["bo"] = ctx.param("bo", (d_model,), P.zeros(), (P.EMBED,))
    else:
        raise ValueError(f"unknown mlp kind {kind!r}")
    return p


def _ffn_axes(h):
    return (P.BATCH,) + (None,) * (h.ndim - 2) + (P.FFN,)


def apply_mlp(params, x, kind: str):
    if kind == "swiglu":
        g = project("...d,df->...f", x, params["wi_gate"])
        u = project("...d,df->...f", x, params["wi_up"])
        h = constrain(jax.nn.silu(g) * u, _ffn_axes(g))
        return project("...f,fd->...d", h, params["wo"])
    h = project("...d,df->...f", x, params["wi"]) + params["bi"].astype(x.dtype)
    h = constrain(h, _ffn_axes(h))
    if kind == "gelu":
        h = jax.nn.gelu(h)
    elif kind == "relu2":                       # Nemotron-4 squared ReLU
        h = jnp.square(jax.nn.relu(h))
    return project("...f,fd->...d", h, params["wo"]) + params["bo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Embeddings / LM head
# ---------------------------------------------------------------------------

def init_embedding(ctx: ParamCtx, vocab: int, d_model: int):
    return {"table": ctx.param("table", (vocab, d_model), P.normal(0.02), (P.VOCAB, P.EMBED))}


def apply_embedding(params, tokens, dtype):
    return params["table"].astype(dtype)[tokens]


def init_positional(ctx: ParamCtx, max_len: int, d_model: int):
    return {"table": ctx.param("pos_table", (max_len, d_model), P.normal(0.02), (P.SEQ, P.EMBED))}


def apply_positional(params, positions, dtype):
    return params["table"].astype(dtype)[positions]


def init_lm_head(ctx: ParamCtx, d_model: int, vocab: int):
    return {"w": ctx.param("w", (d_model, vocab), P.fan_in(), (P.EMBED, P.VOCAB))}


def apply_lm_head(params, x, *, embedding_table=None):
    """Logits; if embedding_table is given, weights are tied (head params unused)."""
    if embedding_table is not None:
        return jnp.einsum("...d,vd->...v", x, embedding_table.astype(x.dtype))
    return jnp.einsum("...d,dv->...v", x, params["w"].astype(x.dtype))
