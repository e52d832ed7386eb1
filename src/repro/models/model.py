"""Unified model assembly for the architecture zoo.

``init_model`` / ``apply_model`` cover all seven families (dense, moe, ssm,
hybrid, vlm, audio, mlm) behind one interface:

    logits, new_cache, aux = apply_model(params, cfg, batch, mode=...,
                                         cache=..., frozen=..., impl=...)

``frozen`` is a STATIC per-freeze-unit bool tuple (FFDAPT Algorithm 1's
consecutive window, possibly wrapped); frozen units run under
``stop_gradient`` so the compiled backward skips their dW entirely.

Freeze units (what Algorithm 1's N counts) per family:
  uniform stacks (dense/moe/mlm/ssm): one unit per layer.
  hybrid:  one unit per mamba block (the shared attention block is shared
           across positions and stays trainable — see DESIGN §Arch-applicability).
  hybrid_mlp: one unit per layer; frozen windows are not supported (the
           stack scans over whole periods of its layer pattern).
  vlm:     one unit per (cross_attn_every-1 self + 1 cross) group.
  audio:   encoder layers ++ decoder layers, concatenated.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.nn import param as P
from repro.nn.attention import abstract_cache  # noqa: F401 (re-export)
from repro.nn.layers import (apply_embedding, apply_lm_head, apply_norm,
                             apply_positional, init_embedding, init_lm_head,
                             init_norm, init_positional)
from repro.nn.mamba import mamba_dims
from repro.nn.param import Box, ParamCtx
from repro.nn.rwkv import rwkv_heads
from repro.nn.stack import init_stack, scan_stack, mask_segments
from repro.sharding.ctx import constrain
from repro.models import blocks as B


# ---------------------------------------------------------------------------
# Freeze-unit accounting
# ---------------------------------------------------------------------------

def n_freeze_units(cfg) -> int:
    if cfg.arch_type == "vlm":
        return cfg.n_layers // cfg.cross_attn_every
    if cfg.arch_type == "audio":
        return cfg.encoder_layers + cfg.n_layers
    return cfg.n_layers


def _split_frozen(frozen, n_first):
    """Split a combined frozen mask into two per-stack masks (audio)."""
    if frozen is None:
        return None, None
    return tuple(frozen[:n_first]), tuple(frozen[n_first:])


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_model(key: jax.Array, cfg) -> Any:
    """Boxed parameter tree.  Use ``P.abstract_init(init_model, key, cfg)``
    for allocation-free specs (the 340B dry-run path)."""
    cfg.validate()
    ctx = ParamCtx(key, cfg.pdtype)
    p: Dict[str, Any] = {"embed": init_embedding(ctx.sub("embed"),
                                                 cfg.vocab_size, cfg.d_model)}
    if not cfg.use_rope and not cfg.nope and cfg.arch_type != "ssm":
        p["pos"] = init_positional(ctx.sub("pos"), cfg.max_seq_len, cfg.d_model)

    at = cfg.arch_type
    if at in ("dense", "moe", "mlm"):
        p["layers"] = init_stack(ctx, "layers", cfg.n_layers,
                                 lambda c: B.init_transformer_block(c, cfg))
    elif at == "ssm":
        p["ln_in"] = init_norm(ctx.sub("ln_in"), cfg.d_model, "layernorm")
        p["layers"] = init_stack(ctx, "layers", cfg.n_layers,
                                 lambda c: B.init_rwkv_block(c, cfg))
    elif at == "hybrid":
        p["layers"] = init_stack(ctx, "layers", cfg.n_layers,
                                 lambda c: B.init_mamba_block(c, cfg))
        p["shared_attn"] = B.init_transformer_block(ctx.sub("shared_attn"), cfg)
    elif at == "hybrid_mlp":
        def init_period(c):
            return {kind: init_stack(c, kind, n, functools.partial(
                        B.init_hybrid_layer, cfg=cfg, kind=kind))
                    for kind, n in _pattern_counts(cfg).items()}

        p["layers"] = init_stack(ctx, "layers", _n_periods(cfg), init_period)
    elif at == "vlm":
        per = cfg.cross_attn_every - 1
        G = cfg.n_layers // cfg.cross_attn_every

        def init_group(c):
            return {
                "self": init_stack(c, "self", per,
                                   lambda cc: B.init_transformer_block(cc, cfg)),
                "cross": B.init_transformer_block(c.sub("cross"), cfg, cross=True),
            }

        p["layers"] = init_stack(ctx, "groups", G, init_group)
    elif at == "audio":
        p["enc_pos"] = init_positional(ctx.sub("enc_pos"),
                                       cfg.n_audio_frames, cfg.d_model)
        p["enc_layers"] = init_stack(ctx, "enc_layers", cfg.encoder_layers,
                                     lambda c: B.init_transformer_block(c, cfg))
        p["enc_norm"] = init_norm(ctx.sub("enc_norm"), cfg.d_model, cfg.norm_type)
        p["layers"] = init_stack(ctx, "dec_layers", cfg.n_layers,
                                 lambda c: B.init_encdec_block(c, cfg))
    else:
        raise ValueError(f"unknown arch_type {at!r}")

    p["final_norm"] = init_norm(ctx.sub("final_norm"), cfg.d_model, cfg.norm_type)
    if not cfg.tie_embeddings:
        p["lm_head"] = init_lm_head(ctx.sub("lm_head"), cfg.d_model, cfg.vocab_size)
    if cfg.arch_type == "mlm":
        # BERT-style MLM transform head
        p["mlm_transform"] = {
            "w": ctx.param("mlm_w", (cfg.d_model, cfg.d_model), P.fan_in(),
                           (P.EMBED, P.EMBED)),
            "b": ctx.param("mlm_b", (cfg.d_model,), P.zeros(), (P.EMBED,)),
            "ln": init_norm(ctx.sub("mlm_ln"), cfg.d_model, cfg.norm_type),
        }
    return p


def _pattern_counts(cfg) -> Dict[str, int]:
    """hybrid_mlp: layers of each mixer kind in one period, in order of
    first appearance."""
    out: Dict[str, int] = {}
    for kind in cfg.layer_pattern:
        out[kind] = out.get(kind, 0) + 1
    return out


def _n_periods(cfg) -> int:
    return cfg.n_layers // len(cfg.layer_pattern)


# ---------------------------------------------------------------------------
# Caches (boxed ShapeDtypeStruct trees -> shardable, allocation-free)
# ---------------------------------------------------------------------------

def _box(shape, dtype, axes):
    return Box(jax.ShapeDtypeStruct(tuple(shape), dtype), tuple(axes))


def cache_struct(cfg, batch: int, cache_len: int, dtype=None) -> Any:
    """Boxed SDS cache tree for (arch, batch, cache_len)."""
    dt = dtype or cfg.cdtype
    at = cfg.arch_type
    L, Kv, D = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim_
    kvax = (P.LAYERS, P.BATCH, P.SEQ, P.KV_HEADS, P.HEAD_DIM)
    c: Dict[str, Any] = {"index": _box((), jnp.int32, ())}
    C = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len

    if at in ("dense", "moe"):
        c["layers"] = {"k": _box((L, batch, C, Kv, D), dt, kvax),
                       "v": _box((L, batch, C, Kv, D), dt, kvax)}
    elif at == "ssm":
        H = rwkv_heads(cfg.d_model, cfg.ssm_heads)
        hd = cfg.d_model // H
        c["layers"] = {
            "tm_x": _box((L, batch, cfg.d_model), dt, (P.LAYERS, P.BATCH, P.EMBED)),
            "cm_x": _box((L, batch, cfg.d_model), dt, (P.LAYERS, P.BATCH, P.EMBED)),
            "wkv": _box((L, batch, H, hd, hd), jnp.float32,
                        (P.LAYERS, P.BATCH, P.HEADS, None, None)),
        }
    elif at == "hybrid":
        _, H, CC = mamba_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_expand,
                              cfg.conv_dim)
        A = len(cfg.shared_attn_positions)
        from repro.nn.mamba import HEAD_P
        c["layers"] = {
            "conv": _box((L, batch, cfg.conv_dim - 1, CC), dt,
                         (P.LAYERS, P.BATCH, None, P.FFN)),
            "ssm": _box((L, batch, H, HEAD_P, cfg.ssm_state), jnp.float32,
                        (P.LAYERS, P.BATCH, P.HEADS, None, P.DSTATE)),
        }
        c["shared"] = {"k": _box((A, batch, cache_len, Kv, D), dt, kvax),
                       "v": _box((A, batch, cache_len, Kv, D), dt, kvax)}
    elif at == "hybrid_mlp":
        from repro.nn.mamba import HEAD_P
        _, H, CC = mamba_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_expand,
                              cfg.conv_dim)
        n = _n_periods(cfg)
        two = (P.LAYERS, P.LAYERS)
        c["layers"] = {}
        for kind, m in _pattern_counts(cfg).items():
            if kind == "mamba":
                c["layers"][kind] = {
                    "conv": _box((n, m, batch, cfg.conv_dim - 1, CC), dt,
                                 two + (P.BATCH, None, P.FFN)),
                    "ssm": _box((n, m, batch, H, HEAD_P, cfg.ssm_state),
                                jnp.float32,
                                two + (P.BATCH, P.HEADS, None, P.DSTATE))}
            else:
                c["layers"][kind] = {
                    "k": _box((n, m, batch, C, Kv, D), dt, two + kvax[1:]),
                    "v": _box((n, m, batch, C, Kv, D), dt, two + kvax[1:])}
    elif at == "vlm":
        per = cfg.cross_attn_every - 1
        G = cfg.n_layers // cfg.cross_attn_every
        sax = (P.LAYERS, None, P.BATCH, P.SEQ, P.KV_HEADS, P.HEAD_DIM)
        xax = (P.LAYERS, P.BATCH, None, P.KV_HEADS, P.HEAD_DIM)
        c["layers"] = {
            "self": {"k": _box((G, per, batch, C, Kv, D), dt, sax),
                     "v": _box((G, per, batch, C, Kv, D), dt, sax)},
            "cross": {"xk": _box((G, batch, cfg.n_image_tokens, Kv, D), dt, xax),
                      "xv": _box((G, batch, cfg.n_image_tokens, Kv, D), dt, xax)},
        }
    elif at == "audio":
        xax = (P.LAYERS, P.BATCH, None, P.KV_HEADS, P.HEAD_DIM)
        c["layers"] = {
            "k": _box((L, batch, C, Kv, D), dt, kvax),
            "v": _box((L, batch, C, Kv, D), dt, kvax),
            "xk": _box((L, batch, cfg.n_audio_frames, Kv, D), dt, xax),
            "xv": _box((L, batch, cfg.n_audio_frames, Kv, D), dt, xax),
        }
    else:
        raise ValueError(f"no cache for arch_type {at!r}")
    return c


def init_cache(cfg, batch: int, cache_len: int, dtype=None) -> Any:
    struct = cache_struct(cfg, batch, cache_len, dtype)
    return jax.tree.map(lambda b: jnp.zeros(b.value.shape, b.value.dtype),
                        struct, is_leaf=P.is_box)


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _positions(mode, Bn, S, index):
    if mode == "decode":
        return jnp.broadcast_to(index[None, None], (Bn, 1)).astype(jnp.int32)
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (Bn, S))


def _learned_pos(p, positions, max_len, dtype):
    pos = jnp.minimum(positions, max_len - 1)
    return apply_positional(p, pos, dtype)


def _head(params, cfg, x, rows=None):
    """Final norm, the MLM transform and the vocabulary projection (named
    scope ``lm_head`` on the device trace), logits divided by
    ``cfg.logits_scaling``.  ``rows`` (flat position indices over batch x
    sequence) gathers those positions first, so the head and its backward
    run at them alone; logits are then (rows, V)."""
    with jax.named_scope("lm_head"):
        if rows is not None:
            x = x.reshape(-1, x.shape[-1])[rows]
        x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        if cfg.arch_type == "mlm":
            t = params["mlm_transform"]
            x = (jnp.einsum("...d,de->...e", x, t["w"].astype(x.dtype))
                 + t["b"].astype(x.dtype))
            x = jax.nn.gelu(x)
            x = apply_norm(t["ln"], x, cfg.norm_type, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = apply_lm_head(None, x,
                                   embedding_table=params["embed"]["table"])
        else:
            logits = apply_lm_head(params["lm_head"], x)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits


def apply_model(params, cfg, batch: Dict[str, Any], *, mode: str = "train",
                cache: Any = None, frozen: Optional[Tuple[bool, ...]] = None,
                impl: str = "xla", last_only: bool = False,
                head_rows: Optional[jax.Array] = None):
    """batch: {"tokens": (B,S) int32, ["image_embeds"], ["frames"]}.

    Returns (logits (B,S,V), new_cache (or None), aux_loss scalar).
    mode: "train" (no cache) | "prefill" (fills cache) | "decode" (S==1).
    last_only: apply the LM head to the final position only (prefill) —
    the (B,S,vocab) buffer is the single largest activation at scale.
    head_rows: (K,) flat indices into the B*S positions; the LM head runs
    at those positions only and logits are (K, V) (training: the positions
    the loss reads).
    """
    tokens = batch["tokens"]
    Bn, S = tokens.shape
    dt = cfg.cdtype
    index = cache["index"] if cache is not None else jnp.zeros((), jnp.int32)
    positions = _positions(mode, Bn, S, index)

    with jax.named_scope("embed"):
        x = apply_embedding(params["embed"], tokens, dt)
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        x = constrain(x, (P.BATCH, P.SEQ, P.EMBED))
        if "pos" in params and cfg.arch_type != "audio":
            x = x + _learned_pos(params["pos"], positions, cfg.max_seq_len,
                                 dt)

    at = cfg.arch_type
    new_layers = None
    aux_total = jnp.zeros((), jnp.float32)

    if at in ("dense", "moe", "mlm"):
        causal = at != "mlm"

        def body(p, x, lc):
            x = constrain(x, (P.BATCH, P.SEQ, P.EMBED))
            x, nlc, aux = B.apply_transformer_block(
                p, x, cfg, lc, mode=mode, causal=causal, positions=positions,
                cache_index=index, impl=impl)
            return constrain(x, (P.BATCH, P.SEQ, P.EMBED)), (nlc, aux)

        lcs = cache["layers"] if cache is not None else None
        x, outs = scan_stack(P.unbox_if(params["layers"]), x, body, aux=lcs,
                             remat=cfg.remat, frozen=frozen,
                             unroll=cfg.scan_unroll)
        new_layers, auxs = outs
        aux_total = jnp.sum(auxs)

    elif at == "ssm":
        x = apply_norm(params["ln_in"], x, "layernorm", cfg.norm_eps)

        def body(p, x, lc):
            x = constrain(x, (P.BATCH, P.SEQ, P.EMBED))
            x, nlc, aux = B.apply_rwkv_block(p, x, cfg, lc, impl=impl)
            return constrain(x, (P.BATCH, P.SEQ, P.EMBED)), (nlc, aux)

        lcs = cache["layers"] if cache is not None else None
        x, outs = scan_stack(P.unbox_if(params["layers"]), x, body, aux=lcs,
                             remat=cfg.remat, frozen=frozen,
                             unroll=cfg.scan_unroll)
        new_layers, auxs = outs
        aux_total = jnp.sum(auxs)

    elif at == "hybrid":
        x, new_layers, new_shared, aux_total = _apply_hybrid(
            params, cfg, x, cache, mode=mode, positions=positions,
            index=index, frozen=frozen, impl=impl)

    elif at == "hybrid_mlp":
        x, new_layers = _apply_hybrid_mlp(
            params, cfg, x, cache, mode=mode, positions=positions,
            index=index, frozen=frozen, impl=impl)

    elif at == "vlm":
        x, new_layers, aux_total = _apply_vlm(
            params, cfg, x, batch, cache, mode=mode, positions=positions,
            index=index, frozen=frozen, impl=impl)

    elif at == "audio":
        x, new_layers, aux_total = _apply_audio(
            params, cfg, x, batch, cache, mode=mode, positions=positions,
            index=index, frozen=frozen, impl=impl)
    else:
        raise ValueError(at)

    if last_only:
        x = x[:, -1:, :]
    logits = _head(params, cfg, x, head_rows)

    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["layers"] = new_layers
        new_cache["index"] = index + (1 if mode == "decode" else S)
        if at == "hybrid":
            new_cache["shared"] = new_shared
    return logits, new_cache, aux_total


# ---------------------------------------------------------------------------
# Hybrid (zamba2): mamba stack with a shared attention block spliced in
# ---------------------------------------------------------------------------

def _apply_hybrid(params, cfg, x, cache, *, mode, positions, index, frozen, impl):
    n = cfg.n_layers
    attn_after = sorted(cfg.shared_attn_positions)   # apply shared attn after these
    frozen = tuple(frozen) if frozen is not None else (False,) * n

    # segment boundaries: frozen-run edges ∪ attention positions
    cuts = {0, n}
    for lo, hi, _ in mask_segments(frozen):
        cuts.update((lo, hi))
    for a in attn_after:
        cuts.add(a + 1)
    cuts = sorted(cuts)

    lcs = cache["layers"] if cache is not None else None
    shared = cache["shared"] if cache is not None else None
    shared_p = P.unbox_if(params["shared_attn"])
    stacked = P.unbox_if(params["layers"])

    def body(p, x, lc):
        x = constrain(x, (P.BATCH, P.SEQ, P.EMBED))
        x, nlc, aux = B.apply_mamba_block(p, x, cfg, lc, impl=impl)
        return constrain(x, (P.BATCH, P.SEQ, P.EMBED)), (nlc, aux)

    new_lcs, new_shared_k, new_shared_v, auxs = [], [], [], []
    app_i = 0
    for lo, hi in zip(cuts[:-1], cuts[1:]):
        pseg = jax.tree.map(lambda t: t[lo:hi], stacked)
        if frozen[lo]:
            pseg = jax.tree.map(jax.lax.stop_gradient, pseg)
        aseg = jax.tree.map(lambda t: t[lo:hi], lcs) if lcs is not None else None
        x, (nlc, aux) = jax.lax.scan(
            jax.checkpoint(lambda c, xs: body(xs[0], c, xs[1])) if cfg.remat
            else (lambda c, xs: body(xs[0], c, xs[1])),
            x, (pseg, aseg), unroll=(hi - lo) if cfg.scan_unroll else 1)
        new_lcs.append(nlc)
        auxs.append(jnp.sum(aux))
        if (hi - 1) in attn_after:
            slc = None
            if shared is not None:
                slc = {"k": shared["k"][app_i], "v": shared["v"][app_i]}
            x, nslc, aux2 = B.apply_transformer_block(
                shared_p, x, cfg, slc, mode=mode, causal=True,
                positions=positions, cache_index=index, impl=impl)
            auxs.append(aux2)
            if nslc is not None:
                new_shared_k.append(nslc["k"])
                new_shared_v.append(nslc["v"])
            app_i += 1

    new_layers = None
    if lcs is not None:
        new_layers = jax.tree.map(lambda *ts: jnp.concatenate(ts, 0), *new_lcs)
    new_shared = None
    if shared is not None:
        new_shared = {"k": jnp.stack(new_shared_k), "v": jnp.stack(new_shared_v)}
    return x, new_layers, new_shared, sum(auxs)


# ---------------------------------------------------------------------------
# Hybrid with an MLP in every layer (granite-4.0-h): a scan over periods
# ---------------------------------------------------------------------------

def _apply_hybrid_mlp(params, cfg, x, cache, *, mode, positions, index,
                      frozen, impl):
    """Scan over whole periods of ``cfg.layer_pattern``; the parameters (and
    caches) are stacked per mixer kind as (periods, layers of that kind in
    a period, ...), and each period runs its layers in published order,
    each under its own remat."""
    if frozen is not None and any(frozen):
        raise NotImplementedError(
            f"{cfg.name}: FFDAPT frozen windows over a hybrid_mlp stack")

    def period(x, xs):
        pp, pc = xs
        taken = {kind: 0 for kind in pp}
        new = {kind: [] for kind in pp}
        for kind in cfg.layer_pattern:
            i = taken[kind]
            taken[kind] += 1
            lp = jax.tree.map(lambda t: t[i], pp[kind])
            lc = (jax.tree.map(lambda t: t[i], pc[kind]) if pc is not None
                  else None)

            def layer(lp, x, lc, kind=kind):
                x = constrain(x, (P.BATCH, P.SEQ, P.EMBED))
                return B.apply_hybrid_layer(lp, x, cfg, lc, kind, mode=mode,
                                            positions=positions,
                                            cache_index=index, impl=impl)

            x, nlc = (jax.checkpoint(layer) if cfg.remat else layer)(lp, x,
                                                                     lc)
            new[kind].append(nlc)
        if pc is None:
            return x, None
        return x, {kind: jax.tree.map(lambda *ts: jnp.stack(ts), *outs)
                   for kind, outs in new.items()}

    lcs = cache["layers"] if cache is not None else None
    n = _n_periods(cfg)
    return jax.lax.scan(period, x, (P.unbox_if(params["layers"]), lcs),
                        unroll=n if cfg.scan_unroll else 1)


# ---------------------------------------------------------------------------
# VLM (llama-3.2-vision): grouped scan, gated cross-attention every Nth layer
# ---------------------------------------------------------------------------

def _apply_vlm(params, cfg, x, batch, cache, *, mode, positions, index,
               frozen, impl):
    per = cfg.cross_attn_every - 1
    img = batch.get("image_embeds")
    if img is not None:
        img = img.astype(x.dtype)

    def group_body(gp, x, glc):
        x = constrain(x, (P.BATCH, P.SEQ, P.EMBED))
        auxs = []
        nself = None
        if glc is not None:
            nks, nvs = [], []
        for i in range(per):
            pi = jax.tree.map(lambda t: t[i], gp["self"])
            lci = None
            if glc is not None:
                lci = {"k": glc["self"]["k"][i], "v": glc["self"]["v"][i]}
            x, nlc, aux = B.apply_transformer_block(
                pi, x, cfg, lci, mode=mode, causal=True, positions=positions,
                cache_index=index, impl=impl)
            auxs.append(aux)
            if glc is not None:
                nks.append(nlc["k"])
                nvs.append(nlc["v"])
        xlc = glc["cross"] if glc is not None else None
        x, nxlc, aux = B.apply_cross_block(gp["cross"], x, cfg, xlc, mode=mode,
                                           kv_embeds=img, impl=impl)
        auxs.append(aux)
        nglc = None
        if glc is not None:
            nglc = {"self": {"k": jnp.stack(nks), "v": jnp.stack(nvs)},
                    "cross": nxlc}
        return x, (nglc, sum(auxs))

    lcs = cache["layers"] if cache is not None else None
    x, outs = scan_stack(P.unbox_if(params["layers"]), x, group_body, aux=lcs,
                         remat=cfg.remat, frozen=frozen, unroll=cfg.scan_unroll)
    new_layers, auxs = outs
    return x, new_layers, jnp.sum(auxs)


# ---------------------------------------------------------------------------
# Audio (whisper): encoder over stub frame embeddings + causal decoder
# ---------------------------------------------------------------------------

def _apply_audio(params, cfg, x, batch, cache, *, mode, positions, index,
                 frozen, impl):
    frz_enc, frz_dec = _split_frozen(frozen, cfg.encoder_layers)
    enc_out = None
    if mode != "decode":
        frames = batch["frames"].astype(x.dtype)          # (B, F, d) stub embeds
        F = frames.shape[1]
        fpos = jnp.arange(F, dtype=jnp.int32)[None, :]
        h = frames + apply_positional(params["enc_pos"], fpos, x.dtype)

        def enc_body(p, h, _):
            h = constrain(h, (P.BATCH, P.SEQ, P.EMBED))
            h, _, aux = B.apply_transformer_block(p, h, cfg, None, mode="train",
                                                  causal=False, impl=impl)
            return h, aux

        h, _ = scan_stack(P.unbox_if(params["enc_layers"]), h, enc_body,
                          remat=cfg.remat, frozen=frz_enc,
                          unroll=cfg.scan_unroll)
        enc_out = apply_norm(params["enc_norm"], h, cfg.norm_type, cfg.norm_eps)

    if "pos" in params:
        x = x + _learned_pos(params["pos"], positions, cfg.max_seq_len, x.dtype)

    def dec_body(p, x, lc):
        x = constrain(x, (P.BATCH, P.SEQ, P.EMBED))
        x, nlc, aux = B.apply_encdec_block(p, x, cfg, lc, mode=mode,
                                           enc_out=enc_out, positions=positions,
                                           cache_index=index, impl=impl)
        return x, (nlc, aux)

    lcs = cache["layers"] if cache is not None else None
    x, outs = scan_stack(P.unbox_if(params["layers"]), x, dec_body, aux=lcs,
                         remat=cfg.remat, frozen=frz_dec,
                         unroll=cfg.scan_unroll)
    new_layers, auxs = outs
    return x, new_layers, jnp.sum(auxs)
