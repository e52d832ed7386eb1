"""Plain reference of the granite-4.0-h-micro-share configuration with its
LoRA bank: forward pass and next-token loss in straightforward
``jax.numpy``, float32 unless a control precision is asked for.  It imports
nothing of the program; it reads the base weights and the bank by the
names of the program's trees (layers stacked per mixer kind as
(periods, layers of that kind in a period, ...)).

It follows Granite-4.0-H (the model's config.json; Mamba-2 of
arXiv:2405.21060): the embedding times ``embedding_multiplier``; then each
layer, in the published order (``layer_pattern`` repeated), is a pre-norm RMSNorm
mixer and a pre-norm RMSNorm SwiGLU MLP, each branch added to the residual
times ``residual_multiplier``.  The mixer is either grouped-query
attention with no positions (NoPE), scores times ``attention_multiplier``,
causal; or Mamba-2: a fused in-projection to (z, x B C, dt), a causal
depthwise convolution with bias over x B C, SiLU, ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``, the state-space recurrence written in its
quadratic form

    y_t = sum_{s<=t} (C_t . B_s) exp(sum_{r=s+1..t} A dt_r) dt_s x_s + D x_t

per sequence and per block of heads (one group of B and C), then an RMSNorm
of ``y * silu(z)`` and the out-projection.  Then a final RMSNorm, the head
tied to the embedding, logits divided by ``logits_scaling``.  LoRA (Hu et
al., arXiv:2106.09685) adds ``(alpha / r) x A B`` beside each adapted
projection, computed unmerged.  The vocabulary is this chip's slice, as
the configuration states.  Departures, the program's: a sequence crosses
the documents packed into it (no segment ids, and the Mamba state runs
across document boundaries).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HEAD_BLOCK = 8          # heads per block of the quadratic SSD


def _rms(x, scale, eps, act):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(act)


def _segsum(c):
    """c: (..., T) -> (..., T, T) with [t, s] = sum_{s < r <= t} c_r, and
    -inf where s > t."""
    t = c.shape[-1]
    rep = jnp.broadcast_to(c[..., :, None], c.shape + (t,))      # [r, s] = c_r
    below = jnp.arange(t)[:, None] > jnp.arange(t)[None, :]       # r > s
    seg = jnp.cumsum(jnp.where(below, rep, 0.0), axis=-2)         # [t, s]
    keep = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    return jnp.where(keep, seg, -jnp.inf)


def make_forward(model: dict, peft: dict):
    """``forward(bank, base, tokens, num) -> logits`` (B, S, V), float32."""
    eps = model["norm_eps"]
    d, vocab = model["d_model"], model["vocab_size"]
    h_q, h_kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    n_state, width = model["ssm_state"], model["conv_dim"]
    d_inner = model["ssm_expand"] * d
    head_p = model["mamba_d_head"]
    heads = d_inner // head_p
    kinds = list(model["layer_pattern"]) * (model["n_layers"]
                                            // len(model["layer_pattern"]))
    emb_mult, att_mult = model["embedding_multiplier"], \
        model["attention_multiplier"]
    res_mult, logit_div = model["residual_multiplier"], \
        model["logits_scaling"]
    scale = (peft.get("alpha") or peft["rank"]) / peft["rank"]
    targets = set(peft["targets"])

    def lora(x, ab, num):
        """(alpha/r) x A B over x's last axis."""
        return scale * num.ein("bsr,ro->bso",
                               num.ein("bsi,ir->bsr", x, ab["a"]), ab["b"])

    def linear(x, w, ab, num):
        """x (b, s, d_in) through w (d_in, d_out), with its LoRA term."""
        y = num.ein("bsi,io->bso", x, w)
        return y + lora(x, ab, num) if ab is not None else y

    def mlp(x, m, mb, num):
        def ab(name):
            return mb[name] if "mlp" in targets else None
        u = jax.nn.silu(linear(x, m["wi_gate"], ab("wi_gate"), num)) \
            * linear(x, m["wi_up"], ab("wi_up"), num)
        return linear(u, m["wo"], ab("wo"), num)

    def attention(x, a, ab, num):
        b, s = x.shape[0], x.shape[1]
        ab = ab if "attn" in targets else {}

        def proj(name, n):
            w = a[name].reshape(d, n * hd)
            return linear(x, w, ab.get(name), num).reshape(b, s, n, hd)

        q, k, v = proj("wq", h_q), proj("wk", h_kv), proj("wv", h_kv)
        g = h_q // h_kv
        sc = num.ein("bskgd,btkd->bkgst", q.reshape(b, s, h_kv, g, hd), k)
        sc = sc.astype(jnp.float32) * att_mult
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        sc = jnp.where(causal, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        o = num.ein("bkgst,btkd->bskgd", w, v).reshape(b, s, h_q * hd)
        return linear(o, a["wo"].reshape(h_q * hd, d), ab.get("wo"), num)

    def ssd(x, dt, a, bm, cm, num):
        """One sequence: x (T, H, P), dt (T, H), a (H,), bm and cm (T, N)
        -> y (T, H, P), by blocks of heads."""
        t = x.shape[0]
        g = num.ein("tn,sn->ts", cm, bm)
        hb = min(HEAD_BLOCK, heads)
        nb = heads // hb

        @jax.checkpoint
        def block(args):
            xb, dtb, ab_ = args                    # (T,hb,P) (T,hb) (hb,)
            decay = jnp.exp(_segsum((dtb * ab_).T))          # (hb, T, S)
            m = g[None] * decay * dtb.T[:, None, :]
            return num.ein("hts,shp->thp", m, xb)

        ys = jax.lax.map(block, (
            x.reshape(t, nb, hb, head_p).transpose(1, 0, 2, 3),
            dt.reshape(t, nb, hb).transpose(1, 0, 2),
            a.reshape(nb, hb)))
        return ys.transpose(1, 0, 2, 3).reshape(t, heads, head_p)

    def mamba(x, p, pb, num):
        b, t = x.shape[0], x.shape[1]
        pb = pb if "mamba" in targets else {}
        zxbcdt = linear(x, p["in_proj"], pb.get("in_proj"), num)
        conv_ch = d_inner + 2 * n_state
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:d_inner + conv_ch].astype(jnp.float32)
        dt = zxbcdt[..., d_inner + conv_ch:].astype(jnp.float32)
        cw = p["conv_w"].astype(jnp.float32)
        padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
        xbc = sum(padded[:, i:i + t] * cw[i] for i in range(width)) \
            + p["conv_b"].astype(jnp.float32)
        xbc = jax.nn.silu(xbc)
        xs = xbc[..., :d_inner].reshape(b, t, heads, head_p)
        bm = xbc[..., d_inner:d_inner + n_state]
        cm = xbc[..., d_inner + n_state:]
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        y = jax.lax.map(lambda v: ssd(v[0], v[1], a, v[2], v[3], num),
                        (xs, dt, bm, cm))
        y = y + p["d_skip"].astype(jnp.float32)[:, None] * xs
        y = y.reshape(b, t, d_inner) * jax.nn.silu(z.astype(jnp.float32))
        y = _rms(y, p["norm"]["scale"], eps, x.dtype)
        return linear(y, p["out_proj"], pb.get("out_proj"), num)

    def layer(x, lp, lb, kind, num):
        h = _rms(x, lp["ln1"]["scale"], eps, x.dtype)
        if kind == "mamba":
            y = mamba(h, lp["mamba"], lb.get("mamba", {}), num)
        else:
            y = attention(h, lp["attn"], lb.get("attn", {}), num)
        x = x + res_mult * y
        h = _rms(x, lp["ln2"]["scale"], eps, x.dtype)
        return x + res_mult * mlp(h, lp["mlp"], lb.get("mlp", {}), num)

    def forward(bank, base, tokens, num):
        act = num.act
        x = base["embed"]["table"][tokens].astype(act) * emb_mult
        seen = {}
        for kind in kinds:                 # every layer, in published order
            c = seen.get(kind, 0)
            seen[kind] = c + 1
            per = jax.tree.leaves(base["layers"][kind])[0].shape[1]

            def pick(t, c=c, per=per):
                return t[c // per, c % per]

            lp = jax.tree.map(pick, base["layers"][kind])
            lb = jax.tree.map(pick, bank["layers"].get(kind, {}))
            x = jax.checkpoint(
                lambda x_, lp_, lb_, kind=kind: layer(x_, lp_, lb_, kind,
                                                      num))(x, lp, lb)
        x = _rms(x, base["final_norm"]["scale"], eps, act)
        logits = num.ein("bsd,vd->bsv", x, base["embed"]["table"])
        assert logits.shape[-1] == vocab
        return logits.astype(jnp.float32) / logit_div

    return forward


def make_loss(model: dict, peft: dict):
    """``loss(bank, base, batch, num) -> (mean next-token loss over the
    loss mask, count)``."""
    forward = make_forward(model, peft)

    def loss(bank, base, batch, num):
        logits = forward(bank, base, batch["tokens"], num)
        gold = jnp.take_along_axis(logits, batch["targets"][..., None],
                                   -1)[..., 0]
        nll = jax.nn.logsumexp(logits, -1) - gold
        mask = batch["loss_mask"].astype(jnp.float32)
        count = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(nll * mask) / count, count

    return loss
