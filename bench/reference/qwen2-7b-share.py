"""Plain reference of the qwen2-7b-share configuration with its LoRA bank:
forward pass and next-token loss in straightforward ``jax.numpy``, float32
unless a control precision is asked for.  It imports nothing of the
program; it reads the base weights and the bank by the names of the
program's trees.

It follows Qwen2 (arXiv:2407.10671): pre-norm RMSNorm layers, grouped-query
attention (28 query heads over 4 key/value heads of 128) with biases on
q, k and v, rotary positions (base 1e6, halves rotated), a SwiGLU MLP, a
final RMSNorm and an untied head.  LoRA (Hu et al., arXiv:2106.09685) adds
``(alpha / r) * x A B`` beside each adapted projection, computed unmerged
here.  The model's vocabulary is this chip's slice, as the configuration
states.  Departures, the program's: a sequence crosses the documents packed
into it (no segment ids), with positions counted from the sequence start.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, p, eps, act):
    x = x.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(act)


def _rope(x, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def make_loss(model: dict, peft: dict):
    eps, theta = model["norm_eps"], model["rope_theta"]
    h_q, h_kv, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    scale = (peft.get("alpha") or peft["rank"]) / peft["rank"]
    targets = set(peft["targets"])

    def lora(x, ab, num):
        """(alpha/r) x A B over x's last axis."""
        return scale * num.ein("bsr,ro->bso",
                               num.ein("bsi,ir->bsr", x, ab["a"]), ab["b"])

    def layer(x, lp, bank, num):
        act, b, s = num.act, x.shape[0], x.shape[1]
        a = lp["attn"]
        ab = bank.get("attn", {}) if "attn" in targets else {}
        h = _rms(x, lp["ln1"], eps, act)

        def proj(name, heads):
            y = num.ein("bsd,dhk->bshk", h, a[name])
            if name in ab:
                y = y + lora(h, ab[name], num).reshape(b, s, heads, hd)
            return y + a["b" + name[1]].astype(act)

        q = _rope(proj("wq", h_q), theta)
        k = _rope(proj("wk", h_kv), theta)
        v = proj("wv", h_kv)
        g = h_q // h_kv
        sc = num.ein("bskgd,btkd->bkgst", q.reshape(b, s, h_kv, g, hd), k)
        sc = sc.astype(jnp.float32) / hd ** 0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        sc = jnp.where(causal, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1).astype(act)
        o = num.ein("bkgst,btkd->bskgd", w, v).reshape(b, s, h_q, hd)
        y = num.ein("bshk,hkd->bsd", o, a["wo"])
        if "wo" in ab:
            y = y + lora(o.reshape(b, s, h_q * hd), ab["wo"], num)
        x = x + y
        m = lp["mlp"]
        mb = bank.get("mlp", {}) if "mlp" in targets else {}
        h = _rms(x, lp["ln2"], eps, act)

        def ff(name, inp, spec):
            y = num.ein(spec, inp, m[name])
            return y + lora(inp, mb[name], num) if name in mb else y

        u = jax.nn.silu(ff("wi_gate", h, "bsd,df->bsf")) \
            * ff("wi_up", h, "bsd,df->bsf")
        return x + ff("wo", u, "bsf,fd->bsd")

    def loss(bank, base, batch, num):
        act = num.act
        x = base["embed"]["table"][batch["tokens"]].astype(act)
        x, _ = jax.lax.scan(
            jax.checkpoint(lambda c, xs: (layer(c, xs[0], xs[1], num), None)),
            x, (base["layers"], bank["layers"]))
        x = _rms(x, base["final_norm"], eps, act)
        logits = num.ein("bsd,dv->bsv", x, base["lm_head"]["w"])
        logits = logits.astype(jnp.float32)
        gold = jnp.take_along_axis(logits, batch["targets"][..., None],
                                   -1)[..., 0]
        nll = jax.nn.logsumexp(logits, -1) - gold
        mask = batch["loss_mask"].astype(jnp.float32)
        count = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(nll * mask) / count, count

    return loss
