"""Plain reference of the distilbert-mlm configuration: forward pass and
masked-LM loss in straightforward ``jax.numpy``, float32 unless a control
precision is asked for.  It imports nothing of the program; it reads the
weights by the names of the program's parameter tree.

It follows DistilBERT (arXiv:1910.01108): learned positions, 6 post-norm
layers of bidirectional multi-head attention with biases and a GELU MLP,
a dense + GELU + LayerNorm transform and a vocabulary projection tied to
the token embedding.  Departures, all of them the program's:

* no LayerNorm on the embeddings; a LayerNorm (``final_norm``) after the
  last layer instead;
* GELU is the tanh approximation (``jax.nn.gelu``'s default), not erf;
* the tied vocabulary projection has no bias.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x * x * x)))


def _layernorm(x, p, eps, act):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(act)


def make_loss(model: dict, peft=None):
    eps, hd = model["norm_eps"], model["head_dim"]

    def layer(x, lp, num):
        a, act = lp["attn"], num.act
        q = num.ein("bsd,dhk->bshk", x, a["wq"]) + a["bq"].astype(act)
        k = num.ein("bsd,dhk->bshk", x, a["wk"]) + a["bk"].astype(act)
        v = num.ein("bsd,dhk->bshk", x, a["wv"]) + a["bv"].astype(act)
        s = num.ein("bshk,bthk->bhst", q, k).astype(jnp.float32) / hd ** 0.5
        w = jax.nn.softmax(s, axis=-1).astype(act)
        o = num.ein("bhst,bthk->bshk", w, v)
        x = _layernorm(x + num.ein("bshk,hkd->bsd", o, a["wo"]),
                       lp["ln1"], eps, act)
        m = lp["mlp"]
        h = _gelu(num.ein("bsd,df->bsf", x, m["wi"]) + m["bi"].astype(act))
        y = num.ein("bsf,fd->bsd", h, m["wo"]) + m["bo"].astype(act)
        return _layernorm(x + y, lp["ln2"], eps, act)

    def loss(params, frozen, batch, num):
        act = num.act
        tok = batch["tokens"]
        s = tok.shape[1]
        x = (params["embed"]["table"][tok].astype(act)
             + params["pos"]["table"][:s].astype(act))
        x, _ = jax.lax.scan(
            jax.checkpoint(lambda c, lp: (layer(c, lp, num), None)),
            x, params["layers"])
        x = _layernorm(x, params["final_norm"], eps, act)
        t = params["mlm_transform"]
        x = _gelu(num.ein("bsd,de->bse", x, t["w"]) + t["b"].astype(act))
        x = _layernorm(x, t["ln"], eps, act)
        logits = num.ein("bsd,vd->bsv", x, params["embed"]["table"])
        logits = logits.astype(jnp.float32)
        gold = jnp.take_along_axis(logits, batch["targets"][..., None],
                                   -1)[..., 0]
        nll = jax.nn.logsumexp(logits, -1) - gold
        mask = batch["loss_mask"].astype(jnp.float32)
        count = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(nll * mask) / count, count

    return loss
