"""Step factories: train / eval / prefill / serve.

``make_train_step(cfg, opt, frozen=...)`` bakes an FFDAPT freeze window into
the program *statically* — the paper-faithful mode, where frozen layers'
backward dW is never compiled.  ``make_masked_train_step`` is the
single-program alternative (traced per-layer mask, masked updates only; no
backward-FLOP saving) used when per-round recompiles are unacceptable.

All steps are functional pytree->pytree and jit/pjit-able; distribution is
applied by the caller (``repro.launch``) via in/out shardings.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.model import apply_model, init_cache, init_model
from repro.optim import apply_updates, clip_by_global_norm


def abstract_train_state(cfg, optimizer, *, boxed: bool = False
                         ) -> Tuple[Any, Any]:
    """(params, opt_state) as ShapeDtypeStructs — no allocation.  The shared
    entry point for everything that lowers a train step on abstract inputs
    (dry-run, telemetry).  ``boxed=True`` keeps the sharding-axis boxes (the
    dry-run derives shardings from them)."""
    from repro.nn import param as P

    def full(key):
        p = init_model(key, cfg)
        return p, optimizer.init(p)

    pb, ob = jax.eval_shape(full, jax.random.PRNGKey(0))
    if boxed:
        return pb, ob
    return P.unbox(pb), P.unbox(ob)


def lm_loss(logits: jax.Array, targets: jax.Array, loss_mask: jax.Array):
    """Mean masked cross-entropy in fp32.  Returns (loss, n_tokens).

    The gold-logit pick uses an iota-compare reduction instead of
    ``take_along_axis``: gathering along a *model-sharded* vocab axis would
    make GSPMD all-gather the full (B,S,V) logits per device (hundreds of GB
    at train_4k scale); the masked reduction stays sharded and lowers to one
    small all-reduce.  Named scope ``loss`` on the device trace."""
    with jax.named_scope("loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                              logits.ndim - 1)
        gold = jnp.sum(jnp.where(vocab_iota == targets[..., None], logits,
                                 0.0), axis=-1)
        nll = (logz - gold) * loss_mask
        count = jnp.maximum(jnp.sum(loss_mask), 1.0)
        return jnp.sum(nll) / count, count


def head_capacity(max_count: int, positions: int,
                  mask_rate: float) -> Optional[int]:
    """Rows the vocabulary head runs at in a train step: the smallest rung
    of ``K0, 2 K0, 4 K0, ...`` that holds ``max_count`` (the most
    loss-masked positions of any batch the step will see), where ``K0`` is
    1.25 x ``mask_rate`` x ``positions`` rounded up to a multiple of 128.
    None (the head at every position) once the rung reaches ``positions``,
    as it does for all-ones (CLM) masks."""
    rung = 128 * max(1, math.ceil(round(1.25 * mask_rate * positions, 6)
                                  / 128))
    while rung < max_count:
        rung *= 2
    return rung if rung < positions else None


def _objective(params, cfg, batch, frozen, impl, capacity=None):
    """``capacity`` (static): run the LM head and the loss at that many
    positions, the loss-masked ones first; it must be at least the
    batch's masked count, so no position the loss reads is dropped.  The
    other positions' logits carry no loss and no gradient, so the loss is
    the same mean and the gradients the same up to f32 summation order.
    None, or a capacity of every position, runs the head everywhere."""
    mask = batch["loss_mask"]
    if capacity is None or capacity >= mask.size:
        logits, _, aux = apply_model(params, cfg, batch, mode="train",
                                     frozen=frozen, impl=impl)
        loss, count = lm_loss(logits, batch["targets"],
                              mask.astype(jnp.float32))
    else:
        with jax.named_scope("lm_head"):
            flat = mask.reshape(-1).astype(jnp.float32)
            # masked positions first, in order; the rows past the batch's
            # count are unmasked positions, which carry mask 0
            rows = jnp.argsort(flat == 0, stable=True)[:capacity]
        logits, _, aux = apply_model(params, cfg, batch, mode="train",
                                     frozen=frozen, impl=impl,
                                     head_rows=rows)
        loss, count = lm_loss(logits, batch["targets"].reshape(-1)[rows],
                              flat[rows])
    total = loss + cfg.router_aux_coef * aux
    return total, {"loss": loss, "aux": aux, "tokens": count}


def proximal_penalty(params: Any, anchor: Any) -> jax.Array:
    """mu-less proximal term: 1/2 ||w - w_anchor||^2 (caller scales by mu).
    The FedProx client objective (Li et al., 2020)."""
    leaves = jax.tree.map(
        lambda p, a: jnp.sum(jnp.square(p.astype(jnp.float32)
                                        - a.astype(jnp.float32))),
        params, anchor)
    return 0.5 * sum(jax.tree.leaves(leaves))


def _split_microbatches(batch: Dict[str, Any], m: int):
    def split(x):
        return x.reshape(m, x.shape[0] // m, *x.shape[1:])
    return jax.tree.map(split, batch)


def _stack_masks(cfg, frozen):
    """Map a per-freeze-unit mask onto the stacked top-level param entries.
    Returns [(top_key, frozen-mask over that entry's leading dim)]."""
    if frozen is None:
        return []
    if cfg.arch_type == "audio":
        e = cfg.encoder_layers
        return [("enc_layers", jnp.asarray(frozen[:e], jnp.float32)),
                ("layers", jnp.asarray(frozen[e:], jnp.float32))]
    return [("layers", jnp.asarray(frozen, jnp.float32))]


def _apply_freeze_to_updates(cfg, frozen, updates, new_opt, old_opt):
    """Frozen units are *fully untouched*: their updates are zeroed and their
    optimizer moments restored (torch requires_grad=False semantics — a zero
    grad would otherwise still move params through Adam momentum)."""
    for key, fmask in _stack_masks(cfg, frozen):
        def mask_u(u):
            keep = (1.0 - fmask).reshape((-1,) + (1,) * (u.ndim - 1))
            return u * keep.astype(u.dtype)

        def restore(new, old):
            sel = fmask.reshape((-1,) + (1,) * (new.ndim - 1)) > 0.5
            return jnp.where(sel, old, new)

        updates = dict(updates)
        updates[key] = jax.tree.map(mask_u, updates[key])
        for field in ("m", "v"):
            if field in new_opt:
                new_opt = dict(new_opt)
                new_opt[field] = dict(new_opt[field])
                new_opt[field][key] = jax.tree.map(
                    restore, new_opt[field][key], old_opt[field][key])
    return updates, new_opt


def make_train_step(cfg, optimizer, *, frozen: Optional[Tuple[bool, ...]] = None,
                    microbatches: int = 1, impl: str = "xla",
                    clip_norm: float = 1.0, prox_mu: float = 0.0,
                    head_capacity: Optional[int] = None):
    """-> train_step(params, opt_state, batch) -> (params, opt_state, metrics).

    ``frozen``: static per-freeze-unit mask (FFDAPT); recompiled per distinct
    window — at most N distinct programs over a whole federated run.
    ``prox_mu`` > 0 adds FedProx's mu/2 ||w - w_global||^2 to the objective
    and changes the signature to ``step(params, opt_state, anchor, batch)``
    (the global anchor changes every round, so it is a per-call argument).
    ``head_capacity``: the rows the LM head runs at, as the module's
    ``head_capacity`` ladder picks them; None runs it at every position.
    """
    def objective(params, anchor, batch):
        total, metrics = _objective(params, cfg, batch, frozen, impl,
                                    head_capacity)
        if prox_mu:
            prox = prox_mu * proximal_penalty(params, anchor)
            total = total + prox
            metrics = dict(metrics, prox=prox)
        return total, metrics

    grad_fn = jax.value_and_grad(objective, has_aux=True)

    def one_micro(params, anchor, mb):
        (total, metrics), grads = grad_fn(params, anchor, mb)
        return grads, metrics

    def train_step(params, opt_state, anchor, batch):
        if microbatches > 1:
            mbs = _split_microbatches(batch, microbatches)

            def acc(carry, mb):
                g_acc, m_acc = carry
                g, m = one_micro(params, anchor, mb)
                g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
                m_acc = jax.tree.map(lambda a, b: a + b, m_acc, m)
                return (g_acc, m_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"loss": jnp.zeros((), jnp.float32),
                  "aux": jnp.zeros((), jnp.float32),
                  "tokens": jnp.zeros((), jnp.float32)}
            if prox_mu:
                m0["prox"] = jnp.zeros((), jnp.float32)
            (grads, metrics), _ = jax.lax.scan(acc, (g0, m0), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            metrics = {k: v / microbatches if k != "tokens" else v
                       for k, v in metrics.items()}
        else:
            grads, metrics = one_micro(params, anchor, batch)

        with jax.named_scope("optimizer"):
            if clip_norm:
                grads, gnorm = clip_by_global_norm(grads, clip_norm)
            else:
                gnorm = jnp.zeros((), jnp.float32)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            if frozen is not None and any(frozen):
                updates, new_opt = _apply_freeze_to_updates(
                    cfg, frozen, updates, new_opt, opt_state)
            params = apply_updates(params, updates)
        metrics = dict(metrics, grad_norm=gnorm)
        return params, new_opt, metrics

    if prox_mu:
        return train_step
    return lambda params, opt_state, batch: train_step(params, opt_state,
                                                       None, batch)


def make_masked_train_step(cfg, optimizer, *, impl: str = "xla",
                           clip_norm: float = 1.0, prox_mu: float = 0.0,
                           head_capacity: Optional[int] = None):
    """Single-program FFDAPT variant: ``freeze_mask`` is a TRACED (L,) float
    {0,1} array multiplying the main-stack gradients — one compiled program
    serves every round, but backward FLOPs are NOT saved (only updates are
    suppressed).  Supported for uniform-stack archs (``layers`` leading dim).
    ``prox_mu`` > 0 adds the FedProx term and the signature becomes
    ``step(params, opt_state, anchor, batch, freeze_mask)``.
    ``head_capacity`` as in ``make_train_step``."""
    def objective(params, anchor, batch):
        total, metrics = _objective(params, cfg, batch, None, impl,
                                    head_capacity)
        if prox_mu:
            prox = prox_mu * proximal_penalty(params, anchor)
            total = total + prox
            metrics = dict(metrics, prox=prox)
        return total, metrics

    grad_fn = jax.value_and_grad(objective, has_aux=True)

    def train_step(params, opt_state, anchor, batch, freeze_mask):
        (total, metrics), grads = grad_fn(params, anchor, batch)
        with jax.named_scope("optimizer"):
            keep = 1.0 - freeze_mask                       # (L,) traced

            def mask_stacked(path_grads):
                def one(g):
                    shape = (-1,) + (1,) * (g.ndim - 1)
                    return g * keep.reshape(shape).astype(g.dtype)
                return jax.tree.map(one, path_grads)

            grads = dict(grads)
            grads["layers"] = mask_stacked(grads["layers"])
            if clip_norm:
                grads, gnorm = clip_by_global_norm(grads, clip_norm)
            else:
                gnorm = jnp.zeros((), jnp.float32)
            updates, new_opt = optimizer.update(grads, opt_state, params)
            # frozen layers fully untouched: zero updates + restore moments
            updates = dict(updates)
            updates["layers"] = mask_stacked(updates["layers"])
            sel = freeze_mask > 0.5

            def restore(new, old):
                s = sel.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(s, old, new)

            for field in ("m", "v"):
                if field in new_opt:
                    new_opt = dict(new_opt)
                    new_opt[field] = dict(new_opt[field])
                    new_opt[field]["layers"] = jax.tree.map(
                        restore, new_opt[field]["layers"],
                        opt_state[field]["layers"])
            params = apply_updates(params, updates)
        return params, new_opt, dict(metrics, grad_norm=gnorm)

    if prox_mu:
        return train_step
    return lambda params, opt_state, batch, freeze_mask: train_step(
        params, opt_state, None, batch, freeze_mask)


def make_eval_step(cfg, *, impl: str = "xla"):
    def eval_step(params, batch):
        logits, _, aux = apply_model(params, cfg, batch, mode="train", impl=impl)
        loss, count = lm_loss(logits, batch["targets"],
                              batch["loss_mask"].astype(jnp.float32))
        return {"loss": loss, "aux": aux, "tokens": count}
    return eval_step


def make_prefill_step(cfg, cache_len: int, *, impl: str = "xla",
                      cache_dtype=None):
    """-> prefill_step(params, batch) -> (last_token_logits, filled_cache).

    Only the LAST position's logits are needed — ``last_only`` makes the LM
    head run on one position instead of materializing (B, S, vocab): at
    nemotron scale that buffer alone is 4.2 TB global (16 GB/device)."""
    def prefill_step(params, batch):
        Bn = batch["tokens"].shape[0]
        cache = init_cache(cfg, Bn, cache_len, cache_dtype)
        logits, cache, _ = apply_model(params, cfg, batch, mode="prefill",
                                       cache=cache, impl=impl, last_only=True)
        return logits[:, -1, :], cache
    return prefill_step


def make_serve_step(cfg, *, impl: str = "xla"):
    """-> serve_step(params, batch{tokens (B,1)}, cache) -> (logits, cache).
    One new token against the existing cache — the decode-shape program."""
    def serve_step(params, batch, cache):
        logits, cache, _ = apply_model(params, cfg, batch, mode="decode",
                                       cache=cache, impl=impl)
        return logits[:, -1, :], cache
    return serve_step


def make_slot_serve_step(cfg, *, impl: str = "xla"):
    """The continuous-batching decode program (``repro.serve``): the batch=1
    serve step vmapped over a leading SLOT axis of stacked per-request
    caches.

    -> slot_serve(params, batch{tokens (slots,1,1)}, pool) -> (logits
    (slots,1,V), pool), where every pool leaf is (slots, *batch1_leaf) and
    each slot carries its OWN cache index — per-slot positions, RoPE phases
    and ring-buffer writes fall out of the vmap instead of threading a
    position vector through the model.  The program's shape depends only on
    the pool, so one compile serves every admit/evict sequence (pinned via
    the jit cache-miss counter in tests/test_serve.py), and its per-slot
    math is the single-request math exactly (engine outputs are bitwise
    identical to static decode)."""
    return jax.vmap(make_serve_step(cfg, impl=impl), in_axes=(None, 0, 0))
