"""PEFT client train step: differentiate the bank, freeze the base.

The step merges ``base + ΔW(bank)`` *inside* the objective and takes
gradients w.r.t. the bank only — the base rides along as a traced argument
(never closed over: the engines' compiled-step cache is process-wide, and a
captured base would alias the wrong model across sessions; never stacked:
the cohort-scan carry stays O(bank)).

Signatures mirror ``models.steps.make_train_step`` with ``base`` spliced in
before the FedProx anchor:

    step(bank, opt_state, base, batch)            -> (bank, opt_state, metrics)
    step(bank, opt_state, base, anchor, batch)    (prox_mu > 0; anchor = the
                                                   round-global *bank*)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.steps import _objective, proximal_penalty
from repro.optim import apply_updates, clip_by_global_norm

from repro.peft.space import ParamSpace


def make_peft_train_step(cfg, optimizer, space: ParamSpace, *,
                         impl: str = "xla", clip_norm: float = 1.0,
                         prox_mu: float = 0.0, head_capacity=None):
    if not space.low_rank:
        raise ValueError(f"make_peft_train_step needs a low-rank space, "
                         f"got {space.kind!r}")

    def objective(bank, base, anchor, batch):
        total, metrics = _objective(space.merge(base, bank), cfg, batch,
                                    None, impl, head_capacity)
        if prox_mu:
            prox = prox_mu * proximal_penalty(bank, anchor)
            total = total + prox
            metrics = dict(metrics, prox=prox)
        return total, metrics

    grad_fn = jax.value_and_grad(objective, has_aux=True)

    def train_step(bank, opt_state, base, anchor, batch):
        (_, metrics), grads = grad_fn(bank, base, anchor, batch)
        with jax.named_scope("optimizer"):
            if clip_norm:
                grads, gnorm = clip_by_global_norm(grads, clip_norm)
            else:
                gnorm = jnp.zeros((), jnp.float32)
            updates, new_opt = optimizer.update(grads, opt_state, bank)
            bank = apply_updates(bank, updates)
        return bank, new_opt, dict(metrics, grad_norm=gnorm)

    if prox_mu:
        return train_step
    return lambda bank, opt_state, base, batch: train_step(
        bank, opt_state, base, None, batch)
