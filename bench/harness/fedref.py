"""Plain federated rounds for the references: FedAvg over clients that each
take local Adam steps, written out from the published algorithms.

One round: every client starts from the global weights with fresh Adam
moments (b1 0.9, b2 0.999, eps 1e-8, no weight decay; the paper re-creates
the optimizer each round), clips each gradient to global norm 1, and takes
its local steps; the new global weights are the client weights averaged by
each client's share of the data, accumulated in float32 in client order.
The round's loss is the same weighted mean of each client's mean step loss.

``loss_fn(trainable, frozen, batch, num) -> (loss, count)`` comes from the
configuration's reference; everything here is generic.  Faults for the
comparison's checks can be planted here, in the reference put in the
program's place:

* ``half_batch``: each step's loss is the mean over the first half of the
  batch's rows (or positions, for a batch of one row);
* ``no_fold``: the new global weights are the first client's, unaveraged;
* ``reversed``: the round's change is applied with its sign turned, a
  change of the right size in the wrong direction.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness.precision import Num

FAULTS = ("half_batch", "no_fold", "reversed")
B1, B2, EPS, CLIP = 0.9, 0.999, 1e-8, 1.0


def leaf_names(tree: Any) -> List[str]:
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(str(getattr(q, "key", getattr(q, "idx", q)))
                            for q in path))
    return out


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in jax.tree.leaves(tree)])


@jax.jit
def _delta_norms(new, old):
    return _norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                               - b.astype(jnp.float32), new, old))


def delta_norms(new: Any, old: Any) -> Dict[str, float]:
    """Per-leaf norm of ``new - old``, by leaf name."""
    return dict(zip(leaf_names(new),
                    np.asarray(_delta_norms(new, old), np.float64).tolist()))


def half_batch(batch: Dict[str, Any]) -> Dict[str, Any]:
    m = np.array(batch["loss_mask"], np.float32)
    if m.shape[0] > 1:
        m[m.shape[0] // 2:] = 0.0
    else:
        m[:, m.shape[1] // 2:] = 0.0
    return dict(batch, loss_mask=m)


@dataclasses.dataclass
class Readings:
    """What the comparison reads from a run of rounds."""

    losses: List[float]                  # each round's loss
    delta_r1: Dict[str, float]           # per-leaf change after round 1
    delta_end: Dict[str, float]          # ... after the last round
    grad_norms: Dict[str, float]         # per-leaf raw gradient norm, summed
    at_r1: Any = None                    # the global weights after round 1
    final: Any = None                    # ... after the last round


def run_rounds(loss_fn: Callable, trainable: Any, frozen: Any,
               clients: Sequence[Sequence[Dict[str, Any]]],
               weights: Sequence[float], n_rounds: int, lr: float,
               num: Num, fault: Optional[str] = None) -> Readings:
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    names = leaf_names(trainable)
    p0 = num.store(trainable)
    w = np.asarray(weights, np.float64)
    w = (w / w.sum()).astype(np.float32)

    @jax.jit
    def step(q, m, v, t, frozen, batch):
        (loss, _), g = jax.value_and_grad(
            lambda q_: loss_fn(q_, frozen, batch, num), has_aux=True)(q)
        gn = _norms(g)
        scale = jnp.minimum(1.0, CLIP / jnp.maximum(
            jnp.sqrt(jnp.sum(jnp.square(gn))), 1e-9))
        g = jax.tree.map(lambda x: x.astype(jnp.float32) * scale, g)
        tf = t.astype(jnp.float32)
        bc1, bc2 = 1.0 - B1 ** tf, 1.0 - B2 ** tf
        m = jax.tree.map(lambda a, x: (B1 * a.astype(jnp.float32)
                                       + (1 - B1) * x).astype(a.dtype), m, g)
        v = jax.tree.map(lambda a, x: (B2 * a.astype(jnp.float32)
                                       + (1 - B2) * x * x).astype(a.dtype),
                         v, g)
        q = jax.tree.map(
            lambda p, a, b: p + (-lr * (a.astype(jnp.float32) / bc1)
                                 / (jnp.sqrt(b.astype(jnp.float32) / bc2)
                                    + EPS)).astype(p.dtype), q, m, v)
        return q, m, v, loss, gn

    @jax.jit
    def fold(acc, q, wk):
        return jax.tree.map(lambda a, x: a + wk * x.astype(jnp.float32),
                            acc, q)

    p = p0
    gsum = np.zeros(len(names))
    losses, d1, p1 = [], None, None
    for r in range(n_rounds):
        acc = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), p)
        round_loss = 0.0
        for k, batches in enumerate(clients):
            q = p
            m = jax.tree.map(jnp.zeros_like, p)
            v = jax.tree.map(jnp.zeros_like, p)
            ls = []
            for i, b in enumerate(batches):
                if fault == "half_batch":
                    b = half_batch(b)
                q, m, v, loss, gn = step(q, m, v, jnp.int32(i + 1), frozen, b)
                ls.append(loss)
                gsum += np.asarray(gn, np.float64)
            round_loss += float(w[k]) * float(np.mean(
                np.asarray(jnp.stack(ls), np.float64)))
            if fault == "no_fold":
                if k == 0:
                    acc = fold(acc, q, jnp.float32(1.0))
            else:
                acc = fold(acc, q, jnp.float32(w[k]))
        if fault == "reversed":
            acc = jax.tree.map(lambda a, x: 2.0 * x.astype(jnp.float32) - a,
                               acc, p)
        p = jax.tree.map(lambda a, x: a.astype(x.dtype), acc, p)
        losses.append(round_loss)
        if r == 0:
            d1, p1 = delta_norms(p, p0), p
    return Readings(losses=losses, delta_r1=d1, delta_end=delta_norms(p, p0),
                    grad_norms=dict(zip(names, gsum.tolist())), at_r1=p1,
                    final=p)
