"""The comparison that decides ``correct`` for a federated training cell.

The program and the plain reference run the same first rounds from the
same weights and batches.  The numbers:

* ``loss_gap``: the largest relative gap between the two rounds' losses,
  over the rounds compared;
* ``update_gap.r1``: the first round's change of the global weights (the
  pseudo-gradient FedAvg hands on, ``w0 - w1``), by the worst leaf;
* ``update_gap.end``: the change after the last round compared, by the
  worst leaf;
* ``update_diff.r1`` and ``update_diff.end``: the same changes compared
  element by element, so that a change of the right size in the wrong
  direction shows.

A leaf's ``update_gap`` is the gap between the program's norm of its
change and the reference's; its ``update_diff`` is the norm of the
difference of the two changes.  Both are taken over the larger of the
reference's norm of that leaf's change and the median leaf's.  Leaves
whose reference gradient, summed over every step compared, is under a
thousandth of the median leaf's are left out: they move under Adam by
round-off alone (a key's bias under softmax is one).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import numpy as np

GRAD_FLOOR = 1e-3


def moved_leaves(grad_norms: Dict[str, float]) -> List[str]:
    med = float(np.median(list(grad_norms.values())))
    return sorted(n for n, g in grad_norms.items() if g >= GRAD_FLOOR * med)


def _worst(gaps: Dict[str, float]) -> Tuple[float, str]:
    """The largest gap and its leaf; a gap that is not a number counts as
    the largest."""
    gaps = {n: (v if v == v else np.inf) for n, v in gaps.items()}
    name = max(sorted(gaps), key=lambda n: gaps[n])
    return float(gaps[name]), name


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             leaves: List[str]) -> Tuple[float, str]:
    med = float(np.median([ref[n] for n in leaves]))
    return _worst({n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
                   for n in leaves})


def leaf_diff(prog: Any, ref: Any, ref_delta: Dict[str, float],
              leaves: List[str]) -> Tuple[float, str]:
    """Worst leaf of |prog - ref| over the reference's change: ``prog`` and
    ``ref`` are the two global weights after the same rounds from the same
    start, so their difference is the difference of the two changes."""
    from harness.fedref import leaf_names
    med = float(np.median([ref_delta[n] for n in leaves]))
    want = set(leaves)
    gaps = {}
    for n, p, r in zip(leaf_names(ref), jax.tree.leaves(prog),
                       jax.tree.leaves(ref)):
        if n in want:
            d = np.asarray(p, np.float64) - np.asarray(r, np.float64)
            gaps[n] = float(np.sqrt(np.sum(d * d))) / max(ref_delta[n], med,
                                                         1e-30)
    return _worst(gaps)


def numbers(prog, ref) -> Dict[str, Tuple[float, str]]:
    """``prog`` and ``ref`` are ``fedref.Readings``; returns name -> (value,
    the round or leaf that set it).  A cell compares those its traffic file
    gives a limit."""
    leaves = moved_leaves(ref.grad_norms)
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog.losses, ref.losses)]
    r = int(np.argmax(gaps))
    out = {"loss_gap": (float(gaps[r]), f"round {r}"),
           "update_gap.r1": leaf_gap(prog.delta_r1, ref.delta_r1, leaves),
           "update_gap.end": leaf_gap(prog.delta_end, ref.delta_end, leaves)}
    if prog.at_r1 is not None and ref.at_r1 is not None:
        out["update_diff.r1"] = leaf_diff(prog.at_r1, ref.at_r1,
                                          ref.delta_r1, leaves)
    if prog.final is not None and ref.final is not None:
        out["update_diff.end"] = leaf_diff(prog.final, ref.final,
                                           ref.delta_end, leaves)
    return out
