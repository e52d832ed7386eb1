"""Unified ``FederatedStrategy`` API — the paper's §5 extension axes as one
pluggable abstraction over both round engines.

A strategy owns the three places federated algorithms differ:

  * the **client objective** — ``make_client_step`` builds the local train
    step (FedProx plugs its proximal term in here);
  * the **server aggregation** — ``aggregate`` (list-of-trees layout, the
    sequential engine) and the streaming contract ``aggregate_init`` /
    ``aggregate_partial`` / ``aggregate_combine`` (the cohort-scan engine
    folds one client shard at a time through a carried fp32 accumulator;
    ``aggregate_stacked`` is the same contract over a single full-cohort
    shard).  Strategies customize via ``effective_weights`` (AsyncFedAvg
    staleness discounts), ``map_clients`` (Compressed delta round-trip),
    and ``server_update`` (FedAvgM momentum) — the reduction order itself
    is fixed (a client-index left fold), which is what keeps results
    bitwise independent of the shard size;
  * the **upload accounting** — ``aggregate`` returns exact client->server
    bytes; ``upload_bytes`` is the static (shape-derived) figure the jitted
    path reports.

Instances are frozen dataclasses: hashable (they key the compiled-step
cache) and comparable (two ``FedAvg()`` are the same strategy).

Strategies:
  ``FedAvg``      — weighted mean (McMahan et al., 2017); the paper's server.
  ``FedAvgM``     — server momentum over the pseudo-gradient (Hsu et al., 2019).
  ``FedProx``     — proximal client objective (Li et al., 2020).
  ``Compressed``  — decorator: top-k sparsified or int8-quantized client
                    DELTAS around any inner strategy's aggregation.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.fedavg import (fedavg, fedavg_fold, fedavg_stacked,
                               fold_finalize, fold_init)
from repro.models.steps import make_masked_train_step, make_train_step


def tree_bytes(tree: Any) -> int:
    """Dense wire size of one upload: sum of leaf nbytes (dtype-aware)."""
    return int(sum(l.size * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(tree)))


def tree_delta(new: Any, base: Any) -> Any:
    """Client delta in fp32 (deltas compress far better than weights)."""
    return jax.tree.map(lambda n, b: n.astype(jnp.float32)
                        - b.astype(jnp.float32), new, base)


def tree_add(base: Any, delta: Any) -> Any:
    """Apply an fp32 delta, casting back to the base leaf dtype."""
    return jax.tree.map(lambda b, d: (b.astype(jnp.float32) + d
                                      ).astype(b.dtype), base, delta)


# ---------------------------------------------------------------------------
# Compressors (jax-pure tree -> tree; trace-safe, vmap-able over a client dim)
# ---------------------------------------------------------------------------

def topk_count(n: int, frac: float) -> int:
    """The k every top-k site uses: ``ceil(frac * n)``, clamped to
    [1, n].  One shared helper so the eager compressor
    (``strategies.topk_sparsify``), the trace-safe compressor
    (``topk_compress``), and the static byte accounting (``topk_bytes``)
    cannot disagree about how many entries "top-frac" means
    (tests/test_strategies.py pins the exact-count law)."""
    return min(n, max(1, math.ceil(n * frac)))


def topk_compress(delta: Any, frac: float) -> Any:
    """Keep the top-``frac`` fraction of entries per leaf by magnitude.
    Ties at the threshold are kept (>=), matching the eager reference."""
    def one(d):
        k = topk_count(d.size, frac)
        flat = d.reshape(-1)
        thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
        return jnp.where(jnp.abs(flat) >= thresh, flat, 0.0).reshape(d.shape)

    return jax.tree.map(one, delta)


def int8_compress(delta: Any) -> Any:
    """Symmetric per-leaf int8 quantize->dequantize round trip."""
    def one(d):
        scale = jnp.maximum(jnp.max(jnp.abs(d)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(d / scale), -127, 127).astype(jnp.int8)
        return q.astype(jnp.float32) * scale

    return jax.tree.map(one, delta)


def topk_bytes(tree: Any, frac: float) -> int:
    """Static top-k upload size: k values (leaf dtype) + k int32 indices."""
    total = 0
    for l in jax.tree.leaves(tree):
        k = topk_count(l.size, frac)
        total += k * (jnp.dtype(l.dtype).itemsize + 4)
    return total


def int8_bytes(tree: Any) -> int:
    """Static int8 upload size: 1 B/entry + one fp32 scale per leaf."""
    return int(sum(l.size + 4 for l in jax.tree.leaves(tree)))


def exact_kept_bytes(compressed_delta: Any) -> int:
    """Exact top-k accounting on concrete (eager) arrays: the ``>= thresh``
    tie rule can keep MORE than k entries — count what actually survived."""
    total = 0
    for l in jax.tree.leaves(compressed_delta):
        kept = int(jnp.sum(l != 0.0))
        total += max(kept, 1) * (jnp.dtype(l.dtype).itemsize + 4)
    return total


# ---------------------------------------------------------------------------
# Strategy base
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FederatedStrategy:
    """Base strategy: plain FedAvg behavior for every hook.

    ``needs_anchor`` tells the engines whether client steps take the round's
    global params as an explicit argument (FedProx does; keeping the
    argument out of FedAvg-family programs preserves bitwise parity with the
    legacy engines)."""

    name = "strategy"
    needs_anchor = False

    # -- state ---------------------------------------------------------
    def init_state(self, global_params: Any) -> Any:
        """Server-side state threaded through every round (a pytree of
        arrays, so the jitted mesh program can carry it)."""
        return ()

    def state_to_tree(self, state: Any) -> Any:
        """Server state as a pytree of ARRAYS for the checkpoint layer
        (``repro.checkpoint`` flattens it next to the global params).  The
        default is the identity — it covers every strategy whose state
        already is such a pytree (FedAvg family / FedProx / AsyncFedAvg:
        ``()``; FedAvgM: the fp32 momentum tree).  A strategy carrying
        non-array state must encode it here and decode in
        ``state_from_tree`` so resumed runs stay bitwise identical."""
        return state

    def state_from_tree(self, tree: Any) -> Any:
        """Inverse of ``state_to_tree``.  ``tree`` holds the exact saved
        leaf values, restored against ``state_to_tree(init_state(params))``
        as the shape/dtype template."""
        return tree

    # -- client objective ---------------------------------------------
    def make_client_step(self, cfg, optimizer, *, frozen=None,
                         masked: bool = False, impl: str = "xla",
                         space=None, head_capacity=None):
        """Local train step.  ``masked=False`` (sequential engine): static
        FFDAPT ``frozen`` window, signature ``step(params, opt, batch)`` —
        or ``step(params, opt, anchor, batch)`` when ``needs_anchor``.
        ``masked=True`` (mesh engine): traced freeze mask appended.
        A low-rank ``space`` (repro.peft) swaps in the PEFT step: ``params``
        becomes the factor bank and the frozen base model splices in as
        ``step(bank, opt, base, [anchor,] batch)``.  ``head_capacity``:
        the LM head's rows (``models.steps.head_capacity``)."""
        if space is not None and space.low_rank:
            from repro.peft.step import make_peft_train_step
            return make_peft_train_step(cfg, optimizer, space, impl=impl,
                                        head_capacity=head_capacity)
        if masked:
            return make_masked_train_step(cfg, optimizer, impl=impl,
                                          head_capacity=head_capacity)
        return make_train_step(cfg, optimizer, frozen=frozen, impl=impl,
                               head_capacity=head_capacity)

    def client_step_key(self) -> Tuple:
        """Cache identity of ``make_client_step``'s program: every strategy
        with the plain objective (FedAvg, FedAvgM, any ``Compressed`` over
        them) shares ONE compiled client step."""
        return ("plain",)

    # -- server aggregation -------------------------------------------
    def aggregate(self, global_params: Any, client_params: Sequence[Any],
                  sizes: Sequence[float], state: Any
                  ) -> Tuple[Any, Any, int]:
        """List layout (sequential engine).  Returns
        ``(new_global, new_state, upload_bytes)`` with exact accounting."""
        new = fedavg(client_params, sizes)
        return new, state, len(client_params) * tree_bytes(global_params)

    def aggregate_stacked(self, global_params: Any, stacked: Any,
                          weights: jax.Array, state: Any) -> Tuple[Any, Any]:
        """Stacked layout: every leaf of ``stacked`` is (K, ...).  Pure jax —
        traced inside the jitted round program (byte accounting is static;
        see ``upload_bytes``).

        Derived from the STREAMING contract below, so the full-width vmapped
        round and the cohort-scan engine share one reduction order: it is
        exactly ``aggregate_partial`` over a single shard holding the whole
        cohort, followed by ``aggregate_combine``."""
        k = int(weights.shape[0])
        wn = self.effective_weights(weights)
        wn = wn / jnp.sum(wn)
        partial = self.aggregate_partial(global_params, stacked, wn,
                                         self.aggregate_init(global_params))
        return self.aggregate_combine(global_params, partial, state, k=k)

    # -- streaming aggregation (the cohort-scan contract) --------------
    #
    # The cohort-scan engine never holds the whole cohort: it folds one
    # fixed-size shard at a time through a carried fp32 ``partial`` and
    # combines once at the end of the round.  Peak live client state is
    # O(shard), not O(cohort).  The reduction is the canonical client-index
    # left fold (``repro.core.fedavg.fedavg_fold``) — shard boundaries
    # cannot change the add sequence, so any shard size produces bitwise
    # the same round as the full-width vmapped program.
    #
    # Strategies customize three orthogonal hooks instead of rewriting the
    # reduction: ``effective_weights`` (AsyncFedAvg's staleness discounts),
    # ``map_clients`` (Compressed's per-client delta round-trip), and
    # ``server_update`` (FedAvgM's momentum, AsyncFedAvg's server step).

    def effective_weights(self, weights: jax.Array) -> jax.Array:
        """Cohort weight vector -> aggregation weights, BEFORE the global
        normalization.  Called once per round on the full cohort's (K,)
        weights — never per shard, so the normalizer sees every client."""
        return weights

    def map_clients(self, global_params: Any, stacked: Any) -> Any:
        """Per-client transform applied to a shard's stacked params before
        they enter the fold (vmapped-style, O(shard) live).  ``Compressed``
        round-trips each client's delta here."""
        return stacked

    def server_update(self, global_params: Any, mean: Any, state: Any,
                      *, k: int) -> Tuple[Any, Any]:
        """Turn the finished weighted mean into the new global params.
        ``k`` is the cohort size (static).  FedAvg: the mean IS the new
        model."""
        return mean, state

    def aggregate_init(self, global_params: Any) -> Any:
        """Fresh fold carry for one round (fp32 zeros, unstacked shapes)."""
        return fold_init(global_params)

    def aggregate_partial(self, global_params: Any, stacked: Any,
                          norm_weights: jax.Array, partial: Any) -> Any:
        """Fold ONE shard into the carry.  ``stacked`` leaves are
        (shard, ...); ``norm_weights`` is this shard's slice of the
        cohort-normalized weights.  Named scope ``fold`` on the device
        trace, for every strategy."""
        with jax.named_scope("fold"):
            return fedavg_fold(partial,
                               self.map_clients(global_params, stacked),
                               norm_weights)

    def aggregate_combine(self, global_params: Any, partial: Any, state: Any,
                          *, k: int) -> Tuple[Any, Any]:
        """Finish the round: cast the fp32 carry back to param dtypes and
        apply the strategy's server update (named scope ``fold``)."""
        with jax.named_scope("fold"):
            mean = fold_finalize(partial, global_params)
            return self.server_update(global_params, mean, state, k=k)

    # -- accounting ----------------------------------------------------
    def upload_bytes(self, global_params: Any, k: int) -> int:
        """Static per-round client->server bytes for ``k`` participants."""
        return k * tree_bytes(global_params)

    def download_bytes(self, global_params: Any, k: int) -> int:
        """Static per-round server->client bytes: the round-start broadcast
        of the global model to ``k`` participants.  Dense for every strategy
        here — ``Compressed`` only compresses the upload direction (client
        deltas; the server's broadcast is the full aggregated model)."""
        return k * tree_bytes(global_params)


@dataclasses.dataclass(frozen=True)
class FedAvg(FederatedStrategy):
    """W = sum_k (n_k/n) W_k — the paper's aggregation, as a strategy."""

    name = "fedavg"


@dataclasses.dataclass(frozen=True)
class FedAvgM(FederatedStrategy):
    """Server momentum over the weighted client delta (pseudo-gradient)."""

    beta: float = 0.9
    lr: float = 1.0
    name = "fedavgm"

    def init_state(self, global_params):
        # zero momentum: round 1 reduces to m = delta, the standard start
        return jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32),
                            global_params)

    def _apply(self, global_params, avg, state):
        delta = tree_delta(avg, global_params)
        m = jax.tree.map(lambda mo, d: self.beta * mo + d, state, delta)
        new = jax.tree.map(lambda g, mo: (g.astype(jnp.float32) + self.lr * mo
                                          ).astype(g.dtype), global_params, m)
        return new, m

    def aggregate(self, global_params, client_params, sizes, state):
        new, m = self._apply(global_params, fedavg(client_params, sizes), state)
        return new, m, len(client_params) * tree_bytes(global_params)

    def server_update(self, global_params, mean, state, *, k):
        return self._apply(global_params, mean, state)


@dataclasses.dataclass(frozen=True)
class FedProx(FederatedStrategy):
    """FedAvg aggregation + proximal client objective
    mu/2 ||w - w_global||^2 (bounds non-IID client drift)."""

    mu: float = 0.01
    name = "fedprox"

    @property
    def needs_anchor(self):                            # type: ignore[override]
        # mu=0 collapses to the plain (anchor-less) FedAvg client program
        return self.mu != 0.0

    def client_step_key(self):
        return ("prox", self.mu) if self.mu else ("plain",)

    def make_client_step(self, cfg, optimizer, *, frozen=None,
                         masked: bool = False, impl: str = "xla",
                         space=None, head_capacity=None):
        if space is not None and space.low_rank:
            # proximal pull toward the round-global BANK: base coordinates
            # never move, so ||bank - anchor||^2 is the whole prox term
            from repro.peft.step import make_peft_train_step
            return make_peft_train_step(cfg, optimizer, space, impl=impl,
                                        prox_mu=self.mu,
                                        head_capacity=head_capacity)
        if masked:
            return make_masked_train_step(cfg, optimizer, impl=impl,
                                          prox_mu=self.mu,
                                          head_capacity=head_capacity)
        return make_train_step(cfg, optimizer, frozen=frozen, impl=impl,
                               prox_mu=self.mu, head_capacity=head_capacity)


@dataclasses.dataclass(frozen=True)
class Compressed(FederatedStrategy):
    """Communication-efficient decorator: clients upload compressed DELTAS
    (deltas compress far better than weights); the inner strategy aggregates
    the reconstructed client trees.  ``kind``: ``topk`` | ``int8``."""

    inner: FederatedStrategy = FedAvg()
    kind: str = "topk"
    frac: float = 0.1

    @property
    def name(self):                                    # type: ignore[override]
        return f"{self.inner.name}+{self.kind}"

    @property
    def needs_anchor(self):                            # type: ignore[override]
        return self.inner.needs_anchor

    def _compress(self, delta):
        if self.kind == "topk":
            return topk_compress(delta, self.frac)
        if self.kind == "int8":
            return int8_compress(delta)
        raise ValueError(self.kind)

    def init_state(self, global_params):
        return self.inner.init_state(global_params)

    def state_to_tree(self, state):
        return self.inner.state_to_tree(state)

    def state_from_tree(self, tree):
        return self.inner.state_from_tree(tree)

    def make_client_step(self, cfg, optimizer, **kw):
        return self.inner.make_client_step(cfg, optimizer, **kw)

    def client_step_key(self):
        return self.inner.client_step_key()

    def aggregate(self, global_params, client_params, sizes, state):
        rebuilt, nbytes = [], 0
        for cp in client_params:
            d = self._compress(tree_delta(cp, global_params))
            if self.kind == "topk":
                nbytes += exact_kept_bytes(d)
            else:
                nbytes += int8_bytes(d)
            rebuilt.append(tree_add(global_params, d))
        new, state, _ = self.inner.aggregate(global_params, rebuilt, sizes,
                                             state)
        return new, state, nbytes

    def map_clients(self, global_params, stacked):
        """Per-client delta -> compress -> rebuild round-trip, vmapped over
        the shard's client axis (O(shard) live — each cohort shard is
        round-tripped as it streams through the fold)."""
        deltas = jax.tree.map(
            lambda s, g: s.astype(jnp.float32) - g.astype(jnp.float32)[None],
            stacked, global_params)
        comp = jax.vmap(self._compress)(deltas)
        rebuilt = jax.tree.map(
            lambda g, d: (g.astype(jnp.float32)[None] + d).astype(g.dtype),
            global_params, comp)
        return self.inner.map_clients(global_params, rebuilt)

    def effective_weights(self, weights):
        return self.inner.effective_weights(weights)

    def server_update(self, global_params, mean, state, *, k):
        return self.inner.server_update(global_params, mean, state, k=k)

    def upload_bytes(self, global_params, k):
        if self.kind == "topk":
            return k * topk_bytes(global_params, self.frac)
        return k * int8_bytes(global_params)


# ---------------------------------------------------------------------------
# Registry (the ``--strategy`` / ``--compress`` driver surface)
# ---------------------------------------------------------------------------

STRATEGIES = ("fedavg", "fedavgm", "fedprox", "asyncfedavg")
COMPRESSORS = ("none", "topk", "int8")


def make_strategy(name: str = "fedavg", *, compress: str = "none",
                  mu: float = 0.01, beta: float = 0.9, server_lr: float = 1.0,
                  frac: float = 0.1, alpha: float = 0.5,
                  staleness: Sequence[int] = ()) -> FederatedStrategy:
    """Build a strategy from flag-shaped arguments (see ``launch/train.py``)."""
    base: FederatedStrategy
    if name == "fedavg":
        base = FedAvg()
    elif name == "fedavgm":
        base = FedAvgM(beta=beta, lr=server_lr)
    elif name == "fedprox":
        base = FedProx(mu=mu)
    elif name == "asyncfedavg":
        # defined with the other server-side algorithms; imported lazily
        # (strategies.py imports this module's helpers)
        from repro.core.strategies import AsyncFedAvg
        base = AsyncFedAvg(alpha=alpha, server_lr=server_lr,
                           staleness=tuple(staleness))
    else:
        raise ValueError(f"unknown strategy {name!r} (want one of {STRATEGIES})")
    if compress == "none":
        return base
    if compress in ("topk", "int8"):
        return Compressed(inner=base, kind=compress, frac=frac)
    raise ValueError(f"unknown compressor {compress!r} (want one of {COMPRESSORS})")
