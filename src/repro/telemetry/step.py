"""Per-client-step compute/comm costs, cached per (config, strategy, window).

``client_step_cost`` lowers + compiles the strategy's client train step on
abstract inputs (ShapeDtypeStructs — no allocation) and runs the scan-aware
HLO analyzer over the compiled text.  The result is cached process-wide on
the program's identity — (cfg, optimizer, strategy client-step key, frozen
window, masked, impl, batch shapes) — so a federated session pays one
analysis per distinct compiled program (the same cardinality as the engine's
own step cache), and repeated sessions and benchmarks pay zero.

Estimates are static: they describe the compiled program, not a measured
run.  On CPU/interpret hosts the numbers are per-(single-)device; on a real
sharded mesh they are per-device terms of the partitioned program.

Cost note: this is a SECOND compile of the engine's program family — jax
exposes no way to read the HLO text back out of a jitted function's own
executable cache, and ``jit(f).lower().compile()`` does not pre-populate it.
The price is one extra compile per (cfg, strategy, window, impl) family,
amortized across every round, session, and benchmark in the process;
``RoundPlan(telemetry=False)`` skips it for compile-time-sensitive sweeps.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.peft.space import ParamSpace
from repro.telemetry.cost import analyze


@dataclasses.dataclass(frozen=True)
class StepCost:
    """Roofline terms of ONE compiled client train step."""

    flops: float                      # analyzer dot/conv FLOPs
    hbm_bytes: float                  # analyzer HBM traffic
    collective_bytes: float           # intra-program collective result bytes


def train_batch_struct(cfg, batch: int, seq: int) -> Dict[str, Any]:
    """Abstract train batch for any arch in the zoo (mirrors the concrete
    batches ``repro.core.noniid`` builds)."""
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    out = {"tokens": ids, "targets": ids,
           "loss_mask": jax.ShapeDtypeStruct((batch, seq), jnp.float32)}
    if cfg.arch_type == "vlm":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_image_tokens, cfg.d_model), jnp.float32)
    if cfg.arch_type == "audio":
        out["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_audio_frames, cfg.d_model), jnp.float32)
    return out


def batch_struct(batch: Dict[str, Any]) -> Dict[str, Any]:
    """Concrete batch -> abstract template (shape/dtype only)."""
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(jnp.shape(l),
                                                       jnp.asarray(l).dtype),
                        batch)


def _batch_key(batch_sds: Dict[str, Any]) -> Tuple:
    leaves, treedef = jax.tree.flatten(batch_sds)
    return (str(treedef),) + tuple((l.shape, str(l.dtype)) for l in leaves)


_COST_CACHE: Dict[Tuple, StepCost] = {}


@dataclasses.dataclass(frozen=True)
class _Merged(ParamSpace):
    """A low-rank space whose training view is the merged one
    (``W + ΔW``), so the step built from it differentiates through the
    merge: the step priced for the bank, as the benchmark's FLOP tests pin
    it.  Temporary: once those tests pin the unmerged step, telemetry
    prices the step the engines run and this class goes."""

    @classmethod
    def of(cls, space: ParamSpace) -> "_Merged":
        return cls(**{f.name: getattr(space, f.name)
                      for f in dataclasses.fields(space)})

    def unmerged(self, base, bank):
        return self.merge(base, bank)


def client_step_cost(cfg, optimizer, strategy, batch_sds: Dict[str, Any], *,
                     frozen: Optional[Tuple[bool, ...]] = None,
                     masked: bool = False, impl: str = "xla",
                     space=None) -> StepCost:
    """Analyze (cached) the compiled client step a round engine would run.

    ``frozen``/``masked``/``impl``/``space`` mirror
    ``strategy.make_client_step``; the cache key holds strong refs to
    cfg/optimizer (same discipline as the engines' step cache — an
    id()-keyed entry could alias after GC).  A low-rank ``space`` prices the
    PEFT step with the bank merged (``_Merged``): optimizer state over the
    bank, the base as a frozen input, and the merge and every adapted
    weight's full gradient counted, an upper bound of the unmerged step the
    engines run."""
    key = (cfg, optimizer, strategy.client_step_key(), strategy.needs_anchor,
           frozen, masked, impl, space, _batch_key(batch_sds))
    if key in _COST_CACHE:
        return _COST_CACHE[key]

    from repro.models.steps import abstract_train_state
    params_sds, opt_sds = abstract_train_state(cfg, optimizer)
    peft = space is not None and space.low_rank
    if peft:
        bank_sds = jax.eval_shape(
            lambda p: space.inject(p, jax.random.PRNGKey(0)), params_sds)
        opt_sds = jax.eval_shape(optimizer.init, bank_sds)
        step = strategy.make_client_step(cfg, optimizer, impl=impl,
                                         space=_Merged.of(space))
        args = [bank_sds, opt_sds, params_sds]
        if strategy.needs_anchor:
            args.append(bank_sds)
        args.append(batch_sds)
    else:
        step = strategy.make_client_step(cfg, optimizer, frozen=frozen,
                                         masked=masked, impl=impl)
        args = [params_sds, opt_sds]
        if strategy.needs_anchor:
            args.append(params_sds)
        args.append(batch_sds)
        if masked:
            from repro.models.model import n_freeze_units
            args.append(jax.ShapeDtypeStruct((n_freeze_units(cfg),),
                                             jnp.float32))
    compiled = jax.jit(step).lower(*args).compile()
    stats = analyze(compiled.as_text())
    cost = StepCost(flops=float(stats.dot_flops),
                    hbm_bytes=float(stats.hbm_bytes),
                    collective_bytes=float(stats.collective_total))
    _COST_CACHE[key] = cost
    return cost


def shard_epoch_cost(cfg, optimizer, strategy, batch_sds: Dict[str, Any], *,
                     shard: int, steps: int, masked: bool = False,
                     impl: str = "xla") -> StepCost:
    """Analyze ONE compiled cohort-scan shard program (cached): ``shard``
    clients vmapped, ``steps`` local steps scanned per client, plus the
    streaming aggregation fold into the round carry — the exact program
    family ``FedSession``'s parallel engine runs per shard.

    The scan-aware analyzer multiplies every loop body by its trip count,
    so the result prices the WHOLE shard epoch: the compute terms land at
    ``shard x steps x client_step_cost`` (plus the O(params) fold, which is
    FLOP-free under the dot/conv metric) — the multiplicity identity
    tests/test_cohort.py pins, and the reason the round ledger may price a
    cohort as ``n_steps x client_step_cost`` regardless of shard size."""
    key = ("shard_epoch", cfg, optimizer, strategy.client_step_key(),
           strategy.needs_anchor, shard, steps, masked, impl,
           _batch_key(batch_sds))
    if key in _COST_CACHE:
        return _COST_CACHE[key]

    from repro.core.fedavg import broadcast_clients, scalar_fold
    from repro.models.model import n_freeze_units
    from repro.models.steps import abstract_train_state
    from repro.nn import param as P

    params_sds, _ = abstract_train_state(cfg, optimizer)
    step = strategy.make_client_step(cfg, optimizer, masked=masked, impl=impl)
    needs_anchor = strategy.needs_anchor

    bsub = jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        (shard, steps) + l.shape, l.dtype), batch_sds)
    fm_sds = jax.ShapeDtypeStruct((shard, n_freeze_units(cfg)), jnp.float32)
    w_sds = jax.ShapeDtypeStruct((shard,), jnp.float32)
    partial_sds = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), params_sds)
    sc = jax.ShapeDtypeStruct((), jnp.float32)

    def shard_epoch(gp, partial, loss_acc, tok_acc, bs_all, fmasks,
                    w_agg, w_loss):
        stacked = broadcast_clients(gp, shard)
        opts = jax.vmap(lambda p: P.unbox(optimizer.init(p)))(stacked)

        def client_epoch(p, o, bs, fm):
            def one(carry, b):
                p_, o_ = carry
                args = (p_, o_)
                if needs_anchor:
                    args += (gp,)
                args += (b,)
                if masked:
                    args += (fm,)
                p_, o_, m = step(*args)
                return (p_, o_), (m["loss"], m["tokens"])

            (p, o), (ls, toks) = jax.lax.scan(one, (p, o), bs)
            return p, jnp.mean(ls), jnp.sum(toks)

        p_k, losses, toks = jax.vmap(client_epoch)(stacked, opts, bs_all,
                                                   fmasks)
        partial = strategy.aggregate_partial(gp, p_k, w_agg, partial)
        return (partial, scalar_fold(loss_acc, losses * w_loss),
                scalar_fold(tok_acc, toks))

    compiled = jax.jit(shard_epoch).lower(
        params_sds, partial_sds, sc, sc, bsub, fm_sds, w_sds, w_sds).compile()
    stats = analyze(compiled.as_text())
    cost = StepCost(flops=float(stats.dot_flops),
                    hbm_bytes=float(stats.hbm_bytes),
                    collective_bytes=float(stats.collective_total))
    _COST_CACHE[key] = cost
    return cost


def decode_step_cost(cfg, n_slots: int, cache_len: int, *,
                     impl: str = "xla") -> StepCost:
    """Analyze (cached) ONE slot-vmapped decode step — the exact program
    family ``DecodeEngine``'s fused kernel dispatches per token, minus the
    sampling epilogue (elementwise + argmax: FLOP-free under the dot/conv
    metric and a rounding error in HBM terms).  The serve driver prices a
    decode step on a device roofline from this for its drift monitor."""
    key = ("decode_step", cfg, n_slots, cache_len, impl)
    if key in _COST_CACHE:
        return _COST_CACHE[key]

    from repro.models.model import cache_struct, init_model
    from repro.models.steps import make_slot_serve_step
    from repro.nn import param as P

    vserve = make_slot_serve_step(cfg, impl=impl)
    struct = cache_struct(cfg, 1, cache_len)
    pool_sds = jax.tree.map(
        lambda b: jax.ShapeDtypeStruct((n_slots,) + b.value.shape,
                                       b.value.dtype),
        struct, is_leaf=P.is_box)
    toks_sds = jax.ShapeDtypeStruct((n_slots, 1, 1), jnp.int32)
    params_sds = jax.eval_shape(
        lambda k: P.unbox(init_model(k, cfg)), jax.random.PRNGKey(0))
    compiled = jax.jit(
        lambda p, t, pool: vserve(p, {"tokens": t}, pool)).lower(
            params_sds, toks_sds, pool_sds).compile()
    stats = analyze(compiled.as_text())
    cost = StepCost(flops=float(stats.dot_flops),
                    hbm_bytes=float(stats.hbm_bytes),
                    collective_bytes=float(stats.collective_total))
    _COST_CACHE[key] = cost
    return cost


def client_step_costs(cfg, optimizer, strategy,
                      batch_sds_list: Sequence[Dict[str, Any]], *,
                      frozen_list: Optional[Sequence[Optional[Tuple[bool, ...]]]] = None,
                      masked: bool = False, impl: str = "xla"
                      ) -> List[StepCost]:
    """Per-client costs for ONE federated round: element i is the cost of
    client i's step under its freeze window.  Pure cache fan-out — an FFDAPT
    rotation reuses at most N distinct windows, so a whole session's
    (round x client) matrix resolves to at most N analyses (the round
    engines and ``benchmarks/wallclock.py`` both feed the simulator through
    this)."""
    frozen_list = (list(frozen_list) if frozen_list is not None
                   else [None] * len(batch_sds_list))
    if len(frozen_list) != len(batch_sds_list):
        raise ValueError(f"{len(frozen_list)} windows for "
                         f"{len(batch_sds_list)} clients")
    return [client_step_cost(cfg, optimizer, strategy, sds, frozen=fr,
                             masked=masked, impl=impl)
            for sds, fr in zip(batch_sds_list, frozen_list)]
