"""Federated round engines, driven by a pluggable ``FederatedStrategy``.

``FedSession`` runs the full FDAPT/FFDAPT process from Appendix A: init every
client from the global model, run one local epoch per round, aggregate with
the session's strategy, repeat.  Two execution engines with identical math:

  * ``engine="sequential"`` — paper-faithful loop over clients (Flower runs
    clients as processes; we run them as successive jit calls).  Supports
    FFDAPT *static* windows: each (window pattern) compiles once, frozen
    layers truly skip backward dW.
  * ``engine="parallel"``  — the cohort-scan engine.  Participants are
    processed in fixed-size SHARDS of the stacked client axis: one jitted
    per-shard program (clients vmapped inside; the client axis mesh-shards
    via ``sharding/rules.py COHORT_RULES`` at production scale) runs each
    shard's local epochs and folds the shard into the strategy's streaming
    aggregation carry (``aggregate_partial``); a second tiny program
    combines the carry into the new global model (``aggregate_combine``).
    Peak live client state is O(shard), not O(cohort), and the compile
    count is independent of cohort size (one shard program, reused —
    plus one remainder-width program when shard does not divide the
    cohort).  ``RoundPlan.cohort_shard=None`` runs a single full-cohort
    shard — the classic all-clients-one-program vmapped round.  Because
    the aggregation is the canonical client-index left fold
    (``repro.core.fedavg.fedavg_fold``), every ``cohort_shard`` setting
    produces BITWISE the same round (pinned in tests/test_cohort.py).
    FFDAPT runs in *masked* mode here (traced per-client masks — a single
    program for all rounds).

``run`` accepts client data either as the materialized
``client_batches[k]`` lists or as a lazy provider (``data.partition.
ClientPool`` — anything with ``batches_for(k)`` / ``sizes`` /
``max_steps`` / ``__len__``): with a provider, only the sampled cohort's
shards are ever materialized, so million-client populations never build
1M datasets.

The round "what" lives in ``RoundPlan`` (strategy, FFDAPT schedule, client
participation, engine); the engines only supply the "how".  Every round
reports upload bytes and tokens/s in ``RoundResult``, plus a static
compute/comm ledger (``flops_estimate`` / ``hbm_bytes_estimate`` /
``comm_bytes``) derived from a scan-aware HLO analysis of the compiled
client step (``repro.telemetry``) — computed once per distinct program and
cached process-wide, so the per-round cost is a dictionary lookup.
``RoundPlan.simulate`` names a device fleet (``repro.sim``); the engines
then also record each round's per-client replay ledger and its ideal
synchronous wall-clock time on that fleet.

``RoundPlan.checkpoint_dir`` makes the run crash-safe: every
``checkpoint_every`` completed rounds both engines write the full run state
through ``repro.checkpoint`` — global params, the strategy's server-state
pytree (``state_to_tree``), the client-sampling RNG bit-state, the FFDAPT
pointer, and the serialized round history.  ``run(..., resume=True)``
restores all of it and skips the completed rounds; a run killed after any
round and resumed is BITWISE identical to the uninterrupted run (params and
history), on both engines, for every strategy (pinned in
tests/test_resume.py).  Checkpointing happens at round boundaries, where
the paper's schedule holds no optimizer state (optimizers re-init each
round), so params + server state + RNG + pointer IS the whole run state.

Both engines record the same ``repro.obs`` spans per round: ``train.round``
holds ``train.prepare`` (sampling the cohort and setting up the round's
weights and accumulators), ``train.dispatch`` (one per shard, or per client
when sequential;
inside it ``train.stack`` materializes the batches and ``train.launch``
calls the program), then ``train.combine`` (the fold's combine launch) and
``train.wait`` (``block_until_ready``); ``train.account`` follows the
round: the host's accounting through the checkpoint.

Per the paper (Appendix E.1): optimizers are re-initialized at the start of
each round's local training; 1 local epoch per round; 15 rounds.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ffdapt as ffd
from repro.core.accounting import split_bytes
from repro.core.fedavg import broadcast_clients, fedavg_stacked, scalar_fold
from repro.core.strategy import FedAvg, FederatedStrategy
from repro.models.steps import head_capacity, make_masked_train_step
from repro.nn import param as P
from repro.peft.space import ParamSpace, frozen_shippable_template
from repro.obs.metrics import registry as _obs_registry
from repro.obs.profile import record_compile
from repro.obs.trace import NULL_SPAN as _NULL_SPAN
from repro.obs.trace import span as _obs_span
from repro.telemetry import batch_struct, client_step_cost


@dataclasses.dataclass
class RoundResult:
    round: int
    loss: float
    round_time_s: float
    windows: Optional[List[ffd.Window]] = None
    upload_bytes: int = 0                 # client->server bytes this round
    tokens: float = 0.0                   # tokens trained on this round
    tokens_per_s: float = 0.0
    clients: Optional[List[int]] = None   # participating client ids
    # static ledger from the compiled client step (repro.telemetry).  With
    # telemetry=False the compute terms are zero and comm_bytes keeps only
    # its shape-derived wire components (down broadcast + upload) — the
    # in-step collective term needs the compiled-program analysis.
    flops_estimate: float = 0.0           # dot/conv FLOPs across all clients
    hbm_bytes_estimate: float = 0.0       # HBM traffic across all clients
    comm_bytes: int = 0                   # down broadcast + upload [+ in-step
                                          # collective bytes, telemetry only]
    download_bytes: int = 0               # server->client bytes this round
    # per-client replay ledger (aligned with ``clients``) — what the
    # wall-clock simulator (repro.sim) needs to place each client's local
    # work on a heterogeneous device: local step count, per-STEP compute
    # terms (FFDAPT windows differ per client), and wire bytes.
    client_steps: Optional[List[int]] = None
    client_step_flops: Optional[List[float]] = None
    client_step_hbm: Optional[List[float]] = None
    client_upload_bytes: Optional[List[int]] = None
    # filled when RoundPlan.simulate is set: ideal (dropout-free) sync
    # round seconds on the plan's fleet (repro.sim.clock.sync_round_s)
    sim_round_s: float = 0.0
    # plan.eval_fn(params) after this round's aggregation; ``loss`` always
    # keeps the round's TRAIN loss (eval used to overwrite it)
    eval_loss: Optional[float] = None

    def to_json(self) -> Dict[str, Any]:
        """JSON-able dict (tuples become lists); ``from_json`` round-trips
        exactly — floats survive via repr, so a serialized history replays
        and compares bitwise."""
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "RoundResult":
        names = {f.name for f in dataclasses.fields(cls)}
        d = {k: v for k, v in d.items() if k in names}
        if d.get("windows") is not None:
            d["windows"] = [(int(s), int(n)) for s, n in d["windows"]]
        return cls(**d)


@dataclasses.dataclass
class RoundPlan:
    """Everything that defines a federated run except model/opt/data."""

    n_rounds: int = 15
    engine: str = "sequential"            # sequential | parallel
    impl: str = "xla"
    # cohort-scan shard size for the parallel engine: at most this many
    # clients are live at once (params/opt-state/batches stacked per shard;
    # the streaming aggregation carry is O(params)).  None = one full-cohort
    # shard (the classic vmapped round).  Any value produces bitwise the
    # same result — the fold reduction is shard-invariant and the schedule
    # never emits a width-1 shard (``_shard_widths``: clamps to >= 2,
    # absorbs a lone remainder) — so this is a pure memory/compile knob,
    # deliberately NOT part of the checkpoint fingerprint (a run may be
    # resumed under a different shard size).
    cohort_shard: Optional[int] = None
    strategy: FederatedStrategy = dataclasses.field(default_factory=FedAvg)
    ffdapt: Optional[ffd.FFDAPTConfig] = None
    # trainable/shippable subspace (repro.peft.ParamSpace).  None resolves to
    # ``frozen_window`` when an FFDAPT schedule is set, else ``full`` — both
    # run literally the pre-ParamSpace engine paths (bitwise; pinned in
    # tests/test_peft.py).  A low-rank space (lora/adapter) turns the
    # strategy's params tree into the factor BANK: aggregation, compression,
    # upload/download accounting, the cohort-scan carry and the checkpoint
    # server state all live in subspace coordinates, and the frozen base
    # rides into the client step as a separate traced argument.  Low-rank
    # does not compose with ``ffdapt`` (two ownership claims on the same
    # update masking — ``run`` raises).
    param_space: Optional[ParamSpace] = None
    participation: float = 1.0            # fraction of clients per round
    seed: int = 0                         # client-sampling seed
    client_sizes: Optional[Sequence[int]] = None   # n_k; default batch counts
    eval_fn: Optional[Callable[[Any], float]] = None
    telemetry: bool = True                # per-round compute/comm ledger
    # wall-clock simulation hook: a repro.sim Fleet, a named-fleet string
    # ("edge-mixed", ...), or a {preset: weight} mixture.  When set, every
    # RoundResult carries sim_round_s — the ideal synchronous round time on
    # that fleet (slowest sampled client; requires telemetry=True for the
    # compute terms).  Deadline/async schedules are post-hoc replays:
    # repro.sim.events.simulate(history, fleet, mode=...) — the async one
    # consumes the ledger's PER-CLIENT step schedule, so quantity skew
    # shows up as staleness.
    simulate: Optional[Any] = None
    # clock mode for sim_round_s: False = sequential down/compute/up sum,
    # True = pipelined overlap clock (repro.sim.clock).
    overlap: bool = False
    # crash-safe checkpointing (repro.checkpoint): when set, both engines
    # write the full run state (params + server state + RNG + FFDAPT
    # pointer + history) every ``checkpoint_every`` completed rounds, plus
    # at the final round and before a ``stop_after_round`` halt; ``_rotate``
    # keeps the newest ``checkpoint_keep``.
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 1
    checkpoint_keep: int = 3
    # preemption hook (tests / the resume smoke): return after completing
    # this many rounds, as if the process were killed right after the
    # checkpoint — resume picks up the remaining rounds.
    stop_after_round: Optional[int] = None
    # extra JSON-able identity merged into the checkpoint plan fingerprint
    # and verified on resume.  The session can fingerprint its own plan but
    # not the optimizer (closures) or the data pipeline — the caller pins
    # those here (train.py records lr/arch/batch/seq/docs/skew).
    fingerprint_extra: Optional[Dict[str, Any]] = None


def _epoch(step, params, opt_state, batches: Sequence[Dict[str, Any]],
           *extra):
    """One local epoch.  ``extra`` args splice between opt_state and the
    batch: ``(anchor,)`` for FedProx, ``(base,)`` for PEFT steps (the frozen
    base model), ``(base, anchor)`` for both."""
    losses, toks = [], []
    for b in batches:
        params, opt_state, m = step(params, opt_state, *extra, b)
        losses.append(m["loss"])
        toks.append(m["tokens"])
    return (params, opt_state, float(jnp.mean(jnp.stack(losses))),
            float(jnp.sum(jnp.stack(toks))))


def _participants(rng, k: int, participation: float) -> List[int]:
    """Sample the round's cohort: m of k clients, without replacement, in
    O(m) memory via Floyd's algorithm — ``rng.choice(k, replace=False)``
    materializes a k-length permutation, which at million-client
    populations dominates the round's host memory.  The draw consumes the
    generator deterministically (one vectorized ``integers`` call), so the
    PR 5 resume contract holds: restoring the checkpointed RNG bit-state
    reproduces the exact cohort sequence."""
    if participation >= 1.0:
        return list(range(k))
    m = max(1, int(round(participation * k)))
    if m >= k:
        return list(range(k))
    # Floyd: for j = k-m .. k-1, draw t in [0, j]; take t unless already
    # chosen, else take j.  Each j is chosen with probability m/k, uniform
    # over all m-subsets.  The m draws vectorize into one generator call.
    ts = rng.integers(0, np.arange(k - m + 1, k + 1))
    chosen: set = set()
    for j, t in zip(range(k - m, k), ts.tolist()):
        chosen.add(t if t not in chosen else j)
    return sorted(chosen)


class _ListClientData:
    """Adapter giving materialized ``client_batches`` lists the lazy
    provider interface the engines consume (``ClientPool`` is the
    million-client implementation; see ``repro.data.partition``)."""

    def __init__(self, client_batches: List[List[Dict[str, Any]]]):
        self._batches = client_batches

    def __len__(self) -> int:
        return len(self._batches)

    @property
    def sizes(self) -> List[int]:
        return [len(bs) for bs in self._batches]

    @property
    def max_steps(self) -> int:
        return max(len(bs) for bs in self._batches)

    def batches_for(self, k: int) -> List[Dict[str, Any]]:
        return self._batches[k]


def _as_client_data(client_batches) -> Any:
    if hasattr(client_batches, "batches_for"):
        return client_batches
    return _ListClientData(client_batches)


def _shard_widths(m: int, shard: Optional[int]) -> List[int]:
    """Cohort-scan shard schedule: widths summing to ``m``, each ``shard``
    except the tail.  Two rules keep every schedule BITWISE equal to the
    full-width program: no shard is ever width 1 (XLA lowers a degenerate
    single-client vmap differently — its lanes come out a ulp off the
    width>=2 programs, which are all per-lane identical), so the requested
    width clamps to >= 2 and a remainder of 1 is absorbed into the last
    shard (width ``shard + 1``) instead of trailing alone.  At most two
    distinct widths -> at most two shard-program compiles per session."""
    if shard is None or shard >= m:
        return [m]
    shard = max(2, shard)
    if shard >= m:
        return [m]
    widths = [shard] * (m // shard)
    r = m % shard
    if r == 1:
        widths[-1] += 1
    elif r:
        widths.append(r)
    return widths


def _stack_shard(data, ids: Sequence[int], max_steps: int):
    """Materialize ONE shard's rectangular batch block: (shard, steps,
    B, ...) per leaf.  Short clients pad by CYCLING their local batches
    (same rule the full-width engine always used), and only this shard's
    clients are ever resident."""
    per_client = []
    for k in ids:
        bs = data.batches_for(k)
        padded = [bs[i % len(bs)] for i in range(max_steps)]
        per_client.append(jax.tree.map(lambda *xs: jnp.stack(xs), *padded))
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_client)


def _footprint(tree) -> Dict[str, int]:
    """``arrays`` and ``bytes`` of a pytree's leaves: the args a
    ``train.stack`` span gives what it hands on."""
    leaves = jax.tree.leaves(tree)
    return {"arrays": len(leaves),
            "bytes": sum(int(x.nbytes) for x in leaves)}


def _head_capacity(cfg, data, part: Sequence[int]) -> Optional[int]:
    """The round's LM-head capacity (``models.steps.head_capacity``) over
    the largest loss-mask count among the participants' batches, chosen
    once per round before sharding so every shard runs one program.
    Records the gauges ``train.head_capacity`` (rows the head runs at) and
    ``train.head_fill`` (largest count over them), and counts a round that
    runs the head at every position in ``train.head_full``."""
    most = positions = 0
    for k in part:
        for b in data.batches_for(k):
            mask = np.asarray(b["loss_mask"])
            most = max(most, int(np.count_nonzero(mask)))
            positions = mask.size
    cap = head_capacity(most, positions, cfg.mlm_mask_rate)
    rows = positions if cap is None else cap
    reg = _obs_registry()
    reg.gauge("train.head_capacity").set(rows)
    reg.gauge("train.head_fill").set(most / rows)
    reg.counter("train.head_full").inc(int(cap is None))
    return cap


def _record_round_metrics(rr: "RoundResult") -> None:
    """Bank one round into the process-wide metrics registry (counters +
    the round-seconds histogram ``--metrics-out`` exports).  Host floats
    only — negligible next to a round."""
    reg = _obs_registry()
    reg.counter("train.rounds").inc()
    reg.counter("train.tokens").inc(rr.tokens)
    reg.counter("train.upload_bytes").inc(rr.upload_bytes)
    reg.counter("train.comm_bytes").inc(rr.comm_bytes)
    reg.histogram("train.round_s").observe(rr.round_time_s)
    reg.gauge("train.last_loss").set(rr.loss)


class FedSession:
    """A federated training session: ``FedSession(cfg, opt, plan).run(...)``.

    Construct with a ``RoundPlan`` or with plan fields as kwargs:
    ``FedSession(cfg, opt, n_rounds=3, strategy=FedProx(mu=0.01))``.
    """

    def __init__(self, cfg, optimizer, plan: Optional[RoundPlan] = None,
                 **plan_overrides):
        if plan is None:
            plan = RoundPlan(**plan_overrides)
        elif plan_overrides:
            plan = dataclasses.replace(plan, **plan_overrides)
        self.cfg = cfg
        self.optimizer = optimizer
        self.plan = plan

    def run(self, params, client_batches, *, resume: bool = False):
        """Returns (final_params, [RoundResult...]).

        ``client_batches`` is either the materialized lists —
        ``client_batches[k]`` = that client's local batches for one epoch
        (re-used each round — the paper re-iterates the local dataset every
        round) — or a lazy provider (``repro.data.partition.ClientPool``)
        exposing ``batches_for(k)`` / ``sizes`` / ``max_steps`` /
        ``__len__``, under which only sampled cohorts materialize.
        ``plan.client_sizes`` defaults to per-client batch counts (n_k of
        Algorithm 1).

        ``resume=True`` restores the latest checkpoint in
        ``plan.checkpoint_dir`` (params, server state, RNG position, FFDAPT
        pointer, history) and runs only the remaining rounds; without a
        checkpoint on disk it starts fresh.  The resumed run is bitwise
        identical to the uninterrupted one.
        """
        plan = self.plan
        space = plan.param_space
        if space is None:
            # implicit spaces: FFDAPT plans ARE frozen_window, all others
            # full — resolution changes nothing about the executed program
            space = ParamSpace("frozen_window" if plan.ffdapt else "full")
        peft = space.low_rank
        if peft and plan.ffdapt is not None:
            raise ValueError(
                f"param space {space.kind!r} does not compose with "
                f"plan.ffdapt frozen windows — both claim the update mask; "
                f"pick one")
        self._space, self._peft = space, peft
        data = _as_client_data(client_batches)
        sizes = (list(plan.client_sizes) if plan.client_sizes is not None
                 else list(data.sizes))
        # the client population is part of the checkpoint fingerprint:
        # resuming over different clients/weights must raise, not diverge
        self._run_sizes = sizes
        if peft and not (isinstance(params, dict)
                         and set(params) == {"base", "peft"}):
            # seed the bank deterministically from the plan seed: a resumed
            # run rebuilds the same template, and two runs with one seed get
            # one bank init (B factors are zero, so round 0 starts from the
            # base model exactly)
            params = {"base": params,
                      "peft": space.inject(params,
                                           jax.random.PRNGKey(plan.seed))}
        from repro.models.model import n_freeze_units
        n_units = n_freeze_units(self.cfg)
        windows = (ffd.schedule(n_units, sizes, plan.n_rounds,
                                epsilon=plan.ffdapt.epsilon,
                                gamma=plan.ffdapt.gamma)
                   if plan.ffdapt else None)
        start, state, rng, history = 0, None, None, None
        if resume:
            got = self._restore(params, windows, n_units)
            if got is not None:
                start, params, state, rng, history = got
        elif plan.checkpoint_dir:
            # a fresh run must not write into a directory that already
            # holds checkpoints: the new rounds would sort OLDEST and be
            # rotated away, and a later resume would silently pick up the
            # stale run's state instead of this one's
            from repro.checkpoint import latest_step
            have = latest_step(plan.checkpoint_dir)
            if have is not None:
                raise ValueError(
                    f"checkpoint_dir {plan.checkpoint_dir!r} already holds "
                    f"round checkpoints (latest {have}) — pass resume=True "
                    f"to continue that run, or use a fresh directory")
        if start >= plan.n_rounds:
            if peft:
                return space.merge(params["base"], params["peft"]), history or []
            return params, history or []
        if plan.engine == "sequential":
            return self._run_sequential(params, data, sizes,
                                        windows, n_units, start=start,
                                        state=state, rng=rng, history=history)
        if plan.engine == "parallel":
            return self._run_parallel(params, data, sizes,
                                      windows, n_units, start=start,
                                      state=state, rng=rng, history=history)
        raise ValueError(plan.engine)

    # -----------------------------------------------------------------
    # Checkpoint / resume (shared by both engines)
    # -----------------------------------------------------------------

    def _ckpt_plan_fingerprint(self) -> Dict[str, Any]:
        # n_rounds is recorded for information only (resuming with a larger
        # n_rounds legitimately extends the run); everything else must
        # match or the resumed math would silently diverge.  The strategy
        # fingerprint carries its full hyperparameters (strategies are
        # frozen dataclasses; Compressed recurses into its inner) — name
        # alone would let e.g. FedAvgM(beta=0.5) resume a beta=0.9 run.
        # JSON-normalized so the fresh fingerprint compares equal to one
        # read back from the sidecar (tuples -> lists, float repr).
        plan = self.plan
        strat = {"name": plan.strategy.name,
                 **dataclasses.asdict(plan.strategy)}
        sizes = [int(s) for s in getattr(self, "_run_sizes", [])]
        if len(sizes) > 4096:
            # mega-cohort populations: fingerprint the size vector by
            # digest, not value — a million-entry list would dominate every
            # checkpoint sidecar.  Deterministic, so fresh and restored
            # fingerprints still compare equal.
            import hashlib
            sizes = {"n": len(sizes),
                     "sha256": hashlib.sha256(
                         np.asarray(sizes, np.int64).tobytes()).hexdigest()}
        fp = {"strategy": strat, "engine": plan.engine, "impl": plan.impl,
              "seed": plan.seed, "participation": plan.participation,
              "ffdapt": (dataclasses.asdict(plan.ffdapt)
                         if plan.ffdapt else None),
              "client_sizes": sizes,
              # recorded for information, like n_rounds — NOT resume-
              # enforced: the fold aggregation is shard-invariant, so a
              # run may legitimately resume under a different cohort_shard
              # (pinned bitwise in tests/test_cohort.py)
              "cohort_shard": plan.cohort_shard,
              # the trainable subspace decides both the archive layout
              # (low-rank runs store base + bank) and the executed math —
              # resuming a rank-4 LoRA run as rank-8 (or as full) must raise
              "param_space": (plan.param_space.to_json()
                              if plan.param_space is not None else None),
              # telemetry/simulate/overlap don't move the params, but they
              # decide the history's ledger columns — a resumed run must
              # fill them the same way or the prefix and suffix disagree
              "telemetry": plan.telemetry, "overlap": plan.overlap,
              "simulate": self._simulate_fingerprint(),
              "extra": plan.fingerprint_extra,
              "n_rounds": plan.n_rounds}
        return json.loads(json.dumps(fp))

    def _simulate_fingerprint(self):
        """plan.simulate's identity for the fingerprint.  A Fleet is
        fingerprinted by its full device composition, not just its name —
        two same-named fleets (e.g. "edge-mixed" datasheet vs calibrated,
        or any two sample_fleet mixtures, both named "custom") would
        otherwise resume into each other and desync sim_round_s between
        the restored prefix and the resumed suffix."""
        sim = self.plan.simulate
        if sim is not None and hasattr(sim, "devices"):
            return {"name": getattr(sim, "name", None),
                    "devices": [dataclasses.asdict(d) for d in sim.devices]}
        return sim

    def _restore(self, params, windows, n_units):
        """Load the newest checkpoint in ``plan.checkpoint_dir``; None when
        the directory holds none (fresh start).  Raises on a checkpoint
        written under an incompatible plan — resuming with a different
        strategy/seed/participation would silently change the math."""
        plan, strategy = self.plan, self.plan.strategy
        if not plan.checkpoint_dir:
            raise ValueError("resume=True needs plan.checkpoint_dir")
        from repro.checkpoint import (latest_step, restore_checkpoint,
                                      restore_extra)
        from repro.checkpoint.npz import FederatedState
        step = latest_step(plan.checkpoint_dir)
        if step is None:
            return None
        meta = restore_extra(plan.checkpoint_dir, step)
        if meta is None or "round" not in meta or "history" not in meta:
            raise ValueError(
                f"checkpoint {step} in {plan.checkpoint_dir!r} is not a "
                f"resumable round checkpoint (no FederatedState sidecar — "
                f"written by an older final-snapshot save?)")
        fed = FederatedState.from_json(meta)
        if fed.plan:
            mine = self._ckpt_plan_fingerprint()
            for key in ("strategy", "engine", "impl", "seed",
                        "participation", "ffdapt", "client_sizes",
                        "param_space", "telemetry", "overlap", "simulate",
                        "extra"):
                if key in fed.plan and fed.plan[key] != mine[key]:
                    raise ValueError(
                        f"checkpoint was written under a different plan: "
                        f"{key}={fed.plan[key]!r} != {mine[key]!r}")
        if windows is not None and fed.round < len(windows):
            want = windows[fed.round][0][0]
            if fed.ffdapt_start != want:
                raise ValueError(
                    f"checkpoint FFDAPT pointer {fed.ffdapt_start} does not "
                    f"match the plan's schedule ({want} at round "
                    f"{fed.round}) — client sizes or gamma/epsilon changed")
        # low-rank runs aggregate in bank coordinates: the server-state
        # template must be built over the bank, while the params template
        # keeps the combined {base, peft} layout the archive stores
        agg_tmpl = params["peft"] if self._peft else params
        template = {
            "params": jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params),
            "server": strategy.state_to_tree(strategy.init_state(agg_tmpl))}
        tree = restore_checkpoint(plan.checkpoint_dir, step, template)
        state = strategy.state_from_tree(tree["server"])
        rng = np.random.default_rng(plan.seed)
        if fed.rng_state is not None:
            rng.bit_generator.state = fed.rng_state
        history = [RoundResult.from_json(h) for h in fed.history]
        return fed.round, tree["params"], state, rng, history

    def _checkpoint(self, t, params, state, rng, history, windows, n_units):
        """Write the full run state after round ``t`` when due: every
        ``checkpoint_every`` rounds, at the final round, and right before a
        ``stop_after_round`` halt (so the simulated preemption always
        leaves a resumable checkpoint behind)."""
        plan, strategy = self.plan, self.plan.strategy
        if not plan.checkpoint_dir:
            return
        done = t + 1
        due = (done % max(plan.checkpoint_every, 1) == 0
               or done == plan.n_rounds or done == plan.stop_after_round)
        if not due:
            return
        from repro.checkpoint import save_checkpoint
        from repro.checkpoint.npz import FederatedState
        if windows is None:
            ptr = 0
        elif done < len(windows):
            ptr = windows[done][0][0]
        else:
            s, nf = windows[-1][-1]
            ptr = (s + nf) % max(n_units, 1)
        fed = FederatedState(
            round=done, ffdapt_start=ptr,
            rng_state=rng.bit_generator.state,
            history=[h.to_json() for h in history],
            plan=self._ckpt_plan_fingerprint())
        with _obs_span("train.checkpoint", cat="train", round=t):
            save_checkpoint(
                plan.checkpoint_dir, done,
                {"params": params, "server": strategy.state_to_tree(state)},
                extra=fed.to_json(), keep=plan.checkpoint_keep)
        _obs_registry().counter("train.checkpoints").inc()

    # -----------------------------------------------------------------
    # Sequential (paper-faithful; static FFDAPT windows)
    # -----------------------------------------------------------------

    def _step_for(self, frozen, cap=None):
        # Keyed on the strategy's CLIENT-STEP identity, not the strategy
        # itself: FedAvg/FedAvgM/Compressed share one compiled program,
        # FedProx compiles per distinct mu.  Keys hold strong refs to
        # cfg/optimizer, so a GC'd optimizer can never alias a live cache
        # entry (the old ``id(optimizer.update)`` key could, after id reuse).
        # The subspace keys the cache through ``space.step_key``: full and
        # frozen_window return ``frozen`` verbatim, so their entries (and
        # compiled programs) are IDENTICAL to pre-ParamSpace sessions;
        # low-rank spaces key on (kind, rank, alpha, targets).
        space = getattr(self, "_space", None)
        skey = space.step_key(frozen) if space is not None else frozen
        key = (self.cfg, self.optimizer, self.plan.strategy.client_step_key(),
               skey, self.plan.impl, cap)
        if key not in _STEP_CACHE:
            # a cache miss means the next call traces+compiles a new client
            # program — mark it so the trace shows which round paid it
            record_compile("client_step",
                           strategy=self.plan.strategy.name,
                           impl=self.plan.impl)
            kw = {}
            if space is not None and space.low_rank:
                kw["space"] = space
            _STEP_CACHE[key] = jax.jit(self.plan.strategy.make_client_step(
                self.cfg, self.optimizer, frozen=frozen, impl=self.plan.impl,
                head_capacity=cap, **kw))
        return _STEP_CACHE[key]

    def _client_upload_bytes(self, params, part, windows, n_units, t):
        """Per-client upload ledger + round total.  FFDAPT rounds price each
        client at its SHIPPABLE subspace (unfrozen layer rows — the ROADMAP
        'frozen-window masking costs full-tree traffic' fix); the strategy's
        tree-generic byte formulas make this compose with top-k/int8.  For
        every other space ``params`` is already the shipped tree (the bank,
        under low-rank), so the strategy's round total splits evenly."""
        strategy = self.plan.strategy
        if windows is not None:
            per = [strategy.upload_bytes(
                frozen_shippable_template(
                    self.cfg, params, ffd.window_mask(n_units, windows[t][k])),
                1) for k in part]
            return per, sum(per)
        nbytes = strategy.upload_bytes(params, len(part))
        return split_bytes(nbytes, len(part)), nbytes

    def _step_cost(self, batch, *, frozen=None, masked=False):
        """Cached telemetry for ONE client step of this session's program
        family (same cache cardinality as the compiled-step cache)."""
        space = getattr(self, "_space", None)
        return client_step_cost(self.cfg, self.optimizer, self.plan.strategy,
                                batch_struct(batch), frozen=frozen,
                                masked=masked, impl=self.plan.impl,
                                space=space if getattr(self, "_peft", False)
                                else None)

    def _fleet(self, n_clients: int):
        """Resolve plan.simulate into a repro.sim Fleet (None = no sim)."""
        if self.plan.simulate is None:
            return None
        from repro.sim.clock import resolve_fleet
        return resolve_fleet(self.plan.simulate, n_clients, self.plan.seed)

    def _run_sequential(self, params, data, sizes, windows,
                        n_units, *, start=0, state=None, rng=None,
                        history=None):
        plan, optimizer, strategy = self.plan, self.optimizer, self.plan.strategy
        space, peft = self._space, self._peft
        base = None
        if peft:
            # from here on ``params`` IS the bank: the strategy aggregates,
            # prices and checkpoints subspace coordinates; the frozen base
            # enters the client step as an extra traced argument
            base, params = params["base"], params["peft"]
        rng = np.random.default_rng(plan.seed) if rng is None else rng
        state = strategy.init_state(params) if state is None else state
        fleet = self._fleet(len(data))
        history = [] if history is None else history
        for t in range(start, plan.n_rounds):
            # loop-ENTRY guard: a resumed run whose restored rounds already
            # reach the threshold halts immediately (stop_after_round=r
            # means "at most r completed rounds", fresh or resumed)
            if (plan.stop_after_round is not None
                    and t >= plan.stop_after_round):
                break
            with _obs_span("train.round", cat="train", round=t,
                           engine="sequential"):
                t0 = time.perf_counter()
                with _obs_span("train.prepare", cat="train"):
                    part = _participants(rng, len(data), plan.participation)
                    down = strategy.download_bytes(params, len(part))
                    cap = _head_capacity(self.cfg, data, part)
                locals_, losses, tokens = [], [], 0.0
                flops_e = hbm_e = coll_e = 0.0
                c_steps, c_flops, c_hbm = [], [], []
                for k in part:
                    frozen = None
                    if windows is not None:
                        frozen = ffd.window_mask(n_units, windows[t][k])
                    # dispatch span = one client's whole local epoch (the
                    # sequential engine's unit of dispatch); jit calls sync
                    # per batch, so its launch child measures real compute
                    with _obs_span("train.dispatch", cat="train", round=t,
                                   client=k):
                        with _obs_span("train.stack", cat="train") as sp:
                            bs_k = data.batches_for(k)
                            if sp is not _NULL_SPAN:
                                sp.set(**_footprint(bs_k))
                        steps_k = len(bs_k)
                        c_steps.append(steps_k)
                        if plan.telemetry:
                            cost = self._step_cost(bs_k[0], frozen=frozen)
                            c_flops.append(cost.flops)
                            c_hbm.append(cost.hbm_bytes)
                            flops_e += cost.flops * steps_k
                            hbm_e += cost.hbm_bytes * steps_k
                            coll_e += cost.collective_bytes * steps_k
                        opt_state = P.unbox(optimizer.init(params))
                        extra = (base,) if peft else ()
                        if strategy.needs_anchor:
                            extra += (params,)   # round-global anchor (the
                                                 # bank, under low-rank —
                                                 # FedProx pulls toward the
                                                 # global subspace)
                        with _obs_span("train.launch", cat="train",
                                       steps=steps_k):
                            p_k, _, loss, tok = _epoch(
                                self._step_for(frozen, cap), params,
                                opt_state, bs_k, *extra)
                    locals_.append(p_k)
                    losses.append(loss)
                    tokens += tok
                with _obs_span("train.combine", cat="train", round=t,
                               clients=len(part)):
                    params, state, nbytes = strategy.aggregate(
                        params, locals_, [sizes[k] for k in part], state)
                with _obs_span("train.wait", cat="train", round=t):
                    jax.block_until_ready(params)
                dt = time.perf_counter() - t0
            with _obs_span("train.account", cat="train", round=t):
                if windows is not None:
                    # FFDAPT accounting fix: clients ship only their
                    # unfrozen layer rows, so the round total is the sum of
                    # per-client subspace prices — not the aggregate()'s
                    # full-tree figure
                    c_up, nbytes = self._client_upload_bytes(
                        params, part, windows, n_units, t)
                else:
                    # aggregate() reports the exact round total; per-client
                    # shares are the static even split + remainder
                    # (Compressed tie-keeps can skew individual clients by
                    # a few entries, but the shares always sum to the exact
                    # round total)
                    c_up = split_bytes(nbytes, len(part))
                rr = RoundResult(
                    t, float(np.mean(losses)), dt,
                    windows[t] if windows else None,
                    upload_bytes=nbytes, tokens=tokens,
                    tokens_per_s=tokens / max(dt, 1e-9), clients=part,
                    flops_estimate=flops_e, hbm_bytes_estimate=hbm_e,
                    comm_bytes=down + nbytes + int(coll_e),
                    download_bytes=down, client_steps=c_steps,
                    client_step_flops=c_flops or None,
                    client_step_hbm=c_hbm or None,
                    client_upload_bytes=c_up)
                if fleet is not None:
                    from repro.sim.clock import sync_round_s
                    rr.sim_round_s = sync_round_s(rr, fleet,
                                                  overlap=plan.overlap)
                if plan.eval_fn is not None:
                    rr.eval_loss = float(plan.eval_fn(
                        space.merge(base, params) if peft else params))
                history.append(rr)
                _record_round_metrics(rr)
                self._checkpoint(t, {"base": base, "peft": params} if peft
                                 else params, state, rng, history, windows,
                                 n_units)
        return (space.merge(base, params) if peft else params), history

    # -----------------------------------------------------------------
    # Parallel (cohort-scan engine; masked FFDAPT)
    # -----------------------------------------------------------------

    def _run_parallel(self, params, data, sizes, windows, n_units,
                      *, start=0, state=None, rng=None, history=None):
        plan, optimizer, strategy = self.plan, self.optimizer, self.plan.strategy
        space, peft = self._space, self._peft
        base = None
        if peft:
            # bank-as-params: the stacked client state, the streaming
            # aggregation carry and the combine program are all O(bank);
            # the base is one unstacked donated-in argument per shard call
            base, params = params["base"], params["peft"]
        K = len(data)
        # rectangular schedule: pad short clients by CYCLING their local
        # batches (quantity skew -> unequal local steps); the n_k
        # aggregation weights stay the true sizes.  NOTE: cycling means a
        # short client re-iterates its data within the round (>1 local
        # epoch), so sequential/parallel only match exactly when all
        # clients have equal step counts; RoundResult.tokens counts the
        # repeats (they were trained on).
        max_steps = data.max_steps

        use_mask = windows is not None
        step_kw = {"space": space} if peft else {}
        needs_anchor = strategy.needs_anchor

        # traced (= compiled) shard-program count this session: the
        # compile-count invariant tests/test_cohort.py pins — one program
        # per distinct shard WIDTH (so 1, or 2 when the shard size does
        # not divide the cohort), never one per shard or per round, while
        # the rounds keep one LM-head capacity.
        self.shard_compiles = 0

        def _fed_shard(client_step, global_params, base_params, partial,
                       loss_acc, tok_acc, bsub, fmasks, w_agg, w_loss):
            """One cohort shard: vmapped local epochs + streaming fold.

            ``client_step`` is bound once per LM-head capacity
            (``_shard_program``); ``global_params`` is the aggregated tree
            (the BANK under a low-rank space, with ``base_params`` the
            frozen base — None, an empty pytree, otherwise); ``partial``/
            ``loss_acc``/``tok_acc`` are the round's carries; ``w_agg`` is
            this shard's slice of the cohort-normalized aggregation
            weights, ``w_loss`` the raw-normalized loss weights.  Traced
            once per shard width and capacity (jit caches on shapes).
            """
            self.shard_compiles += 1          # trace-time, not per call
            ksub = fmasks.shape[0]
            # emit the compile as a trace event too: the Perfetto timeline
            # then shows WHICH round/shard width paid each trace (the
            # shard_compiles counter alone only says how many)
            record_compile("shard_program", width=int(ksub))
            stacked = broadcast_clients(global_params, ksub)
            opts = jax.vmap(lambda p: P.unbox(optimizer.init(p)))(stacked)

            def client_epoch(p, o, bs, fm):
                def one(carry, b):
                    p_, o_ = carry
                    args = (p_, o_)
                    if base_params is not None:
                        args += (base_params,)
                    if needs_anchor:
                        args += (global_params,)
                    args += (b,)
                    if use_mask:
                        args += (fm,)
                    p_, o_, m = client_step(*args)
                    return (p_, o_), (m["loss"], m["tokens"])

                (p, o), (ls, toks) = jax.lax.scan(one, (p, o), bs)
                return p, jnp.mean(ls), jnp.sum(toks)

            p_k, losses, toks = jax.vmap(client_epoch)(stacked, opts, bsub,
                                                       fmasks)
            partial = strategy.aggregate_partial(global_params, p_k, w_agg,
                                                 partial)
            return (partial, scalar_fold(loss_acc, losses * w_loss),
                    scalar_fold(tok_acc, toks))

        shard_programs: Dict[Optional[int], Callable] = {}

        def _shard_program(cap):
            # one jit per LM-head capacity; a round's shards share one
            if cap not in shard_programs:
                program = functools.partial(
                    _fed_shard, strategy.make_client_step(
                        self.cfg, optimizer, masked=use_mask, impl=plan.impl,
                        head_capacity=cap, **step_kw))
                # jit names the module (and the device trace's ops) from it:
                # jit__fed_shard
                program.__name__ = _fed_shard.__name__
                shard_programs[cap] = jax.jit(program)
            return shard_programs[cap]

        # the per-shard program and the abstract arguments of its first
        # call: ``shard_program.lower(*shard_args).compile()`` is the
        # program the rounds run, for checks of its text and memory
        self.shard_program, self.shard_args = None, None

        @jax.jit
        def norm_weights(w):
            """Both weight normalizations, over the FULL cohort vector
            before any sharding — every shard folds with weights the whole
            cohort normalized, exactly like the full-width program."""
            we = strategy.effective_weights(w)
            return we / jnp.sum(we), w / jnp.sum(w)

        combine_cache: Dict[int, Callable] = {}

        def _combine_for(m: int):
            # aggregate_combine takes the cohort size statically (AsyncFedAvg
            # resolves its fresh path on it); participation keeps m constant
            # across rounds, so this compiles once per session
            if m not in combine_cache:
                def _fed_combine(gp, pa, st):
                    return strategy.aggregate_combine(gp, pa, st, k=m)
                combine_cache[m] = jax.jit(_fed_combine)
                # like ``shard_program``: the combine the rounds run
                self.combine_program = combine_cache[m]
            return combine_cache[m]

        rng = np.random.default_rng(plan.seed) if rng is None else rng
        w_all = jnp.asarray(sizes, jnp.float32)
        state = strategy.init_state(params) if state is None else state
        # one program family for the whole session: a single cached analysis
        # covers every round (masked FFDAPT has no per-window programs)
        step_cost = (self._step_cost(data.batches_for(0)[0], masked=use_mask)
                     if plan.telemetry else None)
        fleet = self._fleet(K)
        history = [] if history is None else history
        for t in range(start, plan.n_rounds):
            # loop-ENTRY guard: a resumed run whose restored rounds already
            # reach the threshold halts immediately (stop_after_round=r
            # means "at most r completed rounds", fresh or resumed)
            if (plan.stop_after_round is not None
                    and t >= plan.stop_after_round):
                break
            with _obs_span("train.round", cat="train", round=t,
                           engine="parallel"):
                t0 = time.perf_counter()
                with _obs_span("train.prepare", cat="train"):
                    part = _participants(rng, K, plan.participation)
                    m = len(part)
                    w = (w_all if m == K
                         else w_all[jnp.asarray(part, jnp.int32)])
                    w_agg, w_loss = norm_weights(w)
                    partial = strategy.aggregate_init(params)
                    loss_acc = jnp.zeros((), jnp.float32)
                    tok_acc = jnp.zeros((), jnp.float32)
                    fed_shard = _shard_program(
                        _head_capacity(self.cfg, data, part))
                off = 0
                for si, width in enumerate(_shard_widths(m,
                                                         plan.cohort_shard)):
                    ids = part[off:off + width]
                    # dispatch span = shard materialization (train.stack)
                    # + the async jit dispatch (train.launch); device work
                    # may still be in flight when it closes, and the round
                    # waits for it in train.wait
                    with _obs_span("train.dispatch", cat="train", round=t,
                                   shard=si, width=width):
                        with _obs_span("train.stack", cat="train") as sp:
                            bsub = _stack_shard(data, ids, max_steps)
                            if sp is not _NULL_SPAN:
                                sp.set(**_footprint(bsub))
                            if windows is not None:
                                fmasks = jnp.stack([
                                    jnp.asarray(ffd.window_mask(
                                        n_units, windows[t][k]),
                                        jnp.float32) for k in ids])
                            else:
                                fmasks = jnp.zeros((len(ids), n_units),
                                                   jnp.float32)
                        args = (params, base, partial, loss_acc, tok_acc,
                                bsub, fmasks, w_agg[off:off + width],
                                w_loss[off:off + width])
                        if self.shard_args is None:
                            self.shard_program = fed_shard
                            self.shard_args = jax.tree.map(
                                lambda x: jax.ShapeDtypeStruct(x.shape,
                                                               x.dtype), args)
                        with _obs_span("train.launch", cat="train"):
                            partial, loss_acc, tok_acc = fed_shard(*args)
                    off += width
                with _obs_span("train.combine", cat="train", round=t,
                               clients=m):
                    params, state = _combine_for(m)(params, partial, state)
                loss, toks = loss_acc, tok_acc
                with _obs_span("train.wait", cat="train", round=t):
                    # async dispatch would under-time the round
                    jax.block_until_ready(loss)
                dt = time.perf_counter() - t0
            with _obs_span("train.account", cat="train", round=t):
                toks = float(toks)
                c_up, nbytes = self._client_upload_bytes(params, part,
                                                         windows, n_units, t)
                # rectangular schedule: every participant runs max_steps
                # steps (short clients cycle their data), so the ledger
                # multiplies the single analyzed program by steps x
                # participants
                n_steps = max_steps * len(part)
                down = strategy.download_bytes(params, len(part))
                rr = RoundResult(
                    t, float(loss), dt, windows[t] if windows else None,
                    upload_bytes=nbytes,
                    tokens=toks, tokens_per_s=toks / max(dt, 1e-9),
                    clients=part,
                    flops_estimate=(step_cost.flops * n_steps
                                    if step_cost else 0.0),
                    hbm_bytes_estimate=(step_cost.hbm_bytes * n_steps
                                        if step_cost else 0.0),
                    comm_bytes=(down + nbytes
                                + int(step_cost.collective_bytes * n_steps
                                      if step_cost else 0)),
                    download_bytes=down,
                    client_steps=[max_steps] * len(part),
                    client_step_flops=([step_cost.flops] * len(part)
                                       if step_cost else None),
                    client_step_hbm=([step_cost.hbm_bytes] * len(part)
                                     if step_cost else None),
                    client_upload_bytes=c_up)
                if fleet is not None:
                    from repro.sim.clock import sync_round_s
                    rr.sim_round_s = sync_round_s(rr, fleet,
                                                  overlap=plan.overlap)
                if plan.eval_fn is not None:
                    rr.eval_loss = float(plan.eval_fn(
                        space.merge(base, params) if peft else params))
                history.append(rr)
                _record_round_metrics(rr)
                self._checkpoint(t, {"base": base, "peft": params} if peft
                                 else params, state, rng, history, windows,
                                 n_units)
        return (space.merge(base, params) if peft else params), history


# process-wide program cache: one compiled step per distinct
# (config, optimizer, strategy, frozen pattern, impl) — rotation reuses at
# most N programs, and repeated sessions (benchmarks, resumed runs) pay zero
# recompiles.
_STEP_CACHE: Dict[Any, Callable] = {}


def make_fed_round_program(cfg, optimizer, *, impl: str = "xla"):
    """ONE federated round as a single jit-able program for the production
    mesh: every client runs its local epoch simultaneously (client dim
    sharded over the ``pod`` axis via FED_RULES), then FedAvg aggregates with
    one weighted all-reduce over clients — cross-pod DCN traffic, exactly the
    WAN aggregation the paper's Flower server performs.

    fed_round(stacked_params (K,...), stacked_opt, batches (K,steps,B,S...),
              fmasks (K, n_units), sizes (K,)) ->
        (new stacked params, per-client losses)
    FFDAPT runs in masked mode here (traced per-client windows)."""
    step = make_masked_train_step(cfg, optimizer, impl=impl)

    def fed_round(stacked_params, stacked_opt, batches, fmasks, sizes):
        K = jax.tree.leaves(stacked_params)[0].shape[0]

        def client_epoch(p, o, bs, fm):
            def one(carry, b):
                p_, o_ = carry
                p_, o_, m = step(p_, o_, b, fm)
                return (p_, o_), m["loss"]
            (p, o), losses = jax.lax.scan(one, (p, o), bs)
            return p, jnp.mean(losses)

        p_k, losses = jax.vmap(client_epoch)(stacked_params, stacked_opt,
                                             batches, fmasks)
        new_global = fedavg_stacked(p_k, sizes)
        return broadcast_clients(new_global, K), losses

    return fed_round
