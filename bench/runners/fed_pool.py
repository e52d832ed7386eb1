"""Cross-device federated pre-training rounds: a ``ClientPool`` of many
virtual clients, of which each round samples a few (``participation``),
through ``FedSession.run`` on the cohort-scan engine.  Everything else is
``fed_round``'s run (set-up, window, tap, trace, comparison), which this
runner reuses with parts of its own:

* ``build``: the pool.  A client's batches are made on its first access,
  from (seed, client id) by the same generator ``fed_round``'s traffic
  uses (``harness.data``), so only sampled clients ever exist; the engine
  reads each round's cohort through the pool, which is the round engine's
  host path (sampling, lazy client data, stacking across many shards).
* ``session``: a ``FedSession`` that records the clients each round
  sampled (``RoundResult.clients``) and, through ``check_cohorts``, holds
  that sampling to the traffic: each round drew ``clients`` distinct ids
  of the population, and no two rounds of the run, across its ``run``
  calls, drew the same cohort.  Each ``run`` call samples from the plan's
  seed anew, so the session gives every call a seed of its own (derived
  from the run's seed and the call's number): the window's rounds draw
  cohorts the first call did not, and make their clients' data as any
  other round does, instead of finding it built.
* ``reference``: the plain reference repeats the compared rounds on the
  clients the engine sampled in them, one round at a time
  (``harness.fedref.run_rounds`` chained), each client's batches made anew
  from (seed, client id) and every client weighted alike (each holds
  ``local_steps`` batches).

* ``first_rounds``: the window is sized from the first call's last round
  as the engine timed it (``RoundResult.round_time_s``), not from the gap
  between two taps, which also holds the tap's copy of the weights to the
  host and ended the window after 12-17 of its 20 seconds on a v5e host.

Traffic keys beyond ``fed_round``'s: ``population`` (virtual clients) and
``participation``; ``clients`` is the cohort a round samples.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict, List, Optional

import numpy as np

from harness import data, fedref
from harness.load import load_module
from repro.core.rounds import FedSession, RoundPlan
from repro.data.partition import ClientPool

base = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "fed_round.py"), "runner_fed_pool_base")
_round_build = base.build


def client_batches(traffic: Dict, model: Dict, seed: int,
                   k: int) -> List[Dict[str, np.ndarray]]:
    """Client ``k``'s local batches: what ``harness.data.client_batches``
    gives its ``k``-th client, made for that client alone."""
    steps, b, s = traffic["local_steps"], traffic["batch"], traffic["seq"]
    vocab = model["vocab_size"]
    if model["objective"] != "mlm" or traffic.get("skew", "iid") != "iid":
        raise ValueError("fed_pool makes iid masked-LM clients only")
    ids = data._stream(data._rng(seed, 1, k), traffic["documents"], vocab,
                       steps * b * s).reshape(steps, b, s)
    mrng = data._rng(seed, 2, k)
    return [data._mlm(mrng, step, vocab, model["mlm_mask_rate"])
            for step in ids]


class LazyPool(ClientPool):
    """``ClientPool`` with one builder per virtual client and every
    client's local epoch known to be ``steps`` long, so that nothing is
    built to learn it."""

    def __init__(self, n_clients: int, builders, steps: int):
        super().__init__(n_clients, builders, [steps] * n_clients)
        self._steps = int(steps)

    @property
    def max_steps(self) -> int:
        return self._steps


@dataclasses.dataclass
class PoolJob(base.Job):
    cohorts: List[List[int]] = dataclasses.field(default_factory=list)


def build(config: Dict[str, Any], traffic: Dict[str, Any],
          seed: int) -> PoolJob:
    if config.get("peft"):
        raise ValueError("fed_pool runs full-parameter FDAPT only")
    one = _round_build(config, dict(traffic, clients=1), seed)
    pool = LazyPool(traffic["population"], [
        functools.partial(client_batches, traffic, config, seed, k)
        for k in range(traffic["population"])], traffic["local_steps"])
    k, s = traffic["clients"], traffic["local_steps"]
    return PoolJob(cfg=one.cfg, config=config, traffic=traffic, seed=seed,
                   space=None, batches=pool, make_base=one.make_base,
                   make_bank=None,
                   tokens_per_round=k * s * traffic["batch"] * traffic["seq"],
                   loss_fraction=one.loss_fraction)


def check_cohorts(cohorts: List[List[int]], traffic: Dict[str, Any]):
    """Raises unless every round sampled ``clients`` distinct ids in
    [0, ``population``) and no two rounds sampled the same cohort (two equal
    draws of 32 of 10,000 clients are as good as impossible)."""
    n, k = traffic["population"], traffic["clients"]
    for t, ids in enumerate(cohorts):
        if len(ids) != k or len(set(ids)) != k:
            raise RuntimeError(f"round {t} sampled {len(set(ids))} distinct "
                               f"of {len(ids)} clients, the traffic asks "
                               f"for {k}")
        if min(ids) < 0 or max(ids) >= n:
            raise RuntimeError(f"round {t} sampled a client outside "
                               f"[0, {n}): {sorted(ids)}")
    if len({frozenset(ids) for ids in cohorts}) != len(cohorts):
        raise RuntimeError("two rounds sampled the same cohort")


def call_seed(seed: int, call: int) -> int:
    """The sampling seed of a run's ``call``-th ``run`` call: the run's
    own seed for the first, one derived from it for each later call."""
    if call == 0:
        return seed
    return int(np.random.SeedSequence([int(seed), 3, call]).generate_state(
        1)[0])


class RecordingSession(FedSession):
    """``FedSession`` that samples each ``run`` call's cohorts from a seed
    of its own (``call_seed``), appends the sampled clients to ``cohorts``
    in round order across calls, and checks them all (``check_cohorts``)."""

    def __init__(self, cfg, optimizer, plan, cohorts: List[List[int]],
                 traffic: Dict[str, Any]):
        super().__init__(cfg, optimizer, plan)
        self.cohorts, self.traffic = cohorts, traffic
        self.seed, self.calls = plan.seed, 0

    def run(self, params, client_batches, **kw):
        self.plan = dataclasses.replace(
            self.plan, seed=call_seed(self.seed, self.calls))
        self.calls += 1
        out, hist = super().run(params, client_batches, **kw)
        self.cohorts.extend(list(h.clients) for h in hist)
        check_cohorts(self.cohorts, self.traffic)
        self.round_times = [h.round_time_s for h in hist]
        return out, hist


def session(job: PoolJob, tap):
    from repro import optim
    plan = RoundPlan(n_rounds=job.traffic["compare_rounds"] - 1,
                     engine="parallel", strategy=base.TappedFedAvg(tap=tap),
                     cohort_shard=job.traffic["cohort_shard"],
                     participation=job.traffic["participation"],
                     seed=job.seed)
    return RecordingSession(job.cfg, optim.adam(job.traffic["lr"]), plan,
                            job.cohorts, job.traffic)


def reference(job: PoolJob, bench_dir: str, mode: str = "f32",
              fault: Optional[str] = None):
    """The compared rounds through the plain reference, from the seed, each
    on the cohort the engine sampled for it."""
    from harness.precision import Num
    n = job.traffic["compare_rounds"]
    if len(job.cohorts) < n:
        raise RuntimeError(f"{len(job.cohorts)} cohorts recorded, "
                           f"{n} rounds compared")
    name = job.config["name"]
    mod = load_module(os.path.join(bench_dir, "reference", f"{name}.py"),
                      "reference_" + name.replace("-", "_"))
    loss = mod.make_loss(job.config, job.config.get("peft"))
    start, _ = job.weights()
    steps = job.traffic["local_steps"]
    p, losses, grads, first = Num(mode).store(start), [], None, None
    for ids in job.cohorts[:n]:
        got = fedref.run_rounds(loss, p, None,
                                [client_batches(job.traffic, job.config,
                                                job.seed, k) for k in ids],
                                [steps] * len(ids), 1,
                                job.traffic["lr"], Num(mode), fault=fault)
        losses += got.losses
        grads = got.grad_norms if grads is None else {
            k: v + got.grad_norms[k] for k, v in grads.items()}
        if first is None:
            first = got
        p = got.final
    return fedref.Readings(losses=losses, delta_r1=first.delta_r1,
                           delta_end=fedref.delta_norms(
                               p, Num(mode).store(start)),
                           grad_norms=grads, at_r1=first.at_r1, final=p)


_first_rounds = base.first_rounds


def first_rounds(job: PoolJob, sess, tap, base_params, bank):
    """``fed_round``'s first rounds, the warm round read from the engine's
    time of the call's last round (see the module's docstring)."""
    readings, last, _ = _first_rounds(job, sess, tap, base_params, bank)
    return readings, last, sess.round_times[-1]


Tap, window = base.Tap, base.window
base.build, base.session, base.reference = build, session, reference
base.first_rounds = first_rounds
run = base.run
