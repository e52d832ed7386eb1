"""Federated DAPT training driver (the paper's Stage-2 pipeline, end to end).

Runs FDAPT / FFDAPT on the synthetic biomedical corpus with any arch from the
zoo.  It defaults to the reduced config, which the CPU test runs use;
``--full-config`` runs the published widths, as on one TPU chip
(``chip_smoke.py`` drives this module's ``build`` at DistilBERT's widths).

    PYTHONPATH=src python -m repro.launch.train \
        --arch distilbert-mlm --clients 8 --skew length --rounds 15 --ffdapt \
        --strategy fedprox --compress topk --participation 0.5
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro import optim
from repro.checkpoint import latest_step, tree_digest
from repro.configs import get_config
from repro.core.ffdapt import FFDAPTConfig
from repro.core.noniid import make_client_datasets
from repro.core.rounds import FedSession, RoundPlan
from repro.core.strategy import COMPRESSORS, STRATEGIES, make_strategy
from repro.data.corpus import generate_corpus
from repro.launch.cache import use_compile_cache
from repro.sim import FLEETS, make_fleet
from repro.models.model import init_model
from repro.models.steps import make_eval_step
from repro.nn import param as P


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="distilbert-mlm")
    ap.add_argument("--clients", type=int, default=2,
                    help="client population size; with --client-pool this "
                         "can go to 100k-1M (clients are virtual and only "
                         "sampled cohorts materialize data)")
    ap.add_argument("--client-pool", type=int, default=0,
                    help="mega-cohort mode: back --clients VIRTUAL clients "
                         "with this many lazily-built data shards (client k "
                         "trains shard k %% pool); 0 = materialize every "
                         "client's batches up front")
    ap.add_argument("--cohort-shard", type=int, default=0,
                    help="parallel engine: process the sampled cohort in "
                         "shards of this many clients (O(shard) live "
                         "memory; bitwise-identical to the full-width "
                         "round at any value); 0 = one full-cohort shard")
    ap.add_argument("--skew", default="iid",
                    choices=("iid", "quantity", "length", "vocab"))
    ap.add_argument("--rounds", type=int, default=15)
    ap.add_argument("--ffdapt", action="store_true")
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--epsilon", type=int, default=0)
    ap.add_argument("--param-space", default="",
                    choices=["", "full", "frozen_window", "lora", "adapter"],
                    help="trainable subspace (repro.peft): lora/adapter "
                         "train+ship only a low-rank bank (orders of "
                         "magnitude less upload); frozen_window names the "
                         "--ffdapt masking explicitly; default: implicit")
    ap.add_argument("--lora-rank", type=int, default=4,
                    help="LoRA rank r (--param-space lora)")
    ap.add_argument("--lora-alpha", type=float, default=0.0,
                    help="LoRA merge scale alpha (0 = alpha=r, scale 1)")
    ap.add_argument("--adapter-dim", type=int, default=8,
                    help="adapter bottleneck (--param-space adapter)")
    ap.add_argument("--peft-targets", default="attn,mlp",
                    help="comma list of projection groups to adapt")
    ap.add_argument("--engine", default="sequential",
                    choices=("sequential", "parallel"))
    ap.add_argument("--strategy", default="fedavg", choices=STRATEGIES)
    ap.add_argument("--compress", default="none", choices=COMPRESSORS,
                    help="client-upload delta compression")
    ap.add_argument("--mu", type=float, default=0.01,
                    help="FedProx proximal coefficient")
    ap.add_argument("--server-beta", type=float, default=0.9,
                    help="FedAvgM server momentum")
    ap.add_argument("--topk-frac", type=float, default=0.1,
                    help="fraction of delta entries kept by --compress topk")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled each round")
    ap.add_argument("--fleet", default="",
                    help="simulate wall-clock on a named device fleet "
                         f"(one of {FLEETS}); empty = no simulation")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="with --fleet: also simulate deadline-based "
                         "over-selection (seconds per round)")
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="with --fleet: also simulate FedBuff-style async "
                         "aggregation with this buffer size")
    ap.add_argument("--async-alpha", type=float, default=0.5,
                    help="staleness discount exponent for --strategy "
                         "asyncfedavg / the async simulation report")
    ap.add_argument("--sim-seed", type=int, default=0,
                    help="seed for the fleet's availability process")
    ap.add_argument("--overlap", action="store_true",
                    help="with --fleet: pipelined clock (download/compute "
                         "and compute/upload overlap; only latencies stay "
                         "serial) instead of the sequential phase sum")
    ap.add_argument("--calibrated", action="store_true",
                    help="with --fleet: use the measurement-calibrated "
                         "device registry (repro.sim.calibrate, anchored "
                         "to the paper's 2x RTX 2080 Ti datapoint) instead "
                         "of datasheet presets")
    ap.add_argument("--docs", type=int, default=240)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=5e-5)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (not reduced) arch config")
    ap.add_argument("--max-steps-per-round", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="",
                    help="crash-safe round checkpoints: the session writes "
                         "the full run state (params + server state + RNG "
                         "+ FFDAPT pointer + history) here")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="with --ckpt-dir: checkpoint every N completed "
                         "rounds (the final round always checkpoints)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir "
                         "(bitwise identical to the uninterrupted run); "
                         "starts fresh when the directory is empty")
    ap.add_argument("--stop-after", type=int, default=0,
                    help="simulated preemption: halt after this many "
                         "completed rounds (a checkpoint is written first "
                         "when --ckpt-dir is set); the resume smoke uses it")
    ap.add_argument("--ledger-out", default="",
                    help="write the deterministic run ledger (per-round "
                         "history minus wall-clock fields + a params "
                         "sha256) to this JSON file — two bitwise-equal "
                         "runs produce byte-equal files; wall-clock fields "
                         "go to a <ledger>.timing.json sidecar instead")
    ap.add_argument("--trace-out", default="",
                    help="enable the span tracer and write a Chrome "
                         "trace-event JSON here (load in Perfetto / "
                         "chrome://tracing): train.round with prepare, "
                         "dispatch (stack, launch), combine and wait, then "
                         "train.account and checkpoint spans, each with "
                         "its parent and round; compile events, and — "
                         "with --fleet — the simulated timeline "
                         "side-by-side")
    ap.add_argument("--metrics-out", default="",
                    help="write the process-wide metrics registry "
                         "(counters/gauges/histograms) as JSONL here")
    ap.add_argument("--drift-out", default="",
                    help="run the measured-vs-predicted drift monitor over "
                         "the round history and write its ratio ledger "
                         "(JSON) here; predictions come from --fleet when "
                         "set, else the recorded sim_round_s")
    ap.add_argument("--drift-warn", type=float, default=4.0,
                    help="drift warn threshold: a round warns when "
                         "measured/predicted falls outside [1/W, W]")
    ap.add_argument("--jax-profile", default="",
                    help="also capture a jax.profiler device trace into "
                         "this directory (TensorBoard/xprof format); turns "
                         "the span tracer on, so the train.* spans appear "
                         "on the trace's host plane next to the device "
                         "ops, which carry the programs' named scopes")
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")
    if args.param_space in ("lora", "adapter") and args.ffdapt:
        ap.error(f"--param-space {args.param_space} does not compose "
                 f"with --ffdapt (both claim the update mask)")
    if args.param_space == "frozen_window" and not args.ffdapt:
        ap.error("--param-space frozen_window names the --ffdapt "
                 "schedule — pass --ffdapt (with --gamma/--epsilon) too")
    return args


@dataclasses.dataclass
class Job:
    """Everything ``main`` builds from its arguments before the session
    runs: ``FedSession(job.cfg, job.optimizer, job.plan).run(job.params,
    job.batches, resume=args.resume)`` is the run."""

    cfg: Any
    optimizer: Any
    plan: RoundPlan
    params: Any
    batches: Any
    ds: Optional[Dict[str, Any]]        # None under --client-pool
    held_docs: List[Any]


def build(args: argparse.Namespace) -> Job:
    """Config, synthetic client data (from ``--seed``), initial params and
    the round plan, exactly as the CLI runs them."""
    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()

    from repro.data.corpus import split_holdout
    docs, held_docs = split_holdout(generate_corpus(args.docs, seed=args.seed))
    ds = None
    if args.client_pool:
        from repro.core.noniid import make_client_pool
        batches = make_client_pool(docs, cfg, n_clients=args.clients,
                                   pool=args.client_pool, skew=args.skew,
                                   batch=args.batch_size, seq=args.seq_len,
                                   seed=args.seed,
                                   limit=args.max_steps_per_round)
        sizes = batches.sizes
    else:
        ds = make_client_datasets(docs, cfg, k=args.clients, skew=args.skew,
                                  batch=args.batch_size, seq=args.seq_len,
                                  seed=args.seed)
        batches = ds["batches"]
        if args.max_steps_per_round:
            batches = [b[:args.max_steps_per_round] for b in batches]
        sizes = ds["sizes"]

    params = P.unbox(init_model(jax.random.PRNGKey(args.seed), cfg))

    strategy = make_strategy(args.strategy, compress=args.compress,
                             mu=args.mu, beta=args.server_beta,
                             frac=args.topk_frac, alpha=args.async_alpha)
    pspace = None
    if args.param_space:
        from repro.peft import make_param_space
        pspace = make_param_space(
            args.param_space, rank=args.lora_rank, alpha=args.lora_alpha,
            adapter_dim=args.adapter_dim,
            targets=tuple(t for t in args.peft_targets.split(",") if t))
    plan = RoundPlan(n_rounds=args.rounds, engine=args.engine,
                     strategy=strategy,
                     cohort_shard=args.cohort_shard or None,
                     param_space=pspace,
                     ffdapt=FFDAPTConfig(epsilon=args.epsilon,
                                         gamma=args.gamma) if args.ffdapt
                     else None,
                     participation=args.participation, seed=args.seed,
                     client_sizes=sizes,
                     simulate=(make_fleet(args.fleet, args.clients,
                                          seed=args.seed,
                                          calibrated=args.calibrated)
                               if args.fleet else None),
                     overlap=args.overlap,
                     checkpoint_dir=args.ckpt_dir or None,
                     checkpoint_every=args.ckpt_every,
                     stop_after_round=args.stop_after or None,
                     # identity the session cannot introspect (optimizer
                     # closures, data pipeline) — a resume under different
                     # values raises instead of silently diverging
                     fingerprint_extra={
                         "arch": cfg.name, "lr": args.lr,
                         "batch": args.batch_size, "seq": args.seq_len,
                         "docs": args.docs, "skew": args.skew,
                         "max_steps": args.max_steps_per_round,
                         "client_pool": args.client_pool,
                         "fleet": args.fleet, "calibrated": args.calibrated,
                         "sim_seed": args.sim_seed})
    return Job(cfg, optim.adam(args.lr), plan, params, batches, ds,
               held_docs)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    use_compile_cache()

    from repro import obs
    if args.trace_out or args.jax_profile:
        obs.enable()
        obs.capture_compiles()

    job = build(args)
    cfg, plan, params, ds = job.cfg, job.plan, job.params, job.ds
    strategy = plan.strategy
    print(f"arch={cfg.name} ({cfg.arch_type}) layers={cfg.n_layers} "
          f"d={cfg.d_model} vocab={cfg.vocab_size}")
    if ds is None:
        print(f"client pool: {args.clients:,} virtual clients over "
              f"{args.client_pool} lazily-built data shards")
    else:
        print("per-client local steps:", [len(b) for b in job.batches])
        print("data skew sigmas:", json.dumps(
            {k: round(v["sigma"], 2) for k, v in ds["stats"].items()}))
    print(f"params: {sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params)):,}")
    if plan.param_space is not None:
        print(f"param space: {plan.param_space.to_json()}")
    shard_note = (f" cohort_shard={args.cohort_shard}"
                  if args.cohort_shard else "")
    print(f"strategy={strategy.name} engine={args.engine} "
          f"participation={args.participation}{shard_note}")
    if args.resume and args.ckpt_dir:
        at = latest_step(args.ckpt_dir)
        print("resume: "
              + (f"round checkpoint {at} found" if at is not None
                 else "no checkpoint on disk, starting fresh"))
    t0 = time.perf_counter()
    with obs.jax_profile(args.jax_profile or None):
        params, hist = FedSession(cfg, job.optimizer, plan).run(
            params, job.batches, resume=args.resume)
    wall = time.perf_counter() - t0

    for h in hist:
        w = f" windows={h.windows}" if h.windows else ""
        c = ""
        if h.clients is not None and len(h.clients) < args.clients:
            c = (f" clients={h.clients}" if len(h.clients) <= 32
                 else f" cohort={len(h.clients):,}")
        s = f"  sim {h.sim_round_s:7.1f}s" if args.fleet else ""
        print(f"round {h.round:3d}  loss {h.loss:7.4f}  {h.round_time_s:6.2f}s"
              f"{s}  up {h.upload_bytes / 2**20:7.1f}MB  "
              f"comm {h.comm_bytes / 2**20:7.1f}MB  "
              f"{h.flops_estimate / 1e9:8.2f} GFLOP  "
              f"{h.tokens_per_s:8.0f} tok/s{w}{c}")
    print(f"total {wall:.1f}s; mean round "
          f"{np.mean([h.round_time_s for h in hist]):.2f}s; upload "
          f"{sum(h.upload_bytes for h in hist) / 2**20:.1f}MB; comm "
          f"{sum(h.comm_bytes for h in hist) / 2**20:.1f}MB; compute "
          f"{sum(h.flops_estimate for h in hist) / 1e12:.3f} TFLOP (ledger)")

    if args.fleet:
        from repro.sim import ledger_lines, simulate
        fleet = plan.simulate
        cal = " (calibrated)" if args.calibrated else ""
        print(f"fleet {args.fleet}{cal}: {fleet.counts()}")
        reports = [simulate(hist, fleet, mode="sync", seed=args.sim_seed,
                            overlap=args.overlap)]
        if args.deadline > 0:
            reports.append(simulate(hist, fleet, mode="deadline",
                                    deadline_s=args.deadline,
                                    seed=args.sim_seed,
                                    overlap=args.overlap))
        if args.async_buffer > 0:
            # thread the partition's FULL per-epoch step schedule into the
            # async replay (not the possibly --max-steps-per-round-truncated
            # training schedule): staleness then correlates with client data
            # volume (quantity skew) even on the parallel engine's padded
            # ledger
            reports.append(simulate(hist, fleet, mode="async",
                                    buffer_size=args.async_buffer,
                                    seed=args.sim_seed,
                                    overlap=args.overlap,
                                    client_steps=(ds["steps"] if ds
                                                  else None)))
        for rep in reports:
            print("\n".join(ledger_lines(rep)))
        if args.trace_out:
            # replay the sync report onto the tracer: the simulated
            # timeline lands in its own Perfetto process lane next to the
            # measured rounds
            from repro.sim import emit_spans
            n = emit_spans(reports[0])
            print(f"trace: {n} synthetic sim spans emitted")

    if args.ledger_out:
        # the deterministic ledger: everything a resumed run must reproduce
        # bitwise (wall-clock fields excluded — they measure the host, not
        # the math).  scripts/resume_smoke.sh diffs two of these.
        wall_fields = {"round_time_s", "tokens_per_s"}
        rows = [{k: v for k, v in h.to_json().items()
                 if k not in wall_fields} for h in hist]
        with open(args.ledger_out, "w") as f:
            json.dump({"params_sha256": tree_digest(params), "rounds": rows},
                      f, indent=1, sort_keys=True)
        print("ledger:", args.ledger_out)
        # the stripped wall-clock fields go to a sidecar: the main ledger
        # stays byte-equal across bitwise-equal runs, the timing lives on
        import os
        base, _ = os.path.splitext(args.ledger_out)
        timing_path = base + ".timing.json"
        with open(timing_path, "w") as f:
            json.dump({"total_wall_s": wall,
                       "rounds": [{"round": h.round,
                                   "round_time_s": h.round_time_s,
                                   "tokens_per_s": h.tokens_per_s}
                                  for h in hist]},
                      f, indent=1, sort_keys=True)
        print("timing sidecar:", timing_path)

    stopped_early = args.stop_after and args.stop_after < args.rounds
    if not stopped_early:
        eval_step = jax.jit(make_eval_step(cfg))
        heldout = make_client_datasets(job.held_docs,
                                       cfg, k=1, batch=args.batch_size,
                                       seq=args.seq_len)["batches"][0][:4]
        losses = [float(eval_step(params, b)["loss"]) for b in heldout]
        print(f"held-out eval loss: {np.mean(losses):.4f}")

    if args.ckpt_dir:
        at = latest_step(args.ckpt_dir)
        print(f"checkpoints: {args.ckpt_dir} (latest round {at})")

    if args.drift_out:
        mon = obs.from_history(
            hist, fleet=plan.simulate, overlap=args.overlap,
            warn_ratio=args.drift_warn,
            tracer=obs.get_tracer() if args.trace_out else None)
        print("\n".join(mon.lines()))
        print("drift ledger:", mon.export(args.drift_out))
    if args.trace_out:
        print("chrome trace:", obs.get_tracer().export(args.trace_out))
    if args.metrics_out:
        print("metrics:", obs.registry().export_jsonl(args.metrics_out))


if __name__ == "__main__":
    main()
