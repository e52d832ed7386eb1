"""Serving CLI over ``repro.serve``: continuous batching by default, the
static-batch baseline behind ``--static``.

Serves either fresh-initialized params (default, a shape/perf exercise) or a
real FDAPT checkpoint::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
        --ckpt-dir runs/fed/checkpoints            # serve the global model
    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --static \
        --bench-out BENCH_static.json              # baseline + metrics dump

Traffic is an open-loop Poisson process (``--rate`` requests/s, seeded):
arrivals never wait for the server, so queueing shows up in the latency
percentiles instead of being hidden by closed-loop backpressure.  Stops per
request on ``--tokens`` (max new tokens) or ``--eos-id``.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import use_compile_cache
from repro.models.model import init_model
from repro.nn import param as P
from repro.serve import (DecodeEngine, EngineConfig, PoissonArrivals,
                         load_serving_params, run_static, synthetic_requests,
                         write_bench)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve params from a repro.checkpoint archive "
                         "(a FedSession round checkpoint or bare snapshot)")
    ap.add_argument("--ckpt-step", type=int, default=None,
                    help="checkpoint step (default: newest in --ckpt-dir)")
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent decode slots (static mode: batch size)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32,
                    help="max new tokens per request (incl. the "
                         "prefill-produced token)")
    ap.add_argument("--min-tokens", type=int, default=None,
                    help="per-request stop lengths drawn uniform "
                         "[min,--tokens] (default: all equal --tokens)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, requests/s (0 = all at t=0)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--impl", default="xla", choices=("xla", "pallas"))
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window variant (ring KV cache)")
    ap.add_argument("--static", action="store_true",
                    help="static-batch baseline instead of the engine")
    ap.add_argument("--bench-out", default=None,
                    help="write the metrics summary as JSON")
    ap.add_argument("--trace-out", default="",
                    help="enable the span tracer and write a Chrome "
                         "trace-event JSON here (admit/decode/evict spans "
                         "+ compile events; load in Perfetto)")
    ap.add_argument("--metrics-out", default="",
                    help="write the process-wide metrics registry as JSONL")
    ap.add_argument("--drift-out", default="",
                    help="join the measured mean decode-step seconds "
                         "against a --drift-device roofline prediction and "
                         "write the ratio ledger (JSON) here")
    ap.add_argument("--drift-device", default="rtx2080ti",
                    help="device preset pricing the decode step for "
                         "--drift-out (see repro.sim.fleet.PRESETS)")
    ap.add_argument("--drift-warn", type=float, default=4.0,
                    help="drift warn threshold: warn when "
                         "measured/predicted falls outside [1/W, W]")
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    from repro import obs
    if args.trace_out:
        obs.enable()
        obs.capture_compiles()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = cfg.reduced()
    if args.window:
        cfg = cfg.replace(sliding_window=args.window)
    if cfg.arch_type == "mlm":
        raise SystemExit("mlm is encoder-only: no decode step (see DESIGN.md)")

    if args.ckpt_dir:
        params, step, _ = load_serving_params(args.ckpt_dir, cfg,
                                              args.ckpt_step)
        print(f"params: checkpoint step {step} from {args.ckpt_dir}")
    else:
        params = P.unbox(init_model(jax.random.PRNGKey(args.seed), cfg))
        print("params: fresh init (pass --ckpt-dir to serve a trained model)")

    cache_len = args.prompt_len + args.tokens
    rng = np.random.default_rng(args.seed)
    requests = synthetic_requests(
        cfg, args.requests, prompt_len=args.prompt_len, rng=rng,
        max_new_tokens=args.tokens, min_new_tokens=args.min_tokens,
        eos_id=args.eos_id, temperature=args.temperature, seed=args.seed)
    requests = PoissonArrivals(args.rate, seed=args.seed).assign(requests)

    mode = "static" if args.static else "continuous"
    if args.static:
        outputs, summary = run_static(cfg, params, requests,
                                      n_slots=args.slots,
                                      cache_len=cache_len, impl=args.impl)
    else:
        engine = DecodeEngine(cfg, params, EngineConfig(
            n_slots=args.slots, cache_len=cache_len, impl=args.impl))
        outputs, summary = engine.run(requests)
        print(f"compiled programs: decode={engine.decode_cache_size()} "
              f"prefill={engine.prefill_cache_size()}")

    print(f"{cfg.name} ({cfg.arch_type}) {mode}: "
          f"{summary['n_requests']} requests, "
          f"{summary['generated_tokens']} tokens, "
          f"{summary['tokens_per_s']:.1f} tok/s, "
          f"TTFT p50 {summary['ttft_s']['p50']*1e3:.1f} ms, "
          f"latency p99 {summary['latency_s']['p99']*1e3:.1f} ms, "
          f"slot occupancy {summary['slot_occupancy']:.2f}")
    rid0 = min(outputs)
    print(f"request {rid0} tokens: {outputs[rid0][:16]}")
    if args.bench_out:
        write_bench(args.bench_out, {
            "benchmark": "serve", "arch": cfg.name, "mode": mode,
            "workload": {"requests": args.requests,
                         "prompt_len": args.prompt_len,
                         "max_new_tokens": args.tokens,
                         "rate_rps": args.rate, "seed": args.seed},
            "engine": {"n_slots": args.slots, "cache_len": cache_len,
                       "impl": args.impl},
            "metrics": summary,
        })
        print(f"wrote {args.bench_out}")
    else:
        print(json.dumps(summary, indent=2, sort_keys=True))

    if args.drift_out:
        from repro.sim.clock import device_roofline_s
        from repro.sim.fleet import PRESETS
        from repro.telemetry import decode_step_cost
        dev = PRESETS[args.drift_device]
        cost = decode_step_cost(cfg, args.slots, cache_len, impl=args.impl)
        terms = device_roofline_s(cost.flops, cost.hbm_bytes,
                                  cost.collective_bytes, dev)
        predicted = max(terms["compute"], terms["memory"]) + terms["collective"]
        # measured per-step seconds: the tracer's spans when tracing, else
        # the run's wall seconds over its decode steps
        spans = [e.dur_us / 1e6 for e in obs.get_tracer().events()
                 if e.name == "serve.decode_step"]
        if spans:
            measured = sum(spans) / len(spans)
        else:
            measured = (summary["wall_s"]
                        / max(summary["n_decode_steps"], 1))
        mon = obs.DriftMonitor(warn_ratio=args.drift_warn)
        mon.observe(0, "decode_step", measured, predicted,
                    source=f"device:{dev.name}")
        print("\n".join(mon.lines()))
        print("drift ledger:", mon.export(args.drift_out))
    if args.trace_out:
        print("chrome trace:", obs.get_tracer().export(args.trace_out))
    if args.metrics_out:
        print("metrics:", obs.registry().export_jsonl(args.metrics_out))


if __name__ == "__main__":
    main()
