import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, report memory / FLOPs / collective schedule -> roofline terms.

This is the proof that the distribution config is coherent without real
hardware: a sharding mismatch, an unsupported collective, or a spec that
cannot partition fails HERE.

Usage:
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all          # the full 40-pair matrix
Writes one JSON artifact per run under benchmarks/results/dryrun/.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import optim, telemetry
from repro.configs import ASSIGNED, get_config
from repro.configs.shapes import SHAPES, input_specs, shape_config
from repro.launch import mesh as meshlib
from repro.models.model import init_model
from repro.models.steps import (abstract_train_state, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.nn import param as P
from repro.sharding.ctx import activation_sharding
from repro.sharding.rules import (DECODE_RULES, DEFAULT_RULES,
                                  LONG_CONTEXT_RULES, OPT_RULES,
                                  tree_shardings)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")


@dataclasses.dataclass
class Knobs:
    """Per-run tunables the §Perf hillclimb iterates on."""
    microbatches: int = 1
    opt_state_dtype: Optional[str] = None     # None -> param dtype
    remat: Optional[bool] = None              # None -> config default
    opt_rules: bool = False                   # OPT_RULES (context-parallel attn)
    impl: str = "xla"                         # "chunked": blockwise RWKV scans
    frozen_frac: float = 0.0                  # FFDAPT window fraction (train)
    moe_groups: int = 0                       # local (per-group) MoE dispatch


def count_params_split(cfg):
    """(total, moe_expert) param counts from abstract init — no allocation."""
    boxed = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    total = P.count_params(boxed)
    moe = 0
    layers = boxed.get("layers")
    if isinstance(layers, dict) and "moe" in layers:
        for name in ("wi_gate", "wi_up", "wo"):
            v = layers["moe"][name].value
            n = 1
            for d in v.shape:
                n *= d
            moe += n
    return total, moe


def model_flops(cfg, spec) -> float:
    """Analytic MODEL_FLOPS: 6*N*D for training (N = active params for MoE),
    2*N*D for inference shapes.  Global (all chips)."""
    total, moe = count_params_split(cfg)
    active = total - moe + (moe * cfg.top_k // max(cfg.n_experts, 1))
    if spec.kind == "train":
        d_tokens = spec.global_batch * spec.seq_len
        return 6.0 * active * d_tokens
    if spec.kind == "prefill":
        return 2.0 * active * spec.global_batch * spec.seq_len
    return 2.0 * active * spec.global_batch          # decode: one token




def lower_pair(arch: str, shape: str, *, multi_pod: bool = False,
               knobs: Knobs = Knobs()) -> Dict[str, Any]:
    """Lower+compile one (arch, shape) on the production mesh; return the
    roofline record."""
    spec = SHAPES[shape]
    cfg = shape_config(get_config(arch), shape)
    if knobs.remat is not None:
        cfg = cfg.replace(remat=knobs.remat)
    if knobs.moe_groups:
        cfg = cfg.replace(moe_local_dispatch=True)
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rules = {"long_500k": LONG_CONTEXT_RULES,
             "decode_32k": DECODE_RULES}.get(shape, DEFAULT_RULES)
    if knobs.opt_rules:
        rules = OPT_RULES

    ins = input_specs(cfg, shape)
    batch_sh = tree_shardings(ins["batch"], mesh, rules)
    batch_sds = P.unbox(ins["batch"])

    t0 = time.perf_counter()
    ctx = activation_sharding(mesh, rules)
    ctx.__enter__()
    if spec.kind == "train":
        sdt = jnp.dtype(knobs.opt_state_dtype) if knobs.opt_state_dtype else None
        optimizer = optim.adam(5e-5, state_dtype=sdt)
        params_b, opt_b = abstract_train_state(cfg, optimizer, boxed=True)
        p_sh = tree_shardings(params_b, mesh, rules)
        o_sh = tree_shardings(opt_b, mesh, rules)
        frozen = None
        if knobs.frozen_frac:
            from repro.models.model import n_freeze_units
            from repro.nn.stack import freeze_window_mask
            n = n_freeze_units(cfg)
            frozen = freeze_window_mask(n, (0, int(n * knobs.frozen_frac)))
        step = make_train_step(cfg, optimizer, microbatches=knobs.microbatches,
                               impl=knobs.impl, frozen=frozen)
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, batch_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(P.unbox(params_b), P.unbox(opt_b), batch_sds)
    elif spec.kind == "prefill":
        params_b = jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0))
        p_sh = tree_shardings(params_b, mesh, rules)
        from repro.models.model import cache_struct
        cache_b = cache_struct(cfg, spec.global_batch, spec.seq_len)
        # the filled cache is decode-layout: seq over "model" (kv heads
        # rarely divide it), or it costs 16x cache memory at 32k
        c_sh = tree_shardings(cache_b, mesh,
                              DECODE_RULES if not knobs.opt_rules else rules)
        step = make_prefill_step(cfg, spec.seq_len, impl=knobs.impl)
        jitted = jax.jit(step, in_shardings=(p_sh, batch_sh),
                         out_shardings=(None, c_sh))
        lowered = jitted.lower(P.unbox(params_b), batch_sds)
    else:  # decode
        params_b = jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0))
        p_sh = tree_shardings(params_b, mesh, rules)
        cache_b = ins["cache"]
        c_sh = tree_shardings(cache_b, mesh, rules)
        step = make_serve_step(cfg, impl=knobs.impl)
        jitted = jax.jit(step, in_shardings=(p_sh, batch_sh, c_sh),
                         out_shardings=(None, c_sh), donate_argnums=(2,))
        lowered = jitted.lower(P.unbox(params_b), batch_sds, P.unbox(cache_b))

    ctx.__exit__(None, None, None)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = telemetry.xla_cost(compiled)
    # scan-aware static analysis of the partitioned HLO (cost_analysis counts
    # a while body once; the analyzer multiplies by trip count)
    stats = telemetry.analyze(compiled.as_text())
    coll = {k: int(v) for k, v in stats.collective_bytes.items()}

    flops = float(stats.dot_flops)
    bytes_hbm = float(stats.hbm_bytes)
    coll_total = float(stats.collective_total)
    model_fl = model_flops(cfg, spec)

    record = {
        "arch": arch, "shape": shape, "kind": spec.kind,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "n_chips": n_chips,
        "knobs": dataclasses.asdict(knobs),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
            "peak_estimate_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)
                                       or (getattr(mem, "argument_size_in_bytes", 0)
                                           + getattr(mem, "output_size_in_bytes", 0)
                                           + getattr(mem, "temp_size_in_bytes", 0)
                                           - getattr(mem, "alias_size_in_bytes", 0))),
        },
        # analyzer terms are per-device for the partitioned program
        "flops_per_device": flops,
        "hbm_bytes_per_device": bytes_hbm,
        "collective_bytes_per_device": coll,
        "cost_analysis_flops_per_device": float(cost.get("flops", 0.0)),
        "model_flops_global": model_fl,
        # how much compiled compute is useful (remat/replication waste shows
        # up here): 6ND (or 2ND inference) / (per-device dots x chips)
        "model_vs_hlo_flops": model_fl / max(flops * n_chips, 1.0),
        "roofline_s": {
            "compute": flops / meshlib.PEAK_FLOPS_BF16,
            "memory": bytes_hbm / meshlib.HBM_BW,
            "collective": coll_total / meshlib.ICI_BW,
        },
    }
    r = record["roofline_s"]
    record["bottleneck"] = max(r, key=r.get)
    return record


def lower_fed_round(arch: str = "distilbert-mlm", *, clients: int = 2,
                    local_steps: int = 4, seq_len: int = 4096,
                    global_batch: int = 256) -> Dict[str, Any]:
    """Lower + compile ONE FFDAPT federated round on the 2-pod mesh: clients
    pinned to pods (FED_RULES), local epochs in parallel, FedAvg = the
    cross-pod weighted all-reduce.  The production form of the paper's
    technique."""
    from repro.core.rounds import make_fed_round_program
    from repro.models.model import n_freeze_units
    from repro.sharding.rules import FED_RULES

    cfg = get_config(arch)
    mesh = meshlib.make_production_mesh(multi_pod=True)
    optimizer = optim.adam(5e-5)
    K = clients
    B_local = global_batch // K
    n_units = n_freeze_units(cfg)

    def full(key):
        p = init_model(key, cfg)
        return p, optimizer.init(p)

    pb, ob = jax.eval_shape(full, jax.random.PRNGKey(0))

    def stack_boxed(tree):
        return jax.tree.map(
            lambda b: P.Box(jax.ShapeDtypeStruct((K,) + b.value.shape,
                                                 b.value.dtype),
                            (P.CLIENT,) + tuple(b.axes)) if P.is_box(b)
            else jax.ShapeDtypeStruct((K,) + b.shape, b.dtype),
            tree, is_leaf=P.is_box)

    spb, sob = stack_boxed(pb), stack_boxed(ob)
    p_sh = tree_shardings(spb, mesh, FED_RULES)
    o_sh = tree_shardings(sob, mesh, FED_RULES)
    bshape = (K, local_steps, B_local, seq_len)
    bax = (P.CLIENT, None, P.BATCH, P.SEQ)
    batch = {
        "tokens": P.Box(jax.ShapeDtypeStruct(bshape, jnp.int32), bax),
        "targets": P.Box(jax.ShapeDtypeStruct(bshape, jnp.int32), bax),
        "loss_mask": P.Box(jax.ShapeDtypeStruct(bshape, jnp.float32), bax),
    }
    b_sh = tree_shardings(batch, mesh, FED_RULES)
    fmasks = jax.ShapeDtypeStruct((K, n_units), jnp.float32)
    sizes = jax.ShapeDtypeStruct((K,), jnp.float32)

    prog = make_fed_round_program(cfg, optimizer)
    t0 = time.perf_counter()
    with activation_sharding(mesh, FED_RULES):
        lowered = jax.jit(prog, in_shardings=(p_sh, o_sh, b_sh, None, None),
                          out_shardings=(p_sh, None),
                          donate_argnums=(0, 1)).lower(
            P.unbox(spb), P.unbox(sob), P.unbox(batch), fmasks, sizes)
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    stats = telemetry.analyze(compiled.as_text())
    mem = compiled.memory_analysis()
    coll = {k: int(v) for k, v in stats.collective_bytes.items()}
    return {
        "program": "fed_round_ffdapt", "arch": arch, "clients": K,
        "local_steps": local_steps, "seq_len": seq_len,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "compile_s": round(t_compile, 2),
        "flops_per_device": float(stats.dot_flops),
        "hbm_bytes_per_device": float(stats.hbm_bytes),
        "collective_bytes_per_device": coll,
        "memory_peak_gib": float(getattr(mem, "peak_memory_in_bytes", 0)) / 2**30,
        "roofline_s": {
            "compute": stats.dot_flops / meshlib.PEAK_FLOPS_BF16,
            "memory": stats.hbm_bytes / meshlib.HBM_BW,
            "collective": stats.collective_total / meshlib.ICI_BW,
        },
        "status": "ok",
    }


def run_and_save(arch: str, shape: str, *, multi_pod: bool,
                 knobs: Knobs = Knobs(), tag: str = "") -> Dict[str, Any]:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"{arch}__{shape}__{'pod2' if multi_pod else 'pod1'}{tag}"
    try:
        rec = lower_pair(arch, shape, multi_pod=multi_pod, knobs=knobs)
        rec["status"] = "ok"
    except Exception as e:  # a failure here is a sharding bug — record it
        rec = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    with open(os.path.join(RESULTS_DIR, name + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--opt-state-dtype", default=None)
    ap.add_argument("--opt-rules", action="store_true")
    ap.add_argument("--impl", default="xla")
    ap.add_argument("--frozen-frac", type=float, default=0.0)
    ap.add_argument("--moe-groups", type=int, default=0)
    ap.add_argument("--fed", action="store_true",
                    help="lower the FFDAPT federated-round program (2 pods)")
    ap.add_argument("--fed-steps", type=int, default=4)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    if args.fed:
        rec = lower_fed_round(args.arch or "distilbert-mlm",
                              local_steps=args.fed_steps)
        os.makedirs(RESULTS_DIR, exist_ok=True)
        with open(os.path.join(RESULTS_DIR,
                               f"fed_round__{rec['arch']}.json"), "w") as f:
            json.dump(rec, f, indent=1)
        r = rec["roofline_s"]
        print(f"OK  fed_round {rec['arch']} K={rec['clients']} "
              f"steps={rec['local_steps']} compile={rec['compile_s']}s "
              f"compute={r['compute']:.3e}s memory={r['memory']:.3e}s "
              f"coll={r['collective']:.3e}s")
        return

    knobs = Knobs(microbatches=args.microbatches,
                  opt_state_dtype=args.opt_state_dtype,
                  opt_rules=args.opt_rules, impl=args.impl,
                  frozen_frac=args.frozen_frac, moe_groups=args.moe_groups)
    pairs = []
    if args.all:
        pairs = [(a, s, mp) for a in ASSIGNED for s in SHAPES
                 for mp in (False, True)]
    else:
        pairs = [(args.arch, args.shape, args.multi_pod)]

    for arch, shape, mp in pairs:
        rec = run_and_save(arch, shape, multi_pod=mp, knobs=knobs, tag=args.tag)
        if rec["status"] == "ok":
            r = rec["roofline_s"]
            print(f"OK  {arch:22s} {shape:12s} pods={2 if mp else 1} "
                  f"compile={rec['compile_s']:7.1f}s "
                  f"mem={rec['memory']['peak_estimate_bytes']/2**30:6.2f}GiB "
                  f"compute={r['compute']:.3e}s memory={r['memory']:.3e}s "
                  f"coll={r['collective']:.3e}s -> {rec['bottleneck']}")
        else:
            print(f"ERR {arch:22s} {shape:12s} pods={2 if mp else 1} "
                  f"{rec['error']}")


if __name__ == "__main__":
    main()
