"""Warm federated pre-training rounds through ``FedSession.run`` on the
cohort-scan (``parallel``) engine, the way ``repro.launch.train.build``
builds the job: seeded client batches, seeded weights made on the device,
``optim.adam`` and FedAvg, with the program's defaults otherwise
(``impl="xla"``, ``telemetry=True``).

A run, on one ``FedSession`` object:

1. Set-up.  ``run`` takes all but the last of the ``compare_rounds``
   rounds from the seed (the first call compiles).  A tap on the
   strategy's per-round accounting call reads the global weights after
   each round; the comparison uses those rounds.  Their round times size
   the window.
2. The window.  ``run`` is called again on the same session, from the
   weights the first rounds left, for 1 + ceil(seconds / warm round)
   rounds.  Its round 0 re-traces the shard program (the engine builds its
   jit inside ``run``), is set-up, and is the last compared round, so the
   comparison covers the call the window runs in.  The window runs from
   the end of round 0 to the return of ``run``, whole rounds, ended by
   ``block_until_ready``.
3. After the window: the device's peak memory is read, the program's state
   is dropped, and the plain reference repeats the compared rounds from
   the same seed.

``train_tokens_per_s`` counts every input position that client steps
trained on (clients x local steps x batch x sequence per round), over the
window's seconds on the host clock.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import math
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from harness import compare, data, fedref, weights, xtrace
from harness.load import load_module
from harness.precision import Num
from repro import obs, optim
from repro.core.rounds import FedSession, RoundPlan
from repro.core.strategy import FedAvg
from repro.launch.cache import use_compile_cache
from repro.models.config import ModelConfig
from repro.models.model import init_model
from repro.nn import param as P


class Tap:
    """Holds the function the strategy calls once per round (or None)."""

    fn = None


@dataclasses.dataclass(frozen=True)
class TappedFedAvg(FedAvg):
    """FedAvg, unchanged, whose per-round download accounting (called by
    the engine with the new global weights once per round) also calls the
    tap.  Nothing of it enters a compiled program."""

    tap: Any = dataclasses.field(default=None, compare=False, hash=False)

    def download_bytes(self, global_params, k):
        if self.tap.fn is not None:
            self.tap.fn(global_params)
        return super().download_bytes(global_params, k)


def model_config(config: Dict[str, Any]):
    keys = {f.name for f in dataclasses.fields(ModelConfig)}
    return ModelConfig(**{k: tuple(v) if isinstance(v, list) else v
                          for k, v in config.items() if k in keys})


@dataclasses.dataclass
class Job:
    cfg: Any
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    space: Any                       # repro.peft.ParamSpace or None
    batches: List[List[Dict[str, np.ndarray]]]
    make_base: Any
    make_bank: Any
    tokens_per_round: int
    loss_fraction: float             # share of positions the loss reads

    def weights(self):
        """(base, bank or None), made anew from the seed."""
        base = self.make_base(weights.seed_key(self.seed, 0))
        bank = (self.make_bank(weights.seed_key(self.seed, 1))
                if self.make_bank is not None else None)
        return base, bank


def build(config: Dict[str, Any], traffic: Dict[str, Any], seed: int) -> Job:
    cfg = model_config(config)
    batches = data.client_batches(traffic, config, seed)
    space = None
    if config.get("peft"):
        from repro.peft import make_param_space
        pe = config["peft"]
        space = make_param_space(pe["kind"], rank=pe["rank"],
                                 alpha=pe.get("alpha", 0.0),
                                 targets=tuple(pe["targets"]))
    template = P.unbox(jax.eval_shape(lambda k: init_model(k, cfg),
                                      jax.random.PRNGKey(0)))
    make_bank = None
    if space is not None:
        make_bank = weights.maker(jax.eval_shape(
            lambda p: space.inject(p, jax.random.PRNGKey(0)), template))
    k, s = traffic["clients"], traffic["local_steps"]
    mask = np.mean([b["loss_mask"].mean() for c in batches for b in c])
    return Job(cfg=cfg, config=config, traffic=traffic, seed=seed,
               space=space, batches=batches,
               make_base=weights.maker(template), make_bank=make_bank,
               tokens_per_round=k * s * traffic["batch"] * traffic["seq"],
               loss_fraction=float(mask))


def session(job: Job, tap: Tap):
    plan = RoundPlan(n_rounds=job.traffic["compare_rounds"] - 1,
                     engine="parallel", strategy=TappedFedAvg(tap=tap),
                     cohort_shard=job.traffic["cohort_shard"],
                     param_space=job.space, seed=job.seed)
    return FedSession(job.cfg, optim.adam(job.traffic["lr"]), plan)


def _inputs(job: Job, base, trainable):
    return {"base": base, "peft": trainable} if job.space else trainable


def _on_host(tree):
    return jax.tree.map(np.asarray, tree)


def first_rounds(job: Job, sess, tap: Tap, base, bank):
    """The compared rounds but the last through the program's first
    ``run`` call; the window's call takes the last (``window``).  Returns
    (readings so far, the global weights they leave, seconds of a warm
    round)."""
    n = job.traffic["compare_rounds"] - 1
    if n < 2:
        raise ValueError("compare_rounds has to be 3 or more")
    start = bank if job.space else base
    seen: List[Dict[str, float]] = []
    times: List[float] = []
    last: List[Any] = [None, None]

    def record(g):
        times.append(time.perf_counter())
        seen.append(fedref.delta_norms(g, start))
        if last[1] is None:
            last[1] = _on_host(g)
        last[0] = g

    tap.fn = record
    out, hist = sess.run(_inputs(job, base, start), job.batches)
    tap.fn = None
    del out
    if len(seen) != n or len(hist) != n:
        raise RuntimeError(f"expected {n} rounds, the tap saw {len(seen)} "
                           f"and the history holds {len(hist)}")
    readings = fedref.Readings(losses=[h.loss for h in hist],
                               delta_r1=seen[0], delta_end={},
                               grad_norms={}, at_r1=last[1])
    warm = (times[-1] - times[0]) / (n - 1)
    return readings, last[0], warm


class _Compiles:
    """Host times of the compile events JAX reports (one listener per
    process)."""

    _one = None

    def __init__(self):
        self.events: List[tuple] = []
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def listen(cls) -> "_Compiles":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _on(self, event: str, duration: float, **kw):
        if event.startswith("/jax/core/compile/"):
            self.events.append((time.perf_counter(), event, duration))

    def within(self, t0: float, t1: float) -> List[tuple]:
        return [e for e in self.events if t0 <= e[0] <= t1]


def window(job: Job, sess, tap: Tap, base, start, trainable,
           seconds: float, warm: float, trace_dir: Optional[str],
           prog) -> Dict[str, Any]:
    """The measured rounds, in a second ``run`` call on the same session.
    Its round 0 re-traces the shard program and is set-up; it is also the
    last compared round, which ``prog`` takes.  Returns a dict of what was
    measured."""
    n = 1 + max(1, math.ceil(seconds / warm))
    sess.plan = dataclasses.replace(sess.plan, n_rounds=n)
    st: Dict[str, Any] = {"calls": 0}
    if trace_dir:
        obs.enable()

    def mark(g):
        st["calls"] += 1
        if st["calls"] == 1:
            prog.delta_end = fedref.delta_norms(g, start)
            prog.final = _on_host(g)
            st["t_r0"] = time.perf_counter()
            if trace_dir:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                st["ann"] = jax.profiler.TraceAnnotation("bench.window")
                st["origin_ns"] = time.perf_counter_ns()
                st["ann"].__enter__()
            st["t0"] = time.perf_counter()

    tap.fn = mark
    out, hist = sess.run(_inputs(job, base, trainable), job.batches)
    jax.block_until_ready(out)
    t1 = time.perf_counter()
    tap.fn = None
    if trace_dir:
        st["ann"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    if st["calls"] != n or len(hist) != n:
        raise RuntimeError(f"expected {n} rounds, the tap saw {st['calls']} "
                           f"and the history holds {len(hist)}")
    del out
    prog.losses.append(hist[0].loss)
    res = {"t0": st["t0"], "t1": t1, "t_r0": st["t_r0"], "rounds": n - 1,
           "losses": [h.loss for h in hist[1:]],
           "origin_ns": st.get("origin_ns")}
    if trace_dir:
        tr = obs.get_tracer()
        res["spans"] = [(tr._epoch_ns + e.ts_us * 1e3,
                         tr._epoch_ns + (e.ts_us + e.dur_us) * 1e3, e.name)
                        for e in tr.events() if e.phase == "X"
                        and tr._epoch_ns + e.ts_us * 1e3 >= st["origin_ns"]]
        obs.disable()
    return res


def reference(job: Job, bench_dir: str, mode: str = "f32",
              fault: Optional[str] = None):
    """The compared rounds through the plain reference, from the seed."""
    name = job.config["name"]
    mod = load_module(os.path.join(bench_dir, "reference", f"{name}.py"),
                      "reference_" + name.replace("-", "_"))
    loss = mod.make_loss(job.config, job.config.get("peft"))
    base, bank = job.weights()
    trainable, frozen = (bank, base) if job.space else (base, None)
    sizes = [len(c) for c in job.batches]
    return fedref.run_rounds(loss, trainable, frozen, job.batches, sizes,
                             job.traffic["compare_rounds"],
                             job.traffic["lr"], Num(mode), fault=fault)


def device_memory(stats: Dict[str, Any]) -> Dict[str, Any]:
    """The chip's peak memory by its allocator: ``peak_bytes_in_use``
    counts the buffers, and ``peak_bytes_reserved`` the space the runtime
    holds for the loaded programs' temporaries, which the buffers leave
    out (the shard program's working set is nearly all there)."""
    used, held = stats.get("peak_bytes_in_use"), stats.get(
        "peak_bytes_reserved")
    return {"buffers": used, "programs": held,
            "peak": used + held if used is not None and held is not None
            else None, "limit": stats.get("bytes_limit")}


def run(ctx) -> Dict[str, Any]:
    """One benchmark run of a cell; see ``bench/run.py`` for ``ctx``."""
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = _Compiles.listen()
    marks = [("start", time.perf_counter())]
    job = build(ctx.config, ctx.traffic, ctx.seed)
    tap = Tap()
    sess = session(job, tap)
    base, bank = job.weights()
    jax.block_until_ready((base, bank))
    marks.append(("job and weights", time.perf_counter()))
    prog, trainable, warm = first_rounds(job, sess, tap, base, bank)
    marks.append(("first run call", time.perf_counter()))
    trace_dir = None
    if ctx.trace:
        trace_dir = os.path.join(ctx.out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = window(job, sess, tap, base, bank if job.space else base,
                 trainable, ctx.seconds, warm, trace_dir, prog)
    marks += [("window call's round 0", win["t_r0"]),
              ("window opens", win["t0"])]
    stats = jax.devices()[0].memory_stats() or {}
    memory = device_memory(stats)
    del sess, base, bank, trainable
    gc.collect()

    in_window = compiles.within(win["t0"], win["t1"])
    window_s = win["t1"] - win["t0"]
    tokens = win["rounds"] * job.tokens_per_round
    out: Dict[str, Any] = {
        "attempted": win["rounds"],
        "failed": int(sum(not np.isfinite(x) for x in win["losses"])),
        "e2e": {"train_tokens_per_s": tokens / window_s,
                "setup_s": win["t0"] - ctx.t0},
        "memory": memory,
        "notes": [f"window {win['rounds']} rounds, {window_s} s, "
                  f"{tokens} tokens; warm round {warm} s",
                  f"compile events inside the window: {len(in_window)}",
                  "set-up: " + ", ".join(
                      f"{name} {t - ctx.t0:.3f} s" for name, t in marks),
                  "memory: " + ", ".join(f"{k} {v}" for k, v in
                                         memory.items())],
    }
    out["notes"] += [f"  compile in window: {e[1]} {e[2]} s"
                     for e in in_window[:5]]

    summary = None
    if trace_dir:
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        summary = xtrace.reduce(paths[0], host_spans=win["spans"],
                                host_origin_ns=win["origin_ns"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["device_extra"] = {"busy_s": summary.busy_s,
                               "window_s": summary.window_s}
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in sorted(
                summary.ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:10]]}
        out["notes"] += [f"module {k}: {v} s over {summary.module_runs[k]} "
                         f"runs" for k, v in sorted(
                             summary.modules.items(), key=lambda kv: -kv[1])]

    ref = reference(job, ctx.bench_dir)
    nums = compare.numbers(prog, ref)
    limits = ctx.traffic["limits"]
    out["checks"] = {k: {"value": nums[k][0], "limit": v, "at": nums[k][1]}
                     for k, v in limits.items()}
    out["notes"] += [f"not compared in this cell: {k} {v!r} (at {at})"
                     for k, (v, at) in nums.items() if k not in limits]
    out["correct"] = bool(
        all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in out["checks"].values()) and out["failed"] == 0)
    out["layer_input"] = dict(
        window_rounds=win["rounds"], tokens_per_round=job.tokens_per_round,
        client_steps_per_round=(job.traffic["clients"]
                                * job.traffic["local_steps"]),
        loss_fraction=job.loss_fraction, trace=summary,
        spans=[(s / 1e9, e / 1e9, n) for s, e, n in win.get("spans", [])],
        memory=out["memory"], window_s=window_s)
    return out
