#!/usr/bin/env python3
"""One-chip smoke run of federated DistilBERT rounds at published widths.

    python chip_smoke.py

Drives ``FedSession`` through ``repro.launch.train``'s own ``parse_args`` and
``build`` (the CLI's argument list below: distilbert-mlm at full width,
4 clients, batch 32 x seq 128, 4 local Adam steps per round, synthetic
corpus from ``--seed``), in one process, and checks each phase:

  a. the device is a TPU (anything else exits non-zero before any work);
  b. FDAPT on the cohort-scan engine, 2 rounds in shards of 2 clients:
     finite loss and finite parameters;
  c. the same under FFDAPT (masked frozen windows);
  d. one round on the sequential engine: its loss equals b's round 0;
  e. b's first round with ``impl="pallas"``: the compiled shard program
     holds the Mosaic kernel call, and its loss equals b's round 0;
  f. the forward loss of the first batch at the initial params, on the chip
     and on the host CPU, both at ``highest`` matmul precision;
  g. b stopped after round 1 with a checkpoint, then resumed: the final
     params are bitwise those of b;
  h. the device's peak memory.

Each phase prints one line.  Any failed check raises, which exits non-zero
without the result line.  The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The compile cache follows ``repro.launch.cache``: ``JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache`` in this checkout, so a second run in the same
place compiles for less time.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time

# Phase f compares with the host CPU backend, so keep it among the
# platforms when the environment names them.
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARGV = ["--arch", "distilbert-mlm", "--full-config", "--engine", "parallel",
        "--clients", "4", "--cohort-shard", "2", "--batch-size", "32",
        "--seq-len", "128", "--rounds", "2", "--max-steps-per-round", "4",
        "--seed", "0"]

# Relative tolerances on a round's mean train loss (about ln(30522) = 10.3
# at these params).  The two engines run the same step math, batched or
# not; the Pallas attention kernel accumulates its own matmuls, so its
# rounding differs from XLA's.
ENGINE_RTOL = 1e-3
PALLAS_RTOL = 1e-3
# chip against host CPU at highest matmul precision: both are float32
REFERENCE_RTOL = 1e-4


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / abs(b)


class Compiles:
    """Compile events and backend-compile seconds (what a persistent-cache
    hit saves) since the last ``take``, from the obs compile counters."""

    NAMES = ("compile.events", "compile.backend_compile_s")

    def __init__(self, obs):
        self._reg = obs.registry()
        self._last = (0.0,) * len(self.NAMES)

    def take(self) -> dict:
        now = tuple(self._reg.counter(n).value for n in self.NAMES)
        ev, backend = (n - l for n, l in zip(now, self._last))
        self._last = now
        return {"compiles": int(ev), "backend_compile_s": f"{backend:.2f}"}


def device_gate() -> jax.Device:
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devs)} device(s))",
              file=sys.stderr)
        sys.exit(2)
    import importlib.metadata as md
    versions = {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")}
    log("a device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devs), **versions)
    return dev


def all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def run_session(train, argv, *, plan_changes=None, resume=False):
    """Build the job as the CLI does and run its session; returns
    (session, final params, history)."""
    from repro.core.rounds import FedSession
    job = train.build(train.parse_args(argv))
    plan = dataclasses.replace(job.plan, **(plan_changes or {}))
    sess = FedSession(job.cfg, job.optimizer, plan)
    params, hist = sess.run(job.params, job.batches, resume=resume)
    jax.block_until_ready(params)
    return sess, params, hist


def round_fields(hist) -> dict:
    return {"loss": [f"{h.loss:.6f}" for h in hist],
            "round_s": [f"{h.round_time_s:.3f}" for h in hist],
            "tok_s": [f"{h.tokens_per_s:.0f}" for h in hist]}


def phases(argv, comp: Compiles) -> None:
    from repro.checkpoint import tree_digest
    from repro.launch import train
    from repro.models.steps import make_eval_step

    # b. FDAPT on the cohort-scan engine, two shards per round
    _, p_b, h_b = run_session(train, argv)
    check(all(np.isfinite(h.loss) for h in h_b), "phase b: loss not finite")
    check(all_finite(p_b), "phase b: a parameter is not finite")
    digest_b = tree_digest(p_b)
    log("b fdapt", **round_fields(h_b), **comp.take())
    del p_b

    # c. FFDAPT: masked frozen windows on the same engine
    _, p_c, h_c = run_session(train, argv + ["--ffdapt"])
    check(all(np.isfinite(h.loss) for h in h_c), "phase c: loss not finite")
    check(all_finite(p_c), "phase c: a parameter is not finite")
    log("c ffdapt", **round_fields(h_c),
        windows=[h.windows for h in h_c], **comp.take())
    del p_c

    # d. sequential engine, same data, params and seed
    _, p_d, h_d = run_session(train, argv + ["--engine", "sequential",
                                             "--rounds", "1"])
    gap = rel_gap(h_d[0].loss, h_b[0].loss)
    log("d sequential", **round_fields(h_d), rel_gap=f"{gap:.3e}",
        rtol=ENGINE_RTOL, **comp.take())
    check(gap <= ENGINE_RTOL, f"phase d: sequential round-0 loss "
          f"{h_d[0].loss} vs cohort-scan {h_b[0].loss}")
    del p_d

    # e. the Pallas path: the compiled shard program must hold the kernel
    sess, p_e, h_e = run_session(train, argv + ["--rounds", "1"],
                                 plan_changes={"impl": "pallas"})
    text = sess.shard_program.lower(*sess.shard_args).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    gap = rel_gap(h_e[0].loss, h_b[0].loss)
    log("e pallas", **round_fields(h_e), tpu_custom_calls=n_calls,
        rel_gap=f"{gap:.3e}", rtol=PALLAS_RTOL, **comp.take())
    check(n_calls > 0, "phase e: no Mosaic kernel call in the shard program")
    check(gap <= PALLAS_RTOL, f"phase e: pallas round-0 loss {h_e[0].loss} "
          f"vs xla {h_b[0].loss}")
    del p_e

    # f. forward loss at the initial params: chip against host CPU
    job = train.build(train.parse_args(argv))
    batch = job.batches[0][0]
    eval_step = jax.jit(make_eval_step(job.cfg))
    cpu = jax.devices("cpu")[0]
    p_cpu, b_cpu = jax.device_put((job.params, batch), cpu)
    losses = {}
    for prec in ("highest", "default"):
        with jax.default_matmul_precision(prec):
            on_chip = float(eval_step(job.params, batch)["loss"])
            on_cpu = float(eval_step(p_cpu, b_cpu)["loss"])
        losses[prec] = (on_chip, on_cpu, rel_gap(on_chip, on_cpu))
    (chip_h, cpu_h, gap_h), (_, _, gap_d) = losses["highest"], losses["default"]
    log("f reference", chip_loss=f"{chip_h:.7f}", cpu_loss=f"{cpu_h:.7f}",
        rel_gap_highest=f"{gap_h:.3e}", rtol=REFERENCE_RTOL,
        rel_gap_default=f"{gap_d:.3e}", **comp.take())
    check(gap_h <= REFERENCE_RTOL, f"phase f: chip loss {chip_h} vs CPU "
          f"{cpu_h} at highest precision")
    del job, p_cpu

    # g. kill after round 1, resume, compare with the uninterrupted run
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as d:
        run_session(train, argv + ["--ckpt-dir", d, "--stop-after", "1"])
        _, p_g, h_g = run_session(train, argv + ["--ckpt-dir", d],
                                  resume=True)
    digest_g = tree_digest(p_g)
    log("g resume", rounds=len(h_g), digest=digest_g[:16],
        uninterrupted=digest_b[:16], equal=digest_g == digest_b,
        **comp.take())
    check(digest_g == digest_b, "phase g: resumed params differ from the "
          "uninterrupted run")


def main() -> None:
    dev = device_gate()
    from repro import obs
    from repro.launch.cache import use_compile_cache
    cache = use_compile_cache()
    obs.capture_compiles()
    comp = Compiles(obs)
    log("cache", dir=cache)

    t0 = time.perf_counter()
    phases(ARGV, comp)

    # h. device memory
    stats = dev.memory_stats() or {}
    log("h memory", peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))
    reg = obs.registry()
    log("total", seconds=f"{time.perf_counter() - t0:.1f}",
        backend_compile_s=f"{reg.counter('compile.backend_compile_s').value:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
