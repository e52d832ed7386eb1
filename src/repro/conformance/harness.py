"""Runs the conformance grid: differential forward, differential VJP,
chain properties, and (for the bench) kernel-vs-ref timing.

``run_case`` is the single execution path — pytest, ``kernel_smoke.sh``,
and ``benchmarks/kernel_bench.py`` all call it, so "what does a case
check" cannot fork between CI and the pinned baselines:

  * **forward** — kernel output vs the sequential oracle, every output
    leaf, under ``tolerances.forward_tol(kernel, dtype)``;
  * **vjp** — ``jax.grad`` of an identical scalar loss (sum of squares
    over all output leaves, fp32) through the Pallas op's ``custom_vjp``
    vs through the oracle's autodiff, every input, under ``vjp_tol``;
  * **chain** — the kernel's own split-at-t invariant (no oracle), under
    the forward tolerance;
  * **timing** (opt-in) — jit'd kernel vs jit'd oracle, min-of-reps after
    a warmup call.  On a non-TPU backend the kernel runs in interpret
    mode, so the speed ratio is *recorded but never asserted*
    (``interpret`` is part of every result row; see docs/kernels.md).

Results are plain dataclasses with a ``to_row()`` JSON form — the bench
files are just ``[r.to_row() for r in run_grid(...)]`` plus metadata.
Each executed case is wrapped in an ``obs.span("conformance.case")`` so a
traced run shows per-case wall-clock in the same Perfetto timeline as the
round engine.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.conformance import cases as _cases
from repro.conformance import tolerances as _tol
from repro.conformance.cases import CASES, KERNELS, Case
from repro.kernels.ops import interpret_mode


@dataclasses.dataclass(frozen=True)
class CaseResult:
    """Outcome of one case.  ``*_violation`` is the worst
    ``|got-want| / (atol + rtol*|want|)`` ratio (<= 1 passes); ``None``
    means that check did not run for this case."""

    name: str
    kernel: str
    dtype: str
    tags: Tuple[str, ...]
    fwd_violation: Optional[float]
    vjp_violation: Optional[float]
    chain_violation: Optional[float]
    kernel_ms: Optional[float] = None
    ref_ms: Optional[float] = None
    interpret: bool = True

    @property
    def ok(self) -> bool:
        return all(v is None or v <= 1.0 for v in
                   (self.fwd_violation, self.vjp_violation,
                    self.chain_violation))

    @property
    def speedup(self) -> Optional[float]:
        if self.kernel_ms and self.ref_ms:
            return self.ref_ms / self.kernel_ms
        return None

    def to_row(self) -> Dict[str, Any]:
        row = {"name": self.name, "kernel": self.kernel, "dtype": self.dtype,
               "tags": list(self.tags), "ok": self.ok,
               "fwd_violation": self.fwd_violation,
               "vjp_violation": self.vjp_violation,
               "chain_violation": self.chain_violation,
               "interpret": self.interpret}
        if self.kernel_ms is not None:
            row["kernel_ms"] = self.kernel_ms
            row["ref_ms"] = self.ref_ms
            row["speedup"] = self.speedup
        return row


def _loss(fn, inputs) -> jax.Array:
    """Scalar sum-of-squares over every output leaf, fp32."""
    out = fn(*inputs)
    return sum(jnp.sum(jnp.square(leaf.astype(jnp.float32)))
               for leaf in jax.tree_util.tree_leaves(out))


def _leaf_violation(tol: _tol.Tol, got, want) -> float:
    leaves_g = jax.tree_util.tree_leaves(got)
    leaves_w = jax.tree_util.tree_leaves(want)
    assert len(leaves_g) == len(leaves_w)
    return max(tol.violation(g, w) for g, w in zip(leaves_g, leaves_w))


def _time_ms(fn, inputs, reps: int) -> float:
    jitted = jax.jit(fn)
    jax.block_until_ready(jitted(*inputs))        # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(jitted(*inputs))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def run_case(case: Case, *, timed: bool = False, reps: int = 3) -> CaseResult:
    """Execute one grid point: forward diff always; VJP / chain / timing
    per the case flags."""
    spec = KERNELS[case.kernel]
    interp = interpret_mode()
    with obs.span("conformance.case", case=case.name, kernel=case.kernel,
                  dtype=case.dtype):
        inputs = spec.make_inputs(case)
        kfn, rfn = spec.kernel_fn(case), spec.ref_fn(case)

        def scaled(tol: _tol.Tol) -> _tol.Tol:
            if case.tol_scale == 1.0:
                return tol
            return _tol.Tol(tol.rtol * case.tol_scale,
                            tol.atol * case.tol_scale)

        fwd_tol = scaled(_tol.forward_tol(case.kernel, case.dtype))
        fwd_v = _leaf_violation(fwd_tol, kfn(*inputs), rfn(*inputs))

        vjp_v = None
        if case.vjp:
            argnums = tuple(range(len(inputs)))
            gk = jax.grad(lambda *a: _loss(kfn, a), argnums=argnums)(*inputs)
            gr = jax.grad(lambda *a: _loss(rfn, a), argnums=argnums)(*inputs)
            vjp_v = _leaf_violation(
                scaled(_tol.vjp_tol(case.kernel, case.dtype)), gk, gr)

        chain_v = None
        if case.chain:
            if spec.chain_fn is None:
                raise ValueError(f"{case.kernel} has no chain property")
            got, want = spec.chain_fn(case, inputs)
            chain_v = _leaf_violation(fwd_tol, got, want)

        kernel_ms = ref_ms = None
        if timed:
            kernel_ms = _time_ms(kfn, inputs, reps)
            ref_ms = _time_ms(rfn, inputs, reps)

    return CaseResult(name=case.name, kernel=case.kernel, dtype=case.dtype,
                      tags=case.tags, fwd_violation=fwd_v,
                      vjp_violation=vjp_v, chain_violation=chain_v,
                      kernel_ms=kernel_ms, ref_ms=ref_ms, interpret=interp)


def run_grid(cases: Sequence[Case] = CASES, *, timed: bool = False,
             reps: int = 3, progress=None) -> List[CaseResult]:
    """Run a sequence of cases (the full registry by default)."""
    out = []
    for case in cases:
        res = run_case(case, timed=timed, reps=reps)
        if progress is not None:
            progress(res)
        out.append(res)
    return out


def summarize(results: Sequence[CaseResult]) -> Dict[str, Any]:
    """Aggregate a grid run into the JSON block the bench file pins."""
    by_kernel: Dict[str, Dict[str, int]] = {}
    for r in results:
        k = by_kernel.setdefault(r.kernel, {"cases": 0, "ok": 0, "vjp": 0,
                                            "chain": 0})
        k["cases"] += 1
        k["ok"] += int(r.ok)
        k["vjp"] += int(r.vjp_violation is not None)
        k["chain"] += int(r.chain_violation is not None)
    worst = {
        "fwd": max((r.fwd_violation or 0.0) for r in results),
        "vjp": max((r.vjp_violation or 0.0) for r in results),
        "chain": max((r.chain_violation or 0.0) for r in results),
    }
    return {
        "n_cases": len(results),
        "n_ok": sum(r.ok for r in results),
        "n_failed": sum(not r.ok for r in results),
        "by_kernel": by_kernel,
        "worst_violation": worst,
        "interpret": bool(results[0].interpret) if results else None,
    }
