"""Roofline cost rules over parsed HLO: dot FLOPs, HBM bytes, collective
bytes, with while-trip-aware execution multiplicities.

Why not ``compiled.cost_analysis()`` alone: XLA's aggregate cost counts a
while-loop body ONCE, but a scanned L-layer stack executes it L times — the
dominant share of a transformer step.  Unrolling every stack for analysis is
exact but costs 10-30 min of compile per big arch on a 1-core host.  This
module instead propagates execution multiplicity down the computation call
graph (ENTRY=1, while bodies x trip count) and applies per-op rules:

  * ``dot``: 2 x result elements x product(lhs contracting dims) — the
    contracting dims come from the lhs operand's own printed type, so batch
    dims (in the result once) and contracting dims are each counted exactly
    once.  ``convolution``: 2 x result elements x (kernel elements /
    output-feature dim), from ``dim_labels``.
  * HBM traffic: result + operand bytes of every top-level op; fusion
    internals are hidden (a fused TPU executable only reads its operands and
    writes its result); dynamic (update-)slices move the slice, not the
    buffer they index.
  * Collectives: result bytes by kind (ring all-reduce moves ~2x this on the
    wire — callers annotate when they need the wire figure).

Validated against ``cost_analysis`` on fully-unrolled programs
(tests/test_hlo_analysis.py, tests/test_telemetry.py): dot-FLOP totals agree
within a few percent.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.telemetry.hlo import (Computation, Op, called_computations,
                                 entry_name, parse_computations, shape_bytes,
                                 shape_dims, trip_count, while_parts)

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")

# ops whose "result" is a view/constant/bookkeeping — no HBM traffic
SKIP_BYTES = {"parameter", "constant", "get-tuple-element", "tuple",
              "bitcast", "iota", "after-all", "partition-id", "replica-id"}


@dataclasses.dataclass
class HloStats:
    dot_flops: float
    hbm_bytes: float
    collective_bytes: Dict[str, float]

    @property
    def collective_total(self) -> float:
        return float(sum(self.collective_bytes.values()))


# ---------------------------------------------------------------------------
# Execution multiplicities
# ---------------------------------------------------------------------------

def multiplicities(comps: Dict[str, Computation], entry: str
                   ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Returns ``(flop_mult, byte_mult)`` per computation.

    ``flop_mult`` descends everywhere (dots inside fused computations still
    execute); ``byte_mult`` descends only through control flow
    (while/conditional) — a fusion's internal buffers never touch HBM, only
    the fusion op's own operands/results do (counted at its call site)."""
    flop_mult: Dict[str, float] = {}
    byte_mult: Dict[str, float] = {}

    def visit(name: str, m: float, fused: bool):
        if name not in comps:
            return
        flop_mult[name] = flop_mult.get(name, 0.0) + m
        if not fused:
            byte_mult[name] = byte_mult.get(name, 0.0) + m
        for op in comps[name].ops:
            if op.opcode == "while":
                cond_n, body_n = while_parts(op)
                t = trip_count(op, comps)
                if cond_n in comps:
                    visit(cond_n, m * (t + 1), fused)
                if body_n in comps:
                    visit(body_n, m * t, fused)
            elif op.opcode == "conditional":
                for child in called_computations(op):
                    visit(child, m, fused)
            else:
                for child in called_computations(op):
                    visit(child, m, True)

    visit(entry, 1.0, False)
    return flop_mult, byte_mult


# ---------------------------------------------------------------------------
# Per-op rules
# ---------------------------------------------------------------------------

def _elements(text: str) -> int:
    n = 1
    for d in shape_dims(text):
        n *= d
    return n


_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")
_WINDOW_RE = re.compile(r"window=\{([^}]*)\}")


def dot_flops(op: Op, comp: Computation) -> float:
    """2 x result elements x K.  K = product of the lhs contracting dims
    (each free/batch dim is in the result exactly once, each contracting dim
    exactly once in K)."""
    out_n = _elements(op.result)
    k = 1
    m = _CONTRACT_RE.search(op.rest)
    lhs_dims = shape_dims(comp.operand_type(op, 0))
    if m and lhs_dims:
        for i in m.group(1).split(","):
            if i and int(i) < len(lhs_dims):
                k *= lhs_dims[int(i)]
    return 2.0 * out_n * k


@functools.lru_cache(maxsize=None)
def _conv_taps(n_in: int, n_out: int, size: int, stride: int, pad_lo: int,
               lhs_dilate: int, rhs_dilate: int) -> int:
    """Window taps of one spatial dim that land on a real lhs element,
    summed over the dim's output positions.  Taps on padding or on the holes
    of an lhs dilation multiply zeros and cost nothing."""
    pos = np.arange(n_out) * stride - pad_lo
    last = (n_in - 1) * lhs_dilate
    total = 0
    for w in range(size):
        q = pos + w * rhs_dilate
        total += int(np.count_nonzero((q >= 0) & (q <= last)
                                      & (q % lhs_dilate == 0)))
    return total


def conv_flops(op: Op, comp: Computation) -> float:
    """2 x output batch x output features x input features per group x the
    taps that hit real input, summed over output positions.

    Counting taps instead of window elements matters on TPU, where XLA
    writes a batched matmul as a convolution whose window walks an
    lhs-dilated batch axis (``window={size=32 stride=31 lhs_dilate=32}``):
    each output position meets one real element, not 32."""
    out_dims = shape_dims(op.result)
    lhs_dims = shape_dims(comp.operand_type(op, 0))
    rhs_dims = shape_dims(comp.operand_type(op, 1))
    m = _DIM_LABELS_RE.search(op.rest)
    if not (m and out_dims and lhs_dims and rhs_dims):
        return 2.0 * _elements(op.result)
    lhs_l, rhs_l, out_l = m.groups()
    wm = _WINDOW_RE.search(op.rest)
    win = {}
    for item in (wm.group(1).split() if wm else ()):
        key, _, val = item.partition("=")
        win[key] = val.split("x")

    def attr(key: str, j: int, default: int) -> int:
        return int(win[key][j]) if key in win else default

    taps = 1
    for j in range(sum(c.isdigit() for c in out_l)):
        c = str(j)
        pad_lo = int(win["pad"][j].split("_")[0]) if "pad" in win else 0
        taps *= _conv_taps(lhs_dims[lhs_l.index(c)],
                           out_dims[out_l.index(c)], attr("size", j, 1),
                           attr("stride", j, 1), pad_lo,
                           attr("lhs_dilate", j, 1), attr("rhs_dilate", j, 1))
    return (2.0 * out_dims[out_l.index("b")] * out_dims[out_l.index("f")]
            * rhs_dims[rhs_l.index("i")] * taps)


def op_flops(op: Op, comp: Computation) -> float:
    if op.opcode == "dot":
        return dot_flops(op, comp)
    if op.opcode == "convolution":
        return conv_flops(op, comp)
    return 0.0


def op_hbm_bytes(op: Op, comp: Computation,
                 comps: Optional[Dict[str, Computation]] = None) -> float:
    """HBM traffic attributed to one top-level op: operand reads + result
    writes.  Dynamic (update-)slices only move the slice, not the buffer
    they index into — and a fusion whose root is a dynamic-update-slice (a
    scatter loop body: embedding-gradient accumulation) is the same in-place
    update, so it moves the slice too, NOT the whole buffer it rewrites.
    Without that rule an unrolled train step over-counts HBM by ~10x (the
    full embedding table charged once per scatter row)."""
    if op.opcode in SKIP_BYTES:
        return 0.0
    if op.opcode == "dynamic-slice":
        return 2.0 * shape_bytes(op.result)
    if op.opcode == "dynamic-update-slice":
        upd = shape_bytes(comp.operand_type(op, 1))
        return 2.0 * upd
    if op.opcode == "fusion" and comps is not None:
        called = called_computations(op)
        callee = comps.get(called[0]) if called else None
        root = callee.root() if callee is not None else None
        if root is not None and root.opcode == "dynamic-update-slice":
            return 2.0 * shape_bytes(callee.operand_type(root, 1))
    operand_b = sum(shape_bytes(comp.operand_type(op, i))
                    for i in range(len(op.operand_names)))
    return float(shape_bytes(op.result) + operand_b)


def collective_kind(op: Op) -> str:
    """The collective family of an op ("" if not a collective).  ``-start``
    variants count; ``-done`` halves are skipped (same buffer)."""
    for kind in COLLECTIVES:
        if op.opcode == kind or op.opcode == kind + "-start":
            return kind
    return ""


# ---------------------------------------------------------------------------
# Whole-module analysis
# ---------------------------------------------------------------------------

def analyze(hlo: str) -> HloStats:
    comps = parse_computations(hlo)
    entry = entry_name(comps, hlo)
    flop_mult, byte_mult = multiplicities(comps, entry)

    flops = 0.0
    hbm = 0.0
    coll = {k: 0.0 for k in COLLECTIVES}
    for cname, m in flop_mult.items():
        comp = comps[cname]
        for op in comp.ops:
            flops += m * op_flops(op, comp)
    for cname, m in byte_mult.items():
        comp = comps[cname]
        for op in comp.ops:
            kind = collective_kind(op)
            if kind:
                coll[kind] += m * shape_bytes(op.result)
            hbm += m * op_hbm_bytes(op, comp, comps)
    return HloStats(dot_flops=flops, hbm_bytes=hbm, collective_bytes=coll)


def top_contributors(hlo: str, kind: str = "bytes", n: int = 15
                     ) -> List[Tuple[str, str, str, float, float]]:
    """Diagnosis: the n largest (computation, opcode, result, mult, total)
    contributors to the chosen roofline term (``flops|bytes|collective``)."""
    comps = parse_computations(hlo)
    entry = entry_name(comps, hlo)
    flop_mult, byte_mult = multiplicities(comps, entry)
    rows = []
    mult = flop_mult if kind == "flops" else byte_mult
    for cname, m in mult.items():
        comp = comps[cname]
        for op in comp.ops:
            if kind == "flops":
                f = op_flops(op, comp)
                if f:
                    rows.append((cname, op.opcode, op.result, m, m * f))
            elif kind == "collective":
                if collective_kind(op):
                    rows.append((cname, op.opcode, op.result, m,
                                 m * shape_bytes(op.result)))
            else:
                b = op_hbm_bytes(op, comp, comps)
                if b:
                    rows.append((cname, op.opcode, op.result, m, m * b))
    rows.sort(key=lambda r: -r[-1])
    return rows[:n]


# ---------------------------------------------------------------------------
# XLA cost_analysis normalization
# ---------------------------------------------------------------------------

def xla_cost(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` normalized across jax versions: older
    releases return one dict, newer ones a one-per-partition list."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


def xla_flops(compiled) -> float:
    return float(xla_cost(compiled).get("flops", 0.0))
