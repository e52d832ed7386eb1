"""Reduce a profiler trace (``.xplane.pb``) to device busy time, time per
XLA module, time per op and the idle gaps, each gap labeled by what the
host was doing.

Layout of a TPU trace as JAX 0.9 writes it: one plane per chip named
``/device:TPU:<n>``, with a line ``XLA Ops`` (one event per op execution,
named by its HLO text ``%name = shape op(...)``) and a line ``XLA
Modules`` (one event per program execution, named ``jit_<fn>(<id>)``);
the host plane ``/host:CPU`` carries the ``jax.profiler.TraceAnnotation``
spans.  Event times are nanoseconds on one clock for all planes.

``window`` is the harness's ``TraceAnnotation`` around the measured
rounds.  Busy time is the union of op intervals on each chip, averaged over
the chips; an op that contains others (a loop) counts once.

The device's clock can lag the host's by a few milliseconds.  Where the
host's program launches (``PJRT_LoadedExecutable_Execute``) pair one to one
with chip 0's module executions, device times are shifted by the least
amount that puts every execution after its launch before the gaps are
labeled; busy time does not depend on it.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"

Interval = Tuple[float, float]


@dataclasses.dataclass
class TraceSummary:
    window_s: float                     # length of the window annotation
    busy_s: float                       # union of op intervals, chip mean
    chips: int
    modules: Dict[str, float]           # module name -> device seconds
    module_runs: Dict[str, int]         # module name -> executions
    ops: Dict[str, float]               # op name -> exclusive seconds
    gaps: List[Tuple[str, float]]       # (label, idle seconds), most first
    idle_by_span: Dict[str, float]      # span name -> chip 0's idle seconds


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion f32[8,128]``: the op's name without its numeric suffix, and
    its result's type without the layout, so that instances of one op sum
    together and the shape tells one matmul fusion from another."""
    head, _, rest = hlo_text.partition(" = ")
    name = re.sub(r"(\.\d+)+$", "", head.strip().lstrip("%"))
    if not name:
        return hlo_text[:64]
    result = re.sub(r"\{[^{}]*\}", "", rest.split(" ", 1)[0]) if rest else ""
    return f"{name} {result[:48]}".strip()


def module_name(event_name: str) -> str:
    """``jit_fn(1234)`` -> ``jit_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _exclusive(events: Sequence[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Self time per name, in the events' unit: the time of an event that
    lies inside another (an op inside a loop) is taken from its parent's."""
    out: Dict[str, float] = {}
    stack: List[Tuple[float, str]] = []          # (end, name)
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        out[name] = out.get(name, 0.0) + (e - s)
        if stack:
            parent_end, parent = stack[-1]
            out[parent] -= min(e, parent_end) - s
        stack.append((e, name))
    return out


def _label(mid: float, spans: Sequence[Tuple[float, float, str]]) -> str:
    best: Optional[Tuple[float, str]] = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside program spans"


def reduce(path: str, *, window: str = "bench.window",
           host_spans: Sequence[Tuple[float, float, str]] = (),
           host_origin_ns: float = 0.0, n_gaps: int = 10) -> TraceSummary:
    """Summarize one trace.  ``host_spans`` are (start_ns, end_ns, name)
    on the host's ``perf_counter_ns`` clock, and ``host_origin_ns`` is that
    clock's reading when the ``window`` annotation began: the two put the
    spans on the trace's clock, where each idle gap of chip 0 takes the name
    of the innermost span around its middle.  ``gaps`` sums the idle time
    by that name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    win: Optional[Interval] = None
    chips: List[Tuple[List, List]] = []
    launches: List[float] = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == window and win is None:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name == LAUNCH:
                        launches.append(ev.start_ns)
        elif plane.name.startswith(DEVICE_PREFIX):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name) for ev in line.events]
            if ops or mods:
                chips.append((ops, mods))
    if win is None:
        raise ValueError(f"no {window!r} annotation in {path}")
    if not chips:
        raise ValueError(f"no device plane with ops in {path}")
    busy = []
    for ops, _ in chips:
        busy.append(sum(e - s for s, e in _union([(s, e) for s, e, _ in ops])))
    modules: Dict[str, float] = {}
    runs: Dict[str, int] = {}
    ops0, mods0 = chips[0]
    for s, e, name in mods0:
        m = module_name(name)
        modules[m] = modules.get(m, 0.0) + (e - s) / 1e9
        runs[m] = runs.get(m, 0) + 1
    ops_s = {k: v / 1e9 for k, v in
             _exclusive([(s, e, op_name(n)) for s, e, n in ops0]).items()}
    shift = win[0] - host_origin_ns
    spans = [(s + shift, e + shift, n) for s, e, n in host_spans]
    lag = 0.0
    starts = sorted(s for s, _, _ in mods0)
    if starts and len(starts) == len(launches):
        lag = max(h - m for h, m in zip(sorted(launches), starts))
    merged = _union([(s + lag, e + lag) for s, e, _ in ops0])
    edges = [win[0]] + [x for iv in merged for x in iv] + [win[1]]
    by_label: Dict[str, List[float]] = {}
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e > s:
            by_label.setdefault(_label((s + e) / 2, spans), []).append(
                (e - s) / 1e9)
    gaps = sorted(((f"{k} ({len(v)} gaps, longest {max(v):.6f} s)", sum(v))
                   for k, v in by_label.items()), key=lambda g: -g[1])
    return TraceSummary(window_s=(win[1] - win[0]) / 1e9,
                        busy_s=sum(busy) / len(busy) / 1e9,
                        chips=len(chips), modules=modules, module_runs=runs,
                        ops=ops_s, gaps=gaps[:n_gaps],
                        idle_by_span={k: sum(v) for k, v in by_label.items()})
