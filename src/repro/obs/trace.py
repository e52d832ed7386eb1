"""Low-overhead span tracer with Chrome trace-event export.

A ``Tracer`` records SPANS — named, nested intervals of host wall-clock —
into a fixed-capacity thread-safe ring buffer, and exports them as Chrome
trace-event JSON (the ``{"traceEvents": [...]}`` format Perfetto and
``chrome://tracing`` load directly).  Three kinds of event:

  * measured spans — ``with tracer.span("train.dispatch", round=t): ...``.
    Timestamps come from ``time.perf_counter_ns`` (monotonic; immune to
    wall-clock steps) and are exported relative to the tracer's epoch, one
    track per thread.  Each span records its parent (the innermost open
    span of its thread) and its round (its own ``round`` argument, else
    its parent's); both are exported in the event's ``args``.  A measured
    span also enters a ``jax.profiler.TraceAnnotation`` of the same name,
    so a ``jax.profiler`` trace taken meanwhile shows it on the host plane,
    on the device trace's own clock.
  * instants — ``tracer.instant("train.compile")`` marks a point in time
    (trace-time events like a shard-program compile).
  * synthetic spans — ``tracer.add_span(name, ts_s=..., dur_s=...)``
    places a span at EXPLICIT seconds on a separate process track.  The
    simulator replays its per-client ``ClientTiming`` phases through this
    (``repro.sim.events.emit_spans``), so a simulated round renders next
    to the measured one in a single Perfetto timeline.

Cost discipline: the module-level default tracer starts DISABLED, and a
disabled tracer's ``span()`` returns one shared no-op singleton — no
allocation, no clock read, one attribute check — so the round/decode hot
paths can stay instrumented unconditionally.  Enabled, each span costs two
monotonic clock reads, a push and pop on its thread's span stack, one
profiler annotation (a no-op check while no profiler runs) and one locked
ring-buffer append.

The ring keeps the newest ``capacity`` events and counts what it dropped
(``tracer.dropped``) — a long session degrades to "most recent window",
never to unbounded memory.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

# Chrome trace "pid" lanes: measured events vs synthetic (simulated) events
# render as two named processes in one timeline.
PID_MEASURED = 1
PID_SIM = 2


@dataclasses.dataclass(frozen=True)
class SpanEvent:
    """One recorded event.  ``ts_us``/``dur_us`` are microseconds relative
    to the tracer's epoch; ``phase`` is the Chrome event phase ("X" =
    complete span, "i" = instant).  ``parent`` names the measured span
    this one opened inside (None at the top of its thread), and ``round``
    is its round id, inherited from the parent when not given."""

    name: str
    cat: str
    ts_us: float
    dur_us: float
    pid: int
    tid: int
    phase: str = "X"
    args: Optional[Dict[str, Any]] = None
    parent: Optional[str] = None
    round: Optional[int] = None


class _NullSpan:
    """The disabled-tracer fast path: one process-wide singleton, so a
    disabled ``span()`` call allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> None:
        """Arguments known only inside the span; dropped while disabled."""


NULL_SPAN = _NullSpan()


class _Span:
    """A live span handle (context manager).  ``__enter__`` finds the
    parent on its thread's span stack, reads ``perf_counter_ns`` and enters
    the profiler annotation; ``__exit__`` undoes both and appends the
    finished event."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_parent",
                 "_round", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = 0
        self._parent: Optional["_Span"] = None
        self._round = args.get("round") if args else None
        self._ann = None

    def set(self, **args) -> None:
        """Add arguments known only inside the span (sizes of what it
        made, say); they are exported with the others."""
        self._args = {**(self._args or {}), **args}

    def __enter__(self) -> "_Span":
        stack = self._tracer._stack()
        if stack:
            self._parent = stack[-1]
            if self._round is None:
                self._round = self._parent._round
        stack.append(self)
        # imported here: reading traces and metrics needs no jax
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self._name)
        self._t0 = time.perf_counter_ns()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(None, None, None)
        t1 = time.perf_counter_ns()
        stack = self._tracer._stack()
        if self in stack:
            stack.remove(self)
        self._tracer._append(SpanEvent(
            name=self._name, cat=self._cat,
            ts_us=(self._t0 - self._tracer._epoch_ns) / 1e3,
            dur_us=(t1 - self._t0) / 1e3,
            pid=PID_MEASURED, tid=threading.get_ident() & 0xFFFF,
            args=self._args,
            parent=self._parent._name if self._parent else None,
            round=self._round))
        return False


class Tracer:
    """Thread-safe ring buffer of trace events.

    ``enabled=False`` (the default for the process-wide tracer) makes every
    recording call a no-op returning shared singletons; flipping
    ``enabled`` needs no re-instrumentation of call sites.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        if capacity < 1:
            raise ValueError(f"capacity {capacity} < 1")
        self.enabled = enabled
        self._capacity = capacity
        self._lock = threading.Lock()
        self._buf: List[Optional[SpanEvent]] = [None] * capacity
        self._n = 0                     # total events ever appended
        self._epoch_ns = time.perf_counter_ns()
        self._local = threading.local()  # .stack: the thread's open spans

    # -- recording ------------------------------------------------------

    def span(self, name: str, cat: str = "", **args):
        """Context manager timing a measured span.  Disabled: returns the
        shared no-op singleton (zero allocation)."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, cat, args or None)

    def instant(self, name: str, cat: str = "", **args) -> None:
        """Mark a point event at 'now' (e.g. a compile at trace time)."""
        if not self.enabled:
            return
        self._append(SpanEvent(
            name=name, cat=cat,
            ts_us=(time.perf_counter_ns() - self._epoch_ns) / 1e3,
            dur_us=0.0, pid=PID_MEASURED,
            tid=threading.get_ident() & 0xFFFF, phase="i",
            args=args or None))

    def add_span(self, name: str, *, ts_s: float, dur_s: float,
                 cat: str = "", pid: int = PID_SIM, tid: int = 0,
                 **args) -> None:
        """Record a SYNTHETIC span at explicit times (seconds).  Used by
        the simulator's replay; lands on the ``pid`` process track so
        synthetic and measured timelines stay visually separate."""
        if not self.enabled:
            return
        self._append(SpanEvent(
            name=name, cat=cat, ts_us=ts_s * 1e6, dur_us=dur_s * 1e6,
            pid=pid, tid=tid, args=args or None))

    def _stack(self) -> List["_Span"]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, ev: SpanEvent) -> None:
        with self._lock:
            self._buf[self._n % self._capacity] = ev
            self._n += 1

    # -- inspection / export -------------------------------------------

    @property
    def dropped(self) -> int:
        """Events lost to ring overflow (oldest-first)."""
        return max(0, self._n - self._capacity)

    def __len__(self) -> int:
        return min(self._n, self._capacity)

    def events(self) -> List[SpanEvent]:
        """Surviving events, oldest first."""
        with self._lock:
            n, cap = self._n, self._capacity
            if n <= cap:
                return [e for e in self._buf[:n] if e is not None]
            head = n % cap
            return [e for e in self._buf[head:] + self._buf[:head]
                    if e is not None]

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self._capacity
            self._n = 0
            self._epoch_ns = time.perf_counter_ns()

    def chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (Perfetto-loadable):
        ``traceEvents`` carries one dict per event plus process-name
        metadata separating the measured and simulated tracks."""
        events: List[Dict[str, Any]] = [
            {"ph": "M", "name": "process_name", "pid": PID_MEASURED,
             "tid": 0, "args": {"name": "measured"}},
            {"ph": "M", "name": "process_name", "pid": PID_SIM,
             "tid": 0, "args": {"name": "simulated"}},
        ]
        for e in self.events():
            d: Dict[str, Any] = {"name": e.name, "cat": e.cat or "default",
                                 "ph": e.phase, "ts": e.ts_us,
                                 "pid": e.pid, "tid": e.tid}
            if e.phase == "X":
                d["dur"] = e.dur_us
            if e.phase == "i":
                d["s"] = "t"          # instant scope: thread
            args = dict(e.args or {})
            if e.parent is not None:
                args["parent"] = e.parent
            if e.round is not None:
                args["round"] = e.round
            if args:
                d["args"] = args
            events.append(d)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path`` (dirs created)."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1, sort_keys=True)
            f.write("\n")
        return path


# ---------------------------------------------------------------------------
# Process-wide default tracer
# ---------------------------------------------------------------------------

# Disabled by default: the instrumented hot paths (rounds, serve, sim) pay
# one attribute check per call site until someone opts in via enable().
_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    """The process-wide tracer every instrumented call site records into."""
    return _TRACER


def enable(capacity: int = 65536) -> Tracer:
    """Turn the process-wide tracer on (resetting its buffer) and return
    it.  The singleton object never changes identity, so references taken
    before ``enable()`` stay valid."""
    _TRACER._capacity = capacity
    _TRACER.clear()
    _TRACER.enabled = True
    return _TRACER


def disable() -> Tracer:
    """Turn the process-wide tracer off (events are kept for export)."""
    _TRACER.enabled = False
    return _TRACER


def span(name: str, cat: str = "", **args):
    """Module-level convenience: a span on the process-wide tracer."""
    if not _TRACER.enabled:
        return NULL_SPAN
    return _TRACER.span(name, cat=cat, **args)


def instant(name: str, cat: str = "", **args) -> None:
    """Module-level convenience: an instant on the process-wide tracer."""
    if _TRACER.enabled:
        _TRACER.instant(name, cat=cat, **args)

