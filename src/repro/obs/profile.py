"""Opt-in profiler hooks: ``jax.profiler`` traces + compile-event capture.

Two independent capture layers on top of the span tracer:

  * ``jax_profile(outdir)`` — context manager around ``jax.profiler.trace``
    (TensorBoard/XProf format, device-level detail).  ``outdir=None`` is a
    no-op, so callers can wire it unconditionally; a profiler that cannot
    start raises, so a run asked for a device trace never ends without
    one.  While the span tracer is enabled, each of its spans enters a
    ``TraceAnnotation`` of the same name, so the trace's host plane
    carries the round engine's spans (``train.round``, ``train.prepare``,
    ``train.dispatch`` with ``train.stack`` and ``train.launch``,
    ``train.combine``, ``train.wait``, ``train.account``) on the device ops' own clock, and
    the device ops carry the programs' named scopes (``embed``, ``attn``,
    ``mlp``, ``lm_head``, ``loss``, ``optimizer``, ``fold``) in their
    ``tf_op`` metadata (``launch.cache.use_compile_cache`` keys the
    compile cache on that metadata, so a warm cache cannot serve a
    program without it).

  * ``capture_compiles()`` — registers a ``jax.monitoring`` listener that
    turns every ``/jax/core/compile/*`` duration event (jaxpr trace, MLIR
    lowering, backend compile) into a span on the process-wide tracer
    (category ``compile``) and bumps ``compile.events`` plus one
    ``compile.<phase>_s`` per phase (``compile.backend_compile_s`` is the
    part a persistent-cache hit saves) in the metrics registry.  Phases
    nest, so their seconds are not summed.  Compile time is the #1
    confound in round-time drift — a retrace shows up as a fat span right
    where the round got slow instead of as an unexplained 30s ratio spike.

``record_compile`` is the explicit variant for compiles jax's monitoring
cannot attribute: the round engines call it at trace time of their shard
programs (wrapping the ``FedSession.shard_compiles`` counter), so the
Perfetto timeline shows WHICH round and shard width paid each trace.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator, Optional

from repro.obs.metrics import registry as _registry
from repro.obs.trace import PID_MEASURED, get_tracer

_COMPILE_LISTENER_INSTALLED = False
_COMPILE_PREFIX = "/jax/core/compile/"


def record_compile(what: str, **args: Any) -> None:
    """Mark an explicit compile/trace event 'now' on the process-wide
    tracer (instant, category ``compile``) and count it in the registry.
    Cheap no-op while the tracer is disabled (the counter still counts —
    compile counts are an invariant tests pin even without tracing)."""
    _registry().counter("compile.events").inc()
    tracer = get_tracer()
    if tracer.enabled:
        tracer.instant(f"compile/{what}", cat="compile", **args)


def _on_duration_event(event: str, duration_secs: float, **kw: Any) -> None:
    """jax.monitoring listener: compile-phase durations -> tracer spans.
    The event fires at phase END, so the span is backdated by its own
    duration.  Only ``/jax/core/compile/*`` phases count: the persistent
    cache's ``compile_time_saved_sec`` names time NOT spent compiling."""
    if not event.startswith(_COMPILE_PREFIX):
        return
    name = event[len(_COMPILE_PREFIX):]
    if name.endswith("_duration"):
        name = name[: -len("_duration")]
    secs = max(duration_secs, 0.0)
    _registry().counter("compile.events").inc()
    _registry().counter(f"compile.{name}_s").inc(secs)
    tracer = get_tracer()
    if not tracer.enabled:
        return
    now_s = (time.perf_counter_ns() - tracer._epoch_ns) / 1e9
    tracer.add_span(f"compile/{name}", ts_s=now_s - duration_secs,
                    dur_s=duration_secs, cat="compile", pid=PID_MEASURED,
                    tid=0)


def capture_compiles() -> None:
    """Install the compile-event listener (idempotent).  Raises when this
    jax build has no ``jax.monitoring`` duration events to subscribe to."""
    global _COMPILE_LISTENER_INSTALLED
    if _COMPILE_LISTENER_INSTALLED:
        return
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_duration_event)
    _COMPILE_LISTENER_INSTALLED = True


@contextlib.contextmanager
def jax_profile(outdir: Optional[str]) -> Iterator[None]:
    """``with jax_profile(dir):`` wraps the body in a ``jax.profiler``
    trace written to ``dir`` (viewable in TensorBoard / xprof / Perfetto).
    ``outdir`` of None/"" is a no-op; a profiler that fails to start
    raises."""
    if not outdir:
        yield
        return
    import jax.profiler as jp
    with jp.trace(outdir):
        yield
