"""Milliseconds per round in which the device idles while the round engine
dispatches, the dispatch span's children included: chip 0's idle time in
the gaps labeled ``train.dispatch``, ``train.stack`` or ``train.launch``
(``repro.obs`` spans), over the traced window's rounds.

The trace reduction labels each gap by its innermost span, so once
``train.dispatch`` has children (materializing the shard's batches,
launching its program) they take its gaps, and ``dispatch_idle_ms`` reads
only what is left outside them.  The two children occur only inside
``train.dispatch``, so the three labels together are the idle time under
it.  On a program whose ``train.dispatch`` has no children this reads
what ``dispatch_idle_ms`` reads."""

SPAN = "train.dispatch"
CHILDREN = ("train.stack", "train.launch")


def read(run):
    if run.trace is None or run.window_rounds <= 0:
        return None
    if not any(name == SPAN for _, _, name in run.spans):
        return None
    idle = run.trace.idle_by_span
    total = sum(idle.get(name, 0.0) for name in (SPAN,) + CHILDREN)
    return 1000.0 * total / run.window_rounds
