"""Milliseconds per round in which the device idles while the round engine
dispatches: chip 0's idle time inside the ``train.dispatch`` spans of
``repro.obs`` (materializing a shard's batches and launching its program),
over the traced window's rounds.  The device waits there on the host
alone, so host work moves it and device work does not."""

SPAN = "train.dispatch"


def read(run):
    if run.trace is None or run.window_rounds <= 0:
        return None
    if not any(name == SPAN for _, _, name in run.spans):
        return None
    return 1000.0 * run.trace.idle_by_span.get(SPAN, 0.0) / run.window_rounds
