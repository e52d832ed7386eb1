"""Host milliseconds per round spent materializing the shards' batches: the
summed durations of the round engine's ``train.stack`` spans (``repro.obs``)
inside the traced window, over its rounds.  Host work alone: the stacking
dispatches small device programs but does not wait for them."""

SPAN = "train.stack"


def read(run):
    if run.window_rounds <= 0:
        return None
    secs = [e - s for s, e, name in run.spans if name == SPAN]
    if not secs:
        return None
    return 1000.0 * sum(secs) / run.window_rounds
