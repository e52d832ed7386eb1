"""Host milliseconds per round spent making the round's sampled clients'
batches: the summed durations of the round engine's ``train.cohort`` spans
(``repro.obs``, inside ``train.prepare``) in the traced window, over its
rounds.  A lazy client pool builds each sampled client there on its first
access, from (seed, client id).  A program without the span reads
nothing."""

SPAN = "train.cohort"


def read(run):
    if run.window_rounds <= 0:
        return None
    secs = [e - s for s, e, name in run.spans if name == SPAN]
    if not secs:
        return None
    return 1000.0 * sum(secs) / run.window_rounds
