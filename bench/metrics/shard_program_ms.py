"""Device milliseconds per client step of the cohort-scan shard program:
the traced time of its XLA module (the jit of ``_fed_shard`` in
``core/rounds.py``: vmapped local steps and the partial fold) over the
client steps of the traced window."""

MODULE = "_fed_shard"


def read(run):
    if run.trace is None:
        return None
    secs = sum(v for k, v in run.trace.modules.items() if MODULE in k)
    steps = run.window_rounds * run.client_steps_per_round
    if secs <= 0 or steps <= 0:
        return None
    return 1000.0 * secs / steps
