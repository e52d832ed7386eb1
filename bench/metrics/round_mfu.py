"""Whole-round model FLOP utilization: the training FLOPs the job requires
per input token (``bench/flops/<config>.py``) times the traced window's
input tokens per second, over the chips' bf16 peak (``bench/peaks.json``).
Recomputed and unrequired work the program does is not counted."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    tokens_per_s = run.window_rounds * run.tokens_per_round / run.trace.window_s
    peak = run.chips * run.peaks["bf16_flops_per_s"]
    return 100.0 * run.flops_per_token * tokens_per_s / peak
