#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        [--controls 3] [--out calib.jsonl]

For every seed, in one process: the program's compared rounds (the set-up
of a benchmark run, and a window of one round) against the plain
reference, which gives the lower readings.  For the first ``--controls`` seeds also the control (the
reference one precision below what the configuration states: bfloat16 for
float32, float8 for bfloat16) and each fault of ``harness.fedref``
planted in the reference put in the program's place, against the same
reference, which give the upper readings.  One JSON line per seed.  Not
part of a benchmark run; it needs the cell's chips like one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_mode(config: dict) -> str:
    return {"float32": "bf16", "bfloat16": "fp8"}[config["param_dtype"]]


def readings(config, traffic, seed: int, controls: bool):
    from harness import compare, fedref
    from harness.load import load_module
    run_mod = load_module(os.path.join(BENCH, "runners",
                                       traffic["runner"] + ".py"),
                          "runner_" + traffic["runner"])
    t = time.perf_counter()
    job = run_mod.build(config, traffic, seed)
    tap = run_mod.Tap()
    sess = run_mod.session(job, tap)
    base, bank = job.weights()
    prog, trainable, warm = run_mod.first_rounds(job, sess, tap, base,
                                                 bank)
    run_mod.window(job, sess, tap, base, bank if job.space else base,
                   trainable, 0.0, warm, None, prog)
    del sess, base, bank, trainable
    line = {"seed": seed, "warm_round_s": warm,
            "program_s": time.perf_counter() - t}
    t = time.perf_counter()
    ref = run_mod.reference(job, BENCH, "f32")
    line["reference_s"] = time.perf_counter() - t
    line["program"] = compare.numbers(prog, ref)
    line["losses"] = {"program": prog.losses, "reference": ref.losses}
    if controls:
        t = time.perf_counter()
        got = run_mod.reference(job, BENCH, control_mode(config))
        line["control_s"] = time.perf_counter() - t
        line["control"] = compare.numbers(got, ref)
        line["losses"]["control"] = got.losses
        for fault in fedref.FAULTS:
            got = run_mod.reference(job, BENCH, "default", fault)
            line[fault] = compare.numbers(got, ref)
            line["losses"][fault] = got.losses
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import run as R
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, config, traffic = R.cell_files(spec, args.workload)
    R.check_devices(cell["chips"])
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        line = readings(config, traffic, seed, i < args.controls)
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
