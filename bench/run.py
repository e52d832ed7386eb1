#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything is found by name from
``BENCHMARK.json``: the cell's configuration file, its traffic file
``bench/traffic/<traffic>.json``, the runner that traffic names
(``bench/runners/<runner>.py``), and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``, ``read(run) -> float or
None``).  Adding a cell, a configuration or a metric adds files and edits
none.

With ``--trace 0`` the result line holds the cell's end-to-end metrics; with
``--trace 1`` a separately traced run gives its per-layer metrics, the
device's busy and window seconds and a breakdown.  Every run checks the
program's output against the plain reference (``correct``) and prints each
number compared beside its limit, on standard error and under ``checks``,
the last key of the result line, which is the last line of standard output.

Exit codes: 2 when JAX finds no TPU or fewer chips than the cell asks for,
1 on any other failure; neither prints a result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness.load import load_module  # noqa: E402


def check_devices(chips: int):
    """(platform, device_kind, count) of the chips JAX finds; exits 2 when
    they are not TPUs or too few."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"no TPU: JAX found platform {platform!r}", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"the cell needs {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        sys.exit(2)
    return platform, devs[0].device_kind, len(devs)


def peaks_for(kind: str):
    table = json.load(open(os.path.join(BENCH, "peaks.json")))
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device_kind {kind!r} in bench/peaks.json"
                       f" (have {sorted(table['devices'])})")
    return table["devices"][kind]


def cell_files(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(BENCH, "traffic",
                                          cell["traffic"] + ".json")))
    return cell, config, traffic


def layer_metrics(spec: dict, cell: dict, e2e: list) -> list:
    """The per-layer metrics this cell reports: those listing it, or,
    without a list, those that move an end-to-end metric it reports."""
    out = []
    for m in spec["per_layer"]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e:
            out.append(m)
    return out


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool, device=None) -> dict:
    """One run; returns the result object (without printing it)."""
    platform, kind, count = device or check_devices(cell["chips"])
    peaks = peaks_for(kind)
    runner = load_module(os.path.join(BENCH, "runners",
                                      traffic["runner"] + ".py"),
                         "runner_" + traffic["runner"])
    out_dir = os.path.join(ROOT, ".bench_out")
    os.makedirs(out_dir, exist_ok=True)
    ctx = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace,
                                cell=cell, config=config, traffic=traffic,
                                bench_dir=BENCH, out_dir=out_dir, t0=T0)
    got = runner.run(ctx)
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    metrics = {}
    if trace:
        flops = load_module(os.path.join(BENCH, "flops",
                                         config["name"] + ".py"),
                            "flops_" + config["name"].replace("-", "_"))
        run = types.SimpleNamespace(
            **got["layer_input"], chips=cell["chips"], peaks=peaks,
            flops_per_token=flops.train_flops_per_token(
                config, traffic, got["layer_input"]["loss_fraction"]))
        for m in layer_metrics(spec, cell, [x["name"] for x in e2e]):
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"),
                                 "metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            metrics[m["name"]] = {"value": got["e2e"][m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": got["memory"]["peak"]}
    if trace:
        device.update(got["device_extra"])
    result = {"correct": got["correct"], "attempted": got["attempted"],
              "failed": got["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = got["breakdown"]
    result["checks"] = got["checks"]
    result["_notes"] = got["notes"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell, config, traffic = cell_files(spec, args.workload)
    device = check_devices(cell["chips"])
    result = run_cell(spec, cell, config, traffic, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      device=device)
    for line in result.pop("_notes"):
        print(line, file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"(worst at {c['at']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
