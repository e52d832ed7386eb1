#!/usr/bin/env bash
# Observability smoke: one tiny train run and one tiny serve run with every
# obs flag on, then hold the emitted artifacts to the acceptance bar:
#   * the Chrome trace is valid JSON carrying the expected measured spans
#     (round/dispatch/stack/launch/combine/wait/account/checkpoint,
#     admit/decode), compile events,
#     AND the synthetic simulated timeline (sim.round/sim.client);
#   * the drift ledger has exactly one row per round, each priced by the
#     fleet predictor with a finite ratio;
#   * the metrics JSONL parses and carries the train/serve counters.
# Run from anywhere.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

TMP=$(mktemp -d)
trap 'rm -rf "$TMP"' EXIT
ROUNDS=2

echo "== train (tiny, traced, simulated fleet, drift monitored) =="
scripts/train_env.sh python -m repro.launch.train \
    --arch distilbert-mlm --clients 3 --rounds "$ROUNDS" --docs 40 \
    --batch-size 2 --seq-len 32 --max-steps-per-round 2 \
    --fleet paper-2080ti --ckpt-dir "$TMP/ckpt" \
    --ledger-out "$TMP/ledger.json" \
    --trace-out "$TMP/train_trace.json" \
    --metrics-out "$TMP/train_metrics.jsonl" \
    --drift-out "$TMP/train_drift.json" --drift-warn 1000

echo "== serve (tiny, traced, decode-step drift) =="
bash scripts/serve_env.sh python -m repro.launch.serve \
    --arch qwen2-7b --requests 4 --slots 2 --prompt-len 8 --tokens 6 \
    --trace-out "$TMP/serve_trace.json" \
    --metrics-out "$TMP/serve_metrics.jsonl" \
    --drift-out "$TMP/serve_drift.json" --drift-warn 100000

echo "== artifact assertions =="
python - "$TMP" "$ROUNDS" <<'EOF'
import json, sys
tmp, rounds = sys.argv[1], int(sys.argv[2])

# -- train trace: measured + simulated spans in one Perfetto timeline ----
trace = json.load(open(f"{tmp}/train_trace.json"))
assert trace.get("displayTimeUnit") == "ms", "not a Chrome trace payload"
events = trace["traceEvents"]
names = {e.get("name") for e in events}
for want in ("train.round", "train.prepare", "train.dispatch", "train.stack",
             "train.launch", "train.combine", "train.wait", "train.account",
             "train.checkpoint", "sim.round", "sim.client"):
    assert want in names, f"train trace missing span {want!r}"
assert any(n and n.startswith("compile/") for n in names), \
    "train trace carries no compile events"
n_rounds = sum(1 for e in events if e.get("name") == "train.round")
assert n_rounds == rounds, f"{n_rounds} train.round spans != {rounds}"
pids = {e.get("pid") for e in events if e.get("ph") == "X"}
assert {1, 2} <= pids, "measured and simulated lanes must both be present"

# -- drift ledger: one fleet-priced row per round -----------------------
drift = json.load(open(f"{tmp}/train_drift.json"))
assert drift["n_rows"] == rounds, \
    f"drift ledger has {drift['n_rows']} rows, want {rounds}"
for row in drift["rows"]:
    assert row["source"] == "fleet", f"row priced by {row['source']!r}"
    assert row["ratio"] is not None and row["ratio"] > 0

# -- metrics JSONL: parses, carries the train counters ------------------
train_metrics = {json.loads(l)["name"]: json.loads(l)
                 for l in open(f"{tmp}/train_metrics.jsonl") if l.strip()}
assert train_metrics["train.rounds"]["value"] == rounds
assert train_metrics["train.round_s"]["count"] == rounds
assert train_metrics["compile.events"]["value"] > 0

# -- serve artifacts ----------------------------------------------------
strace = json.load(open(f"{tmp}/serve_trace.json"))
snames = {e.get("name") for e in strace["traceEvents"]}
for want in ("serve.admit", "serve.decode_step"):
    assert want in snames, f"serve trace missing span {want!r}"
sdrift = json.load(open(f"{tmp}/serve_drift.json"))
assert sdrift["n_rows"] == 1 and sdrift["rows"][0]["phase"] == "decode_step"
serve_metrics = {json.loads(l)["name"]: json.loads(l)
                 for l in open(f"{tmp}/serve_metrics.jsonl") if l.strip()}
assert serve_metrics["serve.admits"]["value"] >= 4
assert serve_metrics["serve.decode_steps"]["value"] > 0

print(f"obs smoke OK: {len(events)} train events ({n_rounds} rounds, "
      f"sim lane present), {len(strace['traceEvents'])} serve events, "
      f"drift rows {drift['n_rows']}+{sdrift['n_rows']}, metrics "
      f"{len(train_metrics)}+{len(serve_metrics)}")
EOF
