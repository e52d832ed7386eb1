#!/usr/bin/env bash
# Training runtime hygiene: exec a command under the allocator and XLA
# settings that matter for federated training drivers — especially the
# cohort-scan engine, whose shard loop churns large stacked host buffers.
#
#   scripts/train_env.sh python -m repro.launch.train --clients 100000 ...
#   TRAIN_DEVICES=8 scripts/train_env.sh python benchmarks/round_throughput.py
#
# Everything is opt-out (existing values win) and degrades gracefully on
# machines without the optional pieces.
set -euo pipefail

# tcmalloc: glibc malloc fragments badly under the cohort-scan shard churn
# (every shard stacks/free's client batches and opt state); preload
# tcmalloc when the machine has it, and keep its large-alloc warnings out
# of the logs (stacked shard buffers are big by design).
TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [[ -z "${LD_PRELOAD:-}" && -f "$TCMALLOC" ]]; then
  export LD_PRELOAD="$TCMALLOC"
fi
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"

# quiet TF/XLA init chatter; training logs should be the round ledger
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# TRAIN_DEVICES=N simulates an N-device host platform (client-axis sharding
# experiments — COHORT_RULES / the 512-device fixtures — on one machine)
if [[ -n "${TRAIN_DEVICES:-}" ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-}${XLA_FLAGS:+ }--xla_force_host_platform_device_count=${TRAIN_DEVICES}"
fi

exec "$@"
