#!/usr/bin/env bash
# Serving runtime hygiene: exec a command under the allocator and XLA
# settings that matter for a long-lived decode process.
#
#   scripts/serve_env.sh python -m repro.launch.serve --arch qwen2-7b ...
#   SERVE_DEVICES=8 scripts/serve_env.sh python benchmarks/serving.py --tiny
#
# Everything is opt-out (existing values win) and degrades gracefully on
# machines without the optional pieces.
set -euo pipefail

# tcmalloc: glibc malloc fragments badly under the steady churn of
# per-request host buffers; preload tcmalloc when the machine has it, and
# keep its large-alloc warnings out of the logs (cache pools are big).
TCMALLOC=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
if [[ -z "${LD_PRELOAD:-}" && -f "$TCMALLOC" ]]; then
  export LD_PRELOAD="$TCMALLOC"
fi
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"

# quiet TF/XLA init chatter; serving logs should be the engine's own
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# SERVE_DEVICES=N simulates an N-device host platform (useful for sharded
# serving experiments on one machine)
if [[ -n "${SERVE_DEVICES:-}" ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-}${XLA_FLAGS:+ }--xla_force_host_platform_device_count=${SERVE_DEVICES}"
fi

exec "$@"
