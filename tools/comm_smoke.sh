#!/usr/bin/env bash
# Comm smoke gate: the paddle_tpu.comm gradient-sync policies must hold
# their numerics contract on a forced 8-device CPU run — none-policy
# bit-exactness, fused/hierarchical/multipath fp32-tolerance parity,
# int8 AND 2-shot int8 loss-curve closeness (2% final-loss) with error
# feedback, the 2-shot bytes crossover at n=8, real dispatch reduction
# (buckets < param count), and the comm/compute-overlap matrix: every
# policy x comm_overlap=1 parity plus a no-slower step-time leg (banked
# as a paddle_tpu.bench.v1 row). Companion to tools/lint.sh /
# serve_smoke.sh. One retry damps shared-CI scheduler noise.
set -uo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

python tools/comm_smoke.py "$@" && exit 0
echo "comm_smoke: first attempt failed; retrying once" >&2
exec python tools/comm_smoke.py "$@"
