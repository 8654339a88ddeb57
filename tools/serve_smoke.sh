#!/usr/bin/env bash
# Serving smoke gate: the online micro-batching service must return
# bit-identical outputs to direct CompiledModel.run(), really coalesce
# concurrent requests, and beat a sequential per-request loop on
# throughput — CPU tier-1, in-process, no device or sockets needed.
# Companion to tools/lint.sh (static). One retry damps shared-CI
# scheduler noise before calling a throughput loss real.
set -uo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

python tools/serve_smoke.py "$@" && exit 0
echo "serve_smoke: first attempt failed; retrying once" >&2
exec python tools/serve_smoke.py "$@"
