#!/usr/bin/env bash
# Tune smoke gate: the paddle_tpu.tune autotune loop must complete in
# pallas interpret mode with the deterministic injectable timer on one
# conv and one attention shape, cache a CRC-valid winner, isolate an
# injected per-candidate fault, detect (and re-tune past) a corrupted
# cache entry, and dispatch must honor the cache switch — fallbacks
# recorded with tune=0, hits with the cache armed. Runs against a
# throwaway cache dir. Companion to tools/lint.sh / serve_smoke.sh /
# comm_smoke.sh. One retry damps shared-CI scheduler noise.
set -uo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

python tools/tune_smoke.py "$@" && exit 0
echo "tune_smoke: first attempt failed; retrying once" >&2
exec python tools/tune_smoke.py "$@"
