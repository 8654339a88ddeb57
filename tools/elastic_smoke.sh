#!/usr/bin/env bash
# Elastic smoke gate: paddle_tpu.elastic must survive a real SIGKILL —
# a 4-process --elastic job killed mid-pass resumes on 3 survivors from
# load_latest + the paired task-master snapshot, with the comm plan
# re-factorised for the survivor topology, every dataset task processed
# exactly once across the resize, the probe-loss curve continuous, and
# the no-failure elastic run bit-identical to the fail-fast launcher.
# An armed elastic.replan fault degrades (recorded) instead of killing.
# Real-Trainer legs beside the raw-Executor ones: every rank runs
# Trainer.train(elastic=True) under comm_overlap — the
# lease owner SIGKILLed mid-pass (resize 4->3, exactly-once,
# continuity), a seeded-NaN batch skipped by the numeric guardrail
# (recorded batch_skipped + bounded rewind), and a seeded hung read
# tripping the step watchdog into one transient restart (step_hung,
# exit 75, full world back — never a wedged gang).
# Companion to tools/lint.sh / serve_smoke.sh / comm_smoke.sh /
# tune_smoke.sh. One retry damps shared-CI scheduler
# noise.
set -uo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

python tools/elastic_smoke.py "$@" && exit 0
echo "elastic_smoke: first attempt failed; retrying once" >&2
exec python tools/elastic_smoke.py "$@"
