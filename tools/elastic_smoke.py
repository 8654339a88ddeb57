"""Elastic smoke gate (CPU CI): the paddle_tpu.elastic contract must
hold on a real multi-process chaos run —

(a) **survive-and-resize**: a 4-process ``--elastic`` job whose TRAINER
    rank is SIGKILLed mid-pass resumes on the 3 survivors from
    ``load_latest`` + the paired task-master snapshot: exit 0, exactly
    one ``elastic_resize`` (4 -> 3) recorded;
(b) **re-plan**: the survivor generation's comm plan is re-factorised
    for the new topology (world/hosts shrink, the comm cache signature
    changes so a stale compile cannot be hit);
(c) **exactly-once**: every dataset task lands in the resumed timeline
    exactly once — none double-processed, none lost — with contiguous
    steps across the resize;
(d) **continuity**: the restored model evaluates the fixed probe batch
    like the saved one did (re-sharded dp=4 -> dp=3), and the loss
    trend survives the resize;
(e) **bit-parity**: the no-failure ``--elastic`` run is bit-identical
    to the same job under the fail-fast launcher;
(f) **fault site**: an armed ``elastic.replan`` raise degrades the plan
    to the flat factorisation (recorded) and the job still completes
    with every task processed.

The REAL-TRAINER legs (``mode="trainer"``: every rank runs
``Trainer.train(elastic=True)`` under ``comm_overlap`` — the PR-8 protocol spoken by the actual loop):

(g) **trainer chaos**: rank 0 (the lease owner) SIGKILLed mid-pass —
    resize 4 -> 3, every task exactly once, probe-loss continuity at
    the paired resume;
(h) **numeric guardrail**: a seeded non-finite batch is SKIPPED
    (recorded ``batch_skipped``), the poisoned window rewinds to the
    last paired checkpoint (bounded), and the pass completes with a
    decreasing probe;
(i) **step watchdog**: a seeded hung read trips ``step_timeout_s`` —
    recorded ``step_hung``, exit 75, exactly one TRANSIENT supervisor
    restart at full world (never a resize, never a wedged gang), every
    task still exactly once;
(j) **gray failure**: one rank is delay-armed SLOW (``CHAOS_SLOW_RANK``
    — alive, exiting 0, just 30x over the gang median) — the
    supervisor's SkewDetector condemns it from step-time heartbeats,
    spends its one transient restart, then demotes the recurrence to
    permanent (clean resize 3 -> 2), the pass completes exactly-once
    and step time recovers; the healthy legs above double as the flap
    pin: gray detection armed on (j) never fires on a well-behaved
    gang (checked inside the leg — gen-2 post-resize world is
    slow-free and records nothing).

The measurement lives in benchmark/chaos_run.py — the same harness an
operator points at a real TPU pod (cluster/README.md). Companion to
tools/{lint,serve_smoke,comm_smoke,tune_smoke}.sh. Exit 0
on pass, 1 on failure; prints a one-line JSON summary either way.

Invoked by tools/elastic_smoke.sh; usable directly:
    JAX_PLATFORMS=cpu python tools/elastic_smoke.py
"""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import benchmark.chaos_run as cr

    failures = []

    # (a)-(d): kill one of four mid-pass
    chaos_state = tempfile.mkdtemp(prefix="elastic_smoke_chaos_")
    chaos = cr.run_chaos(chaos_state, nprocs=4, tasks=12, kill_rank=0,
                         kill_after=3, timeout=600)
    if chaos["rc"] != 0:
        failures.append("chaos leg exit code %d" % chaos["rc"])
    if chaos["killed"] is None:
        failures.append("chaos leg never fired its kill (pass finished "
                        "before %d tasks?)" % 3)
    resizes = [e for e in chaos["events"]
               if e["kind"] == "elastic_resize"]
    if len(resizes) != 1:
        failures.append("expected exactly 1 elastic_resize event, got %d"
                        % len(resizes))
    elif not (resizes[0]["from_world"] == 4
              and resizes[0]["to_world"] == 3):
        failures.append("resize was %r, want 4 -> 3" % (resizes[0],))
    for name, probs in (("exactly_once", cr.check_exactly_once(chaos)),
                        ("continuity", cr.check_continuity(chaos)),
                        ("replan", cr.check_replan(chaos))):
        for p in probs:
            failures.append("%s: %s" % (name, p))

    # (e): no-failure elastic run bit-identical to fail-fast
    par_e = cr.run_chaos(tempfile.mkdtemp(prefix="elastic_smoke_on_"),
                         nprocs=4, tasks=6, kill_rank=None, elastic=True,
                         timeout=420)
    par_p = cr.run_chaos(tempfile.mkdtemp(prefix="elastic_smoke_off_"),
                         nprocs=4, tasks=6, kill_rank=None,
                         elastic=False, timeout=420)
    if par_e["rc"] != 0 or par_p["rc"] != 0:
        failures.append("parity legs exit codes %d / %d"
                        % (par_e["rc"], par_p["rc"]))
    for p in cr.check_parity(par_e, par_p):
        failures.append("parity: %s" % p)

    # (f): armed elastic.replan degrades, never kills
    flt = cr.run_chaos(tempfile.mkdtemp(prefix="elastic_smoke_fault_"),
                       nprocs=2, tasks=4, kill_rank=None, elastic=True,
                       fault_spec="elastic.replan:raise:nth=1",
                       timeout=300)
    if flt["rc"] != 0:
        failures.append("fault leg exit code %d" % flt["rc"])
    plan0 = flt["plans"].get(0, {})
    if not plan0.get("degraded") or plan0.get("hosts") != 1:
        failures.append("armed elastic.replan did not degrade the plan "
                        "to hosts=1: %r" % (plan0,))
    for p in cr.check_exactly_once(flt):
        failures.append("fault leg exactly_once: %s" % p)

    # (g): the REAL Trainer as elastic worker — every rank runs
    # Trainer.train(elastic=True) under comm_overlap;
    # the lease-owning rank is SIGKILLed mid-pass
    tleg = cr.run_chaos(
        tempfile.mkdtemp(prefix="elastic_smoke_trainer_"),
        nprocs=4, tasks=10, kill_rank=0, kill_after=2, elastic=True,
        mode="trainer", flags={"comm_overlap": 1}, timeout=600)
    if tleg["rc"] != 0:
        failures.append("trainer leg exit code %d" % tleg["rc"])
    if tleg["killed"] is None:
        failures.append("trainer leg never fired its kill")
    tresizes = [e for e in tleg["events"]
                if e["kind"] == "elastic_resize"]
    if len(tresizes) != 1 or tresizes[0]["from_world"] != 4 \
            or tresizes[0]["to_world"] != 3:
        failures.append("trainer leg resize was %r, want exactly one "
                        "4 -> 3" % (tresizes,))
    for name, probs in (
            ("exactly_once", cr.check_exactly_once(tleg)),
            ("continuity", cr.check_continuity(tleg)),
            ("replan", cr.check_replan(tleg))):
        for p in probs:
            failures.append("trainer %s: %s" % (name, p))

    # (h): seeded non-finite batch -> guardrail skip + bounded rewind
    nan = cr.run_chaos(
        tempfile.mkdtemp(prefix="elastic_smoke_nan_"),
        nprocs=2, tasks=8, kill_rank=None, elastic=True,
        mode="trainer",
        flags={"comm_overlap": 1, "loss_skip_budget": 2},
        extra_env={"CHAOS_NAN_TASK": "3"}, timeout=420)
    if nan["rc"] != 0:
        failures.append("nan leg exit code %d" % nan["rc"])
    for p in cr.check_guardrail(nan, 3):
        failures.append("nan leg: %s" % p)

    # (i): seeded hung read -> watchdog -> transient restart, no wedge
    hang = cr.run_chaos(
        tempfile.mkdtemp(prefix="elastic_smoke_hang_"),
        nprocs=2, tasks=6, kill_rank=None, elastic=True,
        mode="trainer",
        flags={"comm_overlap": 1, "step_timeout_s": 5},
        extra_env={"CHAOS_HANG_TASK": "2"}, timeout=480,
        restart_budget=1)
    if hang["rc"] != 0:
        failures.append("hang leg exit code %d" % hang["rc"])
    for p in cr.check_watchdog(hang):
        failures.append("hang leg: %s" % p)

    # (j): delay-armed slow rank -> gray condemned -> one transient
    # restart -> recurrence resized away -> clean completion.
    # CHAOS_SLOW_GENS=2 keeps the lever armed through the restart so
    # the budget-spent path (demote to permanent) is exercised too;
    # generation 2 runs slow-free and must record no gray events.
    gray = cr.run_chaos(
        tempfile.mkdtemp(prefix="elastic_smoke_gray_"),
        nprocs=3, tasks=12, kill_rank=None, elastic=True,
        mode="trainer", min_workers=2, gray_ratio=3.0, gray_budget=1,
        extra_env={"CHAOS_SLOW_RANK": "0", "CHAOS_SLOW_DELAY": "2.0",
                   "CHAOS_SLOW_GENS": "2"}, timeout=480)
    if gray["rc"] != 0:
        failures.append("gray leg exit code %d" % gray["rc"])
    for p in cr.check_grayfail(gray, slow_rank=0, delay_s=2.0):
        failures.append("gray leg: %s" % p)
    for p in cr.check_exactly_once(gray):
        failures.append("gray leg exactly_once: %s" % p)

    eff = cr.effective_timeline(chaos["rows"])
    summary = {
        "ok": not failures,
        "chaos_rc": chaos["rc"],
        "killed": chaos["killed"],
        "resize": ({"from": resizes[0]["from_world"],
                    "to": resizes[0]["to_world"],
                    "requeued": resizes[0].get("requeued_tasks")}
                   if resizes else None),
        "tasks_processed": len(eff),
        "resume_step": next((r["step"] for r in chaos["rows"]
                             if r["kind"] == "resume" and r["gen"] > 0),
                            None),
        "parity_rows": len([r for r in par_e["rows"]
                            if r["kind"] == "task"]),
        "fault_plan_degraded": bool(plan0.get("degraded")),
        "trainer_rc": tleg["rc"],
        "trainer_resize": ({"from": tresizes[0]["from_world"],
                            "to": tresizes[0]["to_world"]}
                           if tresizes else None),
        "nan_skips": len([r for r in nan["rows"]
                          if r["kind"] == "skip"]),
        "nan_rewinds": len([e for e in nan["events"]
                            if e["kind"] == "guard_rewind"]),
        "hang_restarts": len([e for e in hang["events"]
                              if e["kind"] == "elastic_restart"]),
        "gray_mitigations": [
            (e.get("action"), e.get("rank")) for e in gray["events"]
            if e["kind"] == "gray_mitigated"],
        "state_dir": chaos_state,
    }
    print(json.dumps(summary))
    if failures:
        for f in failures:
            print("elastic_smoke FAIL: %s" % f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
