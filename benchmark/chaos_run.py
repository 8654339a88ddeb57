"""Chaos harness for elastic multi-host training (CPU CI form).

Proves the paddle_tpu.elastic contract end to end by actually killing
things: a 4-process ``paddle_tpu.launch --elastic`` job is SIGKILLed
mid-pass and must resume on 3 survivors from ``load_latest`` + the
paired task-master snapshot, with the comm plan re-factorised for the
survivor topology, every dataset task processed exactly once across
the resize, the loss curve continuous, and every move recorded. The
same script is the recipe for the real TPU-pod chaos run
(cluster/README.md: arm PADDLE_TPU_FAULT_SPEC / kill a pod of the
indexed Job and watch the restart resume).

Shape of the CPU simulation (the honest caveats live in
doc/elasticity.md): rank 0 is the trainer — its LOCAL virtual CPU mesh
of ``world_size`` devices stands in for the pod's (host, chip) mesh,
re-planned per generation via ``elastic.replan`` — while ranks 1..W-1
are liveness bodies (registered + heartbeating in the task master's
worker registry) standing in for the other hosts: their death is what
triggers the resize, exactly as a lost pod would. On a real pod every
rank runs the same SPMD program and a SIGKILL wedges the survivors'
collectives — which the supervisor's SIGTERM->SIGKILL drain escalation
handles identically.

Per completed task the trainer writes the task-master snapshot, then
the checkpoint, then moves the snapshot inside the checkpoint dir
(:mod:`paddle_tpu.elastic.resume` explains why every kill window then
lands on a consistent pair).

Two worker shapes share the harness (``PADDLE_TPU_CHAOS_MODE``):

- ``executor`` (the PR-8 original): rank 0 drives a raw Executor loop;
  ranks 1..W-1 are heartbeating liveness bodies.
- ``trainer`` (the real thing): EVERY rank runs
  ``Trainer.train(elastic=True)`` — the actual training loop with the
  ``comm_overlap`` step builds. Rank 0 owns the
  audited lease stream (``task_reader`` batches leased from the
  supervisor's master, checkpoints PAIRED with master snapshots);
  ranks 1..W-1 run the same code path lease-free on a local data
  stream scoped to the master's pass (on a real pod the leased batch
  shards over the mesh inside ONE SPMD program; CPU processes are
  islands, so only one rank can own the audited stream —
  doc/elasticity.md). Seeding knobs for the failure-policy legs:
  ``CHAOS_NAN_TASK=<i>`` poisons task i's batch with a NaN (the
  numeric guardrail's quarry), ``CHAOS_HANG_TASK=<i>`` wedges task
  i's read once, marker-guarded (the step watchdog's quarry),
  ``CHAOS_SLOW_RANK=<r>`` (+ ``CHAOS_SLOW_DELAY``/``CHAOS_SLOW_GENS``)
  delay-arms rank r's every ``trainer.step`` for the first N
  generations — the gray-failure detector's quarry: alive and
  heartbeating, just consistently slower than its peers.

Worker mode (spawned by the launcher):
    python benchmark/chaos_run.py worker
Driver API (used by tools/elastic_smoke.py and tests/test_elastic.py):
    run_chaos(state_dir, nprocs=4, tasks=12, kill_rank=0, kill_after=3)
    run_chaos(..., mode="trainer")
"""
from __future__ import annotations

import json
import os
import re
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

GLOBAL_BATCH = 12    # divisible by every world size the harness visits
FEATURES = 8
KEEP_LAST = 4
CHAOS_LR = 0.5
TASK_RE = re.compile(rb"^batch-(\d+)$")


def _chaos_graph():
    """The ONE chaos model both worker shapes build (fc-tanh ->
    fc-softmax -> cross-entropy mean): the parity legs compare losses
    across modes, so the graph must be impossible to edit in one place
    only. The optimizer is applied by the caller (the Trainer shape
    minimizes inside Trainer.__init__)."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    main, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main)
    pt.switch_startup_program(startup)
    x = layers.data("x", shape=[FEATURES], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=8, act="tanh",
                  param_attr=pt.ParamAttr(name="chaos_w1"))
    pred = layers.fc(h, size=2, act="softmax",
                     param_attr=pt.ParamAttr(name="chaos_w2"))
    loss = layers.mean(layers.cross_entropy(pred, y))
    return main, startup, x, y, loss


def task_payloads(n):
    return [b"batch-%d" % i for i in range(n)]


def _batch(i):
    """Deterministic batch for task i — a pure function of the payload,
    so the data stream is identical across elastic/fail-fast runs and
    across a resume."""
    import numpy as np
    rng = np.random.RandomState(1000 + i)
    x = rng.rand(GLOBAL_BATCH, FEATURES).astype("float32")
    # learnable labels (a linearly separable rule), so the loss-curve
    # continuity check has a real downward trend to assert on
    y = (x.sum(axis=1) > FEATURES / 2.0).astype("int64").reshape(-1, 1)
    return x, y


def _probe_batch():
    import numpy as np
    rng = np.random.RandomState(999)
    x = rng.rand(GLOBAL_BATCH, FEATURES).astype("float32")
    y = (x.sum(axis=1) > FEATURES / 2.0).astype("int64").reshape(-1, 1)
    return x, y


# ---------------------------------------------------------------------------
# worker


def _append_jsonl(path, row):
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")
        f.flush()
        os.fsync(f.fileno())


def _force_local_mesh(world_size):
    """MUST run before any jax import: the local virtual CPU mesh
    (world_size devices) standing in for the pod."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   flags)
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=%d" % world_size)


def worker_main():
    """One rank of the elastic job, dispatched on the harness mode."""
    world_size = int(os.environ["PADDLE_TPU_NUM_PROCESSES"])
    rank = int(os.environ["PADDLE_TPU_PROCESS_ID"])
    _force_local_mesh(world_size)
    if os.environ.get("PADDLE_TPU_CHAOS_MODE", "executor") == "trainer":
        return trainer_worker_main(world_size, rank)

    state_dir = os.environ["PADDLE_TPU_ELASTIC_STATE"]
    gen = int(os.environ.get("PADDLE_TPU_ELASTIC_GENERATION", "0"))
    addr = os.environ["PADDLE_TPU_MASTER_ADDR"]
    timeout = float(os.environ.get("PADDLE_TPU_MASTER_TIMEOUT", "60"))

    stop = {"sigterm": False}

    def on_sigterm(signum, frame):
        stop["sigterm"] = True

    signal.signal(signal.SIGTERM, on_sigterm)

    from paddle_tpu.v2 import master as v2_master
    client = v2_master.client(addr, timeout_sec=timeout,
                              worker_name="rank%d" % rank)
    try:
        if rank != 0:
            # liveness body: registered + heartbeating; waits out the
            # pass (the peers' death, not their work, is their role)
            while not stop["sigterm"]:
                c = client.counts()
                if c["todo"] + c["pending"] == 0:
                    break
                time.sleep(0.1)
            return 0
        return _trainer_main(client, state_dir, gen, world_size, stop)
    finally:
        client.close()


def _trainer_main(client, state_dir, gen, world_size, stop):
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import checkpoint as ckpt
    from paddle_tpu.elastic import replan as replan_mod
    from paddle_tpu.elastic import resume as resume_mod
    from paddle_tpu.parallel import (DistributeTranspiler,
                                     ShardingStrategy, env)

    env.world()  # validate the launcher's env the shared way
    root = os.path.join(state_dir, "ckpt")
    os.makedirs(root, exist_ok=True)
    log = os.path.join(state_dir, "losses-rank0.jsonl")

    # -- re-plan the mesh + comm for THIS world ---------------------------
    plan = replan_mod.replan(world_size).apply_flags()
    with open(os.path.join(state_dir, "plan-gen%d.json" % gen),
              "w") as f:
        json.dump(plan.summary(), f, indent=1)

    # -- the program (identical across generations and modes) -------------
    main, startup, x, y, loss = _chaos_graph()
    pt.SGD(learning_rate=CHAOS_LR).minimize(loss)

    mesh = plan.make_mesh()
    ctx = DistributeTranspiler().transpile(
        program=main, mesh=mesh,
        strategy=ShardingStrategy(data_axis="dp"))
    exe = pt.Executor(pt.CPUPlace(), dist_context=ctx)
    exe.run(startup)

    # -- cross-world resume ------------------------------------------------
    rp = resume_mod.resume(root, main, dist_context=ctx)
    step = rp.step if rp is not None and rp.step is not None else 0
    eval_prog = main.prune(feeds=["x", "y"], fetches=(loss.name,))
    px, py = _probe_batch()

    def probe():
        out, = exe.run(eval_prog, feed={"x": px, "y": py},
                       fetch_list=[loss])
        return float(np.asarray(out).reshape(-1)[0])

    # the restored model must evaluate (on the NEW mesh) like the saved
    # one did — the continuity anchor the driver asserts on
    _append_jsonl(log, {"kind": "resume", "gen": gen, "step": step,
                        "world": world_size, "probe": probe(),
                        "ckpt": rp.ckpt_dir if rp else None})
    resume_mod.record_stats(exe.stats)

    while not stop["sigterm"]:
        tid, payload = client.get_task(
            should_stop=lambda: stop["sigterm"])
        if tid is None:
            break          # pass finished
        if tid == "wait":
            continue       # only reachable when stopping
        m = TASK_RE.match(payload)
        i = int(m.group(1))
        bx, by = _batch(i)
        out, = exe.run(main, feed={"x": bx, "y": by}, fetch_list=[loss])
        loss_v = float(np.asarray(out).reshape(-1)[0])
        if not client.task_finished(tid):
            # lease lapsed (we were presumed dead): a survivor owns this
            # task now — do NOT commit it to the resumed timeline
            _append_jsonl(log, {"kind": "lease_lost", "gen": gen,
                                "task": i})
            continue
        step += 1
        # audit row BEFORE the snapshot/checkpoint pair: a SIGKILL in
        # the (commit .. row) window used to span both fsync-heavy
        # saves, and the committed task then had no row — the
        # exactly-once checker read it as LOST (~1/3 of chaos runs).
        # Written here, every kill window reconciles: no checkpoint at
        # this step -> the resume truncates the timeline at step-1 and
        # the row (like the task) rolls back with the model; a
        # checkpoint that did land keeps both
        _append_jsonl(log, {"kind": "task", "gen": gen, "step": step,
                            "task": i, "world": world_size,
                            "loss": loss_v, "probe": probe()})
        # snapshot FIRST, checkpoint second, pair third: every kill
        # window lands on a consistent (model, data-pass) point
        snap = resume_mod.snapshot_path(root, step)
        client.snapshot(snap + ".tmp")
        os.replace(snap + ".tmp", snap)
        ckpt_dir = ckpt.save_checkpoint(root, main, step=step,
                                        keep_last=KEEP_LAST)
        os.replace(snap, os.path.join(ckpt_dir, resume_mod.SNAP_IN_DIR))
    return 0


# ---------------------------------------------------------------------------
# the real-Trainer worker: every rank runs Trainer.train(elastic=True)


def _build_chaos_trainer():
    """The chaos model as a Trainer (the optimizer lands via
    Trainer.__init__'s minimize — same ONE graph as the executor leg)."""
    import paddle_tpu as pt

    main, startup, x, y, loss = _chaos_graph()
    trainer = pt.Trainer(cost=loss,
                         optimizer=pt.SGD(learning_rate=CHAOS_LR),
                         feed_list=[x, y], place=pt.CPUPlace(),
                         main_program=main, startup_program=startup)
    return trainer, loss


def trainer_worker_main(world_size, rank):
    """One rank of the real-Trainer elastic job: ``Trainer.train(
    elastic=True)`` (``comm_overlap`` etc. arrive via
    PADDLE_TPU_FLAGS). Rank 0 owns the audited lease
    stream + paired checkpoints; other ranks run the same loop
    lease-free on local batches scoped to the master's pass."""
    import numpy as np

    from paddle_tpu.core.executor import materialize_scalar

    state_dir = os.environ["PADDLE_TPU_ELASTIC_STATE"]
    gen = int(os.environ.get("PADDLE_TPU_ELASTIC_GENERATION", "0"))
    root = os.path.join(state_dir, "ckpt")
    os.makedirs(root, exist_ok=True)
    log = os.path.join(state_dir, "losses-rank0.jsonl")

    # gray-failure lever: ONE rank runs every step through an armed
    # trainer.step delay for the first CHAOS_SLOW_GENS generations —
    # alive, answering, heartbeating, just consistently slow (the
    # failure binary health cannot see). Generation-gated so the story
    # completes: gen 0 slow -> condemned -> transient restart; gen 1
    # still slow -> budget spent -> demoted to a resize; the resized
    # gang runs clean and step time recovers. Armed in-process because
    # the launcher's env is rank-uniform — only the rank itself knows
    # whether it is the slow one.
    slow_rank = int(os.environ.get("CHAOS_SLOW_RANK", "-1"))
    slow_gens = int(os.environ.get("CHAOS_SLOW_GENS", "2"))
    if rank == slow_rank and gen < slow_gens:
        from paddle_tpu import resilience
        resilience.arm("trainer.step", "delay", nth=1, times=None,
                       delay=float(os.environ.get("CHAOS_SLOW_DELAY",
                                                  "1.0")))

    trainer, loss = _build_chaos_trainer()
    eval_prog = trainer.main_program.prune(feeds=["x", "y"],
                                           fetches=(loss.name,))
    px, py = _probe_batch()

    def probe():
        out, = trainer.exe.run(eval_prog, feed={"x": px, "y": py},
                               fetch_list=[loss])
        return float(np.asarray(out).reshape(-1)[0])

    if rank != 0:
        # same Trainer.train(elastic=True) code path, lease-free: a
        # local data stream scoped to the master's pass (the rank still
        # registers + heartbeats through the worker role)
        from paddle_tpu.v2 import master as v2_master
        poll = v2_master.client(
            os.environ["PADDLE_TPU_MASTER_ADDR"],
            timeout_sec=float(os.environ.get("PADDLE_TPU_MASTER_TIMEOUT",
                                             "60")))

        def body_reader():
            i = 0
            while True:
                c = poll.counts()
                if c["todo"] + c["pending"] == 0:
                    return
                bx, by = _batch(10_000 + 100 * rank + (i % 50))
                yield list(zip(bx, by))
                i += 1
                # liveness bodies exercise the loop, they don't race it:
                # unthrottled they starve rank 0 of CPU and flood the
                # log with their own progress lines
                time.sleep(0.05)

        try:
            trainer.train(body_reader, num_passes=1, elastic=True)
        finally:
            poll.close()
        return 0

    nan_task = int(os.environ.get("CHAOS_NAN_TASK", "-1"))
    hang_task = int(os.environ.get("CHAOS_HANG_TASK", "-1"))
    hang_marker = os.path.join(state_dir, "hang-fired")

    def task_reader(payload):
        i = int(TASK_RE.match(payload).group(1))
        if i == hang_task and not os.path.exists(hang_marker):
            # a stalled reader, once (the marker survives the restart):
            # the step watchdog must turn this into exit 75
            with open(hang_marker, "w") as f:
                f.write("1")
            time.sleep(3600)
        bx, by = _batch(i)
        if i == nan_task:
            bx = bx.copy()
            bx[0, 0] = np.nan
        return list(zip(bx, by))

    def on_resume(worker):
        _append_jsonl(log, {"kind": "resume", "gen": gen,
                            "step": worker.step, "world": world_size,
                            "probe": probe()})

    def on_commit(step, tid, payload, cost):
        i = int(TASK_RE.match(payload).group(1))
        # audit row AFTER the lease commit, BEFORE the paired
        # snapshot/checkpoint (the PR-13 kill-window reconciliation)
        _append_jsonl(log, {"kind": "task", "gen": gen, "step": step,
                            "task": i, "world": world_size,
                            "loss": materialize_scalar(cost),
                            "probe": probe()})

    def on_skip(tid, payload):
        i = int(TASK_RE.match(payload).group(1))
        _append_jsonl(log, {"kind": "skip", "gen": gen, "task": i,
                            "world": world_size})

    trainer.train(elastic=True, task_reader=task_reader,
                  elastic_root=root, on_resume=on_resume,
                  on_commit=on_commit, on_skip=on_skip,
                  num_passes=1)
    return 0


# ---------------------------------------------------------------------------
# driver


def _read_jsonl(path):
    rows = []
    if not os.path.exists(path):
        return rows
    with open(path) as f:
        for ln in f:
            try:
                rows.append(json.loads(ln))
            except ValueError:
                pass  # torn final line from a kill mid-write
    return rows


def _worker_env(state_dir, policy, fault_spec, mode="executor",
                flags=None, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PADDLE_TPU_FAULT_SPEC", None)
    if fault_spec:
        env["PADDLE_TPU_FAULT_SPEC"] = fault_spec
    kv = {"comm_policy": policy}
    kv.update(flags or {})
    env["PADDLE_TPU_FLAGS"] = ",".join(
        "%s=%s" % (k, v) for k, v in sorted(kv.items()))
    env["PADDLE_TPU_ELASTIC_STATE"] = state_dir
    env["PADDLE_TPU_CHAOS_MODE"] = mode
    # only rank 0 leases-and-audits in this harness, so the job-start
    # schedule-fingerprint exchange (elastic.fingerprints) may not
    # complete — cap its wait so each generation pays ~2s for the
    # recorded-incomplete advisory instead of the pod-scale timeout
    env["PADDLE_TPU_FINGERPRINT_TIMEOUT"] = "2"
    env.update(extra_env or {})
    return env


def run_chaos(state_dir, nprocs=4, tasks=12, kill_rank=0, kill_after=3,
              elastic=True, policy="hierarchical", fault_spec=None,
              min_workers=2, grace_sec=15.0, timeout=900.0,
              mode="executor", flags=None, extra_env=None,
              restart_budget=1, gray_ratio=None, gray_budget=None):
    """Run one chaos scenario; returns the report dict the checkers
    consume. ``kill_rank=None`` runs failure-free (the parity leg);
    ``elastic=False`` runs the same script under the fail-fast
    launcher (the bit-parity reference); ``mode="trainer"`` runs every
    rank through ``Trainer.train(elastic=True)`` (``flags`` adds
    PADDLE_TPU_FLAGS entries — comm_overlap, step_timeout_s,
    loss_skip_budget — and ``extra_env`` the seeding knobs, including
    CHAOS_SLOW_RANK/_DELAY/_GENS for the gray-failure leg).
    ``gray_ratio``/``gray_budget`` arm the supervisor's gray-failure
    sweep over the workers' step-time heartbeats."""
    from paddle_tpu.launch import launch, launch_elastic

    os.makedirs(state_dir, exist_ok=True)
    env = _worker_env(state_dir, policy, fault_spec, mode=mode,
                      flags=flags, extra_env=extra_env)
    argv = [os.path.join(REPO, "benchmark", "chaos_run.py"), "worker"]
    payloads = task_payloads(tasks)
    box = {}

    def supervise():
        try:
            if elastic:
                box["rc"] = launch_elastic(
                    nprocs, "127.0.0.1", argv, env=env,
                    grace_sec=grace_sec, min_workers=min_workers,
                    restart_budget=restart_budget, state_dir=state_dir,
                    master_tasks=payloads, master_timeout_sec=60.0,
                    snapshot_root=os.path.join(state_dir, "ckpt"),
                    gray_ratio=gray_ratio, gray_budget=gray_budget)
            else:
                box["rc"] = launch(
                    nprocs, "127.0.0.1:0", argv, env=env,
                    grace_sec=grace_sec, master_tasks=payloads,
                    master_timeout_sec=60.0)
        except BaseException as e:          # surfaced by the caller
            box["error"] = e

    t = threading.Thread(target=supervise, daemon=True)
    t.start()

    killed = None
    log = os.path.join(state_dir, "losses-rank0.jsonl")
    deadline = time.time() + timeout
    while t.is_alive() and time.time() < deadline:
        if kill_rank is not None and killed is None:
            done_tasks = [r for r in _read_jsonl(log)
                          if r.get("kind") == "task"
                          and r.get("gen") == 0]
            if len(done_tasks) >= kill_after:
                gen_state = os.path.join(state_dir, "workers-gen0.json")
                try:
                    with open(gen_state) as f:
                        pids = json.load(f)["pids"]
                    os.kill(pids[str(kill_rank)], signal.SIGKILL)
                    killed = {"rank": kill_rank,
                              "after_tasks": len(done_tasks)}
                except (OSError, KeyError, ValueError):
                    pass  # already gone / state mid-write: retry
        t.join(timeout=0.05)
    if t.is_alive():
        raise RuntimeError("chaos run did not finish within %.0fs"
                           % timeout)
    if "error" in box:
        raise box["error"]

    plans = {}
    heartbeats = {}
    for fn in sorted(os.listdir(state_dir)):
        m = re.match(r"^plan-gen(\d+)\.json$", fn)
        if m:
            with open(os.path.join(state_dir, fn)) as f:
                plans[int(m.group(1))] = json.load(f)
        m = re.match(r"^heartbeat-rank(\d+)\.json$", fn)
        if m:
            try:
                with open(os.path.join(state_dir, fn)) as f:
                    heartbeats[int(m.group(1))] = json.load(f)
            except (OSError, ValueError):
                pass  # torn final write from a stopped worker
    return {
        "rc": box["rc"],
        "killed": killed,
        "rows": _read_jsonl(log),
        "events": _read_jsonl(os.path.join(state_dir, "events.jsonl")),
        "plans": plans,
        "heartbeats": heartbeats,
        "tasks": tasks,
        "nprocs": nprocs,
    }


# -- checkers (shared by the smoke gate and the tests) ----------------------

def effective_timeline(rows):
    """The rows that survive into the resumed timeline: a later
    generation's resume step TRUNCATES every earlier generation at that
    step (post-checkpoint partial work was rolled back with the model
    state)."""
    gens = sorted({r["gen"] for r in rows})
    cut = {}
    for g in gens:
        for r in rows:
            if r["gen"] == g and r["kind"] == "resume":
                for g0 in gens:
                    if g0 < g:
                        cut[g0] = min(cut.get(g0, r["step"]), r["step"])
    out = []
    for r in rows:
        if r["kind"] != "task":
            continue
        if r["gen"] in cut and r["step"] > cut[r["gen"]]:
            continue
        out.append(r)
    return sorted(out, key=lambda r: r["step"])


def check_exactly_once(report):
    """Every dataset task processed exactly once across the resize, and
    the step sequence contiguous from 1."""
    eff = effective_timeline(report["rows"])
    seen = [r["task"] for r in eff]
    want = list(range(report["tasks"]))
    problems = []
    if sorted(seen) != want:
        from collections import Counter
        c = Counter(seen)
        dup = sorted(t for t, n in c.items() if n > 1)
        lost = sorted(set(want) - set(c))
        problems.append("task multiset mismatch: duplicated=%r lost=%r"
                        % (dup, lost))
    steps = [r["step"] for r in eff]
    if steps != list(range(1, len(steps) + 1)):
        problems.append("steps not contiguous from 1: %r" % (steps,))
    return problems


def check_continuity(report, tol=1e-4):
    """Each resumed generation's restored model must evaluate the fixed
    probe batch like the saved model did (re-sharded onto the smaller
    mesh — only fp reassociation may differ)."""
    rows = report["rows"]
    problems = []
    by_step = {r["step"]: r for r in rows if r["kind"] == "task"}
    for r in rows:
        if r["kind"] != "resume" or r["gen"] == 0 or r["step"] == 0:
            continue
        prev = by_step.get(r["step"])
        if prev is None:
            problems.append("resume at step %d has no matching task row"
                            % r["step"])
            continue
        rel = abs(r["probe"] - prev["probe"]) / max(abs(prev["probe"]),
                                                    1e-9)
        if rel > tol:
            problems.append(
                "probe loss discontinuous at resume step %d: %.8f -> "
                "%.8f (rel %.2e > %.0e)" % (r["step"], prev["probe"],
                                            r["probe"], rel, tol))
    # trend: per-task training loss compares DIFFERENT batches, so the
    # downward trend is asserted on the fixed probe batch instead —
    # initial model vs final model on the same data
    eff = effective_timeline(rows)
    if eff:
        start = next((r["probe"] for r in rows
                      if r["kind"] == "resume" and r["gen"] == 0),
                     eff[0]["probe"])
        if not eff[-1]["probe"] < start:
            problems.append("probe loss did not decrease across the "
                            "run: %.6f -> %.6f" % (start,
                                                   eff[-1]["probe"]))
    return problems


def check_replan(report):
    """The comm plan must be re-factorised for the survivor topology."""
    plans = report["plans"]
    problems = []
    if 0 not in plans:
        return ["no plan recorded for generation 0"]
    gens = sorted(plans)
    for g in gens[1:]:
        a, b = plans[gens[0]], plans[g]
        if b["world_size"] >= a["world_size"]:
            problems.append("generation %d world %d did not shrink from "
                            "%d" % (g, b["world_size"], a["world_size"]))
        if b["cache_signature"] == a["cache_signature"]:
            problems.append("generation %d comm cache signature did not "
                            "change — a stale compile could be hit" % g)
        if not b["degraded"] and b["hosts"] != b["world_size"]:
            problems.append("generation %d hosts=%d != world=%d"
                            % (g, b["hosts"], b["world_size"]))
    return problems


def check_parity(elastic_report, plain_report):
    """The no-failure elastic run must be bit-identical to the
    fail-fast run of the same script."""
    a = [(r["step"], r["task"], r["loss"], r["probe"])
         for r in elastic_report["rows"] if r["kind"] == "task"]
    b = [(r["step"], r["task"], r["loss"], r["probe"])
         for r in plain_report["rows"] if r["kind"] == "task"]
    if a != b:
        return ["elastic-off vs elastic-on (no failure) rows differ: "
                "%d vs %d rows, first mismatch %r"
                % (len(a), len(b),
                   next((p for p in zip(a, b) if p[0] != p[1]), None))]
    return []


def check_guardrail(report, seeded_task):
    """Seeded-NaN leg: the seeded batch is SKIPPED (skip row +
    batch_skipped event), every task is accounted exactly once across
    task/skip rows, any checkpoint rewind is bounded (one per budget
    window), and the pass completes with a finite, decreasing probe."""
    rows = report["rows"]
    problems = []
    tasks = [r["task"] for r in rows if r["kind"] == "task"]
    skips = [r["task"] for r in rows if r["kind"] == "skip"]
    if seeded_task not in skips:
        problems.append("seeded task %d was not skipped (skips=%r)"
                        % (seeded_task, sorted(skips)))
    if seeded_task in tasks:
        problems.append("seeded task %d also COUNTED as a good step"
                        % seeded_task)
    want = list(range(report["tasks"]))
    if sorted(tasks + skips) != want:
        problems.append("task+skip multiset mismatch: got %r"
                        % sorted(tasks + skips))
    if not [e for e in report["events"]
            if e["kind"] == "batch_skipped"]:
        problems.append("no batch_skipped event recorded")
    rewinds = [e for e in report["events"]
               if e["kind"] == "guard_rewind"]
    if len(rewinds) > 2:
        problems.append("%d guard rewinds — the once-per-window bound "
                        "looks broken" % len(rewinds))
    good = [r for r in rows if r["kind"] == "task"]
    if good:
        import math
        last = good[-1]["probe"]
        if not math.isfinite(last):
            problems.append("final probe loss is not finite: %r" % last)
        start = next((r["probe"] for r in rows
                      if r["kind"] == "resume" and r["gen"] == 0),
                     good[0]["probe"])
        if not last < start:
            problems.append("probe loss did not decrease despite the "
                            "skip policy: %.6f -> %.6f" % (start, last))
    else:
        problems.append("no good steps survived the seeded NaN")
    return problems


def check_watchdog(report):
    """Seeded-hang leg: the watchdog turned the wedged step into a
    TRANSIENT restart — step_hung recorded, exactly one
    elastic_restart, NO resize (full world came back) — and the
    resumed pass still processed every task exactly once."""
    problems = []
    if not [e for e in report["events"] if e["kind"] == "step_hung"]:
        problems.append("no step_hung event recorded")
    restarts = [e for e in report["events"]
                if e["kind"] == "elastic_restart"]
    if len(restarts) != 1:
        problems.append("expected exactly 1 elastic_restart, got %d"
                        % len(restarts))
    resizes = [e for e in report["events"]
               if e["kind"] == "elastic_resize"]
    if resizes:
        problems.append("a hang must restart at FULL world, but the "
                        "job resized: %r" % (resizes,))
    problems.extend(check_exactly_once(report))
    return problems


def check_grayfail(report, slow_rank, delay_s):
    """Slow-rank leg: the delay-armed rank was condemned by latency
    skew alone (it never crashed), mitigated on the budget — exactly
    one transient restart, then the recurrence demoted it to a resize
    — the pass still completed exactly-once, and the final
    generation's step time recovered (well under the injected
    delay)."""
    problems = []
    events = report["events"]
    if not [e for e in events if e["kind"] == "gray_suspected"]:
        problems.append("no gray_suspected recorded")
    mit = [e for e in events if e["kind"] == "gray_mitigated"]
    restarts = [e for e in mit if e.get("action") == "restart"]
    resizes = [e for e in mit if e.get("action") == "resize"]
    if len(restarts) != 1:
        problems.append("expected exactly 1 gray restart, got %d"
                        % len(restarts))
    if len(resizes) != 1:
        problems.append("expected exactly 1 gray resize (budget-spent "
                        "recurrence), got %d" % len(resizes))
    for e in restarts + resizes:
        if e.get("rank") != slow_rank:
            problems.append("gray mitigation condemned rank %r, the "
                            "armed slow rank is %d" % (e.get("rank"),
                                                       slow_rank))
    # the rank was SLOW, never dead: no worker-exit classification ran
    if [e for e in events if e["kind"] == "elastic_worker_exit"]:
        problems.append("an elastic_worker_exit fired — the gray leg "
                        "must mitigate a LIVE rank")
    gens = [e["generation"] for e in events
            if e["kind"] == "elastic_generation"]
    hb = report.get("heartbeats", {})
    final = [h for h in hb.values() if h.get("generation") == max(gens)]
    if not final:
        problems.append("no final-generation heartbeats to prove "
                        "recovery")
    else:
        worst = max(h["step_ms_ewma"] for h in final)
        if worst > delay_s * 1e3 / 2.0:
            problems.append("step time did not recover after the "
                            "resize: worst EWMA %.0fms vs injected "
                            "delay %.0fms" % (worst, delay_s * 1e3))
    problems.extend(check_exactly_once(report))
    return problems


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "worker":
        return worker_main()
    # standalone driver: one kill-one-of-four chaos scenario
    import tempfile
    state = tempfile.mkdtemp(prefix="chaos_run_")
    report = run_chaos(state)
    problems = (check_exactly_once(report) + check_continuity(report)
                + check_replan(report))
    if report["rc"] != 0:
        problems.append("job exit code %d" % report["rc"])
    resizes = [e for e in report["events"]
               if e["kind"] == "elastic_resize"]
    print(json.dumps({"ok": not problems, "rc": report["rc"],
                      "state_dir": state, "killed": report["killed"],
                      "resizes": len(resizes),
                      "problems": problems}, indent=1))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
