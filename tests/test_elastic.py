"""Elastic multi-host training (paddle_tpu.elastic): supervisor
classify/restart/resize semantics over real OS processes, mesh/comm
re-planning for survivor worlds, checkpoint <-> task-master-snapshot
resume pairing, the v2 master's crash re-queue contract from the RPC
(multi-process) side, launcher env validation, and the load_latest
prune-race fallthrough the supervisor's resume path exercises. The full
kill-one-of-four chaos acceptance is tools/elastic_smoke.sh (and the
slow test at the bottom)."""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import checkpoint, layers
from paddle_tpu import resilience as R
from paddle_tpu.elastic import replan as replan_mod
from paddle_tpu.elastic import resume as resume_mod
from paddle_tpu.elastic.supervisor import ElasticSupervisor
from paddle_tpu.flags import FLAGS, flags_guard
from paddle_tpu.launch import launch
from paddle_tpu.parallel import env as penv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# parallel/env.py: validated world


def test_world_parses_and_validates():
    w = penv.world({"PADDLE_TPU_COORDINATOR": "h:1",
                    "PADDLE_TPU_NUM_PROCESSES": "4",
                    "PADDLE_TPU_PROCESS_ID": "3",
                    "PADDLE_TPU_ELASTIC": "1",
                    "PADDLE_TPU_ELASTIC_GENERATION": "2"})
    assert w == ("h:1", 4, 3, True, 2)
    # unset stays None (the TPU-pod auto-detect path)
    w0 = penv.world({})
    assert w0.num_processes is None and w0.process_id is None
    assert not w0.elastic and w0.generation == 0


@pytest.mark.parametrize("env,frag", [
    ({"PADDLE_TPU_NUM_PROCESSES": "four",
      "PADDLE_TPU_PROCESS_ID": "0"}, "not an integer"),
    ({"PADDLE_TPU_NUM_PROCESSES": "0",
      "PADDLE_TPU_PROCESS_ID": "0"}, "must be > 0"),
    ({"PADDLE_TPU_NUM_PROCESSES": "4",
      "PADDLE_TPU_PROCESS_ID": "4"}, "out of range"),
    ({"PADDLE_TPU_NUM_PROCESSES": "4",
      "PADDLE_TPU_PROCESS_ID": "-1"}, ">= 0"),
    ({"PADDLE_TPU_NUM_PROCESSES": "4"}, "set together"),
    ({"PADDLE_TPU_PROCESS_ID": "1"}, "set together"),
])
def test_world_readable_errors(env, frag):
    with pytest.raises(ValueError) as ei:
        penv.world(env)
    assert frag in str(ei.value)


# ---------------------------------------------------------------------------
# elastic.replan: survivor-world re-planning


def test_replan_factorises_survivor_world():
    with flags_guard(comm_policy="hierarchical", comm_hosts=0):
        p4 = replan_mod.replan(4)
        p3 = replan_mod.replan(3)
    assert (p4.world_size, p4.hosts, p4.dp) == (4, 4, 4)
    assert (p3.world_size, p3.hosts, p3.dp) == (3, 3, 3)
    assert p4.policy.hosts == 4 and p3.policy.hosts == 3
    # the rebuilt axis_index_groups differ with the topology
    intra4, ring4 = p4.groups()
    intra3, ring3 = p3.groups()
    assert len(intra4) == 4 and len(intra3) == 3
    assert ring4 != ring3
    # a shrunk world can never hit a stale compile: the signature the
    # executor joins into its jit cache key changes
    assert p4.cache_signature() != p3.cache_signature()


def test_replan_chips_per_host():
    with flags_guard(comm_policy="hierarchical", comm_hosts=0):
        p = replan_mod.replan(2, chips_per_host=4)
    assert (p.hosts, p.dp) == (2, 8)
    intra, _ = p.groups()
    assert intra == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_replan_apply_flags_rekeys_executor_cache():
    from paddle_tpu.core.executor import _comm_flags_sig
    with flags_guard(comm_policy="hierarchical", comm_hosts=0):
        replan_mod.replan(4).apply_flags()
        sig4 = _comm_flags_sig()
        replan_mod.replan(3).apply_flags()
        sig3 = _comm_flags_sig()
    assert sig4 != sig3


def test_replan_step_fn_retraces_per_world(forced_cpu_devices):
    """The SAME loss trains under both the full-world and the
    survivor-world plan: each plan's step fn is a fresh trace at its
    own dp size with its own hierarchical grouping."""
    import jax.numpy as jnp

    def loss_fn(params, x, y):
        return jnp.mean((x @ params["w"] - y) ** 2)

    losses = {}
    with flags_guard(comm_policy="hierarchical", comm_hosts=0):
        for world in (4, 2):
            plan = replan_mod.replan(world)
            step, state0_fn = plan.step_fn(
                loss_fn, devices=forced_cpu_devices[:plan.dp])
            params = {"w": jnp.ones((4,), jnp.float32)}
            state = state0_fn(params)
            x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4) / 32.0
            y = 0.25 * x.sum(axis=1) + 1.0  # not fit by the ones-init
            loss, params2, state = step(params, state, x, y, 0.01)
            losses[world] = float(loss)
            assert not np.allclose(np.asarray(params2["w"]),
                                   np.asarray(params["w"]))
    # same global batch, same init: the mean-gradient step agrees
    # across worlds up to reassociation
    np.testing.assert_allclose(losses[4], losses[2], rtol=1e-5)


def test_replan_fault_degrades_to_flat_with_event():
    R.clear_events()
    R.arm("elastic.replan", "raise")
    try:
        with flags_guard(comm_policy="hierarchical", comm_hosts=0):
            p = replan_mod.replan(4)
    finally:
        R.disarm("elastic.replan")
    assert p.degraded and p.hosts == 1 and p.policy.hosts == 1
    assert p.dp == 4  # the world itself is NOT degraded, only routing
    evs = R.events(kind="elastic_degraded", site="elastic.replan")
    assert len(evs) == 1 and evs[0]["world_size"] == 4
    assert p.summary()["degraded"] is True


# ---------------------------------------------------------------------------
# elastic.resume: checkpoint <-> snapshot pairing


def _fake_complete_ckpt(root, step):
    d = os.path.join(root, "ckpt-%08d" % step)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "_COMPLETE"), "w") as f:
        json.dump({"step": step, "sizes": {}}, f)
    # distinct mtimes so newest-wins ordering is deterministic
    t = 1_700_000_000 + step
    os.utime(d, (t, t))
    return d


def test_resume_point_pairs_snapshot_by_step(tmp_path):
    root = str(tmp_path)
    d1 = _fake_complete_ckpt(root, 1)
    d2 = _fake_complete_ckpt(root, 2)
    # in-dir snapshot for step 1; step 2's was moved in-dir too
    open(os.path.join(d1, resume_mod.SNAP_IN_DIR), "w").write("s1")
    open(os.path.join(d2, resume_mod.SNAP_IN_DIR), "w").write("s2")
    # a NEWER orphan snapshot whose checkpoint never completed must be
    # ignored — restoring it would double-process the step-3 task
    open(resume_mod.snapshot_path(root, 3), "w").write("s3-orphan")
    rp = resume_mod.resume_point(root)
    assert rp.step == 2
    assert rp.snapshot == os.path.join(d2, resume_mod.SNAP_IN_DIR)


def test_resume_point_falls_back_to_root_level_snap(tmp_path):
    # the kill window between "checkpoint complete" and "snapshot moved
    # in-dir": the root-level snapshot with the SAME step still pairs
    root = str(tmp_path)
    d2 = _fake_complete_ckpt(root, 2)
    open(resume_mod.snapshot_path(root, 2), "w").write("s2")
    rp = resume_mod.resume_point(root)
    assert rp.ckpt_dir == d2 and rp.step == 2
    assert rp.snapshot == resume_mod.snapshot_path(root, 2)
    # no snapshot at all: the model alone resumes
    d3 = _fake_complete_ckpt(root, 3)
    rp = resume_mod.resume_point(root)
    assert rp.ckpt_dir == d3 and rp.snapshot is None


def test_resume_fault_walks_to_older_pair(tmp_path):
    root = str(tmp_path)
    d1 = _fake_complete_ckpt(root, 1)
    _fake_complete_ckpt(root, 2)
    R.clear_events()
    R.arm("elastic.resume", "raise")  # nth=1: only the newest is marked
    try:
        rp = resume_mod.resume_point(root)
    finally:
        R.disarm("elastic.resume")
    assert rp.ckpt_dir == d1 and rp.step == 1
    assert R.events(kind="elastic_degraded", site="elastic.resume")


def test_resume_point_empty_root(tmp_path):
    assert resume_mod.resume_point(str(tmp_path)) is None
    assert resume_mod.resume_point(str(tmp_path / "missing")) is None


# ---------------------------------------------------------------------------
# checkpoint.load_latest: concurrent-prune fallthrough (the resume path
# the supervisor exercises while an async save's retention prune runs)


def _build_ckpt_program():
    from paddle_tpu.core import unique_name
    unique_name._counters.clear()
    main, startup = pt.Program(), pt.Program()
    pt.switch_main_program(main)
    pt.switch_startup_program(startup)
    x = layers.data("x", shape=[4], dtype="float32")
    layers.fc(x, size=2, param_attr=pt.ParamAttr(name="el_w"))
    return main, startup


def test_load_latest_survives_pruned_newest(tmp_path, monkeypatch):
    main, startup = _build_ckpt_program()
    scope = pt.Scope()
    root = str(tmp_path / "root")
    with pt.scope_guard(scope):
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        checkpoint.save_checkpoint(root, main, scope=scope, step=1,
                                   keep_last=5)
        checkpoint.save_checkpoint(root, main, scope=scope, step=2,
                                   keep_last=5)
    real = checkpoint.latest_checkpoint
    pruned = os.path.join(root, "ckpt-00000099")
    calls = {"n": 0}

    def racing(r):
        calls["n"] += 1
        # first scan hands back an entry a concurrent prune then deletes
        return pruned if calls["n"] == 1 else real(r)

    monkeypatch.setattr(checkpoint, "latest_checkpoint", racing)
    R.clear_events()
    with pt.scope_guard(scope):
        used, step = checkpoint.load_latest(root, main, scope=scope)
    assert step == 2 and used.endswith("ckpt-00000002")
    assert calls["n"] == 2
    assert R.events(kind="checkpoint_pruned_during_load")


def test_load_latest_real_error_still_raises(tmp_path):
    # a present-but-torn manifest read error must NOT be eaten by the
    # prune-race tolerance
    main, startup = _build_ckpt_program()
    scope = pt.Scope()
    root = str(tmp_path / "root")
    with pt.scope_guard(scope):
        exe = pt.Executor(pt.CPUPlace())
        exe.run(startup)
        checkpoint.save_checkpoint(root, main, scope=scope, step=1,
                                   keep_last=5)
    d = os.path.join(root, "ckpt-00000001")
    os.remove(os.path.join(d, checkpoint._MANIFEST))
    # _COMPLETE still references the shard sizes, manifest is gone ->
    # the dir exists, so the error surfaces (as a read failure)
    with pytest.raises((IOError, OSError)):
        with pt.scope_guard(scope):
            checkpoint.load_latest(root, main, scope=scope)


# ---------------------------------------------------------------------------
# v2 master: crash re-queue semantics from the RPC (multi-process) side


_LEASE_AND_DIE = textwrap.dedent("""
    import os, signal, sys
    sys.path.insert(0, %(repo)r)
    from paddle_tpu.v2 import master as v2m
    c = v2m.client(%(addr)r)
    tid, payload = c.get_task()
    assert tid not in (None, "wait"), tid
    print("LEASED %%s" %% payload.decode(), flush=True)
    os.kill(os.getpid(), signal.SIGKILL)
""")


def _serve_master(n_tasks, timeout_sec, failure_max=3):
    native = pytest.importorskip("paddle_tpu.native")
    if not native.available():
        pytest.skip("no native toolchain")
    m = native.TaskMaster(failure_max=failure_max,
                          timeout_sec=timeout_sec)
    for i in range(n_tasks):
        m.add_task(b"t-%d" % i)
    port = m.serve(0)
    return m, "127.0.0.1:%d" % port


def test_master_rpc_dead_worker_task_releases_exactly_once():
    """A SIGKILLed worker's leased task is re-leased EXACTLY once to a
    survivor past timeout_sec, and the pass still ends."""
    from paddle_tpu.v2 import master as v2m
    m, addr = _serve_master(4, timeout_sec=0.5)
    try:
        child = subprocess.Popen(
            [sys.executable, "-c",
             _LEASE_AND_DIE % {"repo": REPO, "addr": addr}],
            stdout=subprocess.PIPE, text=True)
        line = child.stdout.readline()
        assert line.startswith("LEASED"), line
        dead_payload = line.split()[1].encode()
        child.wait(timeout=30)

        survivor = v2m.client(addr, worker_name="survivor")
        seen = []
        deadline = time.time() + 30
        while time.time() < deadline:
            tid, payload = survivor.get_task(block=False)
            if tid is None:
                break
            if tid == "wait":
                time.sleep(0.05)  # the dead lease has not expired yet
                continue
            seen.append(payload)
            assert survivor.task_finished(tid)
        assert sorted(seen) == sorted(b"t-%d" % i for i in range(4))
        assert seen.count(dead_payload) == 1  # re-leased exactly once
        c = survivor.counts()
        assert c == {"todo": 0, "pending": 0, "done": 4, "failed": 0}
        survivor.close()
    finally:
        m.close()


def test_master_rpc_failure_max_drops_with_event_and_pass_ends():
    """failure_max exhaustion DROPS the task with a recorded
    task_dropped event — and pass-end still fires for the survivors."""
    from paddle_tpu.v2 import master as v2m
    m, addr = _serve_master(2, timeout_sec=30.0, failure_max=2)
    R.clear_events()
    try:
        c = v2m.client(addr)
        dropped = None
        finished = []
        while True:
            tid, payload = c.get_task(block=False)
            if tid is None:
                break
            assert tid != "wait"
            if payload == b"t-0":
                # poison: report failure; the second one exhausts
                # failure_max=2 and must record the drop
                was_dropped = c.task_failed(tid)
                if was_dropped:
                    dropped = payload
            else:
                assert c.task_finished(tid)
                finished.append(payload)
        assert dropped == b"t-0"
        assert finished == [b"t-1"]
        counts = c.counts()
        assert counts["failed"] == 1 and counts["done"] == 1
        # pass end fired (get_task returned None) despite the poison
        evs = R.events(kind="task_dropped", site="master.task")
        assert len(evs) == 1 and evs[0]["failed_total"] == 1
        c.close()
    finally:
        m.close()


# ---------------------------------------------------------------------------
# supervisor: classify / restart / resize / quorum over real processes


def _worker_script(tmp_path, body):
    p = tmp_path / "worker.py"
    p.write_text(textwrap.dedent("""
        import os, signal, sys, time
        rank = int(os.environ["PADDLE_TPU_PROCESS_ID"])
        gen = int(os.environ.get("PADDLE_TPU_ELASTIC_GENERATION", "0"))
        world = int(os.environ["PADDLE_TPU_NUM_PROCESSES"])
        state = os.environ.get("PADDLE_TPU_ELASTIC_STATE", "")
    """) + textwrap.dedent(body))
    return str(p)


def _events_of(state_dir, kind=None):
    path = os.path.join(state_dir, "events.jsonl")
    evs = []
    if os.path.exists(path):
        with open(path) as f:
            evs = [json.loads(ln) for ln in f]
    return [e for e in evs if kind is None or e["kind"] == kind]


def test_supervisor_resizes_on_signal_death(tmp_path):
    script = _worker_script(tmp_path, """
        if gen == 0 and rank == 1:
            os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(0.2)
    """)
    sd = str(tmp_path / "state")
    rc = ElasticSupervisor(3, "127.0.0.1", [script], min_workers=2,
                           restart_budget=2, grace_sec=3.0, state_dir=sd,
                           sweep_interval=0.1).run()
    assert rc == 0
    resizes = _events_of(sd, "elastic_resize")
    assert len(resizes) == 1
    assert resizes[0]["from_world"] == 3 and resizes[0]["to_world"] == 2
    assert resizes[0]["lost_rank"] == 1 and resizes[0]["rc"] == -9
    gens = _events_of(sd, "elastic_generation")
    assert [g["world"] for g in gens] == [3, 2]
    assert _events_of(sd, "elastic_job_complete")


def test_supervisor_transient_restart_consumes_budget(tmp_path):
    # crash-exit (rc 3) once, then succeed: ONE full-world restart, no
    # resize — the transient classification
    script = _worker_script(tmp_path, """
        marker = os.path.join(state, "crashed-once")
        if rank == 0 and not os.path.exists(marker):
            open(marker, "w").close()
            sys.exit(3)
        time.sleep(0.1)
    """)
    sd = str(tmp_path / "state")
    os.makedirs(sd)
    rc = ElasticSupervisor(2, "127.0.0.1", [script], min_workers=1,
                           restart_budget=2, grace_sec=3.0, state_dir=sd,
                           sweep_interval=0.1).run()
    assert rc == 0
    restarts = _events_of(sd, "elastic_restart")
    assert len(restarts) == 1 and restarts[0]["rc"] == 3
    assert not _events_of(sd, "elastic_resize")
    assert [g["world"] for g in _events_of(sd, "elastic_generation")] \
        == [2, 2]


def test_supervisor_exhausted_budget_resizes(tmp_path):
    # rank 1 crash-exits EVERY generation: budget 1 -> one restart,
    # then the loss is permanent -> resize to 1 -> completes
    script = _worker_script(tmp_path, """
        if rank == 1:
            sys.exit(7)
        time.sleep(0.1)
    """)
    sd = str(tmp_path / "state")
    rc = ElasticSupervisor(2, "127.0.0.1", [script], min_workers=1,
                           restart_budget=1, grace_sec=3.0, state_dir=sd,
                           sweep_interval=0.1).run()
    assert rc == 0
    assert len(_events_of(sd, "elastic_restart")) == 1
    resizes = _events_of(sd, "elastic_resize")
    assert len(resizes) == 1 and resizes[0]["to_world"] == 1


def test_supervisor_quorum_lost_propagates_real_rc(tmp_path):
    script = _worker_script(tmp_path, """
        if rank == 0:
            os.kill(os.getpid(), signal.SIGKILL)
        time.sleep(0.2)
    """)
    sd = str(tmp_path / "state")
    rc = ElasticSupervisor(2, "127.0.0.1", [script], min_workers=2,
                           restart_budget=0, grace_sec=3.0, state_dir=sd,
                           sweep_interval=0.1).run()
    assert rc == -9  # the real exit code, never masked
    assert _events_of(sd, "elastic_quorum_lost")
    assert not _events_of(sd, "elastic_resize")


def test_supervisor_heartbeat_fault_is_counted_not_fatal(tmp_path):
    from paddle_tpu import profiler as prof
    script = _worker_script(tmp_path, """
        time.sleep(0.5)
    """)
    sd = str(tmp_path / "state")
    before = prof.elastic_counters().get("elastic_heartbeat_failures", 0)
    R.arm("elastic.heartbeat", "raise", times=2)
    try:
        rc = ElasticSupervisor(1, "127.0.0.1", [script], min_workers=1,
                               grace_sec=3.0, state_dir=sd,
                               sweep_interval=0.1).run()
    finally:
        R.disarm("elastic.heartbeat")
    assert rc == 0  # a flaky probe can never kill a healthy job
    assert _events_of(sd, "elastic_heartbeat_failed")
    after = prof.elastic_counters().get("elastic_heartbeat_failures", 0)
    assert after >= before + 1


def _gray_worker_script(tmp_path, slow_rank, slow_gens, slow_ms=900.0,
                        iters=60):
    """Workers that publish their own heartbeats: ``slow_rank`` reports
    a step-time EWMA ~18x its peers while ``gen < slow_gens``, everyone
    else (and every later generation) reports healthy 50 ms. The
    supervisor sees exactly what a real Trainer-published heartbeat
    stream would say, without the training loop's runtime."""
    p = tmp_path / "gray_worker.py"
    p.write_text(textwrap.dedent("""
        import json, os, time
        rank = int(os.environ["PADDLE_TPU_PROCESS_ID"])
        gen = int(os.environ.get("PADDLE_TPU_ELASTIC_GENERATION", "0"))
        state = os.environ["PADDLE_TPU_ELASTIC_STATE"]
        slow = rank == %d and gen < %d
        for i in range(%d):
            hb = {"rank": rank, "generation": gen, "step": i,
                  "step_ms_ewma": %r if slow else 50.0}
            tmp = os.path.join(state, ".hb-%%d.tmp" %% rank)
            with open(tmp, "w") as f:
                json.dump(hb, f)
            os.replace(tmp, os.path.join(
                state, "heartbeat-rank%%d.json" %% rank))
            time.sleep(0.1)
    """ % (slow_rank, slow_gens, iters, slow_ms)))
    return str(p)


def test_supervisor_gray_restart_then_resize(tmp_path):
    """The mitigation ladder: a persistently slow rank is condemned
    from its heartbeats, spends the one transient restart, recurs, and
    is demoted to a permanent loss (clean resize) — the post-resize
    2-member world cannot condemn anyone (no majority) and the job
    completes."""
    script = _gray_worker_script(tmp_path, slow_rank=1, slow_gens=2)
    sd = str(tmp_path / "state")
    rc = ElasticSupervisor(3, "127.0.0.1", [script], min_workers=2,
                           restart_budget=0, grace_sec=3.0, state_dir=sd,
                           sweep_interval=0.1, gray_ratio=3.0,
                           gray_budget=1).run()
    assert rc == 0
    mits = _events_of(sd, "gray_mitigated")
    assert [(m["action"], m["rank"]) for m in mits] == \
        [("restart", 1), ("resize", 1)]
    assert _events_of(sd, "gray_suspected")
    resizes = _events_of(sd, "elastic_resize")
    assert len(resizes) == 1 and resizes[0]["gray"] is True
    assert resizes[0]["rc"] is None  # nothing died: there IS no rc
    assert [g["world"] for g in _events_of(sd, "elastic_generation")] \
        == [3, 3, 2]
    assert not _events_of(sd, "elastic_worker_exit")
    assert _events_of(sd, "elastic_job_complete")


def test_supervisor_gray_never_breaks_quorum(tmp_path):
    """Budget spent and the world already at min_workers: the verdict
    is recorded (gray_mitigation_skipped, reason=quorum) and the job
    keeps running SLOW to completion — degraded beats dead."""
    script = _gray_worker_script(tmp_path, slow_rank=1, slow_gens=99,
                                 iters=30)
    sd = str(tmp_path / "state")
    rc = ElasticSupervisor(3, "127.0.0.1", [script], min_workers=3,
                           restart_budget=0, grace_sec=3.0, state_dir=sd,
                           sweep_interval=0.1, gray_ratio=3.0,
                           gray_budget=0).run()
    assert rc == 0
    skips = _events_of(sd, "gray_mitigation_skipped")
    assert skips and skips[0]["reason"] == "quorum" \
        and skips[0]["rank"] == 1
    assert not _events_of(sd, "gray_mitigated")
    assert not _events_of(sd, "elastic_resize")
    assert _events_of(sd, "elastic_job_complete")


def test_supervisor_gray_quiet_on_healthy_gang(tmp_path):
    """The flap pin at the supervisor tier: identical healthy
    heartbeats with detection armed produce ZERO gray events."""
    script = _gray_worker_script(tmp_path, slow_rank=0, slow_gens=0,
                                 iters=20)
    sd = str(tmp_path / "state")
    rc = ElasticSupervisor(3, "127.0.0.1", [script], min_workers=2,
                           restart_budget=0, grace_sec=3.0, state_dir=sd,
                           sweep_interval=0.1, gray_ratio=3.0,
                           gray_budget=1).run()
    assert rc == 0
    assert not _events_of(sd, "gray_suspected")
    assert not _events_of(sd, "gray_mitigated")
    assert not _events_of(sd, "gray_mitigation_skipped")


def test_launch_fail_fast_escalates_hung_worker(tmp_path):
    # rank 0 ignores SIGTERM (a worker wedged in a dead collective);
    # rank 1 fails -> launch must SIGKILL past grace and return the
    # REAL failing code promptly instead of wedging for 60s
    script = _worker_script(tmp_path, """
        if rank == 0:
            signal.signal(signal.SIGTERM, signal.SIG_IGN)
            time.sleep(60)
        else:
            time.sleep(0.2)
            sys.exit(5)
    """)
    t0 = time.monotonic()
    rc = launch(2, "127.0.0.1:0", [script], grace_sec=0.5)
    assert rc == 5
    assert time.monotonic() - t0 < 20


def test_launch_success_exit_zero(tmp_path):
    script = _worker_script(tmp_path, """
        sys.exit(0)
    """)
    assert launch(2, "127.0.0.1:0", [script]) == 0


# ---------------------------------------------------------------------------
# observability: counters / timeline / executor stats


def test_elastic_counters_and_timeline_section(tmp_path):
    from paddle_tpu import profiler as prof
    prof.reset_elastic_counters()
    prof.update_elastic_counters(elastic_resizes=1, elastic_lost_ranks=1,
                                 elastic_requeued_tasks=5,
                                 elastic_resume_ms=12.5)
    art = prof.write_timeline(str(tmp_path / "t.json"))
    assert art["elastic"]["elastic_resizes"] == 1
    assert art["elastic"]["elastic_requeued_tasks"] == 5
    stats = {"elastic_resizes": 0, "elastic_lost_ranks": 0,
             "elastic_requeued_tasks": 0, "elastic_resume_ms": 0.0}
    resume_mod.record_stats(stats)
    assert stats["elastic_resizes"] == 1
    assert stats["elastic_resume_ms"] == 12.5
    prof.reset_elastic_counters()
    assert prof.elastic_counters() == {}


def test_executor_stats_have_elastic_section():
    exe = pt.Executor(pt.CPUPlace())
    for k in ("elastic_resizes", "elastic_lost_ranks",
              "elastic_requeued_tasks", "elastic_resume_ms"):
        assert k in exe.stats


def test_elastic_flags_declared():
    assert FLAGS.elastic is False
    assert FLAGS.elastic_min_workers >= 1
    assert FLAGS.elastic_restart_budget >= 0


# ---------------------------------------------------------------------------
# the full chaos acceptance (the smoke gate's leg, pytest form)


@pytest.mark.slow
def test_chaos_kill_one_of_four_resumes_on_survivors(tmp_path):
    sys.path.insert(0, REPO)
    import benchmark.chaos_run as cr
    report = cr.run_chaos(str(tmp_path / "chaos"), nprocs=4, tasks=8,
                          kill_rank=0, kill_after=2, timeout=600)
    assert report["rc"] == 0
    assert report["killed"] is not None
    resizes = [e for e in report["events"]
               if e["kind"] == "elastic_resize"]
    assert len(resizes) == 1
    assert (resizes[0]["from_world"], resizes[0]["to_world"]) == (4, 3)
    assert cr.check_exactly_once(report) == []
    assert cr.check_continuity(report) == []
    assert cr.check_replan(report) == []


# ---------------------------------------------------------------------------
# cross-replica schedule-fingerprint exchange at job start (PR-12's open
# follow-on): ranks publish into --state-dir, divergence refuses the
# first collective with a readable PT020 error naming both fingerprints


def _fp_env(state_dir, rank=0, world=2, generation=0):
    return {"PADDLE_TPU_ELASTIC_STATE": str(state_dir),
            "PADDLE_TPU_NUM_PROCESSES": str(world),
            "PADDLE_TPU_PROCESS_ID": str(rank),
            "PADDLE_TPU_ELASTIC_GENERATION": str(generation)}


def _template(n=4):
    import jax
    return {"p%d@GRAD" % i: jax.ShapeDtypeStruct((256,),
                                                 np.dtype("float32"))
            for i in range(n)}


def _peer_fp(tpl, policy, axis_size):
    from paddle_tpu.analysis import comm_rules
    diags, fp = comm_rules.verify_comm(tpl, policy, axis_size=axis_size)
    assert not diags and fp
    return fp


def test_fingerprint_clean_exchange(tmp_path):
    from paddle_tpu.comm import CommPolicy
    from paddle_tpu.elastic import fingerprints as fps
    tpl = _template()
    pol = CommPolicy(base="fused", bucket_bytes=1024)
    fps.publish_fingerprint(str(tmp_path), 1, _peer_fp(tpl, pol, 8))
    fp = fps.check_replica_schedule(
        tpl, policy=pol, axis_size=8, overlap=False,
        env=_fp_env(tmp_path), timeout_sec=5)
    assert fp == _peer_fp(tpl, pol, 8)


def test_fingerprint_divergence_refuses_with_both_named(tmp_path):
    from paddle_tpu.analysis import ProgramVerifyError
    from paddle_tpu.comm import CommPolicy
    from paddle_tpu.elastic import fingerprints as fps
    R.clear_events()
    tpl = _template()
    pol_mine = CommPolicy(base="fused", bucket_bytes=1024)
    pol_peer = CommPolicy(base="fused", bucket_bytes=256)  # stale flag
    peer = _peer_fp(tpl, pol_peer, 8)
    fps.publish_fingerprint(str(tmp_path), 1, peer)
    with pytest.raises(ProgramVerifyError) as ei:
        fps.check_replica_schedule(
            tpl, policy=pol_mine, axis_size=8, overlap=False,
            env=_fp_env(tmp_path), timeout_sec=5)
    msg = str(ei.value)
    mine = _peer_fp(tpl, pol_mine, 8)
    assert "PT020" in msg and "refusing the first collective" in msg
    assert mine in msg and peer in msg  # names BOTH fingerprints
    assert R.events("fingerprint_divergence")
    R.clear_events()


def test_fingerprint_incomplete_exchange_is_advisory(tmp_path):
    from paddle_tpu.comm import CommPolicy
    from paddle_tpu.elastic import fingerprints as fps
    R.clear_events()
    tpl = _template()
    pol = CommPolicy(base="fused", bucket_bytes=1024)
    # world of 3, nobody else publishes: a slow peer must not convert
    # the monitoring feature into a new failure mode
    fp = fps.check_replica_schedule(
        tpl, policy=pol, axis_size=8, overlap=False,
        env=_fp_env(tmp_path, rank=0, world=3), timeout_sec=0.2)
    assert fp
    evs = R.events("fingerprint_exchange_incomplete")
    assert evs and evs[0]["world"] == 3 and evs[0]["have"] == [0]
    R.clear_events()


def test_fingerprint_inert_without_elastic_env(tmp_path):
    from paddle_tpu.comm import CommPolicy
    from paddle_tpu.elastic import fingerprints as fps
    tpl = _template()
    pol = CommPolicy(base="fused", bucket_bytes=1024)
    fp = fps.check_replica_schedule(tpl, policy=pol, axis_size=8,
                                    overlap=False, env={})
    assert fp  # the local fingerprint still comes back
    assert not os.path.isdir(fps.fingerprint_dir(str(tmp_path)))


def test_step_fn_refuses_first_collective_on_divergence(
        tmp_path, monkeypatch, forced_cpu_devices):
    """The wiring leg: a data_parallel_step_fn built under the elastic
    env contract runs the exchange in its tracing first call — a peer
    rank launched with a divergent comm flag makes the FIRST step
    raise readably, before any collective rendezvous."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.analysis import ProgramVerifyError
    from paddle_tpu.comm import CommPolicy
    from paddle_tpu.elastic import fingerprints as fps
    from paddle_tpu.parallel import data_parallel_step_fn
    from paddle_tpu.parallel.mesh import make_mesh

    def loss_fn(params, x, y):
        return jnp.mean((x @ params["w"] - y) ** 2)

    params = {"w": jnp.ones((4,), jnp.float32)}
    tpl = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(jnp.shape(p),
                                       jnp.result_type(p)), params)
    peer_pol = CommPolicy(base="fused", bucket_bytes=256)
    fps.publish_fingerprint(str(tmp_path), 1,
                            _peer_fp(tpl, peer_pol, 2))
    for k, v in _fp_env(tmp_path, rank=0, world=2).items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("PADDLE_TPU_FINGERPRINT_TIMEOUT", "5")
    mesh = make_mesh({"dp": 2}, devices=forced_cpu_devices[:2])
    with flags_guard(comm_policy="fused", comm_bucket_mb=4.0,
                     comm_overlap=False):
        step, state0_fn = data_parallel_step_fn(loss_fn, mesh=mesh,
                                                axis_name="dp")
        state = state0_fn(params)
        x = jnp.ones((8, 4), jnp.float32)
        y = jnp.ones((8,), jnp.float32)
        with pytest.raises(ProgramVerifyError) as ei:
            step(params, state, x, y, 0.01)
    assert "refusing the first collective" in str(ei.value)


def test_fingerprint_exchange_latches_once_per_generation(tmp_path):
    """A later grad-bearing build in the same process must not
    overwrite the agreed job-start record (a slow peer would compare
    mixed programs) — but only a SUCCESSFUL exchange latches."""
    from paddle_tpu.comm import CommPolicy
    from paddle_tpu.elastic import fingerprints as fps
    tpl = _template()
    pol = CommPolicy(base="fused", bucket_bytes=1024)
    fps.publish_fingerprint(str(tmp_path), 1, _peer_fp(tpl, pol, 8))
    env = _fp_env(tmp_path)
    fp1 = fps.check_replica_schedule(tpl, policy=pol, axis_size=8,
                                     overlap=False, env=env,
                                     timeout_sec=5)
    assert fp1
    rank0 = os.path.join(fps.fingerprint_dir(str(tmp_path)),
                         "gen0-rank0.json")
    before = open(rank0).read()
    # second build, different policy: would diverge, but the exchange
    # already completed for this generation — local check only, the
    # published record stays untouched
    pol2 = CommPolicy(base="fused", bucket_bytes=256)
    fp2 = fps.check_replica_schedule(tpl, policy=pol2, axis_size=8,
                                     overlap=False, env=env,
                                     timeout_sec=5)
    assert fp2 and fp2 != fp1
    assert open(rank0).read() == before


# ---------------------------------------------------------------------------
# Trainer.train(elastic=True): the real loop as an elastic worker (PR 15)


def _worker_trainer(checkpoint_dir=None):
    main = pt.default_main_program()
    startup = pt.default_startup_program()
    x = layers.data("wx", shape=[4], dtype="float32")
    y = layers.data("wy", shape=[1], dtype="int64")
    h = layers.fc(x, size=8, act="tanh")
    pred = layers.fc(h, size=2, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    return pt.Trainer(cost=loss, optimizer=pt.SGD(learning_rate=0.3),
                      feed_list=[x, y], place=pt.CPUPlace(),
                      main_program=main, startup_program=startup,
                      checkpoint_dir=checkpoint_dir)


def _task_batch(payload, nan=False):
    i = int(payload.decode().split("-")[1])
    rng = np.random.RandomState(100 + i)
    bx = rng.rand(8, 4).astype("float32")
    if nan:
        bx = bx.copy()
        bx[0, 0] = np.nan
    by = (bx.sum(axis=1) > 2).astype("int64").reshape(-1, 1)
    return list(zip(bx, by))


def _lease_env(monkeypatch, master, state_dir, timeout="30"):
    monkeypatch.setenv("PADDLE_TPU_NUM_PROCESSES", "1")
    monkeypatch.setenv("PADDLE_TPU_PROCESS_ID", "0")
    monkeypatch.setenv("PADDLE_TPU_ELASTIC", "1")
    monkeypatch.setenv("PADDLE_TPU_ELASTIC_GENERATION", "0")
    monkeypatch.setenv("PADDLE_TPU_ELASTIC_STATE", str(state_dir))
    if master is not None:
        monkeypatch.setenv("PADDLE_TPU_MASTER_ADDR", master.addr)
        monkeypatch.setenv("PADDLE_TPU_MASTER_TIMEOUT", timeout)
    else:
        monkeypatch.delenv("PADDLE_TPU_MASTER_ADDR", raising=False)


def _mk_master(tasks, timeout_sec=30.0, failure_max=3):
    from paddle_tpu.elastic.supervisor import TaskMasterHost
    return TaskMasterHost([b"batch-%d" % i for i in range(tasks)],
                          timeout_sec=timeout_sec,
                          failure_max=failure_max)


def test_trainer_elastic_worker_leases_pairs_and_resumes(
        tmp_path, monkeypatch):
    """The tentpole contract in one process: Trainer.train(elastic=True)
    leases every task exactly once through the supervisor-owned master,
    pairs each checkpoint with a master snapshot, writes the
    plan-gen<G>.json audit artifact, and folds lease accounting into
    Executor.stats."""
    import glob
    master = _mk_master(5)
    root = str(tmp_path / "ckpt")
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    commits = []
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            tr.train(elastic=True, task_reader=_task_batch,
                     elastic_root=root,
                     on_commit=lambda s, t, p, c: commits.append(
                         (s, p.decode())))
    finally:
        master.close()
    assert [c[0] for c in commits] == [1, 2, 3, 4, 5]
    assert sorted(c[1] for c in commits) == \
        ["batch-%d" % i for i in range(5)]
    assert tr.exe.stats["elastic_tasks_committed"] == 5
    assert tr.exe.stats["elastic_lease_losses"] == 0
    # a leased batch is taken after the commit of the one before it: asked
    # for earlier, the master would say "wait" on this worker's own lease
    assert tr.exe.stats["lookahead_steps"] == 0
    # every retained checkpoint carries its paired master snapshot
    snaps = glob.glob(os.path.join(root, "ckpt-*",
                                   resume_mod.SNAP_IN_DIR))
    assert snaps
    assert os.path.exists(os.path.join(str(tmp_path), "plan-gen0.json"))
    # the worker went through the paired-resume path (fresh run: step 0)
    assert tr._elastic_worker.step == 5


def test_trainer_elastic_lease_path_dispatches_nothing_ahead(
        tmp_path, monkeypatch):
    """No batch n+1 before commit(n), so no step n+1 to dispatch ahead:
    the lease path asks the executor to hold nothing back and runs the
    donating step, one dispatch after the other's commit."""
    master = _mk_master(3)
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    run, calls = tr.exe.run, []

    def logged_run(program=None, **k):
        if program is tr.main_program:
            calls.append(k.get("hold", False))
        return run(program, **k)
    tr.exe.run = logged_run
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            tr.train(elastic=True, task_reader=_task_batch,
                     elastic_root=str(tmp_path / "ckpt"))
    finally:
        master.close()
    assert calls == [False, False, False]
    assert tr.exe.stats["elastic_tasks_committed"] == 3
    assert tr.exe.stats["ahead_steps"] == 0
    assert tr.exe.stats["ahead_dropped"] == 0 and tr.exe._held is None


def test_trainer_elastic_worker_resumes_from_paired_point(
        tmp_path, monkeypatch):
    """A second generation over the same root resumes at the paired
    step and only processes the still-owed tasks."""
    root = str(tmp_path / "ckpt")
    master = _mk_master(4)
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    with flags_guard(comm_hosts=FLAGS.comm_hosts):
        tr.train(elastic=True, task_reader=_task_batch,
                 elastic_root=root)
    master.close()
    assert tr._elastic_worker.step == 4
    # generation 1: a fresh master restored from the PAIRED snapshot
    # (the supervisor's restore path) has nothing left to lease
    rp = resume_mod.resume_point(root)
    assert rp is not None and rp.step == 4 and rp.snapshot
    master2 = _mk_master(0)
    n = master2.restore_from(rp.snapshot)
    assert n == 0                      # all 4 committed before the pair
    monkeypatch.setenv("PADDLE_TPU_ELASTIC_GENERATION", "1")
    monkeypatch.setenv("PADDLE_TPU_MASTER_ADDR", master2.addr)
    tr2 = _worker_trainer()
    commits2 = []
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            tr2.train(elastic=True, task_reader=_task_batch,
                      elastic_root=root,
                      on_commit=lambda s, t, p, c: commits2.append(s))
    finally:
        master2.close()
    assert commits2 == []              # nothing double-processed
    assert tr2._elastic_worker.step == 4   # resumed, not restarted


def test_trainer_elastic_lease_lapse_not_double_counted(
        tmp_path, monkeypatch):
    """A commit whose lease lapsed (task_finished -> False) must NOT
    advance the step or checkpoint — the task belongs to a survivor."""
    master = _mk_master(2, timeout_sec=0.5)
    root = str(tmp_path / "ckpt")
    _lease_env(monkeypatch, master, tmp_path, timeout="0.5")
    tr = _worker_trainer()
    from paddle_tpu.elastic.worker import ElasticWorker

    worker = ElasticWorker(tr, task_reader=_task_batch, root=root)
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            worker.setup()
            tr._maybe_init(load=False)
            gen = worker.reader()()
            next(gen)                        # lease batch-0
            time.sleep(1.2)                  # ... let the lease expire
            worker.client.counts()           # server-side reclaim sweep
            # the stale commit must come back False and count nothing
            assert worker.commit(cost=1.0) is False
            assert worker.step == 0
            assert worker.lease_losses == 1
            # the reclaimed task re-leases and commits exactly once
            seen = [next(gen), next(gen)]
            assert worker.commit(cost=1.0) is True
            assert worker.commit(cost=1.0) is True
            assert worker.step == 2
    finally:
        worker.close()
        master.close()
    ev = R.events(kind="elastic_lease_lost")
    assert ev and ev[-1]["site"] == "trainer.elastic"


def test_trainer_elastic_poison_task_follows_failure_contract(
        tmp_path, monkeypatch):
    """A task_reader raise fails the lease back to the master (the
    PR-1 poison-task contract): the task re-leases and, within
    failure_max, still lands exactly once."""
    R.clear_events()
    master = _mk_master(3)
    root = str(tmp_path / "ckpt")
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    poisoned = {"left": 1}

    def flaky_reader(payload):
        if payload == b"batch-1" and poisoned["left"]:
            poisoned["left"] -= 1
            raise RuntimeError("seeded poison read")
        return _task_batch(payload)

    commits = []
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            tr.train(elastic=True, task_reader=flaky_reader,
                     elastic_root=root,
                     on_commit=lambda s, t, p, c: commits.append(
                         p.decode()))
    finally:
        master.close()
    assert sorted(commits) == ["batch-0", "batch-1", "batch-2"]
    ev = R.events(kind="elastic_task_read_failed")
    assert len(ev) == 1 and not ev[0]["dropped"]
    assert tr.exe.stats["elastic_task_failures"] == 1


def test_trainer_elastic_lease_n_plus_1_is_asked_for_after_commit_n(
        tmp_path, monkeypatch):
    """The lease path under the default loop: the master hands out no
    lease past a pending one, so batch n+1 is asked for only after
    ``commit(n)`` — the loop looks ahead of nothing there."""
    master = _mk_master(4)
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    log = []

    def on_resume(worker):      # the worker is set up: listen to its client
        get_task, finished = (worker.client.get_task,
                              worker.client.task_finished)

        def logged_get(**kw):
            tid, payload = get_task(**kw)
            log.append(("lease", payload and payload.decode()))
            return tid, payload

        def logged_finished(tid):
            log.append(("commit",))
            return finished(tid)
        worker.client.get_task = logged_get
        worker.client.task_finished = logged_finished

    def handler(e):
        if isinstance(e, pt.EndIteration):
            log.append(("end", e.batch_id))
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            tr.train(elastic=True, task_reader=_task_batch,
                     elastic_root=str(tmp_path / "ckpt"),
                     on_resume=on_resume, event_handler=handler)
    finally:
        master.close()
    leased = [e[1] for e in log if e[0] == "lease"]
    assert sorted(leased[:-1]) == ["batch-%d" % i for i in range(4)]
    assert leased[-1] is None                   # the pass's end
    assert [e[:1] + e[2:] if e[0] == "lease" else e for e in log] == [
        step for n in range(4)
        for step in (("lease",), ("commit",), ("end", n))] + [("lease",)]


def test_trainer_elastic_feed_that_raises_on_task_k_commits_the_k_before(
        tmp_path, monkeypatch):
    """``DataFeeder.feed`` raising on task k ends ``train()``: tasks
    0..k-1 are committed exactly once, task k is not, and its lease is
    still pending at the master for a survivor to take when it lapses."""
    k = 2
    master = _mk_master(4)
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    leases, commits = [], []

    def a_slot_short_on_lease_k(payload):
        leases.append(payload.decode())
        batch = _task_batch(payload)
        return [row[:1] for row in batch] if len(leases) == k + 1 else batch
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            with pytest.raises(Exception) as err:
                tr.train(elastic=True, task_reader=a_slot_short_on_lease_k,
                         elastic_root=str(tmp_path / "ckpt"),
                         on_commit=lambda s, t, p, c: commits.append(
                             p.decode()))
        counts = master.counts()
    finally:
        master.close()
    assert not isinstance(err.value, (KeyboardInterrupt, SystemExit))
    assert len(leases) == k + 1 and commits == leases[:k]
    assert len(set(commits)) == k
    assert tr.exe.stats["elastic_tasks_committed"] == k
    assert tr.exe.stats["elastic_lease_losses"] == 0
    assert (counts["done"], counts["pending"], counts["todo"]) == (
        k, 1, 4 - k - 1)


def test_trainer_elastic_reader_next_fault_retries_exactly_once(
        tmp_path, monkeypatch):
    """PR-1 contract inside the elastic pass: task payloads are
    recordio paths, an armed reader.next raise poisons one read —
    the worker fails the lease, the master re-queues it, and the retry
    (fault window passed) commits the task exactly once."""
    from paddle_tpu import native
    if not native.available():
        pytest.skip("no native toolchain")
    R.clear_events()
    rng = np.random.RandomState(7)
    paths = []
    for i in range(3):
        p = str(tmp_path / ("task%d.rio" % i))
        with native.Writer(p) as w:
            for _ in range(8):
                w.write(rng.rand(4).astype("float32").tobytes())
        paths.append(p)
    from paddle_tpu.elastic.supervisor import TaskMasterHost
    master = TaskMasterHost([p.encode() for p in paths],
                            timeout_sec=30.0, failure_max=3)
    root = str(tmp_path / "ckpt")
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()

    def rio_reader(payload):
        rows = [np.frombuffer(rec, dtype="float32")
                for rec in native.Reader(payload.decode())]
        bx = np.stack(rows).astype("float32")
        by = (bx.sum(axis=1) > 2).astype("int64").reshape(-1, 1)
        return list(zip(bx, by))

    commits = []
    R.arm("reader.next", "raise", nth=4, times=1)
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            tr.train(elastic=True, task_reader=rio_reader,
                     elastic_root=root,
                     on_commit=lambda s, t, p, c: commits.append(
                         os.path.basename(p.decode())))
    finally:
        R.disarm("reader.next")
        master.close()
    assert sorted(commits) == ["task0.rio", "task1.rio", "task2.rio"]
    assert len(R.events(kind="elastic_task_read_failed")) == 1


def test_train_elastic_argument_validation(tmp_path, monkeypatch):
    _lease_env(monkeypatch, None, tmp_path)
    tr = _worker_trainer()
    # task_reader without a master address is a readable error
    with pytest.raises(ValueError, match="task master"):
        tr.train(elastic=True, task_reader=_task_batch,
                 elastic_root=str(tmp_path / "r"))
    # both reader shapes at once is a readable error
    master = _mk_master(1)
    monkeypatch.setenv("PADDLE_TPU_MASTER_ADDR", master.addr)
    try:
        with pytest.raises(ValueError, match="not both"):
            tr.train(lambda: iter(()), elastic=True,
                     task_reader=_task_batch)
    finally:
        master.close()
    # no reader at all is a readable error
    with pytest.raises(ValueError, match="needs a reader"):
        tr.train()


def test_trainer_elastic_guardrail_skip_commits_but_does_not_pair(
        tmp_path, monkeypatch):
    """A guardrail-skipped batch consumes its lease (the task is done —
    its CONTRIBUTION is what the policy discarded) but neither advances
    the audited step nor pairs a checkpoint of the poisoned model."""
    R.clear_events()
    master = _mk_master(6)
    root = str(tmp_path / "ckpt")
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    skips, commits = [], []

    def nan_at_2(payload):
        return _task_batch(payload, nan=payload == b"batch-2")

    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts,
                         loss_skip_budget=2):
            tr.train(elastic=True, task_reader=nan_at_2,
                     elastic_root=root,
                     on_commit=lambda s, t, p, c: commits.append(
                         (s, p.decode())),
                     on_skip=lambda t, p: skips.append(p.decode()))
    finally:
        master.close()
    skipped = set(skips)
    assert "batch-2" in skipped            # the seeded batch
    committed = [p for _, p in commits]
    assert sorted(committed + skips) == \
        ["batch-%d" % i for i in range(6)]
    # steps stay contiguous over the GOOD batches only
    assert [s for s, _ in commits] == list(range(1, len(commits) + 1))
    assert len(R.events(kind="guard_rewind")) == 1


def test_worker_rewind_rolls_the_step_back_with_the_model(
        tmp_path, monkeypatch):
    """At ckpt_period > 1 the newest pair can be OLDER than the last
    good commit: the rewind must roll the step counter back with the
    model, or later pairs would be labelled with erased training."""
    from paddle_tpu.elastic.worker import ElasticWorker
    master = _mk_master(4)
    root = str(tmp_path / "ckpt")
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()
    worker = ElasticWorker(tr, task_reader=_task_batch, root=root,
                           ckpt_period=2)
    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            worker.setup()
            tr._maybe_init(load=False)
            gen = worker.reader()()
            for _ in range(3):
                next(gen)
                assert worker.commit(cost=1.0)
            assert worker.step == 3            # pair landed at step 2
            assert worker._last_pair_step == 2
            assert worker.rewind() is True
            assert worker.step == 2            # counter follows the model
            assert worker._last_pair_step == 2
    finally:
        worker.close()
        master.close()


def test_train_elastic_setup_failure_closes_the_master_client(
        tmp_path, monkeypatch):
    """A raise between worker.setup() (which REGISTERS a heartbeating
    worker) and the training loop's own finally must not leak the
    registered client until process exit."""
    master = _mk_master(2)
    _lease_env(monkeypatch, master, tmp_path)
    tr = _worker_trainer()

    def boom(worker):
        raise RuntimeError("seeded on_resume failure")

    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            with pytest.raises(RuntimeError, match="seeded on_resume"):
                tr.train(elastic=True, task_reader=_task_batch,
                         elastic_root=str(tmp_path / "ckpt"),
                         on_resume=boom)
        assert tr._elastic_worker.client is None   # close() ran
    finally:
        master.close()


def test_lease_wait_tick_never_masks_an_owed_step(tmp_path, monkeypatch):
    """The feed thread's idle tick extends a live deadline ONLY while
    no lease is outstanding: an uncommitted lease means the main thread
    owes a step — if that step is the wedged one, polling for the NEXT
    lease must not keep re-arming the deadline over it."""
    from paddle_tpu.elastic.worker import ElasticWorker
    from paddle_tpu.resilience.watchdog import StepWatchdog
    monkeypatch.setenv("PADDLE_TPU_NUM_PROCESSES", "1")
    monkeypatch.setenv("PADDLE_TPU_PROCESS_ID", "0")
    tr = _worker_trainer()
    worker = ElasticWorker(tr, task_reader=_task_batch,
                           root=None, env={"PADDLE_TPU_NUM_PROCESSES": "1",
                                           "PADDLE_TPU_PROCESS_ID": "0",
                                           "PADDLE_TPU_MASTER_ADDR": "x:1"})
    fired = []
    wd = StepWatchdog(10.0, on_hang=fired.append, poll_s=0.02)
    try:
        worker.watchdog = wd
        wd.arm("step")
        d0 = wd._deadline
        time.sleep(0.05)
        worker._leases.append(("t1", b"batch-0"))   # an owed step
        assert worker._lease_wait_tick() is False
        assert wd._deadline == d0                   # NOT re-armed
        worker._leases.clear()                      # idle: no step owed
        assert worker._lease_wait_tick() is False
        assert wd._deadline > d0                    # re-armed
    finally:
        wd.close()


def test_disowned_batch_excluded_from_pass_metrics(tmp_path, monkeypatch):
    """A batch whose lease lapsed (commit -> False) already ran, but the
    audited timeline disowns it: EndPass avg_cost must agree with the
    lease accounting, not with raw batch count."""
    from paddle_tpu.elastic.worker import ElasticWorker
    # short lease TTL: the simulated lapse leaves the task pending until
    # the master reclaims it, and the pass can only end after the retry
    master = _mk_master(3, timeout_sec=1.0)
    root = str(tmp_path / "ckpt")
    _lease_env(monkeypatch, master, tmp_path, timeout="1.0")
    tr = _worker_trainer()
    real_commit = ElasticWorker.commit
    calls = {"n": 0}

    def lapse_second(self, cost=None, skipped=False):
        calls["n"] += 1
        if calls["n"] == 2:
            # simulate the lapsed lease: pop the ledger head without
            # committing — the master re-leases the task later
            self._leases.popleft()
            self.lease_losses += 1
            return False
        return real_commit(self, cost=cost, skipped=skipped)

    monkeypatch.setattr(ElasticWorker, "commit", lapse_second)
    committed, end_iters, end_pass = [], [], []

    def handler(e):
        name = type(e).__name__
        if name == "EndIteration":
            end_iters.append(e.batch_id)
        elif name == "EndPass":
            end_pass.append(e.metrics["avg_cost"])

    try:
        with flags_guard(comm_hosts=FLAGS.comm_hosts):
            tr.train(elastic=True, task_reader=_task_batch,
                     elastic_root=root, event_handler=handler,
                     on_commit=lambda s, t, p, c: committed.append(
                         float(c)))
    finally:
        master.close()
    assert len(committed) == 3                 # every task exactly once
    assert len(end_iters) == 4                 # one disowned re-run
    assert end_pass and end_pass[0] == pytest.approx(
        float(np.mean(committed)))             # metrics == accounting
