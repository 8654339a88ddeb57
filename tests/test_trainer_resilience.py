"""Trainer-loop failure policy (PR 15): the step-hang watchdog
(resilience.watchdog), the numeric guardrails (resilience.guardrails),
the SIGTERM preemption drain budget, and the fault-registry conformance
walk (code <-> faults.py site table <-> docstring <-> cluster/README.md
must agree). The elastic-worker integration lives in test_elastic.py;
the full multi-process chaos legs in tools/elastic_smoke.sh."""
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import resilience as R
from paddle_tpu.flags import FLAGS, flags_guard
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.guardrails import NumericGuard
from paddle_tpu.resilience.watchdog import StepWatchdog, STEP_HUNG_EXIT

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_slate():
    faults.reset()
    R.clear_events()
    yield
    faults.reset()
    R.clear_events()


def _build_trainer(checkpoint_dir=None, linear=False, lr=0.1):
    """Tiny classifier Trainer on the per-test fresh programs.
    ``linear=True`` drops the tanh bottleneck so a scaled input can
    produce a genuinely spiking (but finite) loss."""
    main = pt.default_main_program()
    startup = pt.default_startup_program()
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = x if linear else layers.fc(x, size=8, act="tanh")
    pred = layers.fc(h, size=2, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    return pt.Trainer(cost=loss, optimizer=pt.SGD(learning_rate=lr),
                      feed_list=[x, y], place=pt.CPUPlace(),
                      main_program=main, startup_program=startup,
                      checkpoint_dir=checkpoint_dir)


def _batches(n, nan_at=None, scale_at=None, scale=1e3, seed=0):
    def reader():
        rng = np.random.RandomState(seed)
        for i in range(n):
            bx = rng.rand(8, 4).astype("float32")
            if i == nan_at:
                bx = bx.copy()
                bx[0, 0] = np.nan
            by = (bx.sum(axis=1) > 2).astype("int64").reshape(-1, 1)
            if i == scale_at:
                # a confidently-WRONG batch: saturated logits against
                # flipped labels -> a large but FINITE loss spike
                bx = (bx * scale).astype("float32")
                by = 1 - by
            yield list(zip(bx, by))
    return reader


# ---------------------------------------------------------------------------
# fault-registry conformance (code <-> table <-> docs)


def _docstring_table_sites():
    """Site names out of the faults.py docstring table (the first
    backticked token of each table row)."""
    rows = re.findall(r"^``([a-z_0-9]+\.[a-z_0-9]+)``",
                      faults.__doc__, re.MULTILINE)
    return rows


def test_site_table_matches_docstring_table():
    doc = _docstring_table_sites()
    assert sorted(doc) == sorted(faults.SITE_TABLE), \
        "faults.py docstring table and SITE_TABLE drifted: doc-only=%r " \
        "table-only=%r" % (sorted(set(doc) - set(faults.SITE_TABLE)),
                           sorted(set(faults.SITE_TABLE) - set(doc)))
    assert len(doc) == len(set(doc)), "duplicate docstring rows"


def test_every_armable_site_arms_and_fires():
    for site, (_, armable, _delay) in faults.SITE_TABLE.items():
        if not armable:
            continue
        faults.arm(site, "raise", nth=1, times=1)
        with pytest.raises(faults.FaultError):
            faults.fault_point(site)
        # outside the firing window the site is pass-through again
        assert faults.fault_point(site, "payload") == "payload"
        faults.disarm(site)


def test_sites_exist_at_documented_modules():
    for site, (module, armable, _delay) in faults.SITE_TABLE.items():
        path = os.path.join(REPO, "paddle_tpu", module)
        assert os.path.isfile(path), \
            "%s documents module %s which does not exist" % (site, module)
        with open(path) as f:
            src = f.read()
        assert site in src, \
            "site %r never appears in its documented module %s" \
            % (site, module)
        if armable:
            assert "fault_point(" in src, \
                "armable site %r's module %s has no fault_point call" \
                % (site, module)


def test_every_site_documented_in_cluster_readme():
    with open(os.path.join(REPO, "cluster", "README.md")) as f:
        readme = f.read()
    missing = [s for s in faults.SITE_TABLE if s not in readme]
    assert not missing, \
        "cluster/README.md has no row for fault site(s) %r" % missing


def test_delay_marked_sites_document_delay_semantics():
    """A site the gray chaos legs delay-arm must say what a delay
    MEANS in its docstring row — the mark in SITE_TABLE is a claim
    about the docs, so the docs must hold it."""
    rows = re.split(r"^``", faults.__doc__, flags=re.MULTILINE)
    doc_of = {}
    for row in rows:
        m = re.match(r"([a-z_0-9]+\.[a-z_0-9]+)``", row)
        if m:
            doc_of[m.group(1)] = row
    for site, (_m, _armable, delay_doc) in faults.SITE_TABLE.items():
        if delay_doc:
            assert "delay" in doc_of.get(site, ""), \
                "site %r is marked delay_documented but its docstring " \
                "row never mentions delay semantics" % site
    # the gray legs' actual levers must be marked
    for site in ("trainer.step", "serving.dispatch", "serving.generate",
                 "serving.route"):
        assert faults.SITE_TABLE[site][2], \
            "gray chaos lever %r lost its delay_documented mark" % site


# the gray-failure event vocabulary: every kind the detector tiers emit
# must have a row in the operator docs — doc/elasticity.md covers the
# training tier, doc/serving.md the serving tier, cluster/README.md
# both (the chaos-operations face)
GRAY_EVENT_DOCS = {
    "gray_suspected": ("doc/elasticity.md", "doc/serving.md",
                       "cluster/README.md"),
    "gray_mitigated": ("doc/elasticity.md", "doc/serving.md",
                       "cluster/README.md"),
    "gray_mitigation_skipped": ("doc/elasticity.md",
                                "cluster/README.md"),
}


def test_gray_events_documented_row_for_row():
    for kind, docs in GRAY_EVENT_DOCS.items():
        for rel in docs:
            with open(os.path.join(REPO, rel)) as f:
                text = f.read()
            assert kind in text, \
                "gray event %r has no row in %s" % (kind, rel)


def test_gray_events_actually_emitted_by_the_code():
    """The vocabulary above is not aspirational: each kind appears in
    the module that claims to emit it."""
    emitters = {
        "gray_suspected": ("paddle_tpu/elastic/supervisor.py",
                           "paddle_tpu/serving/router.py"),
        "gray_mitigated": ("paddle_tpu/elastic/supervisor.py",
                           "paddle_tpu/serving/router.py"),
        "gray_mitigation_skipped": ("paddle_tpu/elastic/supervisor.py",),
    }
    for kind, modules in emitters.items():
        for rel in modules:
            with open(os.path.join(REPO, rel)) as f:
                src = f.read()
            assert kind in src, \
                "%s never emits documented gray event %r" % (rel, kind)


# ---------------------------------------------------------------------------
# step watchdog


def test_watchdog_fires_once_on_lapse():
    fired = []
    wd = StepWatchdog(0.15, on_hang=fired.append, poll_s=0.02)
    try:
        wd.arm("stepA")
        time.sleep(0.5)
        assert len(fired) == 1
        assert fired[0]["label"] == "stepA"
        assert fired[0]["timeout_s"] == pytest.approx(0.15)
        # one firing suspends the deadline: no repeat fire
        time.sleep(0.3)
        assert len(fired) == 1
    finally:
        wd.close()


def test_watchdog_ping_defers_and_disarm_suspends():
    fired = []
    wd = StepWatchdog(0.2, on_hang=fired.append, poll_s=0.02)
    try:
        wd.arm("s0")
        for _ in range(5):           # keep making "progress"
            time.sleep(0.1)
            wd.ping("s")
        assert not fired
        wd.disarm()                  # a checkpoint-sized pause is legal
        time.sleep(0.4)
        assert not fired
    finally:
        wd.close()


def test_watchdog_rejects_zero_timeout_and_closes_clean():
    with pytest.raises(ValueError):
        StepWatchdog(0.0)
    wd = StepWatchdog(5.0)
    wd.close()
    assert not wd._thread.is_alive()


def test_trainer_watchdog_wiring(monkeypatch):
    """A seeded wedged step (trainer.step delay) inside Trainer.train
    trips the armed deadline at a step label. The kill action is
    injected so the suite survives the firing; the real os._exit path
    is tools/elastic_smoke.sh's hang leg."""
    from paddle_tpu import trainer as trainer_mod

    fired = []

    def factory(timeout_s, **kw):
        return StepWatchdog(timeout_s, on_hang=fired.append, poll_s=0.02)

    monkeypatch.setattr(trainer_mod, "StepWatchdog", factory)
    tr = _build_trainer()
    # warm the step program before the deadline is armed: the 0.3 s
    # deadline is sized for steady steps, and the first batch's XLA
    # compile under a loaded six-worker suite can outlast it — a second,
    # legitimate firing (every ping re-arms a fired deadline) that this
    # test's "exactly the seeded wedge" count would misread
    tr.train(_batches(2), num_passes=1)
    assert not fired
    faults.arm("trainer.step", "delay", nth=3, times=1, delay=1.2)
    with flags_guard(step_timeout_s=0.3):
        tr.train(_batches(5), num_passes=1)
    assert len(fired) == 1
    assert fired[0]["label"].startswith("pass0/batch")


def test_step_hung_exit_code_is_transient_for_the_supervisor():
    # the supervisor classifies rc >= 0 as transient (restartable);
    # 128+N signal mapping never produces 75
    assert STEP_HUNG_EXIT == 75
    from paddle_tpu.resilience.supervise import SlotSupervision
    sup = SlotSupervision(1)
    d = sup.classify_exit("job")
    assert d.action == "restart"


# ---------------------------------------------------------------------------
# numeric guardrails (unit)


def test_guard_accepts_finite_and_skips_nonfinite():
    g = NumericGuard(3)
    assert g.check(0.5) == "ok"
    assert g.check(float("nan")) == "skip"
    assert g.check(float("inf")) == "skip"
    assert g.check(0.4) == "ok"          # a good batch resets the streak
    assert g.skips == 2
    ev = R.events(kind="batch_skipped")
    assert len(ev) == 2
    assert {e["reason"] for e in ev} == {"nonfinite"}


def test_guard_spike_detection_after_warmup():
    g = NumericGuard(5, spike_factor=10.0)
    for v in (1.0, 1.1, 0.9):
        assert g.check(v) == "ok"
    assert g.check(50.0) == "skip"       # > 10x median(~1.0)
    assert g.check(5.0) == "ok"          # below the factor: accepted
    ev = R.events(kind="batch_skipped")
    assert ev and ev[-1]["reason"] == "spike"


def test_guard_spike_off_by_default():
    g = NumericGuard(2)
    for v in (1.0, 1.0, 1.0, 1e9):
        assert g.check(v) == "ok"


def test_guard_budget_exhaustion_rewinds_once_then_gives_up():
    rewinds = []
    g = NumericGuard(2, rewind_fn=lambda: rewinds.append(1) or True)
    nan = float("nan")
    assert g.check(nan) == "skip"
    assert g.check(nan) == "skip"        # budget hit -> rewind, window spent
    assert rewinds == [1]
    assert g.check(nan) == "skip"
    with pytest.raises(FloatingPointError):
        g.check(nan)                     # second exhaustion, same window
    assert rewinds == [1]                # bounded: once per window
    assert len(R.events(kind="guard_rewind")) == 1


def test_guard_good_batch_reopens_the_rewind_window():
    g = NumericGuard(1, rewind_fn=lambda: True)
    nan = float("nan")
    assert g.check(nan) == "skip"        # rewind #1
    assert g.check(1.0) == "ok"          # window reopens
    assert g.check(nan) == "skip"        # rewind #2 allowed
    assert g.rewinds == 2


def test_guard_without_rewind_target_gives_up_at_budget():
    g = NumericGuard(1)                  # no rewind_fn
    with pytest.raises(FloatingPointError):
        g.check(float("nan"))


def test_guard_rejects_zero_budget():
    with pytest.raises(ValueError):
        NumericGuard(0)


# ---------------------------------------------------------------------------
# numeric guardrails (Trainer integration)


def test_trainer_nan_batch_skipped_and_rewound(tmp_path):
    tr = _build_trainer(checkpoint_dir=str(tmp_path))
    tr.train(_batches(4), num_passes=1)          # seeds a checkpoint
    R.clear_events()
    with flags_guard(loss_skip_budget=2):
        tr.train(_batches(8, nan_at=3), num_passes=1)
    skips = R.events(kind="batch_skipped")
    assert skips and all(e["reason"] == "nonfinite" for e in skips)
    # the NaN batch poisons the params, so the follow-on batch skips
    # too; the exhausted budget then rewinds and training recovers
    assert len(R.events(kind="guard_rewind")) == 1
    assert R.events(kind="preempt_checkpoint") == []


def test_trainer_nan_without_checkpoint_gives_up():
    tr = _build_trainer()                        # nothing to rewind to
    with flags_guard(loss_skip_budget=1):
        with pytest.raises(FloatingPointError):
            tr.train(_batches(6, nan_at=1), num_passes=1)
    assert R.events(kind="batch_skipped")


def test_trainer_spike_skipped_without_rewind(tmp_path):
    # lr tiny so even the spike batch's gradient barely moves the
    # params: exactly one skip, and the follow-on batches stay accepted
    tr = _build_trainer(checkpoint_dir=str(tmp_path), linear=True,
                        lr=1e-4)
    with flags_guard(loss_skip_budget=3, loss_spike_factor=10.0):
        tr.train(_batches(8, scale_at=5, scale=100.0), num_passes=1)
    skips = R.events(kind="batch_skipped")
    assert skips and skips[0]["reason"] == "spike"
    # a finite spike does not poison the params: no rewind needed
    assert R.events(kind="guard_rewind") == []


def test_trainer_guard_is_inert_by_default():
    tr = _build_trainer()
    # budget 0 = off: a NaN loss flows through exactly as before
    costs = []
    tr.train(_batches(4, nan_at=2), num_passes=1,
             event_handler=lambda e: costs.append(e.cost)
             if type(e).__name__ == "EndIteration" else None)
    assert any(not np.isfinite(c) for c in costs)
    assert R.events(kind="batch_skipped") == []


# ---------------------------------------------------------------------------
# preemption x supervisor escalation (trainer.py SIGTERM hook)


def test_preemption_hook_off_main_thread_falls_back(tmp_path):
    """train() on a non-main thread must not touch signal handlers
    (signal.signal raises ValueError there) — and request_preempt()
    is the programmatic drain for exactly that case."""
    import signal as _signal
    before = _signal.getsignal(_signal.SIGTERM)
    tr = _build_trainer(checkpoint_dir=str(tmp_path))
    started = threading.Event()

    def slow_batches():
        rng = np.random.RandomState(0)
        for i in range(50):
            started.set()
            time.sleep(0.05)
            bx = rng.rand(8, 4).astype("float32")
            by = (bx.sum(axis=1) > 2).astype("int64").reshape(-1, 1)
            yield list(zip(bx, by))

    box = {}

    def run():
        try:
            tr.train(slow_batches, num_passes=1)
            box["done"] = True
        except BaseException as e:           # surfaced below
            box["error"] = e

    t = threading.Thread(target=run)
    t.start()
    assert started.wait(60.0)
    tr.request_preempt()
    t.join(timeout=60.0)
    assert not t.is_alive()
    assert "error" not in box, box.get("error")
    assert _signal.getsignal(_signal.SIGTERM) is before
    assert R.events(kind="preempt_checkpoint")


def test_preempt_truncated_recorded_when_grace_cannot_fit(
        tmp_path, monkeypatch):
    """A drain whose final checkpoint cannot plausibly fit the
    remaining --grace-sec window records preempt_truncated BEFORE the
    save — the supervisor-exported PADDLE_TPU_GRACE_SEC is the budget."""
    monkeypatch.setenv("PADDLE_TPU_GRACE_SEC", "0.001")
    tr = _build_trainer(checkpoint_dir=str(tmp_path))
    tr.train(_batches(2), num_passes=1)      # measures a real save
    R.clear_events()
    tr._last_ckpt_secs = 30.0                # a save this window can't fit

    def handler(e):
        if type(e).__name__ == "EndIteration" and e.batch_id == 1:
            tr.request_preempt()

    tr.train(_batches(6), num_passes=1, event_handler=handler)
    trunc = R.events(kind="preempt_truncated")
    assert trunc and trunc[0]["phase"] == "pre"
    # the save is STILL attempted (atomic: SIGKILL mid-write is safe)
    assert R.events(kind="preempt_checkpoint")


def test_preempt_within_grace_not_truncated(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_GRACE_SEC", "300")
    tr = _build_trainer(checkpoint_dir=str(tmp_path))

    def handler(e):
        if type(e).__name__ == "EndIteration" and e.batch_id == 1:
            tr.request_preempt()

    tr.train(_batches(6), num_passes=1, event_handler=handler)
    assert R.events(kind="preempt_checkpoint")
    assert R.events(kind="preempt_truncated") == []
    assert tr._grace_sec == pytest.approx(300.0)


def test_launcher_exports_grace_sec():
    from paddle_tpu.elastic.supervisor import ElasticSupervisor
    sup = ElasticSupervisor(2, "127.0.0.1", ["x.py"], grace_sec=7.5,
                            master_tasks=None)
    env = sup._rank_env(0, 2, 0, "127.0.0.1:1", None)
    assert env["PADDLE_TPU_GRACE_SEC"] == "7.5"


# ---------------------------------------------------------------------------
# observability


def test_trainer_counters_and_timeline_section(tmp_path):
    from paddle_tpu import profiler as _prof
    _prof.reset_trainer_counters()
    _prof.update_trainer_counters(batches_skipped=2, guard_rewinds=1,
                                  elastic_tasks_committed=5)
    c = _prof.trainer_counters()
    assert c["batches_skipped"] == 2.0
    assert c["guard_rewinds"] == 1.0
    art = _prof.write_timeline(str(tmp_path / "t.json"))
    assert art["trainer"]["elastic_tasks_committed"] == 5.0
    _prof.reset_trainer_counters()
    assert _prof.trainer_counters() == {}


def test_new_flags_declared():
    assert FLAGS.step_timeout_s == 0.0
    assert FLAGS.loss_spike_factor == 0.0
    assert FLAGS.loss_skip_budget == 0
    assert FLAGS.elastic_ckpt_period == 1


# ---------------------------------------------------------------------------
# review-hardening regressions


def test_watchdog_tick_rearms_live_deadline_only():
    fired = []
    wd = StepWatchdog(0.2, on_hang=fired.append, poll_s=0.02)
    try:
        wd.arm("s")
        for _ in range(5):               # an idle lease wait IS progress
            time.sleep(0.1)
            wd.tick("lease-wait")
        assert not fired
        wd.disarm()                      # a checkpoint-save pause...
        for _ in range(3):
            time.sleep(0.05)
            wd.tick("lease-wait")        # ...must STAY paused
        assert wd._deadline is None
        time.sleep(0.3)
        assert not fired
    finally:
        wd.close()


def test_lease_free_worker_never_snapshots_the_shared_master(
        tmp_path, monkeypatch):
    """A rank that merely SEES the master (PADDLE_TPU_MASTER_ADDR is
    exported to everyone) but owns no leases must not pair the shared
    master's state with its own unrelated step counter."""
    from paddle_tpu.elastic import resume as resume_mod
    from paddle_tpu.elastic.supervisor import TaskMasterHost
    from paddle_tpu.elastic.worker import ElasticWorker
    from paddle_tpu.flags import flags_guard as fg

    master = TaskMasterHost([b"batch-0"], timeout_sec=30.0)
    monkeypatch.setenv("PADDLE_TPU_NUM_PROCESSES", "1")
    monkeypatch.setenv("PADDLE_TPU_PROCESS_ID", "0")
    monkeypatch.setenv("PADDLE_TPU_ELASTIC", "1")
    monkeypatch.setenv("PADDLE_TPU_ELASTIC_STATE", str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_MASTER_ADDR", master.addr)
    root = str(tmp_path / "ckpt")
    tr = _build_trainer()
    worker = ElasticWorker(tr, task_reader=None, root=root)
    try:
        with fg(comm_hosts=FLAGS.comm_hosts):
            worker.setup()
            tr._maybe_init(load=False)
            assert worker.client is not None     # registered, heartbeating
            worker.commit(cost=1.0)              # lease-free step 1
        ckpts = [d for d in os.listdir(root) if d.startswith("ckpt-")]
        assert ckpts                             # checkpoint written...
        assert not os.path.exists(os.path.join(
            root, ckpts[0], "master.snap"))      # ...but UNPAIRED
        rp = resume_mod.resume_point(root)
        assert rp is not None and rp.snapshot is None
    finally:
        worker.close()
        master.close()


def test_guard_rewind_pauses_the_step_deadline(tmp_path, monkeypatch):
    """A checkpoint restore longer than step_timeout_s is recovery, not
    a hang: the rewind must not be killed mid-restore."""
    from paddle_tpu import trainer as trainer_mod

    fired = []

    def factory(timeout_s, **kw):
        return StepWatchdog(timeout_s, on_hang=fired.append, poll_s=0.02)

    monkeypatch.setattr(trainer_mod, "StepWatchdog", factory)
    tr = _build_trainer(checkpoint_dir=str(tmp_path))
    tr.train(_batches(2), num_passes=1)          # seeds the rewind target
    real_load = tr._load_checkpoint_state

    def slow_load():
        time.sleep(0.8)                          # >> step_timeout_s
        return real_load()

    monkeypatch.setattr(tr, "_load_checkpoint_state", slow_load)
    with flags_guard(loss_skip_budget=1, step_timeout_s=0.3):
        tr.train(_batches(6, nan_at=2), num_passes=1)
    assert not fired
    assert len(R.events(kind="guard_rewind")) == 1


def test_durable_events_write_strict_json_for_nonfinite(tmp_path,
                                                        monkeypatch):
    import json as _json
    monkeypatch.setenv("PADDLE_TPU_ELASTIC_STATE", str(tmp_path))
    R.record_durable_event("batch_skipped", site="trainer.guard",
                           loss=float("nan"), baseline=float("inf"))
    line = open(os.path.join(str(tmp_path), "events.jsonl")).read()
    assert "NaN" not in line and "Infinity" not in line
    row = _json.loads(line)
    assert row["loss"] == "nan" and row["baseline"] == "inf"


def test_tainted_pass_end_keeps_the_last_clean_checkpoint(tmp_path):
    """A pass ending on a skipped (possibly non-finite) batch must not
    persist the poisoned params as the newest resume state."""
    tr = _build_trainer(checkpoint_dir=str(tmp_path))
    tr.train(_batches(3), num_passes=1)          # the clean save
    with flags_guard(loss_skip_budget=3):
        # NaN on the LAST batch: one within-budget skip, pass ends
        # with the poisoned update still in the params
        tr.train(_batches(4, nan_at=3), num_passes=1)
    assert R.events(kind="checkpoint_skipped_tainted")
    # the on-disk state is still the CLEAN save: restoring and
    # training from it stays finite
    assert tr._load_checkpoint_state() is True
    costs = []
    tr.train(_batches(3), num_passes=1,
             event_handler=lambda e: costs.append(e.cost)
             if type(e).__name__ == "EndIteration" else None)
    assert costs and all(np.isfinite(c) for c in costs)
