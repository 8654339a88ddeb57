"""The program's own tracing: host spans of Trainer / DataFeeder / Executor
in the jax profiler's trace (profiler.span / step_span), forward / backward /
update marks on the ops and their named scopes on the device ops
(profiler.device_scopes), and one executable whether profiling is on or off.
jax.profiler records the host's ``python`` line on the CPU too."""
import glob
import os
import re

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core import unique_name

BATCH, STEPS = 4, 3
PHASES = ("forward", "backward", "update")


def _build():
    """conv + batch norm + fc under Momentum, in fresh programs and scope."""
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with unique_name.guard(), pt.program_guard(main, startup):
        x = layers.data("x", shape=[3, 8, 8])
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.conv2d(x, num_filters=4, filter_size=3)
        h = layers.batch_norm(h, act="relu")
        pred = layers.fc(h, size=5, act="softmax")
        loss = layers.mean(layers.cross_entropy(pred, y))
        trainer = pt.Trainer(
            cost=loss,
            optimizer=pt.Momentum(learning_rate=0.1, momentum=0.9),
            feed_list=[x, y], place=pt.CPUPlace(), main_program=main,
            startup_program=startup)
    return trainer, scope, main


def _reader():
    rng = np.random.RandomState(0)
    for i in range(STEPS):
        yield [(rng.rand(3, 8, 8).astype("float32"),
                np.array([i % 5], "int64")) for _ in range(BATCH)]


BATCH_NBYTES = BATCH * (3 * 8 * 8 * 4 + 8)


def _spans(logdir):
    """[(name, start_ns, end_ns, args, line)] of the paddle_tpu/ spans, by
    start; ``line`` tells the host thread that opened the span."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1, found
    out = []
    for plane in ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("paddle_tpu/"):
                    assert plane.name == "/host:CPU"
                    out.append((ev.name[len("paddle_tpu/"):],
                                int(ev.start_ns),
                                int(ev.start_ns) + int(ev.duration_ns),
                                dict(ev.stats), line.name))
    return sorted(out, key=lambda t: (t[1], -t[2]))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three Trainer steps under profiler.xla_trace; the startup program
    ran (and compiled) before the session opened."""
    executor_mod.clear_warm_cache()
    trainer, scope, main = _build()
    logdir = tmp_path_factory.mktemp("trace")
    with pt.scope_guard(scope):
        trainer._maybe_init()
        with profiler.xla_trace(logdir):
            trainer.train(_reader, num_passes=1)
    steps = executor_mod.compiled_steps()
    return {"spans": _spans(logdir), "trainer": trainer, "main": main,
            "scope": scope,
            "step": next(s for s in steps
                         if s._span_args["program"] == main._uid)}


def _inside(spans, outer, name):
    return [s for s in spans
            if s[0] == name and s[1] >= outer[1] and s[2] <= outer[2]]


def _train_steps(spans):
    return [s for s in spans
            if s[0] == "train_step" and not s[3].get("end_of_pass")]


@pytest.mark.parametrize("k", range(STEPS))
def test_each_step_span_holds_its_run_the_next_feed_and_its_fetch(traced, k):
    """train_step(k): batch k+1 is fed and uploaded, run(k+1) returns
    once step k+1 is dispatched on it, then step k's loss is fetched.
    Step 0 also takes its own batch and dispatches itself first, the last
    step finds the reader exhausted and dispatches nothing."""
    spans = traced["spans"]
    step = _train_steps(spans)[k]
    assert (step[3]["step_num"], step[3]["pass_id"],
            step[3]["batch_id"]) == (k, 0, k)
    runs, (fetch,) = _inside(spans, step, "run"), _inside(spans, step,
                                                          "fetch")
    own = 1 if k == 0 else 0
    ahead = 1 if k < STEPS - 1 else 0
    assert len(runs) == own + ahead
    for run in runs:
        assert run[3]["program"] == traced["main"]._uid
        (dispatch,) = _inside(spans, run, "dispatch")
        assert not _inside(spans, run, "upload")    # the batch was here
        assert not _inside(spans, run, "fetch")     # run() does not wait
        assert dispatch[2] <= run[2] <= fetch[1]
    feeds, uploads = (_inside(spans, step, n) for n in ("feed", "upload"))
    assert len(feeds) == len(uploads) == own + ahead
    for feed, upload in zip(feeds, uploads):
        assert feed[3]["rows"] == BATCH
        assert feed[3]["bytes"] == upload[3]["bytes"] == BATCH_NBYTES
        assert feed[2] <= upload[1]
    if own:
        assert uploads[0][2] <= runs[0][1]      # fed, then run
    if own and ahead:
        assert runs[0][2] <= feeds[-1][1]
    if ahead:           # fed, dispatched on it, and then the loss
        assert uploads[-1][2] <= runs[-1][1] and runs[-1][2] <= fetch[1]
    # all of it on the thread that called train()
    assert {s[4] for n in ("feed", "upload", "run", "dispatch", "fetch")
            for s in _inside(spans, step, n)} == {step[4]}


def test_one_upload_span_a_batch_and_none_of_no_bytes(traced):
    uploads = [s for s in traced["spans"] if s[0] == "upload"]
    assert [u[3]["bytes"] for u in uploads] == [BATCH_NBYTES] * STEPS
    steps = _train_steps(traced["spans"])
    assert all(any(st[1] <= u[1] and u[2] <= st[2] for st in steps)
               for u in uploads)


def test_prepare_feed_holds_the_upload_span_and_run_opens_none(tmp_path):
    x = layers.data("x", shape=[4])
    out = layers.mean(layers.fc(x, size=2))
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    host = {"x": np.ones((2, 4), np.float32)}
    exe.run(feed=host, fetch_list=[out])
    with profiler.xla_trace(tmp_path):
        here = exe.prepare_feed(host)
        exe.run(feed=here, fetch_list=[out])
        exe.run(feed=here, fetch_list=[out])
        exe.run(feed=host, fetch_list=[out])
    spans = _spans(tmp_path)
    runs = [s for s in spans if s[0] == "run"]
    uploads = [s for s in spans if s[0] == "upload"]
    assert [u[3]["bytes"] for u in uploads] == [32, 32]
    assert uploads[0][2] <= runs[0][1]          # prepare_feed's, outside
    assert [len(_inside(spans, r, "upload")) for r in runs] == [0, 0, 1]


@pytest.mark.parametrize("name,count", [
    ("train_step", STEPS), ("feed", STEPS), ("run", STEPS),
    ("upload", STEPS), ("dispatch", STEPS), ("fetch", STEPS),
    ("compile", 1)])
def test_span_counts(traced, name, count):
    spans = traced["spans"]
    got = _train_steps(spans) if name == "train_step" else [
        s for s in spans if s[0] == name]
    assert len(got) == count


def test_the_one_compile_span_is_in_step_0_inside_dispatch(traced):
    spans = traced["spans"]
    (compile_,) = [s for s in spans if s[0] == "compile"]
    assert compile_[3] == {"program": traced["main"]._uid,
                           "version": traced["main"]._version}
    step0 = _train_steps(spans)[0]
    # step 0's own dispatch; step 1's, ahead, finds the step compiled
    dispatch, _ahead = _inside(spans, step0, "dispatch")
    assert dispatch[1] <= compile_[1] and compile_[2] <= dispatch[2]


def test_the_call_that_finds_the_reader_exhausted_is_marked(traced):
    last = [s for s in traced["spans"]
            if s[0] == "train_step" and s[3].get("end_of_pass")]
    assert len(last) == 1 and last[0][3]["step_num"] == STEPS
    assert not any(_inside(traced["spans"], last[0], n)
                   for n in ("feed", "run"))


def test_the_lazy_fetch_is_spanned_where_it_materialises(tmp_path):
    x = layers.data("x", shape=[4])
    out = layers.mean(layers.fc(x, size=2))
    exe = pt.Executor(pt.CPUPlace())
    exe.run(pt.default_startup_program())
    feed = {"x": np.ones((2, 4), np.float32)}
    exe.run(feed=feed, fetch_list=[out])
    with profiler.xla_trace(tmp_path):
        (handle,) = exe.run(feed=feed, fetch_list=[out], sync=False)
        handle.value()
        handle.value()
    spans = _spans(tmp_path)
    (run,) = [s for s in spans if s[0] == "run"]
    (fetch,) = [s for s in spans if s[0] == "fetch"]
    assert fetch[1] >= run[2]                   # after run() returned


@pytest.mark.parametrize("host_profiler", [False, True])
def test_no_session_no_file_and_one_executable_on_and_off(
        tmp_path, monkeypatch, host_profiler):
    """With no jax.profiler session the spans leave nothing behind, and the
    host profiler (profiler.profiler()) neither compiles nor runs another
    executable: what is profiled is what runs."""
    monkeypatch.chdir(tmp_path)
    trainer, scope, _main = _build()
    with pt.scope_guard(scope):
        trainer.train(_reader, num_passes=1)
        compiles = trainer.exe.stats["compiles"]
        step_fns = set(map(id, executor_mod.compiled_steps()))
        if host_profiler:
            timeline = str(tmp_path / "timeline.json")
            with profiler.profiler(timeline_path=timeline):
                trainer.train(_reader, num_passes=1)
            os.remove(timeline)
            # still there once the session is over (chip_smoke.py asks so)
            assert profiler.get_program_analysis(
                "program_%d" % _main._uid)["flops"] > 0
        else:
            trainer.train(_reader, num_passes=1)
    assert trainer.exe.stats["compiles"] == compiles
    assert set(map(id, executor_mod.compiled_steps())) == step_fns
    assert glob.glob(str(tmp_path / "**" / "*"), recursive=True) == []


def test_the_programs_section_comes_from_the_step_that_runs(tmp_path):
    """write_timeline's ``programs`` is fed on demand from the kept
    abstract values of the step that ran, compiled before profiling."""
    trainer, scope, main = _build()
    with pt.scope_guard(scope):
        trainer.train(_reader, num_passes=1)
        with profiler.profiler():
            trainer.train(_reader, num_passes=1)
            art = profiler.write_timeline(str(tmp_path / "t.json"))
    entry = art["programs"]["program_%d" % main._uid]
    assert entry["flops"] > 0 and entry["mesh_devices"] == 1
    assert "collectives" in entry


def test_every_op_carries_one_phase_in_program_order(traced):
    ops = traced["main"].global_block().ops
    phases = [op.phase for op in ops]
    assert set(phases) == set(PHASES)
    # forward ops, then what append_backward appended, then the optimizer's
    assert phases == sorted(phases, key=PHASES.index)
    assert all(op.phase == "update" for op in ops if op.type == "momentum")
    assert all(op.phase == "backward" for op in ops
               if op.type.endswith("_grad"))
    assert all(op.phase == "forward"
               for op in traced["trainer"].startup_program
               .global_block().ops)
    clone = traced["main"].clone()
    assert [op.phase for op in clone.global_block().ops] == phases


@pytest.mark.parametrize("phase", PHASES)
def test_the_lowered_step_has_the_scope_of_each_phase(traced, phase):
    step = traced["step"]
    text = step.fn.lower(*step._avals).as_text(debug_info=True)
    assert re.search(r'"[^"]*/%s/\w+' % phase, text), phase
    assert step.fn.__name__.startswith("paddle_tpu_step_")


def test_device_scopes_cover_every_instruction_of_the_entry(traced):
    step = traced["step"]
    text = step.fn.lower(*step._avals).compile().as_text()
    module = text.split()[1].rstrip(",")
    assert module == "jit_" + step.fn.__name__
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")].splitlines()[2:]
    names = [ln.split(" = ")[0].replace("ROOT", "").strip().lstrip("%")
             for ln in entry if " = " in ln]
    assert len(names) > 10
    table = profiler.device_scopes()[module]
    assert set(names) <= set(table)
    valid = re.compile(r"^(?:(?:forward|backward|update)/\w+|unscoped)$")
    assert all(valid.match(v) for v in table.values())
    assert {v.split("/")[0] for v in table.values()} >= set(PHASES)
    assert "update/momentum" in table.values()
    assert "backward/conv2d_grad" in table.values()


def test_startup_and_main_steps_have_tables_of_their_own(traced):
    scopes = profiler.device_scopes()
    mine = ["jit_" + s.fn.__name__ for s in executor_mod.compiled_steps()
            if s._span_args["program"] in (
                traced["main"]._uid,
                traced["trainer"].startup_program._uid)]
    assert len(set(mine)) == 2 and set(mine) <= set(scopes)
    startup = [m for m in mine if m != "jit_" + traced["step"].fn.__name__]
    assert {v.split("/")[0] for v in scopes[startup[0]].values()} <= {
        "forward", "unscoped"}


def test_the_steps_name_is_the_same_for_the_same_script():
    """The name is part of the persistent compile cache's key: two builds
    of one script must agree on it, two different steps must not."""
    names = []
    for _ in range(2):
        executor_mod.clear_warm_cache()
        trainer, scope, main = _build()
        with pt.scope_guard(scope):
            trainer.train(_reader, num_passes=1)
        names.append(sorted(s.fn.__name__
                            for s in executor_mod.compiled_steps()))
    assert names[0] == names[1] and len(set(names[0])) == 2


def test_scopes_of_module_reads_fusions_and_leaves_the_rest_unscoped():
    text = "\n".join([
        "HloModule jit_paddle_tpu_step_0badf00d, is_scheduled=true",
        "%fused_computation (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        '  ROOT %m.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit('
        'paddle_tpu_step_0badf00d)/forward/relu/mul"}',
        "}",
        "ENTRY %main.3 (x.1: f32[8]) -> f32[8] {",
        '  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}',
        "  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%x.1)",
        '  %fusion.7 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_'
        'computation, metadata={op_name="jit(paddle_tpu_step_0badf00d)/'
        'backward/while_grad/forward/relu/mul" stack_frame_id=3}',
        '  ROOT %sub.2 = f32[8]{0} subtract(%fusion.7, %x.1), metadata={'
        'op_name="jit(paddle_tpu_step_0badf00d)/update/momentum/sub"}',
        "}"])
    module, table = profiler.scopes_of_module(text)
    assert module == "jit_paddle_tpu_step_0badf00d"
    assert table == {"p": "unscoped", "m.1": "forward/relu",
                     "x.1": "unscoped", "copy-start": "unscoped",
                     "fusion.7": "backward/while_grad",   # the outermost
                     "sub.2": "update/momentum"}
