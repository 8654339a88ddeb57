"""The program's set-up record (profiler.setup_span / setup_spans): import,
build, init and every step's compile, kept in memory with jax's own trace /
lower / compile / cache-read phases as their children, and nothing per
step."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.core import executor as executor_mod
from paddle_tpu.core import unique_name

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from chipbench import setup_trace  # noqa: E402
JAX = ("jax.trace", "jax.lower", "xla.compile", "cache.read")
STEPS = 3


def _build():
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with unique_name.guard(), pt.program_guard(main, startup):
        x = layers.data("x", shape=[4])
        y = layers.data("y", shape=[1])
        loss = layers.mean(layers.square_error_cost(
            layers.fc(x, size=3), y))
        trainer = pt.Trainer(
            cost=loss, optimizer=pt.SGD(learning_rate=0.1),
            feed_list=[x, y], place=pt.CPUPlace(), main_program=main,
            startup_program=startup)
    return trainer, scope, main


def _reader(steps):
    def read():
        rng = np.random.RandomState(0)
        for _ in range(steps):
            yield [(rng.rand(4).astype("float32"),
                    rng.rand(1).astype("float32")) for _ in range(2)]
    return read


@pytest.fixture(scope="module")
def trained():
    """A tiny Trainer's build, init and three steps; the record before and
    after."""
    executor_mod.clear_warm_cache()
    before = len(profiler.setup_spans())
    trainer, scope, main = _build()
    with pt.scope_guard(scope):
        trainer._maybe_init()
        trainer.train(_reader(STEPS), num_passes=1)
    records = profiler.setup_spans()
    return {"trainer": trainer, "scope": scope, "main": main,
            "records": records, "before": before, "after": len(records)}


def test_import_build_init_compile_each_with_its_parent(trained):
    rec, n0 = trained["records"], trained["before"]
    imports = [r for r in rec if r[0] == "import"]
    assert len(imports) == 1 and imports[0][3] is None
    mine = rec[n0:]
    names = [r[0] for r in mine]
    assert names.count("build") == 1 and names.count("init") == 1
    (build,) = [r for r in mine if r[0] == "build"]
    (init_i,) = [n0 + i for i, r in enumerate(mine) if r[0] == "init"]
    assert build[3] is None and rec[init_i][3] is None
    compiles = [r for r in mine if r[0] == "compile"]
    startup = trained["trainer"].startup_program._uid
    assert {r[4]["program"] for r in compiles} == {startup,
                                                   trained["main"]._uid}
    for r in compiles:
        assert r[4]["module"].startswith("jit_paddle_tpu_step_")
        assert r[4]["why"] in ("first_call", "fit_check")
        # the startup program's compile is the init's; the main step's is
        # the first train step's, under no set-up span
        assert r[3] == (init_i if r[4]["program"] == startup else None)
    assert all(r[2] is not None and r[2] >= r[1] for r in rec)


def test_jax_phases_lie_inside_their_parents_and_self_times_add_up(trained):
    rec = trained["records"]
    mine = range(trained["before"], len(rec))
    phases = [i for i in mine if rec[i][0] in JAX]
    assert {rec[i][0] for i in phases} >= {"jax.trace", "jax.lower",
                                           "xla.compile"}
    under = [i for i in phases if rec[i][3] is not None]
    assert under
    for i in under:
        parent = rec[rec[i][3]]
        assert parent[1] <= rec[i][1] <= rec[i][2] <= parent[2]
    # the first compile span of each step holds jax's trace of it
    first = {}
    for i in mine:
        if rec[i][0] == "compile":
            first.setdefault(rec[i][4]["module"], i)
    for i in first.values():
        assert any(rec[j][3] == i and rec[j][0] == "jax.trace"
                   for j in mine)
    # self times of a tree add up to its root's length: a partition
    own = setup_trace.self_times(rec, max(r[2] for r in rec))
    assert all(v >= -1e-9 for v in own.values())
    roots = [i for i in mine if rec[i][3] is None]

    def subtree(i):
        return [i] + [k for j in mine if rec[j][3] == i
                      for k in subtree(j)]
    for r in roots:
        total = sum(own[k] for k in subtree(r))
        assert total == pytest.approx(rec[r][2] - rec[r][1], abs=1e-9)


def test_more_steps_add_no_entry(trained):
    assert len(profiler.setup_spans()) == trained["after"]
    with pt.scope_guard(trained["scope"]):
        trained["trainer"].train(_reader(10), num_passes=1)
    assert len(profiler.setup_spans()) == trained["after"]


def test_a_held_step_records_one_fit_check_and_one_first_call(trained):
    uid = trained["main"]._uid
    whys = sorted(r[4]["why"] for r in trained["records"][trained["before"]:]
                  if r[0] == "compile" and r[4]["program"] == uid)
    assert whys == ["first_call", "fit_check"]
    assert trained["trainer"].exe.stats["ahead_steps"] >= STEPS - 1


def test_a_phase_with_no_span_open_has_no_parent():
    n0 = len(profiler.setup_spans())
    jax.jit(lambda v: v * 3.0 + 1.0)(np.float32(2.0))
    new = profiler.setup_spans()[n0:]
    assert {r[0] for r in new} >= {"jax.trace", "jax.lower", "xla.compile"}
    assert all(r[3] is None or new[r[3] - n0][0] in JAX for r in new)
    top = [r for r in new if r[3] is None]
    assert top and top[-1][0] == "xla.compile"


def test_setup_span_nests_and_a_phase_recorded_after_the_fact_adopts():
    n0 = len(profiler.setup_spans())
    with profiler.setup_span("build", what="outer"):
        with profiler.setup_span("init"):
            pass
    # phases recorded once they are over, as jax reports them: the inner
    # one first; the later, longer one takes it as its child, and the
    # one after both is neither's
    base = time.monotonic() + 1e3
    try:
        profiler.record_setup_span("cache.read", base + 1, base + 2)
        profiler.record_setup_span("xla.compile", base, base + 3, fun="f")
        profiler.record_setup_span("jax.lower", base + 4, base + 5)
        rec = profiler.setup_spans()[n0:]
        assert [(r[0], r[3]) for r in rec] == [
            ("build", None), ("init", n0), ("cache.read", n0 + 3),
            ("xla.compile", None), ("jax.lower", None)]
        assert rec[0][4] == {"what": "outer"} and rec[3][4] == {"fun": "f"}
        assert rec[0][1] <= rec[1][1] <= rec[1][2] <= rec[0][2]
    finally:
        del profiler._setup[n0 + 2:]    # no future-dated entries stay


def test_write_timeline_has_a_setup_section(tmp_path):
    art = profiler.write_timeline(str(tmp_path / "t.json"))
    assert [s["name"] for s in art["setup"]] == [
        r[0] for r in profiler.setup_spans()]
    with open(str(tmp_path / "t.json")) as f:
        assert json.load(f)["setup"][0]["name"] == "import"


def test_on_a_warm_persistent_cache_the_read_is_inside_xla_compile(tmp_path):
    """Two builds of one program in one process, jax's in-memory caches
    cleared between them: the second reads the persistent cache, and jax
    reports both its compile phase and its cache read."""
    script = textwrap.dedent("""
        import json, sys
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        sys.path.insert(0, %r)
        import test_setup_spans as t
        import paddle_tpu as pt
        from paddle_tpu import profiler
        from paddle_tpu.core import executor
        for k in range(2):
            n0 = len(profiler.setup_spans())
            trainer, scope, _main = t._build()
            with pt.scope_guard(scope):
                trainer.train(t._reader(2), num_passes=1)
            jax.clear_caches()
            executor.clear_warm_cache()
        print(json.dumps([n0, profiler.setup_spans()]))
    """) % os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    n0, rec = json.loads(out.stdout.strip().splitlines()[-1])
    reads = [r for r in rec[n0:] if r[0] == "cache.read"]
    assert reads
    assert all(rec[r[3]][0] == "xla.compile" for r in reads)
    # ... under the step's compile span, where the program compiled it
    assert any(rec[rec[r[3]][3]][0] == "compile" for r in reads
               if rec[r[3]][3] is not None)


def test_scope_table_prints_the_setup_record_as_a_tree(trained):
    """tools/scope_table.py: each program span with its self time and
    jax's phases under it; the startup step's compile under init."""
    spec = importlib.util.spec_from_file_location(
        "scope_table", os.path.join(ROOT, "tools", "scope_table.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rec = trained["records"]
    lines = tool.setup_tree(rec, max(r[2] for r in rec))
    assert lines[0].startswith("set-up record: ")
    assert "under no program span" in lines[-1]
    tops = [ln.split()[0] for ln in lines[1:-1] if not ln.startswith("    ")]
    assert tops[0] == "import" and {"build", "init", "compile"} <= set(tops)
    init = next(i for i, ln in enumerate(lines) if ln.split()[0] == "init")
    assert lines[init + 1].startswith("    compile why=first_call")
    assert "jax.trace" in lines[init + 1]
