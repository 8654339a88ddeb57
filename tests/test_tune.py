"""paddle_tpu.tune: search spaces, autotune loop, winner cache
(round trip + corruption), both fault sites, dispatch integration
(hits/misses/fallbacks + bit-identity), and the CLI verb's exit codes.

Everything runs in pallas interpret mode with deterministic timers —
the subsystem's own CI-testability requirement.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, tune
from paddle_tpu.core.executor import clear_warm_cache
from paddle_tpu.flags import flags_guard
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.events import clear_events, events
from paddle_tpu.tune.results import device_kind

CONV_KEY = {"n": 2, "h": 8, "w": 8, "c": 16, "o": 32, "dtype": "float32"}


@pytest.fixture(autouse=True)
def _isolated_tune(tmp_path):
    """Every test gets a throwaway cache dir, fresh counters, disarmed
    faults, and a cold in-memory cache layer."""
    with flags_guard(tune_cache_dir=str(tmp_path / "tune"), tune=True):
        tune.clear_memory_cache()
        tune.reset_counters()
        faults.reset()
        clear_events()
        yield tmp_path / "tune"
    tune.clear_memory_cache()
    tune.reset_counters()
    faults.reset()


# -- spaces ------------------------------------------------------------------

def test_space_candidates_valid_and_pruned():
    sp = tune.get_space("conv3x3")
    cands = sp.candidates(CONV_KEY)
    assert cands[0] == sp.default_config(CONV_KEY)
    for cfg in cands:
        assert sp.is_valid(cfg, CONV_KEY)
        assert sp.vmem_bytes(cfg, CONV_KEY) <= tune.space.VMEM_BUDGET
        # block_n must divide n=2; block_o 128/256 can't tile o=32
        assert cfg["block_n"] in (1, 2)
        assert cfg["block_o"] == 0
    assert sp.candidates(CONV_KEY, budget=2) == cands[:2]


def test_matmul_space_alignment_constraints():
    sp = tune.get_space("matmul")
    key = {"m": 64, "k": 256, "n": 256, "dtype": "float32"}
    for cfg in sp.candidates(key):
        bm = cfg["block_m"] or 64
        bn = cfg["block_n"] or 256
        bk = cfg["block_k"] or 256
        assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
        assert 64 % bm == 0 and 256 % bn == 0 and 256 % bk == 0


CELL_ATTN = {"b": 2, "s": 4096, "h": 32, "d": 192, "dv": 128,
             "causal": True, "dtype": "bfloat16"}


@pytest.mark.parametrize("key,want", [
    (CELL_ATTN, {"block_q": 512, "block_k": 512}),
    (dict(CELL_ATTN, s=300), {"block_q": 128, "block_k": 128}),
    (dict(CELL_ATTN, s=100), {"block_q": 100, "block_k": 100}),
    # the forward's own pick there is 256 x 512; a config is one pair for
    # all three kernels, so the narrower side of the backward's stands
    (dict(CELL_ATTN, s=8192), {"block_q": 256, "block_k": 256}),
])
def test_flash_space_default_is_the_kernels_own_rule(key, want):
    from paddle_tpu.kernels.flash_attention import (KERNELS, default_blocks,
                                                    padded_len)
    sp = tune.get_space("flash_attention")
    s = padded_len(key["s"])
    picks = [default_blocks(k, s, s, key["d"], key["dv"], key["dtype"], True)
             for k in KERNELS]
    assert sp.default_config(key) == want == {
        "block_q": min(p[0] for p in picks),
        "block_k": min(p[1] for p in picks)}
    assert sp.candidates(key)[0] == want


def test_flash_space_reckons_the_backward_and_the_padded_length():
    """A candidate has to divide the length the kernels run (300 -> 384)
    and fit the BACKWARD kernels' residency too: whole-sequence q, dO and
    the statistics, which the forward-only footprint left out."""
    from paddle_tpu.kernels.flash_attention import VMEM_LIMIT, vmem_bytes
    sp = tune.get_space("flash_attention")
    wide = {"block_q": 512, "block_k": 512}
    assert sp.vmem_bytes(wide, CELL_ATTN) == max(
        vmem_bytes(k, 512, 512, 4096, 4096, 192, 128, 2)
        for k in ("fwd", "dq", "dkv")) <= VMEM_LIMIT
    assert wide in sp.candidates(CELL_ATTN)
    # twice the length: 12 MB of resident operands, 512 x 512 cannot be
    # crowned (Mosaic refuses the forward and dK/dV there)
    long = dict(CELL_ATTN, s=8192)
    assert sp.is_valid(wide, long) and sp.vmem_bytes(wide, long) > VMEM_LIMIT
    assert wide not in sp.candidates(long)
    # the forward alone would have fitted at 512 x 256: the backward rules
    assert vmem_bytes("dq", 512, 256, 8192, 8192, 192, 128, 2) > VMEM_LIMIT
    assert {"block_q": 512, "block_k": 256} not in sp.candidates(long)
    for cfg in sp.candidates(dict(CELL_ATTN, s=300)):
        assert 384 % cfg["block_q"] == 0 and 384 % cfg["block_k"] == 0
        assert cfg["block_k"] % 128 == 0 and cfg["block_q"] % 8 == 0
    # one short tile: every width clamps to the sequence, one stands for all
    assert sp.candidates(dict(CELL_ATTN, s=100)) == [
        {"block_q": 100, "block_k": 100}, {"block_q": 128, "block_k": 128}]


# -- loop --------------------------------------------------------------------

def test_autotune_deterministic_winner_and_parity_gate():
    sp = tune.get_space("conv3x3")
    cands = sp.candidates(CONV_KEY)
    # table timer: make a specific non-default candidate the fastest
    target = dict(cands[-1])
    table = {frozenset(target.items()): 0.01,
             frozenset(tune.XLA_CONFIG.items()): 0.5}
    res = tune.autotune("conv3x3", CONV_KEY,
                        timer=tune.table_timer(table, default=1.0))
    assert res.ok and res.winner == target
    assert res.timer_kind == "table"
    # every candidate that was timed passed the parity gate
    assert all(r["status"] == "ok" for r in res.records)
    # the persisted entry survives a cold reload
    tune.clear_memory_cache()
    assert tune.WinnerCache().get_config(res.cache_key) == target


def test_autotune_stock_xla_always_in_the_race():
    res = tune.autotune("conv3x3", CONV_KEY, timer=tune.table_timer({}))
    # table timer default 1.0 everywhere -> first candidate (stock) wins
    assert res.winner == tune.XLA_CONFIG
    assert res.records[0]["config"] == tune.XLA_CONFIG


def test_candidate_fault_recorded_and_skipped():
    faults.arm("tune.candidate", "raise", nth=3, times=1)
    res = tune.autotune("conv3x3", CONV_KEY, timer=tune.model_timer())
    assert res.ok  # the loop survived
    errs = [r for r in res.records if r["status"] == "error"]
    assert len(errs) == 1
    assert events(kind="tune_candidate_failed")
    assert events(kind="fault_injected", site="tune.candidate")


def test_zero_eligible_candidates_degrades_not_raises():
    faults.arm("tune.candidate", "raise", nth=1, times=None)
    res = tune.autotune("conv3x3", CONV_KEY, timer=tune.model_timer(),
                        persist=False)
    assert not res.ok and res.winner is None
    assert all(r["status"] == "error" for r in res.records)


# -- cache -------------------------------------------------------------------

def test_cache_round_trip_and_drop(_isolated_tune):
    cache = tune.WinnerCache()
    key = tune.cache_key("cpu", "conv3x3", "sig=1")
    cache.put(key, {"block_n": 2}, time_ms=1.5, timer="model")
    assert cache.get_config(key) == {"block_n": 2}
    tune.clear_memory_cache()
    again = tune.WinnerCache()
    assert again.get_config(key) == {"block_n": 2}
    assert again.get(key)["timer"] == "model"
    assert again.drop(key)
    tune.clear_memory_cache()
    assert tune.WinnerCache().get_config(key) is None


def test_cache_entry_crc_detects_manual_bit_rot(_isolated_tune):
    cache = tune.WinnerCache()
    k1 = tune.cache_key("cpu", "conv3x3", "sig=1")
    k2 = tune.cache_key("cpu", "conv3x3", "sig=2")
    cache.put(k1, {"block_n": 2})
    cache.put(k2, {"block_n": 1})
    # flip the stored config of k1 on disk without updating its CRC
    with open(cache.path) as f:
        doc = json.load(f)
    doc["entries"][k1]["config"]["block_n"] = 8
    with open(cache.path, "w") as f:
        json.dump(doc, f)
    tune.clear_memory_cache()
    fresh = tune.WinnerCache()
    assert fresh.get_config(k1) is None          # dropped, not served
    assert fresh.get_config(k2) == {"block_n": 1}  # others survive
    assert events(kind="tune_cache_corrupt")


def test_cache_fault_site_corruption_detected_and_retuned(_isolated_tune):
    timer = tune.model_timer()
    faults.arm("tune.cache", "corrupt", nth=1, times=1, seed=3)
    res = tune.autotune("conv3x3", CONV_KEY, timer=timer)
    faults.reset()
    tune.clear_memory_cache()
    assert tune.WinnerCache().get_config(res.cache_key) is None
    assert events(kind="tune_cache_corrupt")
    # re-tune repopulates with a valid entry
    res2 = tune.autotune("conv3x3", CONV_KEY, timer=timer)
    tune.clear_memory_cache()
    assert tune.WinnerCache().get_config(res2.cache_key) == res2.winner


def test_unparseable_cache_file_is_empty_not_fatal(_isolated_tune):
    cache = tune.WinnerCache()
    cache.put(tune.cache_key("cpu", "x", "s"), {"a": 1})
    with open(cache.path, "w") as f:
        f.write("{ not json")
    tune.clear_memory_cache()
    assert tune.WinnerCache().entries() == {}
    assert events(kind="tune_cache_corrupt")


# -- dispatch ----------------------------------------------------------------

def _conv_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        img = layers.data("img", shape=[16, 8, 8], dtype="float32")
        out = layers.conv2d(input=img, num_filters=32, filter_size=3,
                            padding=1)
    return main, startup, out


def _run_conv(main, startup, out, scope=None):
    clear_warm_cache()
    scope = scope or pt.Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {"img": rng.randn(2, 16, 8, 8).astype(np.float32)}
    val, = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    return np.asarray(val), exe.stats


def test_dispatch_fallback_then_hit_and_bit_identity():
    main, startup, out = _conv_program()
    # no winner cached: records a fallback, lowers through stock XLA
    v_stock, stats = _run_conv(main, startup, out)
    assert stats["tune_hits"] == 0 and stats["tune_fallbacks"] >= 1

    # seed a winner that says stock XLA: hit + bit-identical output
    ck = tune.cache_key(device_kind(), "conv3x3",
                        tune.signature(CONV_KEY))
    tune.WinnerCache().put(ck, dict(tune.XLA_CONFIG))
    tune.reset_counters()
    v_hit, stats = _run_conv(main, startup, out)
    assert stats["tune_hits"] >= 1
    np.testing.assert_array_equal(v_stock, v_hit)


def test_dispatch_winner_config_routes_kernel():
    # a real (non-default) kernel config as winner: the kernel runs with
    # it and agrees with stock XLA within the parity tolerance
    ck = tune.cache_key(device_kind(), "conv3x3",
                        tune.signature(CONV_KEY))
    tune.WinnerCache().put(ck, {"block_n": 2, "block_o": 0,
                                "grid_order": "on"})
    main, startup, out = _conv_program()
    v_kernel, stats = _run_conv(main, startup, out)
    assert stats["tune_hits"] >= 1

    with flags_guard(tune=False):
        tune.reset_counters()
        v_stock, stats = _run_conv(main, startup, out)
    assert stats["tune_hits"] == 0 and stats["tune_fallbacks"] >= 1
    np.testing.assert_allclose(v_kernel, v_stock, rtol=2e-4, atol=1e-5)


def test_dispatch_miss_with_flag_enabled_equals_legacy_kernel():
    # winner == the kernel's default config must be bit-identical to the
    # legacy conv_impl=pallas3x3 path (which is exactly default config)
    from paddle_tpu.kernels.conv3x3 import DEFAULT_CONFIG
    main, startup, out = _conv_program()
    with flags_guard(conv_impl="pallas3x3", tune=False):
        v_legacy, stats = _run_conv(main, startup, out)
        assert stats["tune_misses"] >= 1
    ck = tune.cache_key(device_kind(), "conv3x3",
                        tune.signature(CONV_KEY))
    tune.WinnerCache().put(ck, dict(DEFAULT_CONFIG))
    tune.reset_counters()
    v_winner, stats = _run_conv(main, startup, out)
    assert stats["tune_hits"] >= 1
    np.testing.assert_array_equal(v_legacy, v_winner)


def test_profiler_timeline_has_tune_section(tmp_path):
    from paddle_tpu import profiler
    ck = tune.cache_key(device_kind(), "conv3x3",
                        tune.signature(CONV_KEY))
    tune.WinnerCache().put(ck, dict(tune.XLA_CONFIG))
    main, startup, out = _conv_program()
    profiler.reset_profiler()
    tune.reset_counters()
    _run_conv(main, startup, out)
    art = profiler.write_timeline(str(tmp_path / "tl.json"))
    assert art["tune"].get("tune_hits", 0) >= 1


# -- CLI ---------------------------------------------------------------------

TINY_CONFIG = """\
import paddle_tpu as pt
from paddle_tpu import layers


def model():
    img = layers.data(name="img", shape=[16, 8, 8], dtype="float32")
    out = layers.conv2d(input=img, num_filters=32, filter_size=3,
                        padding=1)
    cost = layers.mean(x=out)
    return {"cost": cost, "feed_list": [img], "reader": None}
"""


@pytest.fixture
def tiny_config(tmp_path):
    p = tmp_path / "tiny_conv_config.py"
    p.write_text(TINY_CONFIG)
    return str(p)


def test_cli_tune_dry_run_exit_zero(tiny_config, capsys):
    from paddle_tpu import cli
    rc = cli.main(["tune", tiny_config, "--dry-run", "--batch", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "dry run" in out and "conv3x3" in out


def test_cli_tune_bad_config_exit_two(tmp_path):
    from paddle_tpu import cli
    bad = tmp_path / "bad_config.py"
    bad.write_text("def model():\n    raise RuntimeError('nope')\n")
    assert cli.main(["tune", str(bad)]) == 2


def test_cli_tune_end_to_end_caches_winners(tiny_config, tmp_path,
                                            capsys):
    from paddle_tpu import cli
    out = tmp_path / "tune_evidence.json"
    # small budget keeps interpret-mode compiles CI-sized; model timer is
    # the CPU default (recorded in the evidence)
    rc = cli.main(["tune", tiny_config, "--batch", "2", "--budget", "3",
                   "--out", str(out)])
    assert rc == 0
    tune.clear_memory_cache()
    entries = tune.WinnerCache().entries()
    assert entries, "tune CLI persisted no winners"
    for e in entries.values():
        assert e["timer"] == "model"
        assert e["crc32"]
    rec = json.loads(out.read_text())
    assert rec["schema"] == "paddle_tpu.bench.v1"
    assert rec["rows"] and rec["rows"][0]["kernel"] == "conv3x3"

# -- paged attention space ---------------------------------------------------

PA_KEY = {"r": 4, "mb": 3, "t": 4, "nh": 2, "dh": 8, "dtype": "float32"}


def test_paged_attention_space_candidates_and_validity():
    sp = tune.get_space("paged_attention")
    cands = sp.candidates(PA_KEY)
    assert cands[0] == sp.default_config(PA_KEY)
    for cfg in cands:
        assert sp.is_valid(cfg, PA_KEY)
        assert sp.vmem_bytes(cfg, PA_KEY) <= tune.space.VMEM_BUDGET
        # block_r must divide r=4, block_kv must divide mb=3
        assert 4 % cfg["block_r"] == 0
        assert 3 % cfg["block_kv"] == 0
    # (1,2,4) x (1,): block_r=8 is pruned by r=4 divisibility and of
    # block_kv (1,2,4,8) only 1 divides mb=3
    assert len(cands) == 3
    assert sp.candidates(PA_KEY, budget=2) == cands[:2]


def test_paged_attention_population_key_is_engine_signature():
    # the CLI's artifact walk and the engine's dispatch consult must
    # produce the same signature or winners can never be re-hit
    from paddle_tpu.kernels.paged_attention import population_key
    assert population_key(4, 3, 4, 2, 8) == PA_KEY


def test_paged_attention_autotune_end_to_end_model_timer():
    res = tune.autotune("paged_attention", PA_KEY,
                        timer=tune.model_timer())
    assert res.ok and res.winner is not None
    # stock gather rides as candidate 0 and every timed candidate
    # passed the parity gate against the gather reference
    assert res.records[0]["config"] == tune.XLA_CONFIG
    assert all(r["status"] == "ok" for r in res.records)
    tune.clear_memory_cache()
    assert tune.WinnerCache().get_config(res.cache_key) == res.winner


def test_paged_attention_winner_rehit_by_second_process(_isolated_tune):
    import subprocess
    import sys
    target = {"block_r": 2, "block_kv": 1}
    table = {frozenset(target.items()): 0.01,
             frozenset(tune.XLA_CONFIG.items()): 0.5}
    res = tune.autotune("paged_attention", PA_KEY,
                        timer=tune.table_timer(table, default=1.0))
    assert res.ok and res.winner == target
    code = (
        "import os\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "from paddle_tpu import tune\n"
        "cfg = tune.lookup('paged_attention', %r)\n"
        "print('HIT', sorted((cfg or {}).items()))\n" % (PA_KEY,))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_FLAGS="tune_cache_dir=%s,tune=true"
               % _isolated_tune)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "HIT [('block_kv', 1), ('block_r', 2)]" in out.stdout


def test_paged_attention_dispatch_reaches_engine():
    # a cached kernel winner for the pool geometry is picked up by a
    # GenerationEngine at construction (the compiled-once consult)
    from paddle_tpu.kernels.paged_attention import population_key
    from paddle_tpu.models import transformer as tm
    from paddle_tpu.serving import GenerationEngine, reference_decode
    cfg = tm.TransformerConfig(vocab_size=17, hidden=16, num_layers=1,
                               num_heads=2, max_seq=12)
    model = tm.TransformerLM(tm.init_params(cfg, seed=1), cfg)
    key = population_key(2, 3, 4, 2, 8)
    target = {"block_r": 2, "block_kv": 1}
    table = {frozenset(target.items()): 0.01}
    res = tune.autotune("paged_attention", key,
                        timer=tune.table_timer(table, default=1.0))
    assert res.winner == target
    eng = GenerationEngine(model, max_running=2, kv_pages=8,
                           page_tokens=4, name="dispatch")
    try:
        assert eng.attn_config == target
        out = eng.generate([1, 2, 3], max_new_tokens=4, timeout=300)
        st = eng.stats
    finally:
        eng.close()
    assert st["attn_kernel"] is True and st["kernel_hits"] > 0
    assert out.tokens == reference_decode(model, [1, 2, 3], 4)
    c = tune.counters()
    assert c["tune_hits"] >= 1


def test_cli_tune_generative_artifact_dry_run(tmp_path, capsys):
    from paddle_tpu import cli
    from paddle_tpu.flags import FLAGS
    from paddle_tpu.inference import export_generative
    from paddle_tpu.kernels.paged_attention import population_key
    from paddle_tpu.models import transformer as tm
    from paddle_tpu.serving import pages_for
    cfg = tm.TransformerConfig(vocab_size=17, hidden=16, num_layers=1,
                               num_heads=2, max_seq=16)
    art = str(tmp_path / "lm_artifact")
    export_generative(art, cfg, params=tm.init_params(cfg, seed=0))
    rc = cli.main(["tune", art, "--dry-run"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "paged_attention" in out and "dry run" in out
    # the printed candidate count is the real space arithmetic + stock
    key = population_key(FLAGS.serve_max_running,
                         pages_for(cfg.max_seq, FLAGS.serve_page_tokens),
                         FLAGS.serve_page_tokens, 2, 8)
    n = len(tune.get_space("paged_attention").candidates(key)) + 1
    line = [l for l in out.splitlines() if "paged_attention" in l][0]
    assert line.split()[-1] == str(n)
