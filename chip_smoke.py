"""Chip smoke: the quickest proof that paddle_tpu still starts on the TPU.

    python chip_smoke.py            # one chip: train, serve, kernels
    python chip_smoke.py --chips 4  # ONLY the dp=4 step vs the 1-chip step

Drives the system's main paths once through the entry points a user
calls, at full width, with seeded random data and weights (no dataset, no
network), and checks what comes out by the repo's own references:

- ``train``  : ResNet-50 (3x224x224, 1000 classes), batch 128, Momentum,
  pure AMP, through ``pt.Trainer(place=TPUPlace(0)).train(reader)`` — the
  path ``paddle_tpu train`` takes; then the rule by which its
  ``DataFeeder`` writes a fed array again, tried with no device step to
  wait behind (``_staging_reuse``).
- ``serve``  : ``TransformerLM`` hidden 768 / 12 layers / 12 heads / vocab
  50257 / max_seq 1024, ``export_generative`` -> ``python -m paddle_tpu
  serve --port 0`` as a child process, concurrent ``:generate`` requests
  over HTTP, SIGTERM, exit 0; greedy tokens vs ``reference_decode``.
- ``kernels``: each of the six Pallas kernels compiled on the chip at one
  real shape against its jnp reference.
- ``dp4`` (``--chips 4`` only): the same ResNet-50 step data-parallel over
  the four real chips vs the one-chip step, same seed and global batch.

One process per chip: this parent never imports jax; it runs each phase
as a child, one after another, and passes their JSON lines on. Inside the
``serve`` phase the server child holds the chip while the phase process
has not yet touched jax; the reference runs after the server is gone.

The LAST stdout line is ``{"ok": true, "device": {...}}`` with the device
as jax reported it to the phases. Any failed check, a phase that raises,
or a platform other than ``tpu`` exits non-zero with no such line.
Step times printed here are smoke output for the named device, not
benchmark results.

``--rehearse`` runs the same phases tiny on the CPU backend (Pallas in
interpret mode) to find wrong paths before chip time is spent; it never
prints the ``ok`` line.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES_1CHIP = ("train", "serve", "kernels", "decoder")

# events whose kind says a path degraded or fell back instead of running
# what was asked; any of these fails the phase that recorded it
_BAD_EVENT = ("degrad", "fallback", "fail", "exhaust", "corrupt")

REAL = {
    "train": {"depth": 50, "image": 224, "classes": 1000, "batch": 128,
              "lr": 0.01, "steps": 8},
    "serve": {"config": {"vocab_size": 50257, "hidden": 768,
                         "num_layers": 12, "num_heads": 12, "max_seq": 1024},
              "kv_pages": 2048, "page_tokens": 16, "max_running": 8,
              "prompt_lens": [8, 24, 61, 130, 300], "max_new_tokens": 6},
    "kernels": {"flash": (8, 1024, 12, 64), "rnn": (100, 64, 512),
                "paged": (8, 64, 16, 12, 64), "conv": (128, 28, 128),
                "matmul": 4096},
    "dp4": {"depth": 50, "image": 224, "classes": 1000, "batch": 128,
            "lr": 0.01, "steps": 4},
    # the latent-attention / sparse-expert block at its real head widths
    # (192-wide q/k, 128-wide v), 4 of 8 experts and 128 of 512 rows held
    "decoder": {"hidden": 256, "heads": 4, "nope": 128, "rope": 64, "v": 128,
                "rank": 128, "dense": 512, "expert": 128, "experts": 8,
                "top_k": 2, "held": [4, 4], "vocab": 512,
                "vocab_held": [128, 128], "seq": 256, "rows": 2,
                # the window / full block: 8 q heads on 2 k/v heads of 128,
                # a window of half the row on the first of its two layers
                "q_heads": 8, "kv_heads": 2, "head": 128, "window": 128},
}
TINY = {
    "train": {"depth": 18, "image": 32, "classes": 10, "batch": 8,
              "lr": 0.01, "steps": 3},
    "serve": {"config": {"vocab_size": 97, "hidden": 32, "num_layers": 2,
                         "num_heads": 4, "max_seq": 64},
              "kv_pages": 32, "page_tokens": 4, "max_running": 4,
              "prompt_lens": [3, 9, 17], "max_new_tokens": 4},
    "kernels": {"flash": (1, 256, 2, 64), "rnn": (4, 8, 128),
                "paged": (4, 4, 4, 2, 16), "conv": (2, 8, 128),
                "matmul": 256},
    "dp4": {"depth": 18, "image": 32, "classes": 10, "batch": 8,
            "lr": 0.01, "steps": 3},
    "decoder": {"hidden": 64, "heads": 4, "nope": 24, "rope": 8, "v": 16,
                "rank": 32, "dense": 96, "expert": 32, "experts": 8,
                "top_k": 2, "held": [4, 4], "vocab": 256,
                "vocab_held": [64, 64], "seq": 32, "rows": 2,
                "q_heads": 4, "kv_heads": 2, "head": 16, "window": 16},
}


def _emit(rec):
    print(json.dumps(rec), flush=True)


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def _check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# helpers that run INSIDE a phase child (the only processes touching jax)
# ---------------------------------------------------------------------------

def _device(rehearse, want_count):
    """The device this phase holds; refuses anything but a TPU unless
    rehearsing (and then anything but the CPU)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if rehearse:
        _check(d.platform == "cpu",
               "--rehearse runs on the CPU backend only (JAX_PLATFORMS=cpu); "
               "got %r" % d.platform)
    else:
        _check(d.platform == "tpu",
               "no TPU: jax.devices()[0].platform is %r" % d.platform)
    _check(len(devs) >= want_count,
           "need %d device(s), jax reports %d" % (want_count, len(devs)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _cache_entries():
    """(directory, entry count) of the persistent compile cache, by the
    program's own placement rule."""
    from paddle_tpu.core.compile_cache import compile_cache_dir
    dirname = compile_cache_dir()
    try:
        return dirname, len(os.listdir(dirname))
    except FileNotFoundError:
        return dirname, 0


def _cache_report(before, **extra):
    dirname, after = _cache_entries()
    return dict({"dir": dirname, "entries_before": before,
                 "entries_after": after}, **extra)


def _audit(phase, extra_events=()):
    """After-phase audit: resilience events and tune counters."""
    from paddle_tpu import resilience, tune
    evs = [{"kind": e["kind"], "site": e.get("site")}
           for e in resilience.events()] + list(extra_events)
    bad = [e for e in evs if any(b in e["kind"] for b in _BAD_EVENT)]
    _check(not bad, "%s: degradation/fallback events recorded: %r"
           % (phase, bad))
    from paddle_tpu.tune.cache import WinnerCache
    return {"events": evs, "tune": tune.counters(),
            "tune_winner_cache_entries": len(WinnerCache().entries()),
            "tune_note": "empty winner cache expected: nothing was tuned "
                         "on this machine, every tunable site lowers "
                         "through its default"}


def _resnet_trainer(pt, cfg, seed, dist_mesh=None):
    """Build ResNet + Momentum + pure AMP and the Trainer around it, in
    fresh programs and a fresh scope (returned so the caller can read
    parameters back)."""
    from paddle_tpu import layers, models
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    scope = pt.Scope()
    with pt.program_guard(main, startup):
        img = layers.data("img", shape=[3, cfg["image"], cfg["image"]],
                          dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        pred = models.resnet_imagenet(img, class_dim=cfg["classes"],
                                      depth=cfg["depth"])
        avg = layers.mean(layers.cross_entropy(pred, label))
        pt.amp.enable(main, pure=True)
        dist = None
        if dist_mesh is not None:
            from paddle_tpu.parallel import (DistributeTranspiler,
                                             ShardingStrategy)
            dist = DistributeTranspiler().transpile(
                main, mesh=dist_mesh,
                strategy=ShardingStrategy(data_axis="dp"))
        trainer = pt.Trainer(
            cost=avg, optimizer=pt.Momentum(learning_rate=cfg["lr"],
                                            momentum=0.9),
            feed_list=[img, label], place=pt.TPUPlace(0),
            main_program=main, startup_program=startup, dist_context=dist)
    return trainer, scope, main


def _batches(cfg, seed, steps):
    """``steps`` seeded minibatches in the reader protocol (a list of
    per-sample (image, [label]) tuples each). Two distinct batches are
    made up front and alternated, so a step's time below holds the
    Trainer's own feed path and not the random-number generator."""
    import numpy as np
    rng = np.random.RandomState(seed)
    pool = []
    for _ in range(2):
        imgs = rng.rand(cfg["batch"], 3, cfg["image"],
                        cfg["image"]).astype("float32")
        labels = rng.randint(0, cfg["classes"],
                             (cfg["batch"], 1)).astype("int64")
        pool.append([(imgs[i], labels[i]) for i in range(cfg["batch"])])

    def reader():
        for i in range(steps):
            yield pool[i % 2]
    return reader


def _train_steps(pt, trainer, scope, reader):
    """Run the Trainer over ``reader``; returns (losses, wall seconds per
    step, XLA compiles after the first step, programs read back from the
    persistent compile cache). A step's time ends when its
    loss has been read on the host. Compiles are counted below the
    Executor, at jax's own compile events, so a silent re-compile of an
    unchanged step program is seen too."""
    import jax.monitoring
    losses, stamps, compiles, hits = [], [time.perf_counter()], [], []

    def on_event(name, *_a, **_kw):
        if name.endswith("backend_compile_duration") or \
                name.endswith("cache_retrieval_time_sec"):
            compiles.append(len(losses))   # steps finished when it fired
        if name.endswith("cache_retrieval_time_sec"):
            hits.append(name)              # read from the persistent cache

    def handler(e):
        if isinstance(e, pt.trainer_mod.EndIteration):
            losses.append(float(e.cost))  # host read: the step is done
            stamps.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_event)
    with pt.scope_guard(scope):
        trainer.train(reader, num_passes=1, event_handler=handler)
    late = sum(1 for at in compiles if at >= 1)
    return (losses, [b - a for a, b in zip(stamps, stamps[1:])], late,
            len(hits))


def _staging_reuse(trainer, cfg, rounds, rehearse):
    """``DataFeeder`` writes a staging array again once its own reference
    is the last one; that rests on jax holding one until the
    host-to-device copy is done. Tried here at the earliest moment the
    rule allows, with no device step to wait behind: batch n is fed and
    its upload enqueued (``prepare_feed``, not waited for); after an odd
    batch the next is fed at once, after an even one as soon as jax has
    let go of the array (its reference count is polled, a tiny
    ``device_put`` between polls as the label's is in the loop). Every
    row of a batch differs from that row of the three before it, so a
    copy that was still reading would land later values in batch n's
    device array, which is read back and compared."""
    import jax
    import numpy as np
    feeder, exe = trainer.feeder, trainer.exe
    device = exe._device()
    row_ids = np.arange(cfg["batch"], dtype="float32")[:, None, None, None]
    pools = [np.empty((cfg["batch"], 3, cfg["image"], cfg["image"]),
                      "float32") for _ in range(4)]
    for k, pool in enumerate(pools):
        pool[:] = 1000.0 * k + row_ids
    labels = np.zeros((cfg["batch"], 1), "int64")
    batches = [[(pool[i], labels[i]) for i in range(len(pool))]
               for pool in pools]
    tiny = np.zeros(1, "float32")
    rec = {"rounds": rounds, "taken_again": 0, "taken_again_when_let_go": 0,
           "let_go_ms": [], "copy_done_when_let_go": 0, "never_let_go": 0}
    seen, pending = set(), None

    def settle(number, dev):
        got = np.asarray(jax.device_get(dev["img"]))
        torn = int((got != pools[number % 4]).any(axis=(1, 2, 3)).sum())
        _check(torn == 0, "a staging array was written while its upload "
               "still read it: %d of %d rows of batch %d differ on the "
               "device" % (torn, len(got), number))

    for number in range(rounds):
        fed = feeder.feed(batches[number % 4])
        if pending is not None:     # written over batch number-1's array?
            settle(*pending)
        img = fed.pop("img")        # the feeder's reference and this one
        again = img.ctypes.data in seen
        seen.add(img.ctypes.data)
        rec["taken_again"] += again
        rec["taken_again_when_let_go"] += again and number % 2 == 1
        base = sys.getrefcount(img)
        t0 = time.perf_counter()
        dev = exe.prepare_feed(dict(img=img, **fed))
        del fed
        if number % 2 == 0:
            ready_at = None
            while sys.getrefcount(img) > base:
                now = time.perf_counter()
                if ready_at is None and dev["img"].is_ready():
                    ready_at = now
                if ready_at is not None and now - ready_at > 0.25:
                    rec["never_let_go"] += 1    # XLA:CPU aliases the array
                    break
                jax.device_put(tiny, device)
            else:
                rec["let_go_ms"].append(
                    round(1e3 * (time.perf_counter() - t0), 2))
                rec["copy_done_when_let_go"] += bool(dev["img"].is_ready())
        del img
        pending = (number, dev)
    settle(*pending)
    _check(rehearse or rec["taken_again_when_let_go"] > 0,
           "no staging array was taken again when jax let go of it, so "
           "the comparison showed nothing: %r" % rec)
    return rec


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def phase_train(cfg, seed, rehearse):
    import math

    import jax
    import numpy as np

    import paddle_tpu as pt

    dev = _device(rehearse, 1)
    cache_before = _cache_entries()[1]
    # one warm-up/compile step + cfg["steps"] steady steps, ONE train()
    trainer, scope, main = _resnet_trainer(pt, cfg, seed)
    losses, secs, late_compiles, cache_hits = _train_steps(
        pt, trainer, scope, _batches(cfg, seed, cfg["steps"] + 1))
    exe = trainer.exe
    exe_dev = exe._device()
    _check(exe_dev == jax.devices()[0] and exe_dev.platform == dev["platform"],
           "Executor device %r is not the %s device" % (exe_dev,
                                                        dev["platform"]))
    params = [v.name for v in main.list_vars()
              if isinstance(v, pt.core.ir.Parameter)]
    off = [n for n in params
           if set(scope.find_var(n).devices()) != {exe_dev}]
    _check(params and not off,
           "parameters not resident on %r: %r" % (exe_dev, off[:5]))
    _check(len(losses) == cfg["steps"] + 1 and
           all(math.isfinite(x) for x in losses),
           "non-finite or missing losses: %r" % losses)
    _check(len(set(losses)) > 1, "losses are constant: %r" % losses)
    # startup program + exactly one step program; nothing recompiled later
    _check(exe.stats["compiles"] == 2 and exe.stats["eager_runs"] == 0
           and exe.stats["hybrid_runs"] == 0,
           "expected 1 startup + 1 step compile and no eager/hybrid run, "
           "got %r" % {k: exe.stats[k] for k in
                       ("compiles", "jit_runs", "eager_runs",
                        "hybrid_runs")})
    _check(late_compiles == 0, "%d XLA compile(s) after the first step"
           % late_compiles)
    _check(exe.stats["ahead_steps"] == cfg["steps"]
           and exe.stats["ahead_dropped"] == 0,
           "expected every step but the first dispatched ahead and none "
           "dropped, got %r" % {k: exe.stats[k] for k in
                                ("ahead_steps", "ahead_dropped")})
    mem = jax.devices()[0].memory_stats() or {}
    with pt.scope_guard(scope):
        staging = _staging_reuse(trainer, cfg, 12, rehearse)
    rec = {"phase": "train", "passed": True, "device": dev,
           "model": "resnet%d %dx%d classes=%d" % (
               cfg["depth"], cfg["image"], cfg["image"], cfg["classes"]),
           "batch": cfg["batch"], "amp": "pure", "optimizer": "momentum",
           "losses": [round(x, 5) for x in losses],
           "first_step_s_incl_compile": round(secs[0], 2),
           "smoke_step_ms_after_compile": [round(1e3 * s, 2)
                                           for s in secs[1:]],
           "smoke_step_ms_median": round(
               1e3 * float(np.median(secs[1:])), 2),
           "n_params": len(params),
           "executor": {k: exe.stats[k] for k in
                        ("compiles", "jit_runs", "eager_runs",
                         "hybrid_runs", "compile_cache_hits")},
           "xla_compiles_after_first_step": late_compiles,
           "peak_hbm_bytes": mem.get("peak_bytes_in_use"),
           "staging_reuse": staging,
           # the loop's dispatch ahead: every step but the first was in
           # the chip's queue before the loss of the one before it was read
           "ahead_steps": exe.stats["ahead_steps"],
           "ahead_dropped": exe.stats["ahead_dropped"],
           "compile_cache": _cache_report(
               cache_before, programs_read_from_cache=cache_hits)}
    rec.update(_audit("train"))
    return rec


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _post(port, path, body, timeout):
    import urllib.request
    req = urllib.request.Request(
        "http://127.0.0.1:%d%s" % (port, path),
        data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


class _PaddedForward(object):
    """``reference_decode``'s model, with the same full-sequence
    ``forward`` jitted over the sequence right-padded to a multiple of
    64. Attention is causal and everything else is per position, so the
    logits at the real positions are those of the unpadded forward; the
    reference then compiles once per 64 tokens of length and not once per
    token (eager op-by-op recompute took 324 s of the first chip run).
    The weights are an ARGUMENT of the jitted function: closed over, they
    are baked into every executable as 1.8 GB of constants."""

    def __init__(self, model):
        import jax

        from paddle_tpu.models import transformer as tm
        self.config = config = model.config
        self._params = model.params
        self._fwd = jax.jit(lambda params, toks: tm.forward(params, toks,
                                                            config))

    def forward(self, tokens):
        import jax.numpy as jnp
        n = tokens.shape[1]
        pad = min(-n % 64, self.config.max_seq - n)
        return self._fwd(self._params,
                         jnp.pad(tokens, ((0, 0), (0, pad))))[:, :n]


def phase_serve(cfg, seed, rehearse):
    """Export -> serve child -> HTTP -> SIGTERM, all BEFORE this process
    touches a jax backend (the child holds the chip); then the reference
    decode here, once the child is gone."""
    import numpy as np

    from paddle_tpu import inference
    from paddle_tpu.models import transformer as tm

    config = tm.TransformerConfig(**cfg["config"])
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, config.vocab_size, n).tolist()
               for n in cfg["prompt_lens"]]
    cache_before = _cache_entries()[1]
    with tempfile.TemporaryDirectory(prefix="smoke_lm_") as art:
        inference.export_generative(art, config,
                                    params=tm.init_params(config, seed=seed))
        env = dict(os.environ)
        # f32 weights at full f32 matmul precision in BOTH the server and
        # the reference below: token equality is then a meaningful check.
        # At the TPU's default (one bf16 pass) two correct programs with
        # different reduction shapes may flip a near-tied argmax.
        env["JAX_DEFAULT_MATMUL_PRECISION"] = "highest"
        t0 = time.perf_counter()
        child = subprocess.Popen(
            [sys.executable, "-u", "-m", "paddle_tpu", "serve", art,
             "--port", "0", "--max_running", str(cfg["max_running"]),
             "--kv_pages", str(cfg["kv_pages"]),
             "--page_tokens", str(cfg["page_tokens"])],
            cwd=HERE, env=env, stdout=subprocess.PIPE, text=True)
        try:
            ready = None
            for line in child.stdout:
                if line.startswith("{") and '"serving"' in line:
                    ready = json.loads(line)["serving"]
                    break
            _check(ready is not None, "serve child exited (rc=%r) before "
                   "its readiness line" % child.poll())
            ready_s = time.perf_counter() - t0
            if not rehearse:
                _check(ready["platform"] == "tpu",
                       "serve child runs on %r" % ready["platform"])
            results = [None] * len(prompts)
            gate = threading.Barrier(len(prompts))

            def ask(i):
                gate.wait()
                t = time.perf_counter()
                try:
                    out = _post(ready["port"], "/v1/models/default:generate",
                                {"tokens": prompts[i], "temperature": 0.0,
                                 "max_new_tokens": cfg["max_new_tokens"]},
                                timeout=600)
                except Exception as e:  # noqa: BLE001 - reported below
                    out = {"error": repr(e)}
                out["wall_ms"] = 1e3 * (time.perf_counter() - t)
                results[i] = out

            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=900)
            child.send_signal(signal.SIGTERM)
            tail = child.stdout.read()
            rc = child.wait(timeout=120)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        _check(rc == 0, "serve child exited %r after SIGTERM" % rc)
        stopped = None
        for line in tail.splitlines():
            if line.startswith("{") and '"serving_stopped"' in line:
                stopped = json.loads(line)["serving_stopped"]
        _check(stopped is not None, "no serving_stopped line")
        errors = [r for r in results if r is None or "error" in r]
        _check(not errors, "generate requests failed: %r" % errors)
        gen = stopped["stats"]["generation"]["default"]
        _check(gen["device_sample"] is True,
               "engine did not sample on the device")
        _check(gen["max_running_seen"] > 1,
               "never more than one sequence per step (max_running_seen=%r)"
               % gen["max_running_seen"])
        _check(gen["completed"] == len(prompts) and gen["failed"] == 0,
               "engine completed %r failed %r" % (gen["completed"],
                                                  gen["failed"]))

        # -- the server is gone: this process may take the chip now -------
        import jax
        jax.config.update("jax_default_matmul_precision", "highest")
        dev = _device(rehearse, 1)
        if not rehearse:
            _check(ready["device_kind"] == dev["kind"],
                   "serve child device %r != %r" % (ready["device_kind"],
                                                    dev["kind"]))
        from paddle_tpu.serving import reference_decode
        model = inference.load_generative(art)
        t0 = time.perf_counter()
        want = [reference_decode(_PaddedForward(model), p,
                                 cfg["max_new_tokens"]) for p in prompts]
        ref_s = time.perf_counter() - t0
    got = [r["tokens"] for r in results]
    _check(got == want, "greedy tokens differ from reference_decode: "
           "served %r reference %r" % (got, want))
    rec = {"phase": "serve", "passed": True, "device": dev,
           "model": dict(cfg["config"], params_dtype="float32",
                         matmul_precision="highest"),
           "pool": {"kv_pages": ready["kv_pages"],
                    "page_tokens": ready["page_tokens"],
                    "max_running": ready["max_running"],
                    "max_context": ready["max_context"]},
           "ready_s_incl_load_and_warmup": round(ready_s, 2),
           "warmup_ms": ready["warmup_ms"],
           "requests": len(prompts), "prompt_lens": cfg["prompt_lens"],
           "max_new_tokens": cfg["max_new_tokens"],
           "tokens_equal_reference_decode": True,
           "smoke_request_wall_ms": [round(r["wall_ms"], 1)
                                     for r in results],
           "smoke_ttft_ms": [round(r["ttft_ms"], 1) for r in results],
           "engine": {k: gen[k] for k in
                      ("device_sample", "device_sample_steps",
                       "host_logit_syncs", "max_running_seen",
                       "running_occupancy", "decode_steps", "prefills",
                       "tokens_generated", "attn_kernel", "kernel_hits",
                       "page_utilization_max")},
           "serve_exit_code": rc, "reference_decode_s": round(ref_s, 2),
           "compile_cache": _cache_report(cache_before)}
    rec.update(_audit("serve", extra_events=stopped["events"]))
    return rec


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def _run_kernel(name, fn, ref, args, tol, rehearse):
    """Compile ``fn`` for the held device, find the Mosaic call in the
    compiled text, run it, and compare with ``ref`` (computed at full f32
    matmul precision). Error is max-abs over all outputs, relative to the
    reference's own max-abs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    has_call = "tpu_custom_call" in compiled.as_text()
    if not rehearse:
        _check(has_call, "%s: no tpu_custom_call in the compiled text — "
               "the kernel did not lower through Mosaic" % name)
    got = jax.block_until_ready(compiled(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(ref)(*args))
    got = jax.tree_util.tree_leaves(got)
    want = jax.tree_util.tree_leaves(want)
    _check(len(got) == len(want), "%s: output arity" % name)
    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g.astype(jnp.float32))
        w = np.asarray(w.astype(jnp.float32))
        _check(g.shape == w.shape, "%s: shape %r vs reference %r"
               % (name, g.shape, w.shape))
        _check(np.isfinite(g).all(), "%s: non-finite output" % name)
        worst = max(worst, float(np.abs(g - w).max()
                                 / max(np.abs(w).max(), 1e-6)))
    _check(worst <= tol, "%s: max-abs error %.3g (relative to the "
           "reference's max-abs) exceeds %.3g" % (name, worst, tol))
    return {"kernel": name, "tpu_custom_call": has_call,
            "max_abs_err_rel": float("%.3g" % worst), "tolerance": tol,
            "compile_s": round(compile_s, 2),
            "shapes": [list(a.shape) for a in args],
            "dtype": str(args[0].dtype)}


def phase_kernels(cfg, seed, rehearse):
    import importlib

    import jax
    import jax.numpy as jnp

    dev = _device(rehearse, 1)
    fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
    pa = importlib.import_module("paddle_tpu.kernels.paged_attention")
    from paddle_tpu.kernels.conv3x3 import conv3x3_s1_nhwc
    from paddle_tpu.kernels.fused_gru import fused_gru
    from paddle_tpu.kernels.fused_lstm import fused_lstm
    from paddle_tpu.kernels.matmul import matmul

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def rnd(shape, dtype, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    # tolerances, relative to the reference's max-abs, set from the dtype:
    # bf16 keeps 8 mantissa bits (2^-8 = 3.9e-3 per rounding, a handful of
    # roundings per output); f32 kernels may contract on the MXU in bf16
    # passes, and the recurrent ones compound that over T steps
    TOL_BF16, TOL_F32 = 3e-2, 2e-2
    rows = []

    # flash attention: forward, and dq/dk/dv through the custom vjp
    B, S, H, D = cfg["flash"]
    q, k, v = (rnd((B, S, H, D), jnp.bfloat16) for _ in range(3))
    do = rnd((B, S, H, D), jnp.bfloat16)

    def to3(x):
        return (x.astype(jnp.float32).transpose(0, 2, 1, 3)
                .reshape(B * H, S, D))

    def from3(x):
        return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    def dense(q, k, v):
        return from3(fa._dense_reference(to3(q), to3(k), to3(v), True,
                                         D ** -0.5))

    rows.append(_run_kernel(
        "flash_attention_fwd",
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
        dense, (q, k, v), TOL_BF16, rehearse))
    rows.append(_run_kernel(
        "flash_attention_bwd",
        lambda q, k, v, do: jax.vjp(
            lambda *a: fa.flash_attention(*a, causal=True), q, k, v)[1](do),
        lambda q, k, v, do: jax.vjp(dense, q, k, v)[1](
            do.astype(jnp.float32)),
        (q, k, v, do), TOL_BF16, rehearse))

    # fused LSTM / GRU vs a plain lax.scan of the same gate math
    T, N, Dh = cfg["rnn"]
    lens = jax.random.randint(next(keys), (N,), 1, T + 1)
    mask = (jnp.arange(T)[:, None] < lens[None, :]).astype(jnp.float32)
    h0, c0 = rnd((N, Dh), jnp.float32, 0.2), rnd((N, Dh), jnp.float32, 0.2)

    def lstm_scan(xs, w, h0, c0, mask):
        def step(carry, inp):
            h_prev, c_prev = carry
            x_t, m = inp
            g = x_t + h_prev @ w
            cand = jnp.tanh(g[:, :Dh])
            i = jax.nn.sigmoid(g[:, Dh:2 * Dh])
            f = jax.nn.sigmoid(g[:, 2 * Dh:3 * Dh])
            o = jax.nn.sigmoid(g[:, 3 * Dh:])
            c = f * c_prev + i * cand
            h = o * jnp.tanh(c)
            m = m[:, None]
            h = h * m + h_prev * (1 - m)
            c = c * m + c_prev * (1 - m)
            return (h, c), (h, c)
        return jax.lax.scan(step, (h0, c0), (xs, mask))[1]

    def gru_scan(xs, w, h0, mask):
        def step(h_prev, inp):
            x_t, m = inp
            ur = jax.nn.sigmoid(x_t[:, :2 * Dh] + h_prev @ w[:, :2 * Dh])
            u, r = ur[:, :Dh], ur[:, Dh:]
            cand = jnp.tanh(x_t[:, 2 * Dh:] + (r * h_prev) @ w[:, 2 * Dh:])
            h = (1 - u) * h_prev + u * cand
            m = m[:, None]
            h = h * m + h_prev * (1 - m)
            return h, h
        return jax.lax.scan(step, h0, (xs, mask))[1]

    rows.append(_run_kernel(
        "fused_lstm", lambda *a: fused_lstm(*a), lstm_scan,
        (rnd((T, N, 4 * Dh), jnp.float32, 0.4),
         rnd((Dh, 4 * Dh), jnp.float32, Dh ** -0.5), h0, c0, mask),
        TOL_F32, rehearse))
    rows.append(_run_kernel(
        "fused_gru", lambda *a: fused_gru(*a), gru_scan,
        (rnd((T, N, 3 * Dh), jnp.float32, 0.4),
         rnd((Dh, 3 * Dh), jnp.float32, Dh ** -0.5), h0, mask),
        TOL_F32, rehearse))

    # paged attention (default block config) vs the block-table gather
    R, MB, Tp, nh, dh = cfg["paged"]
    pages = R * MB
    tables = jax.random.permutation(
        next(keys), pages).reshape(R, MB).astype(jnp.int32)
    positions = jax.random.randint(next(keys), (R,), 0, MB * Tp)
    rows.append(_run_kernel(
        "paged_attention",
        lambda q, kp, vp, t, p: pa.paged_attention(q, kp, vp, t, p),
        pa.paged_attention_reference,
        (rnd((R, nh, dh), jnp.float32),
         rnd((pages + 1, Tp, nh, dh), jnp.float32),
         rnd((pages + 1, Tp, nh, dh), jnp.float32), tables, positions),
        TOL_F32, rehearse))

    # conv3x3 (NHWC, HWIO) vs lax.conv
    Nb, Hc, C = cfg["conv"]
    rows.append(_run_kernel(
        "conv3x3", lambda x, w: conv3x3_s1_nhwc(x, w),
        lambda x, w: jax.lax.conv_general_dilated(
            x.astype(jnp.float32), w.astype(jnp.float32), (1, 1),
            ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC")),
        (rnd((Nb, Hc, Hc, C), jnp.bfloat16),
         rnd((3, 3, C, C), jnp.bfloat16, (9 * C) ** -0.5)),
        TOL_BF16, rehearse))

    # blocked matmul vs jnp.matmul
    n = cfg["matmul"]
    tile = {"block_m": min(256, n), "block_n": min(256, n),
            "block_k": min(512, n)}
    rows.append(_run_kernel(
        "matmul", lambda x, w: matmul(x, w, config=tile),
        lambda x, w: jnp.matmul(x.astype(jnp.float32),
                                w.astype(jnp.float32)),
        (rnd((n, n), jnp.bfloat16), rnd((n, n), jnp.bfloat16, n ** -0.5)),
        TOL_BF16, rehearse))

    for r in rows:
        _emit(dict(r, phase="kernels", device_kind=dev["kind"]))
    rec = {"phase": "kernels", "passed": True, "device": dev,
           "kernels": {r["kernel"]: r["max_abs_err_rel"] for r in rows},
           "all_tpu_custom_call": all(r["tpu_custom_call"] for r in rows)}
    rec.update(_audit("kernels"))
    return rec


# ---------------------------------------------------------------------------
# phase: dp4 (four chips, behind --chips 4)
# ---------------------------------------------------------------------------

def phase_dp4(cfg, seed, rehearse):
    import math

    import jax
    import numpy as np

    from jax.sharding import NamedSharding

    import paddle_tpu as pt
    from paddle_tpu.parallel import make_mesh

    dev = _device(rehearse, 4)
    steps = cfg["steps"]

    # the one-chip step it is compared with: same seed, same global batch
    t1, s1, _ = _resnet_trainer(pt, cfg, seed)
    loss1, secs1, _, _ = _train_steps(pt, t1, s1,
                                      _batches(cfg, seed, steps))
    _check(t1.exe._device() == jax.devices()[0], "one-chip step not on chip 0")
    del t1, s1

    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    t4, s4, main4 = _resnet_trainer(pt, cfg, seed, dist_mesh=mesh)
    exe = t4.exe
    # a step run while the host profiler is on is noted, and XLA's
    # collective census of its executable is taken on demand (profiler.
    # get_program_analysis) — the repo's own way to see what GSPMD inserted
    pt.profiler.start_profiler()
    try:
        loss4, secs4, late4, _ = _train_steps(pt, t4, s4,
                                              _batches(cfg, seed, steps))
    finally:
        pt.profiler.stop_profiler()
    analysis = pt.profiler.get_program_analysis("program_%d" % main4._uid)
    _check(analysis is not None and analysis["mesh_devices"] == 4,
           "no compiled-program analysis for the dp=4 step: %r" % analysis)
    collectives = analysis["collectives"]
    _check(collectives.get("all-reduce", 0)
           + collectives.get("reduce-scatter", 0) > 0,
           "no gradient reduction in the compiled dp=4 step: %r"
           % collectives)

    # where the Executor tells jit to put a step's feeds (the strategy's
    # feed spec over the mesh, core/executor._dist_shardings): the batch
    # must split four ways over four distinct chips
    dist = exe.dist_context
    placement = {}
    for name, shape in (("img", (cfg["batch"], 3, cfg["image"],
                                 cfg["image"])),
                        ("label", (cfg["batch"], 1))):
        sh = NamedSharding(mesh, dist.strategy.spec_for_feed(name, shape,
                                                             mesh))
        idx = sh.devices_indices_map(shape)
        slices = sorted((d.id, i[0].start or 0) for d, i in idx.items())
        placement[name] = {"global_shape": list(shape),
                           "shard_shape": list(sh.shard_shape(shape)),
                           "device_id_and_batch_offset": slices}
        _check(len(sh.device_set) == 4
               and sh.shard_shape(shape)[0] * 4 == shape[0]
               and len({off for _, off in slices}) == 4,
               "feed %r is not split 4 ways along the batch over 4 chips: "
               "%r" % (name, placement[name]))
    # where the step's outputs really sit: every parameter the dp=4 step
    # wrote back must be whole on each of the four distinct chips, as
    # dist.sharding_for says
    params = [v.name for v in main4.list_vars()
              if isinstance(v, pt.core.ir.Parameter)]
    for n in params:
        arr = s4.find_var(n)
        shards = arr.addressable_shards
        _check(len({sh_.device for sh_ in shards}) == 4
               and all(sh_.data.shape == arr.shape for sh_ in shards)
               and arr.sharding.is_equivalent_to(
                   dist.sharding_for(n, arr), arr.ndim),
               "param %r is not replicated over the four chips per "
               "dist.sharding_for: %r" % (n, arr.sharding))
    hbm = {d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in jax.devices()}
    _check(all(math.isfinite(x) for x in loss1 + loss4),
           "non-finite losses: 1-chip %r dp4 %r" % (loss1, loss4))
    # bf16 activations and a different reduction order across shards
    # (per-shard batch-norm statistics stay GLOBAL under GSPMD): the two
    # trajectories agree to bf16 rounding, amplified a little per step
    tol = 5e-2
    gaps = [abs(a - b) / max(abs(a), 1e-6) for a, b in zip(loss1, loss4)]
    _check(len(loss1) == len(loss4) == steps and max(gaps) <= tol,
           "dp4 losses %r differ from 1-chip losses %r by more than %g "
           "(relative)" % (loss4, loss1, tol))
    rec = {"phase": "dp4", "passed": True, "device": dev,
           "mesh": {"dp": 4}, "mesh_devices": [d.id for d in
                                               mesh.devices.flat],
           "global_batch": cfg["batch"],
           "losses_1chip": [round(x, 5) for x in loss1],
           "losses_dp4": [round(x, 5) for x in loss4],
           "max_rel_gap": float("%.3g" % max(gaps)), "tolerance": tol,
           "feed_placement": placement, "params_replicated": len(params),
           "collectives_in_compiled_text": collectives,
           "comm_path": exe.stats.get("comm_path"),
           "xla_compiles_after_first_dp4_step": late4,
           "peak_hbm_bytes_by_device": hbm,
           "smoke_step_ms_1chip": [round(1e3 * s, 2) for s in secs1[1:]],
           "smoke_step_ms_dp4": [round(1e3 * s, 2) for s in secs4[1:]],
           "smoke_step_ms_median_1chip": round(
               1e3 * float(np.median(secs1[1:])), 2),
           "smoke_step_ms_median_dp4": round(
               1e3 * float(np.median(secs4[1:])), 2)}
    rec.update(_audit("dp4"))
    return rec


# ---------------------------------------------------------------------------
# phase: decoder (latent attention + an expert layer that holds a share)
# ---------------------------------------------------------------------------

def _two_decoder_steps(model, config, cfg, seed):
    """``model(tokens, config, labels=)`` trained two steps through Trainer
    under pure AMP with Adam, its half-layers recomputed; every step's
    ``Load`` has to count every (token, pick) pair and ``RowsHeld`` the
    pairs on the held experts. Returns (losses, loads, rows held)."""
    import math

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import layers

    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[cfg["seq"]], dtype="int64")
        labels = layers.data("labels", shape=[cfg["seq"]], dtype="int64")
        out = model(tokens, config, labels=labels)
        pt.amp.enable(main, pure=True)
        trainer = pt.Trainer(
            cost=out["loss"], optimizer=pt.optimizer.AdamOptimizer(
                learning_rate=1e-3, beta2=0.95),
            feed_list=[tokens, labels], fetch_list=out["loads"]
            + out["rows_held"], place=pt.TPUPlace(0), main_program=main,
            startup_program=startup)
        pt.memory_optimize(main)
    rng = np.random.default_rng(seed)
    first, count = cfg["vocab_held"]

    def reader():
        for _ in range(2):
            ids = rng.integers(first, first + count,
                               (cfg["rows"], cfg["seq"] + 1), dtype=np.int64)
            yield [(row[:-1], row[1:]) for row in ids]

    losses, loads, held = [], [], []

    def handler(e):
        if isinstance(e, pt.trainer_mod.EndIteration):
            losses.append(float(e.cost))
            load, rows = (np.asarray(v) for v in e.metrics["fetches"])
            loads.append(load.tolist())
            held.append(int(rows.sum()))

    with pt.scope_guard(scope):
        trainer.train(reader, num_passes=1, event_handler=handler)
    pairs = cfg["rows"] * cfg["seq"] * cfg["top_k"]
    lo, n = cfg["held"]
    _check(len(losses) == 2 and all(math.isfinite(x) for x in losses),
           "non-finite or missing losses: %r" % losses)
    _check(all(sum(l) == pairs for l in loads),
           "Load does not count every (token, pick) pair: %r" % loads)
    _check(held == [sum(l[lo:lo + n]) for l in loads],
           "RowsHeld %r is not Load over the held experts %r" % (held, loads))
    stats = trainer.exe.stats
    _check(stats["compiles"] == 2 and stats["eager_runs"] == 0,
           "expected 1 startup + 1 step compile, got %r"
           % {k: stats[k] for k in ("compiles", "jit_runs", "eager_runs")})
    return losses, loads, held


def phase_decoder(cfg, seed, rehearse):
    """Two steps each (``_two_decoder_steps``) of ``models.latent_moe_lm``
    and of ``models.window_moe_lm`` (grouped k/v heads, a window on the
    first layer and none on the second), 1 dense + 1 expert layer each;
    the banded launches have to visit fewer tiles than the square."""
    from paddle_tpu import layers, tune
    from paddle_tpu.models.latent_moe_lm import latent_moe_lm
    from paddle_tpu.models.window_moe_lm import window_moe_lm

    dev = _device(rehearse, 1)
    shared = dict(
        hidden_size=cfg["hidden"], intermediate_size=cfg["dense"],
        moe_intermediate_size=cfg["expert"],
        num_experts_per_tok=cfg["top_k"], num_hidden_layers=2,
        vocab_size=cfg["vocab"], experts_held=cfg["held"],
        vocab_held=cfg["vocab_held"])
    latent = dict(
        shared, num_attention_heads=cfg["heads"],
        num_key_value_heads=cfg["heads"], qk_nope_head_dim=cfg["nope"],
        qk_rope_head_dim=cfg["rope"], qk_head_dim=cfg["nope"] + cfg["rope"],
        v_head_dim=cfg["v"], kv_lora_rank=cfg["rank"],
        n_routed_experts=cfg["experts"], n_shared_experts=2,
        routed_scaling_factor=2.448, first_k_dense_replace=1,
        rms_norm_eps=1e-6, rope_theta=1e6)
    window = dict(
        shared, num_attention_heads=cfg["q_heads"],
        num_key_value_heads=cfg["kv_heads"], head_dim=cfg["head"],
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=cfg["window"], num_experts=cfg["experts"],
        num_shared_experts=1, route_scale=2.826, route_norm=True,
        num_dense_layers=1, rms_norm_eps=1e-5, rope_theta=10000,
        mup_enabled=True)
    losses, loads, held = _two_decoder_steps(latent_moe_lm, latent, cfg, seed)
    tune.reset_counters()
    w_losses, w_loads, w_held = _two_decoder_steps(window_moe_lm, window,
                                                   cfg, seed + 1)
    tiles = tune.counters()["flash_tiles"]
    banded = {k: t for k, t in tiles.items()
              if " w%d" % cfg["window"] in k}
    group = " g%d" % (cfg["q_heads"] // cfg["kv_heads"])
    _check(banded and all(group in k for k in tiles),
           "no banded, grouped flash launch was traced: %r" % tiles)
    share = lambda t: t["visited"] / float(t["square"])
    _check(all(share(t) <= min(share(u) for k, u in tiles.items()
                               if k not in banded)
               for t in banded.values()),
           "a banded launch visits a larger share of its square than a "
           "full one: %r" % tiles)
    rec = {"phase": "decoder", "passed": True, "device": dev,
           "model": "latent_moe_lm d%d heads %dx(%d+%d/%d) experts %d top %d"
                    % (cfg["hidden"], cfg["heads"], cfg["nope"], cfg["rope"],
                       cfg["v"], cfg["experts"], cfg["top_k"]),
           "losses": [round(x, 5) for x in losses], "loads": loads,
           "rows_held": held,
           "moe_max_over_mean_load": layers.moe_load_stats(
               loads[-1], held[-1])["moe_max_over_mean_load"],
           "window_model": "window_moe_lm d%d heads %d/%dx%d window %d of %d"
                           % (cfg["hidden"], cfg["q_heads"], cfg["kv_heads"],
                              cfg["head"], cfg["window"], cfg["seq"]),
           "window_losses": [round(x, 5) for x in w_losses],
           "window_rows_held": w_held, "flash_tiles": tiles}
    rec.update(_audit("decoder"))
    return rec


PHASES = {"train": phase_train, "serve": phase_serve,
          "kernels": phase_kernels, "dp4": phase_dp4,
          "decoder": phase_decoder}


# ---------------------------------------------------------------------------
# parent: no jax here, ever
# ---------------------------------------------------------------------------

def run_phase_child(name, args):
    """Run one phase in its own process and pass its stdout on; returns the
    phase's final record (its last JSON line) or raises SmokeFailure."""
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--phase", name,
           "--seed", str(args.seed)]
    if args.rehearse:
        cmd.append("--rehearse")
    env = dict(os.environ)
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if name == "dp4":
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_"
                                "host_platform_device_count=4").strip()
    proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=subprocess.PIPE,
                            text=True)
    last = None
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith("{"):
                try:
                    last = json.loads(line)
                except ValueError:
                    pass
            print(line, flush=True)
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0 or not last or last.get("phase") != name \
            or last.get("passed") is not True:
        raise SmokeFailure("phase %r failed (exit code %r)" % (name, rc))
    return last


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the dp=4 phase and the one-chip "
                         "step it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend; never prints the "
                         "ok line")
    ap.add_argument("--phase", choices=sorted(PHASES), default=None,
                    help=argparse.SUPPRESS)  # internal: run as a child
    args = ap.parse_args(argv)

    if args.phase:
        sys.path.insert(0, HERE)
        size = TINY if args.rehearse else REAL
        try:
            rec = PHASES[args.phase](size[args.phase], args.seed,
                                     args.rehearse)
        except SmokeFailure as e:
            print("chip_smoke: %s: %s" % (args.phase, e), file=sys.stderr,
                  flush=True)
            return 1
        _emit(rec)
        return 0

    phases = ("dp4",) if args.chips == 4 else PHASES_1CHIP
    device = None
    try:
        for name in phases:
            rec = run_phase_child(name, args)
            if device is not None and rec["device"] != device:
                raise SmokeFailure("phases disagree on the device: %r vs %r"
                                   % (device, rec["device"]))
            device = rec["device"]
    except SmokeFailure as e:
        print("chip_smoke: FAILED: %s" % e, file=sys.stderr, flush=True)
        return 1
    if args.rehearse:
        _emit({"rehearsal": True, "phases_passed": list(phases),
               "device": device})
        return 0
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
