"""Driver of a served configuration: an open-loop generator on its own thread
sends the seed's schedule through ``GenerationEngine.submit`` in this
process (engine thread, ``PagePool``, prefill buckets, fused device-sampled
decode). The engine that is warmed is the engine that the window drives.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from chipbench import compare, flops, harness, loadgen, trace_reduce

DRAIN_S = 60.0        # an answer that comes late is late, not wrong
SAMPLE_REQUESTS = 6   # finished requests the reference runs over
NEVER_MS = 1e9        # a failed, shed or unfinished request: worse than any


def build_engine(config, cell, seed, reference):
    """The model with weights made on the device from the seed (handed to
    ``TransformerLM`` directly: no pickle round trip) and the engine around
    it, not warmed."""
    from paddle_tpu.models.transformer import TransformerConfig, TransformerLM
    from paddle_tpu.serving.generator import GenerationEngine
    if config["precision"] != "f32_default_matmul":
        raise ValueError("unknown precision %r" % (config["precision"],))
    params = reference.init_params(
        reference.key_data(seed), vocab=config["vocab_size"],
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        ffn=config["ffn_dim"], positions=config["max_position_embeddings"])
    tcfg = TransformerConfig(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        ffn_mult=config["ffn_dim"] // config["hidden_size"],
        max_seq=config["max_position_embeddings"])
    model = TransformerLM(params, tcfg)
    del params
    eng = cell["engine"]
    engine = GenerationEngine(
        model, max_running=eng["max_running"], kv_pages=eng["kv_pages"],
        page_tokens=eng["page_tokens"], queue_depth=eng["queue_depth"],
        reserve=eng["reserve"], warm=False, device_sample=True,
        prefix_sharing=False)
    return model, engine


def warm(engine, mix, vocab):
    """One real request per prefill bucket that the mix can reach, two
    tokens each so that the decode step runs too: through ``submit``, the
    window's own call (``warm_up()`` on a live engine races its loop)."""
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    buckets, prev = [], 0
    for b in engine._buckets:            # padding_buckets(max_context)
        if max(lo, prev + 1) <= min(hi, b):
            buckets.append(b)
        prev = b
    rng = np.random.default_rng(0)
    for b in buckets:
        n = min(b, hi)
        engine.submit(rng.integers(0, vocab, n).tolist(),
                      max_new_tokens=2).wait(timeout=1200)
    return buckets


class Dispatches(object):
    """Traced runs only: the order in which the engine launched its decode
    and prefill programs, taken by wrapping the two jitted faces from
    outside, with host spans around them and around the engine's step and
    admission. The device runs programs in launch order, which lets the
    readers tell decode from prefill without guessing from names; a count
    of launches that differs from the trace's is an error, not a guess."""

    def __init__(self, engine):
        # ("decode", rows, live tokens) / ("prefill", prompt tokens, 0)
        self.kinds = []
        self.on = False
        dec, pre = engine._decode_s, engine._prefill_s
        step, admit = engine._step, engine._admit

        def decode(*a, **kw):
            if self.on:
                seqs = list(engine._seqs)
                self.kinds.append(("decode", len(seqs),
                                   sum(q.cached + 1 for q in seqs)))
            with harness.span("decode_dispatch"):
                return dec(*a, **kw)

        def prefill(params, kp, vp, tokens, length, *a, **kw):
            if self.on:
                self.kinds.append(("prefill", int(length), 0))
            with harness.span("prefill_dispatch"):
                return pre(params, kp, vp, tokens, length, *a, **kw)

        def spanned(name, fn):
            def inner(*a, **kw):
                with harness.span(name):
                    return fn(*a, **kw)
            return inner
        engine._decode_s, engine._prefill_s = decode, prefill
        engine._step = spanned("engine_step", step)
        engine._admit = spanned("engine_admit", admit)


def window(engine, schedule, seconds, trace_out=None, dispatches=None):
    """Send ``schedule`` open loop for ``seconds``, then wait (at most
    ``DRAIN_S``) for what is outstanding. Returns per-request records and
    the window's clocks."""
    records = [dict(r, handle=None, late_s=None, error=None)
               for r in schedule]
    state = {}

    def generate():
        t0 = state["t0"]
        for rec in records:
            wait = t0 + rec["due_s"] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            rec["late_s"] = time.monotonic() - (t0 + rec["due_s"])
            try:
                with harness.span("submit", trace_out is not None):
                    rec["handle"] = engine.submit(
                        rec["prompt"], max_new_tokens=rec["max_new_tokens"],
                        temperature=0.0)
            except Exception as e:           # shed: counts as failed
                rec["error"] = repr(e)

    sender = threading.Thread(target=generate, name="chipbench-generator")
    before = engine.stats
    with harness.traced_window(trace_out is not None,
                               trace_out if trace_out is not None else {}):
        if dispatches is not None:
            dispatches.on = True
        state["t0"] = t0 = time.monotonic()
        with harness.span("window", trace_out is not None):
            sender.start()
            sender.join()
            left = t0 + seconds - time.monotonic()
            if left > 0:
                time.sleep(left)
        close = time.monotonic()
        at_close = engine.stats
        if dispatches is not None:
            dispatches.on = False
    deadline = close + DRAIN_S
    for rec in records:
        h = rec["handle"]
        if h is None:
            continue
        try:
            rec["result"] = h.wait(timeout=max(deadline - time.monotonic(),
                                               0.001))
        except Exception as e:
            rec["error"] = repr(e)
    return records, {"t0": t0, "close": close, "before": before,
                     "at_close": at_close, "after": engine.stats,
                     "drain_s": time.monotonic() - close}


def reduce_records(records, clocks):
    """End-to-end numbers of one window from its per-request records:
    ``serve_tokens_per_s`` all output tokens of requests COMPLETED in the
    window over the whole window; ``serve_ttft_p95_ms`` over ALL requests
    due in it, from the scheduled send time, a failed, shed or unfinished
    one counting as never; ``serve_tpot_p95_ms`` over the completed ones."""
    t0 = clocks["t0"]
    ttft, tpot, done, failed, tokens_in_window = [], [], [], 0, 0
    for rec in records:
        res, h = rec.get("result"), rec["handle"]
        if res is None or res.finish_reason not in ("length", "eos"):
            failed += 1
            ttft.append(NEVER_MS)
            continue
        first = h.enqueue_t + res.ttft_ms / 1e3
        ttft.append((first - (t0 + rec["due_s"])) * 1e3)
        if len(res.tokens) > 1:
            tpot.append((res.latency_ms - res.ttft_ms)
                        / (len(res.tokens) - 1))
        if h.enqueue_t + res.latency_ms / 1e3 <= clocks["close"]:
            tokens_in_window += len(res.tokens)
        done.append(rec)
    late = [r["late_s"] * 1e3 for r in records if r["late_s"] is not None]
    produced = (clocks["at_close"]["tokens_generated"]
                - clocks["before"]["tokens_generated"])
    return {
        "serve_tokens_per_s": tokens_in_window / (clocks["close"] - t0),
        "tokens_produced_per_s": produced / (clocks["close"] - t0),
        "serve_ttft_p95_ms": harness.quantile(ttft, 0.95),
        "serve_tpot_p95_ms": harness.quantile(tpot, 0.95) if tpot else None,
        "ttft_p50_ms": harness.quantile(ttft, 0.5),
        "generator_late_p95_ms": harness.quantile(late, 0.95),
        "attempted": len(records), "failed": failed, "done": done,
        "queued_at_close": clocks["at_close"]["queued"],
        "running_at_close": clocks["at_close"]["running"],
        "drain_s": clocks["drain_s"],
    }


def pick_sample(done, seed):
    """The finished requests the reference runs over: the longest, and
    others drawn from the seed."""
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"])
                  + len(r["result"].tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 4])
    idx = rng.permutation(len(rest))[:SAMPLE_REQUESTS - 1]
    return [longest] + [rest[i] for i in idx]


def check_sample(sample, config, seed, reference, mix, control=False):
    """Run the reference once over each sampled prompt with its served
    tokens; the widest gaps over all sampled tokens."""
    params = reference.init_params(
        reference.key_data(seed), vocab=config["vocab_size"],
        hidden=config["hidden_size"], layers=config["num_hidden_layers"],
        ffn=config["ffn_dim"], positions=config["max_position_embeddings"])
    pad = reference.pad_to(mix["prompt_tokens"]["hi"]
                           + mix["output_tokens"]["hi"])
    gaps = {"logit_gap": [], "logprob_gap": [], "control_gap": [],
            "control_logprob_gap": []}
    for rec in sample:
        res = rec["result"]
        g = reference.served_gaps(
            params, rec["prompt"], res.tokens, res.logprobs,
            heads=config["num_attention_heads"],
            layers=config["num_hidden_layers"], pad=pad,
            precision_control="fp8" if control else None)
        for k, v in g.items():
            gaps[k].extend(v.tolist())
    del params
    return gaps


def _free(engine, model):
    engine.close()
    engine._kp = engine._vp = None
    model.params = None
    gc.collect()


def run(cell, seed, seconds, trace, devices, t_start, tamper=None,
        control=False):
    config, mix = cell["config"], cell["traffic"]
    reference = harness.load_module("reference", config["reference"] + ".py")
    watch = harness.CompileWatch()
    model, engine = build_engine(config, cell["cell"], seed, reference)
    dispatches = Dispatches(engine) if trace else None
    warm(engine, mix, config["vocab_size"])
    if tamper is not None:
        tamper(engine)
    schedule = loadgen.generate(mix, seed, seconds=seconds,
                                vocab=config["vocab_size"])
    compiles_before = watch.total
    trace_out = {} if trace else None
    setup_s = time.monotonic() - t_start
    records, clocks = window(engine, schedule, seconds, trace_out,
                             dispatches)
    compiles_in_window = watch.total - compiles_before
    red = reduce_records(records, clocks)
    device = harness.device_report(devices)
    stats = {k: clocks[k] for k in ("before", "at_close", "after")}
    _free(engine, model)
    del engine, model

    sample = pick_sample(red["done"], seed)
    gaps = check_sample(sample, config, seed, reference, mix, control=control)
    numbers = {
        "token_logit_gap": max(gaps["logit_gap"]) if gaps["logit_gap"]
        else float("inf"),
        "logprob_gap": max(gaps["logprob_gap"]) if gaps["logprob_gap"]
        else float("inf"),
        "requests_failed": red["failed"],
        "compiles_in_window": compiles_in_window,
    }
    compared = compare.judge(numbers, config["limits"])
    notes = {"sampled_requests": len(sample),
             "sampled_tokens": len(gaps["logit_gap"]),
             "queued_at_close": red["queued_at_close"],
             "running_at_close": red["running_at_close"],
             "drain_s": red["drain_s"], "ttft_p50_ms": red["ttft_p50_ms"],
             "generator_late_p95_ms": red["generator_late_p95_ms"]}
    if control:
        notes["control_gap"] = max(gaps["control_gap"])
        notes["control_logprob_gap"] = max(gaps["control_logprob_gap"])
        notes["raw_gaps"] = gaps
    metrics = {"serve_tokens_per_s": red["serve_tokens_per_s"],
               "serve_ttft_p95_ms": red["serve_ttft_p95_ms"],
               "serve_tpot_p95_ms": red["serve_tpot_p95_ms"],
               "setup_s": setup_s}
    notes["tokens_produced_per_s"] = red["tokens_produced_per_s"]
    ctx = None
    if trace:
        reduction = trace_reduce.reduce_planes(
            trace_reduce.read_planes(trace_out["xplane"]))
        b, a = stats["before"], stats["at_close"]
        ctx = {"reduction": reduction,
               "window_ns": trace_reduce.window_of(reduction),
               "window_s": clocks["close"] - clocks["t0"],
               "programs": trace_reduce.programs_by_launch(
                   reduction, dispatches.kinds), "stats_before": b,
               "stats_at_close": a, "config": config, "cell": cell["cell"],
               "generator_late_p95_ms": red["generator_late_p95_ms"],
               "peaks": harness.peaks_for(device["kind"]), "flops": flops,
               "trace_reduce": trace_reduce}
    return {"correct": all(c["ok"] for c in compared.values()),
            "attempted": red["attempted"], "failed": red["failed"],
            "metrics": metrics, "device": device, "compared": compared,
            "notes": notes, "ctx": ctx}


def sweep(cell, seed, rates, seconds, devices):
    """Find the knee once: one engine, one window per offered rate; a rate
    is sustained when nothing is queued at the close and the drain is
    short. Run by calibrate.py, never by the benchmark's own runs."""
    config = cell["config"]
    reference = harness.load_module("reference", config["reference"] + ".py")
    model, engine = build_engine(config, cell["cell"], seed, reference)
    warm(engine, cell["traffic"], config["vocab_size"])
    out = []
    for rate in rates:
        mix = dict(cell["traffic"], rate_per_s=rate)
        schedule = loadgen.generate(mix, seed, seconds=seconds,
                                    vocab=config["vocab_size"])
        records, clocks = window(engine, schedule, seconds)
        red = reduce_records(records, clocks)
        b, a = clocks["before"], clocks["after"]
        steps = a["decode_steps"] - b["decode_steps"]
        rows = ((a["running_occupancy"] * a["decode_steps"]
                 - b["running_occupancy"] * b["decode_steps"]) / steps
                if steps else 0.0)
        red.pop("done")
        out.append(dict(red, rate_per_s=rate, decode_steps=steps,
                        running_rows=rows,
                        prefills=a["prefills"] - b["prefills"],
                        preemptions=a["preemptions"] - b["preemptions"],
                        page_utilization_max=a["page_utilization_max"]))
        print("sweep", out[-1], flush=True)
    _free(engine, model)
    return out


def control_readings(cell, seed, devices):
    """On the chip at the cell's own load: one short window whose sampled
    requests are read by the reference AND by the control (the reference's
    pass with fp8 operands, at the same prompts and tokens: the widest gap
    of the token that the control puts first, and of its logprob). The
    control's numbers go through the run's own comparison, where they have
    to come out as not correct."""
    config = cell["config"]
    res = run(cell, seed, cell["cell"].get("control_window_s", 20.0), False,
              devices, time.monotonic(), control=True)
    numbers = {k: v["value"] for k, v in res["compared"].items()}
    control = dict(numbers,
                   token_logit_gap=res["notes"]["control_gap"],
                   logprob_gap=res["notes"]["control_logprob_gap"])
    compared = compare.judge(control, config["limits"])
    notes = {k: v for k, v in res["notes"].items() if k != "raw_gaps"}
    return {"program": {"numbers": numbers, "correct": res["correct"]},
            "control_fp8": {"numbers": control, "compared": compared,
                            "correct": all(c["ok"]
                                           for c in compared.values())},
            "notes": notes, "metrics": res["metrics"]}
