"""Continuous-batching generation measurement harness.

The ONE implementation shared by tools/gen_smoke.py (CI gate) and any
benchmark generation cell, so the parity check, the trace-count
assertion, and the throughput criterion cannot drift between the
evidence record and the gate.

Workload: a small decoder-only transformer LM (random weights — the
engine's economics do not depend on training) flooded with
mixed-length prompts. Two engines over the SAME model answer the same
flood:

- **continuous**: ``max_running`` slots, iteration-level scheduling —
  the thing under test;
- **sequential**: ``max_running=1`` — the same paged machinery, one
  request at a time; the honest per-request-decode baseline (it shares
  every per-step cost, so the ratio isolates the batching win, not
  harness overhead).

Both engines are warmed before timing, waves are INTERLEAVED
(continuous/sequential per wave — the comm_bench lesson: sequential
phases measure CPU load drift, interleaved ones measure the code), and
the gated ratio is the best wave. Greedy parity is judged against
``serving.reference_decode`` (full-sequence recompute per token) —
token-identical, the continuous-batching correctness bar — and the
continuous engine must finish the whole flood with ONE decode trace.

``bench_fused`` runs the decode-fast-path matrix on the same flood:
device-side sampling (and optionally the paged-attention kernel) vs
host sampling — token-identical greedy output, zero host logit syncs
on the fused path, and fused throughput no worse than host.

``bench_speculative`` runs the draft-propose / fused-verify rounds
(self-draft, 100% greedy acceptance) against the plain fused engine on
the same flood — the paired ratio isolates dispatch-count
amortization, the only speculation win a CPU box measures honestly.
"""
from __future__ import annotations

import time


def build_model(vocab=29, hidden=32, num_layers=2, num_heads=4,
                max_seq=96, seed=0):
    from paddle_tpu.models import transformer as tm
    cfg = tm.TransformerConfig(vocab_size=vocab, hidden=hidden,
                               num_layers=num_layers, num_heads=num_heads,
                               max_seq=max_seq)
    return tm.TransformerLM(tm.init_params(cfg, seed=seed), cfg)


def mixed_prompts(model, n, max_new, seed=0):
    """Mixed-length flood: prompt lengths spread over [2, ~max_seq/2],
    the shape that breaks request-level batching."""
    import numpy as np
    rng = np.random.RandomState(seed)
    V = model.config.vocab_size
    top = max(3, (model.config.max_seq - max_new) // 2)
    return [list(rng.randint(0, V, int(rng.randint(2, top))))
            for _ in range(n)]


def _flood(engine, prompts, max_new):
    """Submit everything async, wait for everything; returns wall
    seconds (the engine's stats carry the rest)."""
    t0 = time.perf_counter()
    handles = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    results = [h.wait(timeout=600) for h in handles]
    return time.perf_counter() - t0, results


def bench(requests=12, max_new=12, max_running=8, kv_pages=None,
          page_tokens=8, waves=2, seed=0):
    """Run the continuous-vs-sequential matrix; returns the summary dict
    the smoke gate asserts over."""
    from paddle_tpu.serving import GenerationEngine, reference_decode

    model = build_model(seed=seed)
    cfg = model.config
    if kv_pages is None:
        # room for max_running full-reservation sequences plus slack
        kv_pages = -(-cfg.max_seq // page_tokens) * (max_running + 2)
    prompts = mixed_prompts(model, requests, max_new, seed=seed)
    want = [reference_decode(model, p, max_new) for p in prompts]

    cont = GenerationEngine(model, max_running=max_running,
                            kv_pages=kv_pages, page_tokens=page_tokens,
                            queue_depth=4 * requests, warm=True,
                            name="cont")
    seq = GenerationEngine(model, max_running=1, kv_pages=kv_pages,
                           page_tokens=page_tokens,
                           queue_depth=4 * requests, warm=True,
                           name="seq")
    try:
        t_cont, t_seq, outputs = [], [], None
        for _ in range(waves):
            tc, results = _flood(cont, prompts, max_new)
            ts, _ = _flood(seq, prompts, max_new)
            t_cont.append(tc)
            t_seq.append(ts)
            outputs = results
        cont_stats = cont.stats
        seq_stats = seq.stats
    finally:
        cont.close()
        seq.close()

    bit_exact = all(r.tokens == w for r, w in zip(outputs, want))
    tokens = requests * max_new
    ratio = max(s / c for s, c in zip(t_seq, t_cont))
    best_cont = min(t_cont)
    return {
        "requests": requests,
        "max_new_tokens": max_new,
        "max_running": max_running,
        "kv_pages": kv_pages,
        "page_tokens": page_tokens,
        "prompt_lens": sorted(len(p) for p in prompts),
        "bit_exact": bit_exact,
        "tokens_per_wave": tokens,
        "continuous_s": [round(t, 4) for t in t_cont],
        "sequential_s": [round(t, 4) for t in t_seq],
        "throughput_ratio": round(ratio, 3),
        "continuous_tokens_per_s": round(tokens / best_cont, 1),
        "running_occupancy": round(cont_stats["running_occupancy"], 3),
        "max_running_seen": cont_stats["max_running_seen"],
        "decode_traces": cont_stats["decode_traces"],
        "sequential_decode_traces": seq_stats["decode_traces"],
        "decode_steps": cont_stats["decode_steps"],
        "sequential_decode_steps": seq_stats["decode_steps"],
        "page_utilization_max": round(cont_stats["page_utilization_max"],
                                      3),
        "completed": cont_stats["completed"],
        "failed": cont_stats["failed"] + cont_stats["shed"],
        "ttft_ms_p50": round(cont_stats["ttft_ms_p50"], 3),
        "ttft_ms_p99": round(cont_stats["ttft_ms_p99"], 3),
        "intertoken_ms_p50": round(cont_stats["intertoken_ms_p50"], 3),
        "intertoken_ms_p99": round(cont_stats["intertoken_ms_p99"], 3),
    }


def bench_fused(requests=12, max_new=12, max_running=8, kv_pages=None,
                page_tokens=8, waves=3, seed=0, attn_config=None,
                vocab=2048):
    """The decode-fast-path leg: fused (device-side sampling, and the
    paged-attention kernel when ``attn_config`` is given) vs host
    sampling, same flood, interleaved waves. Greedy output must stay
    token-identical across both engines, the fused engine must run the
    whole flood without a single host logit sync, and both must hold
    the one-decode-trace contract. The gated criterion is the PAIRED
    per-wave ratio (host/fused, best wave) >= 1 — the fused step's win
    is the [R, V] logits device->host sync plus the host-side per-row
    sampling it deletes, which scales with VOCAB, so this leg runs a
    realistic-vocab model (a vocab-29 toy would understate the tax
    being measured to the noise floor)."""
    from paddle_tpu import profiler
    from paddle_tpu.serving import GenerationEngine, reference_decode

    model = build_model(vocab=vocab, seed=seed)
    cfg = model.config
    if kv_pages is None:
        kv_pages = -(-cfg.max_seq // page_tokens) * (max_running + 2)
    prompts = mixed_prompts(model, requests, max_new, seed=seed)
    want = [reference_decode(model, p, max_new) for p in prompts]

    fused = GenerationEngine(model, max_running=max_running,
                             kv_pages=kv_pages, page_tokens=page_tokens,
                             queue_depth=4 * requests, warm=True,
                             name="fused", device_sample=True,
                             attn_config=attn_config)
    host = GenerationEngine(model, max_running=max_running,
                            kv_pages=kv_pages, page_tokens=page_tokens,
                            queue_depth=4 * requests, warm=True,
                            name="host", device_sample=False)
    try:
        t_fused, t_host, outputs = [], [], None
        for _ in range(waves):
            tf, results = _flood(fused, prompts, max_new)
            th, host_results = _flood(host, prompts, max_new)
            t_fused.append(tf)
            t_host.append(th)
            outputs = results
        fused_stats = fused.stats
        host_stats = host.stats
    finally:
        fused.close()
        host.close()

    tokens = requests * max_new
    prof = profiler.generation_counters()
    return {
        "requests": requests,
        "max_new_tokens": max_new,
        "max_running": max_running,
        "attn_config": attn_config,
        "attn_kernel": fused_stats["attn_kernel"],
        "bit_exact": all(r.tokens == w for r, w in zip(outputs, want)),
        "host_bit_exact": all(r.tokens == w
                              for r, w in zip(host_results, want)),
        "logprobs_present": all(r.logprobs is not None
                                and len(r.logprobs) == len(r.tokens)
                                for r in outputs),
        "fused_s": [round(t, 4) for t in t_fused],
        "host_s": [round(t, 4) for t in t_host],
        "fused_tokens_per_s": round(tokens / min(t_fused), 1),
        "host_tokens_per_s": round(tokens / min(t_host), 1),
        "speedup": round(max(h / f for h, f in zip(t_host, t_fused)), 3),
        "fused_decode_traces": fused_stats["decode_traces"],
        "host_decode_traces": host_stats["decode_traces"],
        "fused_host_logit_syncs": fused_stats["host_logit_syncs"],
        "host_host_logit_syncs": host_stats["host_logit_syncs"],
        "device_sample_steps": fused_stats["device_sample_steps"],
        "kernel_hits": fused_stats["kernel_hits"],
        "gen_device_sample_steps": prof.get("gen_device_sample_steps", 0),
        "completed": fused_stats["completed"],
        "failed": fused_stats["failed"] + fused_stats["shed"],
    }


def bench_speculative(requests=12, max_new=12, max_running=8,
                      kv_pages=None, page_tokens=8, waves=3, seed=0,
                      spec_k=4, vocab=29, hidden=16, num_layers=1,
                      num_heads=2, max_seq=64):
    """The speculative-decoding leg: draft-propose / fused-verify vs
    the plain fused engine, same flood, interleaved waves. The draft is
    the TARGET ITSELF (self-draft): greedy acceptance is 100% by
    construction, every round emits ``spec_k + 1`` tokens in exactly
    TWO dispatches (one draft scan, one k-wide verify), and the paired
    per-wave ratio isolates the one mechanism a CPU box can measure
    honestly — dispatch-count amortization. A genuinely small draft's
    acceptance economics are a TPU question (doc/serving.md); here a
    "small" draft would not be meaningfully cheaper and the ratio
    would measure model size, not the round structure. For the same
    reason this leg runs a SMALL model (the other legs' vocab-2048
    geometry is compute-bound on CPU, where a self-draft round's ~2x
    FLOPs swamps the dispatch structure it exists to measure; the
    small geometry is dispatch/host-overhead-bound, the regime a real
    TPU decode step is in for its memory-bandwidth reasons). Greedy
    output must stay token-identical across both engines and the
    reference at any k, the speculative flood must report
    acceptance > 0 with zero host logit syncs, and the propose/verify
    programs must each compile exactly once."""
    from paddle_tpu.serving import GenerationEngine, reference_decode

    model = build_model(vocab=vocab, hidden=hidden, num_layers=num_layers,
                        num_heads=num_heads, max_seq=max_seq, seed=seed)
    cfg = model.config
    if kv_pages is None:
        kv_pages = -(-cfg.max_seq // page_tokens) * (max_running + 2)
    prompts = mixed_prompts(model, requests, max_new, seed=seed)
    want = [reference_decode(model, p, max_new) for p in prompts]

    spec = GenerationEngine(model, max_running=max_running,
                            kv_pages=kv_pages, page_tokens=page_tokens,
                            queue_depth=4 * requests, warm=True,
                            name="spec", draft_model=model,
                            spec_k=spec_k)
    plain = GenerationEngine(model, max_running=max_running,
                             kv_pages=kv_pages, page_tokens=page_tokens,
                             queue_depth=4 * requests, warm=True,
                             name="plain_fused", device_sample=True)
    try:
        t_spec, t_plain, outputs, plain_results = [], [], None, None
        for _ in range(waves):
            ts, results = _flood(spec, prompts, max_new)
            tp, plain_results = _flood(plain, prompts, max_new)
            t_spec.append(ts)
            t_plain.append(tp)
            outputs = results
        spec_stats = spec.stats
        plain_stats = plain.stats
    finally:
        spec.close()
        plain.close()

    tokens = requests * max_new
    return {
        "requests": requests,
        "max_new_tokens": max_new,
        "max_running": max_running,
        "spec_k": spec_k,
        "bit_exact": all(r.tokens == w for r, w in zip(outputs, want)),
        "plain_bit_exact": all(r.tokens == w
                               for r, w in zip(plain_results, want)),
        "spec_s": [round(t, 4) for t in t_spec],
        "plain_s": [round(t, 4) for t in t_plain],
        "spec_tokens_per_s": round(tokens / min(t_spec), 1),
        "plain_tokens_per_s": round(tokens / min(t_plain), 1),
        "speedup": round(max(p / s for p, s in zip(t_plain, t_spec)), 3),
        "acceptance_rate": spec_stats["acceptance_rate"],
        "spec_steps": spec_stats["spec_steps"],
        "draft_tokens": spec_stats["draft_tokens"],
        "accepted_tokens": spec_stats["accepted_tokens"],
        "spec_degraded": spec_stats["spec_degraded"],
        "spec_host_logit_syncs": spec_stats["host_logit_syncs"],
        "spec_propose_traces": spec_stats["spec_propose_traces"],
        "spec_verify_traces": spec_stats["spec_verify_traces"],
        "plain_decode_traces": plain_stats["decode_traces"],
        "completed": spec_stats["completed"],
        "failed": spec_stats["failed"] + spec_stats["shed"],
    }


def bench_exhaustion(page_tokens=4, seed=1):
    """The degrade-and-record leg: a pool too small for the big request
    sheds it AT SUBMIT with a recorded kv_pool_exhausted event, keeps
    serving the small ones, and under reserve='prompt' a mid-flight
    starvation resolves by preemption with identical greedy output."""
    from paddle_tpu import resilience
    from paddle_tpu.serving import (GenerationEngine, PoolExhausted,
                                    reference_decode)

    model = build_model(max_seq=64, seed=seed)
    resilience.clear_events()
    out = {}
    # pool of 6 pages x 4 tokens = 24 cache positions
    eng = GenerationEngine(model, max_running=2, kv_pages=6,
                           page_tokens=page_tokens, queue_depth=16,
                           warm=True, name="exhaust")
    try:
        shed = False
        try:
            eng.submit(list(range(20)), max_new_tokens=8)  # needs 7 pages
        except PoolExhausted:
            shed = True
        small = [[1, 2, 3], [4, 5]]
        res = [eng.generate(p, max_new_tokens=6, timeout=300)
               for p in small]
        out["shed_at_submit"] = shed
        out["survivors_ok"] = all(
            r.tokens == reference_decode(model, p, 6)
            for r, p in zip(res, small))
        out["engine_alive"] = eng.stats["completed"] == len(small)
    finally:
        eng.close()
    evs = resilience.events(kind="kv_pool_exhausted")
    out["exhaustion_events"] = len(evs)
    # preemption leg: prompt-only reservation, two sequences racing a
    # pool that cannot hold both to completion
    pre = GenerationEngine(model, max_running=2, kv_pages=5,
                           page_tokens=page_tokens, queue_depth=16,
                           reserve="prompt", warm=True, name="preempt")
    try:
        prompts = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12]]
        handles = [pre.submit(p, max_new_tokens=8) for p in prompts]
        res = [h.wait(timeout=300) for h in handles]
        out["preempt_parity"] = all(
            r.tokens == reference_decode(model, p, 8)
            for r, p in zip(res, prompts))
        st = pre.stats
        out["preemptions"] = st["preemptions"]
        out["preempt_completed"] = st["completed"]
    finally:
        pre.close()
    return out


def bench_prefix(requests=4, max_new=8, prefix_tokens=32, page_tokens=8,
                 waves=2, seed=0):
    """The paired shared-vs-private wave: N requests over one long
    common prefix answered by two engines over the SAME model — prefix
    sharing off, then on — reporting the footprint and admission deltas
    at equal (token-identical greedy) output.

    Two legs, both paired:

    - **footprint**: a pool comfortable for either engine; the row
      reports peak live pages per engine and their ratio. Sharing must
      not change a single token; it only changes how many physical
      pages the wave pins.
    - **admission**: a pool sized BELOW requests x the private
      per-request footprint. The shared engine (cache warmed by one
      request) admits the whole wave concurrently because admission
      reserves dedup-aware effective tokens; the private engine
      serializes against physical pages. Nothing is shed either way.
    """
    from paddle_tpu.serving import GenerationEngine, reference_decode

    model = build_model(max_seq=96, seed=seed)
    V = model.config.vocab_size
    prefix = [(7 * i + 3) % V for i in range(prefix_tokens)]
    prompts = [prefix + [(i + 1) % V, (2 * i + 5) % V]
               for i in range(requests)]
    want = [reference_decode(model, p, max_new) for p in prompts]

    # private per-request footprint in pages (prompt + decode budget)
    pages_per_req = -(-(prefix_tokens + 2 + max_new) // page_tokens)
    prefix_pages = prefix_tokens // page_tokens   # full pages only
    tail_pages = pages_per_req - prefix_pages
    roomy = pages_per_req * requests
    tight = prefix_pages + tail_pages * requests  # < roomy for N > 1

    out = {
        "requests": requests,
        "prefix_tokens": prefix_tokens,
        "max_new_tokens": max_new,
        "page_tokens": page_tokens,
        "private_pages_per_request": pages_per_req,
        "roomy_kv_pages": roomy,
        "tight_kv_pages": tight,
    }

    def _run(sharing, kv_pages, label):
        eng = GenerationEngine(model, max_running=requests,
                               kv_pages=kv_pages, page_tokens=page_tokens,
                               queue_depth=4 * requests, warm=True,
                               prefix_sharing=sharing, name=label)
        try:
            # one solo request first: publishes the prefix so the
            # timed wave probes a warm cache (no-op when sharing off)
            eng.generate(prompts[0], max_new_tokens=max_new, timeout=600)
            results = None
            for _ in range(waves):
                _, results = _flood(eng, prompts, max_new)
            st = eng.stats
        finally:
            eng.close()
        exact = all(r.tokens == w for r, w in zip(results, want))
        return st, exact

    # footprint leg: roomy pool, paired engines
    peaks = {}
    for label, sharing in (("private", False), ("shared", True)):
        st, exact = _run(sharing, roomy, "fp_" + label)
        peaks[label] = st["page_utilization_max"] * roomy
        out["footprint_%s_bit_exact" % label] = exact
        out["footprint_%s_peak_pages" % label] = round(peaks[label], 1)
        if sharing:
            out["prefix_hits"] = st["prefix_hits"]
            out["prefix_hit_requests"] = st["prefix_hit_requests"]
            out["cow_copies"] = st["cow_copies"]
            util = st["page_utilization"]
            out["dedup_ratio"] = util.get("dedup_ratio")
    out["footprint_ratio"] = (round(peaks["private"] / peaks["shared"], 3)
                              if peaks["shared"] else 0.0)

    # admission leg: tight pool, same wave
    for label, sharing in (("private", False), ("shared", True)):
        st, exact = _run(sharing, tight, "adm_" + label)
        out["admission_%s_bit_exact" % label] = exact
        out["admission_%s_max_running_seen" % label] = \
            st["max_running_seen"]
        out["admission_%s_shed" % label] = st["shed"] + st["failed"]
    out["bit_exact"] = all(
        out[k] for k in out if k.endswith("_bit_exact"))
    return out


if __name__ == "__main__":
    import argparse
    import json
    import os
    import sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-running", type=int, default=8)
    ap.add_argument("--waves", type=int, default=2)
    ap.add_argument("--bank", action="store_true",
                    help="persist a paddle_tpu.bench.v1 row under "
                         "benchmark/results/")
    ap.add_argument("--mode", choices=["all", "prefix"], default="all",
                    help="'prefix' runs only the paired shared-vs-"
                         "private wave and banks it as gen_prefix")
    a = ap.parse_args()
    if a.mode == "prefix":
        summary = bench_prefix()
        bench_name = "gen_prefix"
    else:
        summary = bench(requests=a.requests, max_new=a.max_new,
                        max_running=a.max_running, waves=a.waves)
        summary["fused"] = bench_fused(requests=a.requests,
                                       max_new=a.max_new,
                                       max_running=a.max_running,
                                       waves=a.waves)
        summary["speculative"] = bench_speculative(
            requests=a.requests, max_new=a.max_new,
            max_running=a.max_running, waves=a.waves)
        summary["exhaustion"] = bench_exhaustion()
        summary["prefix"] = bench_prefix()
        bench_name = "gen"
    print(json.dumps(summary, indent=1))
    if a.bank:
        from paddle_tpu.tune import results as results_mod
        rec = results_mod.bench_record(bench_name, [summary])
        print("banked:", results_mod.write_result(rec))
