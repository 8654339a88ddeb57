"""Comm smoke gate (CPU tier-1): the paddle_tpu.comm gradient-sync
policies must hold their numerics contract on a forced 8-device run —

(a) ``none`` policy losses BIT-identical to the bare per-leaf pmean
    path it replaced;
(b) ``fused``, ``hierarchical`` and ``multipath`` within fp32 reduction
    tolerance of ``none``;
(c) ``int8`` AND ``int8_2shot`` (error feedback on) within 2% relative
    final loss of fp32 over a 3-pass mnist-sized run, with zero
    dynamic-range fallbacks — and the 2-shot form's modelled wire bytes
    strictly below BOTH the gather int8 form and the fp32 ring at n=8
    (the crossover doc/comm.md documents);
(d) fusion is real: collective dispatches (buckets) strictly below the
    parameter count;
(e) overlap parity: EVERY policy x comm_overlap=1 trains bit-identical
    (``none``) / within fp32 tolerance (the rest) of its own
    serialized run — the staged step restructures issue order and
    update staging, never values;
(f) overlap step-time: the staged fused step is no slower than the
    serialized one (best-of-3; the CPU fabric has nothing to hide
    behind, so the gate allows scheduler noise — the >=1.0 target is
    judged on the banked real-TPU row), and the run banks a
    ``paddle_tpu.bench.v1`` row for that comparison.

The measurement lives in benchmark/comm_bench.py — the SAME harness any
bench comm phase emits evidence from, so gate and evidence cannot
drift. Companion to tools/lint.sh (static), tools/serve_smoke.sh
(serving). Exit 0 on pass, 1 on failure; prints a one-line JSON summary either way.

Invoked by tools/comm_smoke.sh; usable directly:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/comm_smoke.py
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# best-of-3 damps scheduler noise, but two identical CPU graphs still
# jitter a few percent run to run; the hard >=1.0 throughput target is
# judged on a real fabric (the banked row carries the CPU number)
OVERLAP_NO_SLOWER_SLACK = 0.90


def main():
    from benchmark.comm_bench import bench, bench_overlap, \
        bank_overlap_result
    r = bench(passes=3, batches=3)
    pol = r["policies"]
    failures = []

    if pol["none"]["losses"] != r["bare_losses"]:
        failures.append("none policy not bit-identical to the bare pmean "
                        "path")
    ref = pol["none"]["losses"]
    for name in ("fused", "hierarchical", "multipath"):
        ls = pol[name]["losses"]
        worst = max(abs(a - b) / max(abs(b), 1e-9)
                    for a, b in zip(ls, ref))
        if worst > 1e-4:
            failures.append("%s policy deviates %.2e rel from none "
                            "(fp32 reduction tolerance 1e-4)"
                            % (name, worst))
    for name in ("int8", "int8_2shot"):
        q_rel = abs(pol[name]["final_loss"] - pol["none"]["final_loss"]) \
            / max(abs(pol["none"]["final_loss"]), 1e-9)
        if q_rel > 0.02:
            failures.append("%s final loss %.4f vs fp32 %.4f: %.1f%% > 2%%"
                            % (name, pol[name]["final_loss"],
                               pol["none"]["final_loss"], 100 * q_rel))
        if pol[name]["comm_quant_fallbacks"]:
            failures.append("%s run hit %d dynamic-range fallbacks on a "
                            "healthy model"
                            % (name, pol[name]["comm_quant_fallbacks"]))
    if not pol["fused"]["comm_buckets"] < r["n_params"]:
        failures.append("no fusion: %d buckets for %d params"
                        % (pol["fused"]["comm_buckets"], r["n_params"]))

    # 2-shot bytes crossover at n=8 (the row the gather form loses)
    from paddle_tpu.comm import CommPolicy, bytes_on_wire
    B, n = 1 << 20, 8
    b_2shot = bytes_on_wire(B, CommPolicy(base="fused",
                                          quant="int8_2shot"), n)
    b_gather = bytes_on_wire(B, CommPolicy(base="fused", quant="int8"), n)
    b_fp32 = bytes_on_wire(B, CommPolicy(base="fused"), n)
    if not (b_2shot < b_gather and b_2shot < b_fp32):
        failures.append("2-shot int8 bytes %d do not beat gather %d / "
                        "fp32 %d at n=8" % (b_2shot, b_gather, b_fp32))

    # overlap parity matrix: every policy, staged vs its serialized run
    if r["overlap"]["none"]["losses"] != pol["none"]["losses"]:
        failures.append("overlap-on none policy not bit-identical to "
                        "serialized none")
    for name, ov in r["overlap"].items():
        if name == "none":
            continue
        worst = max(abs(a - b) / max(abs(b), 1e-9)
                    for a, b in zip(ov["losses"], pol[name]["losses"]))
        if worst > 1e-5:
            failures.append("overlap-on %s deviates %.2e rel from its "
                            "serialized run" % (name, worst))

    # overlap step-time: parity + no-slower, banked as a bench row
    ov = bench_overlap()
    if not ov["comm_overlap_parity"]:
        failures.append("overlap step-time phase lost bit-parity under "
                        "policy none")
    if ov["comm_overlap_speedup"] < OVERLAP_NO_SLOWER_SLACK:
        failures.append("overlap step is slower than serialized: "
                        "%.2f steps/s vs %.2f (x%.3f < %.2f)"
                        % (ov["comm_overlap_steps_s"],
                           ov["comm_serial_steps_s"],
                           ov["comm_overlap_speedup"],
                           OVERLAP_NO_SLOWER_SLACK))
    try:
        banked = bank_overlap_result(ov)
    except Exception as e:  # banking must not fail the numerics gate
        banked = None
        print("comm_smoke: result banking failed: %r" % e, file=sys.stderr)

    summary = {
        "ok": not failures,
        "n_params": r["n_params"],
        "fused_buckets": pol["fused"]["comm_buckets"],
        "none_final": pol["none"]["final_loss"],
        "int8_final": pol["int8"]["final_loss"],
        "int8_2shot_final": pol["int8_2shot"]["final_loss"],
        "bytes_per_chip": {k: v["comm_bytes"] for k, v in pol.items()},
        "bytes_n8_model": {"int8_2shot": b_2shot, "int8_gather": b_gather,
                           "fp32_ring": b_fp32},
        "overlap_speedup": ov["comm_overlap_speedup"],
        "overlap_parity": ov["comm_overlap_parity"],
        "overlap_banked": banked,
    }
    print(json.dumps(summary))
    if failures:
        for f in failures:
            print("comm_smoke FAIL: %s" % f, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
