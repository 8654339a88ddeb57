"""Layer ``step``: the share of its roofline that the state-space scan
reaches: the larger of the scan's FLOPs of a step over the chip's bf16
peak and its HBM bytes over the chip's bandwidth (both the MODEL's counts,
forward + backward, from shapes, by the configuration's network module:
``attention_flops_per_step``'s ``ssm_scan`` / ``ssm_scan_bytes``), over
``train.ssm_scan_ms``. A network that counts no scan reads nothing. Moves
train_images_per_s."""
from chipbench import harness


def read(ctx):
    ms = harness.load_module(
        "layer_metrics", "train.ssm_scan_ms.py").read(ctx)
    counts = ctx.get("attention_flops_per_step")
    if not ms or not isinstance(counts, dict) or "ssm_scan" not in counts:
        return None
    peaks = ctx["peaks"]
    floor_s = max(counts["ssm_scan"] / peaks["flops_bf16"],
                  counts["ssm_scan_bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms * 1e-3)
