"""Layer ``step``: the causal triangle's FLOPs of a step (global layers
only) over ``train.gqa_full_attn_ms`` at the chip's bf16 peak: the share of
its roofline that the full-row kernels reach. Moves train_images_per_s."""
from chipbench import harness


def read(ctx):
    return harness.load_module(
        "layer_metrics", "train.gqa_attn_roofline_pct.py").read(
            ctx, ("full",))
