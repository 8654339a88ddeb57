"""Layer ``step``: the band's FLOPs of a step (sliding layers only) over
``train.gqa_window_attn_ms`` at the chip's bf16 peak: a kernel that visits
tiles outside the band pays for them here. Moves train_images_per_s."""
from chipbench import harness


def read(ctx):
    return harness.load_module(
        "layer_metrics", "train.gqa_attn_roofline_pct.py").read(
            ctx, ("window",))
