"""Layer ``step``: ``train.gqa_attn_ms`` of the sliding-window layers alone
(part scope ``attn_window``). Moves train_images_per_s."""
from chipbench import harness


def read(ctx):
    return harness.load_module(
        "layer_metrics", "train.gqa_attn_ms.py").read(ctx, ("window",))
