"""Layer ``step``: causal attention's own FLOPs of a step (forward +
backward = 3 x forward, recomputation not counted; from shapes, by the
configuration's network module) over ``train.attn_ms`` at the chip's bf16
peak: compute-bound at 4,096 tokens a row. Moves train_images_per_s."""
from chipbench import harness


def read(ctx):
    ms = harness.load_module("layer_metrics", "train.attn_ms.py").read(ctx)
    if not ms or not ctx.get("attention_flops_per_step"):
        return None
    return (100.0 * ctx["attention_flops_per_step"]
            / (ms * 1e-3 * ctx["peaks"]["flops_bf16"]))
