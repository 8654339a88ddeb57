"""Layer ``step``: the MODEL's attention FLOPs of a step (the band's pairs on
sliding layers, the causal triangle's on full ones; forward + backward = 3 x
forward, recomputation not counted; from shapes, by the configuration's
network module) over ``train.gqa_attn_ms`` at the chip's bf16 peak. Moves
train_images_per_s."""
from chipbench import harness


def read(ctx, kinds=("window", "full")):
    ms = harness.load_module(
        "layer_metrics", "train.gqa_attn_ms.py").read(ctx, kinds)
    flops = ctx.get("attention_flops_per_step")
    if not ms or not isinstance(flops, dict):
        return None
    return (100.0 * sum(flops[k] for k in kinds)
            / (ms * 1e-3 * ctx["peaks"]["flops_bf16"]))
