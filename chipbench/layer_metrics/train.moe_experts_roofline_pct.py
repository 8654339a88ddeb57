"""Layer ``step``: the held experts' FLOPs of a step, for the (token, pick)
pairs that the window's steps themselves counted on held experts (their
``RowsHeld``, mean over the steps; forward + backward = 3 x forward,
recomputation not counted), over ``train.moe_experts_ms`` at the chip's
bf16 peak: the expected 384 rows an expert lie above the ridge. Moves
train_images_per_s."""
from chipbench import harness


def read(ctx):
    ms = harness.load_module(
        "layer_metrics", "train.moe_experts_ms.py").read(ctx)
    if not ms or not ctx.get("expert_flops_per_step"):
        return None
    return (100.0 * ctx["expert_flops_per_step"]
            / (ms * 1e-3 * ctx["peaks"]["flops_bf16"]))
