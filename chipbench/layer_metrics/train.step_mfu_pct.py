"""Layer ``step``: the whole step's share of the chip's bf16 peak. FLOPs of
forward + backward of one row, counted from the shapes by the
configuration's network module (backward = 2 x forward, recompute not
counted), times the rows per second of the traced window. Moves
train_images_per_s."""


def read(ctx):
    if not ctx.get("images_per_s") or not ctx.get("train_flops_per_row"):
        return None
    return (100.0 * ctx["train_flops_per_row"] * ctx["images_per_s"]
            / ctx["peaks"]["flops_bf16"])
