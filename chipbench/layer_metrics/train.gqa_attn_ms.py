"""Layer ``step``: device self time per step of the attention kernels of
``grouped_attention``, forward and backward, on sliding-window layers (part
scope ``attn_window``) and full ones (``attn_full``); the backward scopes
hold the recomputed forward kernel too. A program whose scopes have no such
part reads nothing. Moves train_images_per_s."""
from chipbench import program_trace

KINDS = {"window": "attn_window", "full": "attn_full"}


def scopes(*kinds):
    return tuple("%s/grouped_attention/%s" % (phase, KINDS[k])
                 for k in kinds for phase in ("forward", "backward"))


def read(ctx, kinds=("window", "full")):
    return program_trace.phase_ms(ctx, *scopes(*kinds)) or None
