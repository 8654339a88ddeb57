"""Layer ``step``: device self time per step of the attention kernels of
``latent_attention``, forward and backward (scopes
``forward/latent_attention/attn`` and ``backward/latent_attention/attn``; the
backward one holds the recomputed forward kernel too). A program whose
scopes have no such part reads nothing. Moves train_images_per_s."""
from chipbench import program_trace

SCOPES = ("forward/latent_attention/attn", "backward/latent_attention/attn")


def read(ctx):
    return program_trace.phase_ms(ctx, *SCOPES) or None
