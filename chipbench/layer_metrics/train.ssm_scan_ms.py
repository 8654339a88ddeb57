"""Layer ``step``: device self time per step of the mixers' state-space
scan alone, forward and backward: the part scopes
``forward/ssm_mixer/scan`` and ``backward/ssm_mixer/scan`` (from x, B, C
and the step to y with the D skip; the chunked products, the pass over the
chunks' states and their gradients). A program with no such part reads
nothing. Moves train_images_per_s."""
from chipbench import program_trace

SCOPES = ("forward/ssm_mixer/scan", "backward/ssm_mixer/scan")


def read(ctx):
    return program_trace.phase_ms(ctx, *SCOPES) or None
