"""Layer ``step``: device self time per step of the Mamba-2 mixers
(``ssm_mixer``), forward and backward: the scopes ``forward/ssm_mixer`` and
``backward/ssm_mixer`` with every part under them (``proj``, ``conv``,
``scan``, ``norm``; the backward ones hold the recomputed forward). A
program with no such op reads nothing. Moves train_images_per_s."""
from chipbench import program_trace

SCOPES = ("forward/ssm_mixer", "backward/ssm_mixer")


def read(ctx):
    return program_trace.phase_ms(ctx, *SCOPES) or None
