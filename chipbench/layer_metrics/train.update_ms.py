"""Layer ``step``: device self time per step of the operations whose scope
starts ``update/``: the optimizer's pass (see ``train.fwd_ms``). Moves
train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "update/")
