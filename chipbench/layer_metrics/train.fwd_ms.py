"""Layer ``step``: device self time per step of the operations whose scope
(``profiler.device_scopes()``) starts ``forward/``. With ``train.bwd_ms``,
``train.update_ms`` and the unscoped time it adds up to
``train.device_step_ms``. Moves train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "forward/")
