"""Layer ``step`` (core/executor.py): median length of ``paddle_tpu/dispatch``,
the host's work to launch the step (state gather, the jitted call, write
back). Moves train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.median_span_ms(ctx, "dispatch")
