"""Layer ``step`` (core/executor.py): median length of ``paddle_tpu/fetch``:
the host blocked on the step's results, and their device -> host copy.
Moves train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.median_span_ms(ctx, "fetch")
