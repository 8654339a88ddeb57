"""Layer ``step`` (core/executor.py): median length of ``paddle_tpu/upload``,
the feed's host -> device copy in ``Executor.run``. Moves
train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.median_span_ms(ctx, "upload")
