"""Layer ``entry`` (data_feeder.py): median length of the program's own span
``paddle_tpu/feed`` (``DataFeeder.feed``: sample tuples -> stacked arrays) in
the traced window; the benchmark's ``chipbench/feed`` shadows it from
outside. Moves train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.median_span_ms(ctx, "feed")
