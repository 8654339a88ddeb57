"""Layer ``device``: device idle time, as a share of the traced window, while
the innermost program span open on the host was
``paddle_tpu/run`` outside ``upload`` (dispatch, the wait for
the results, their copy back). The four ``train.idle_*_pct`` add up to
``train.device_idle_pct``. Moves train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.idle_pct(ctx, "run_other")
