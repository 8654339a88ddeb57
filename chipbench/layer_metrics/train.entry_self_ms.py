"""Layer ``entry`` (trainer.py): median over the window's ``paddle_tpu/
train_step`` spans of the span's length less what its children ``feed`` and
``run`` cover: the reader, the event handler and Trainer's own loop. Moves
train_images_per_s."""
import statistics

from chipbench import program_trace


def read(ctx):
    view = program_trace.load(ctx)
    if not view or not view["entry_self_ms"]:
        return None
    return statistics.median(view["entry_self_ms"])
