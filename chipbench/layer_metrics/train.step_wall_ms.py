"""Layer ``entry`` (trainer.py, data_feeder.py): median gap between
EndIteration events of the window, by the benchmark's own handler on the
host clock. Moves train_images_per_s."""
import statistics


def read(ctx):
    ends = ctx.get("step_ends") or []
    if len(ends) < 3:
        return None
    return statistics.median(b - a for a, b in zip(ends, ends[1:])) * 1e3
