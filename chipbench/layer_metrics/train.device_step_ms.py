"""Layer ``step`` (core/executor.py): device busy time of the traced window
over the steps finished in it. Moves train_images_per_s."""


def read(ctx):
    if not ctx.get("steps") or ctx.get("window_ns") is None:
        return None
    busy = ctx["trace_reduce"].busy_seconds(ctx["reduction"],
                                            ctx["window_ns"])
    if busy <= 0:
        return None
    return busy / ctx["steps"] * 1e3
