"""Layer ``device``: 1 - union of the device's operation intervals over the
traced window. Moves train_images_per_s."""


def read(ctx):
    if ctx.get("window_ns") is None or not ctx.get("window_s"):
        return None
    busy = ctx["trace_reduce"].busy_seconds(ctx["reduction"],
                                            ctx["window_ns"])
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ctx["window_s"])
