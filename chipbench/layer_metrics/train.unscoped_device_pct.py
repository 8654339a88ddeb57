"""Layer ``step``: share of the device's busy time in the window on
operations with no scope: instructions the compiler made with no ``op_name``
of the program's (copies, async starts) or absent from the table. Moves
train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    view = program_trace.load(ctx)
    if not view or not view["scope_ns"]:
        return None
    return (100.0 * view["scope_ns"].get(program_trace.UNSCOPED, 0)
            / sum(view["scope_ns"].values()))
