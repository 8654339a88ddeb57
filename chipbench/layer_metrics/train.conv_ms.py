"""Layer ``step``: device self time per step of the convolutions, forward and
backward (scopes ``forward/conv2d``, ``backward/conv2d_grad`` and, where the
generic vjp differentiates it, ``backward/conv2d``): what a
``train.conv_roofline`` will divide. Moves train_images_per_s."""
from chipbench import program_trace


def read(ctx):
    return program_trace.phase_ms(ctx, "forward/conv2d", "backward/conv2d")
