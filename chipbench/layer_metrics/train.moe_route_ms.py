"""Layer ``step``: device self time per step of an expert layer's routing,
forward and backward (scopes ``forward/moe_ffn/route`` and
``backward/moe_ffn/route``): the router's product, sigmoid and top-k, the
sort of the (token, pick) pairs, the gather of their rows and the weighted
sum back. Moves train_images_per_s."""
from chipbench import program_trace

SCOPES = ("forward/moe_ffn/route", "backward/moe_ffn/route")


def read(ctx):
    return program_trace.phase_ms(ctx, *SCOPES) or None
