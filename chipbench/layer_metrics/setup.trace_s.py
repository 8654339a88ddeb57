"""Layer ``compile``: self time of jax's ``jax.trace`` phases
(``/jax/core/compile/jaxpr_trace_duration``: the Python trace to a jaxpr)
under the program's set-up spans, s. Moves setup_s."""
from chipbench import setup_trace


def read(ctx):
    return setup_trace.self_s(ctx, "jax.trace")
