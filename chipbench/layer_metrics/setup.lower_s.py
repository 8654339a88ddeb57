"""Layer ``compile``: self time of jax's ``jax.lower`` phases
(``/jax/core/compile/jaxpr_to_mlir_module_duration``: jaxpr to MLIR) under
the program's set-up spans, s. Moves setup_s."""
from chipbench import setup_trace


def read(ctx):
    return setup_trace.self_s(ctx, "jax.lower")
