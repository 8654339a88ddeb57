"""Layer ``compile``: self time of jax's ``xla.compile`` phases
(``/jax/core/compile/backend_compile_duration``) under the program's set-up
spans, s: XLA's compile on a cache miss, the executable's load on a hit
(the ``cache.read`` inside it taken out). Moves setup_s."""
from chipbench import setup_trace


def read(ctx):
    return setup_trace.self_s(ctx, "xla.compile")
