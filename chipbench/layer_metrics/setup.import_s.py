"""Layer ``setup`` (paddle_tpu/__init__.py): self time of the set-up span
``import``, the body of ``paddle_tpu/__init__.py`` less the jax phases
inside it, s. Moves setup_s."""
from chipbench import setup_trace


def read(ctx):
    return setup_trace.self_s(ctx, "import")
