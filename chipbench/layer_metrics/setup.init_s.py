"""Layer ``init`` (trainer.py: ``Trainer._maybe_init``): self time of the
set-up span ``init`` (the startup program's run, a checkpoint load) less
its ``compile`` children and jax phases, s. Moves setup_s."""
from chipbench import setup_trace


def read(ctx):
    return setup_trace.self_s(ctx, "init")
