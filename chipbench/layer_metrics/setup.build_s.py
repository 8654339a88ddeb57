"""Layer ``build`` (trainer.py: ``Trainer.__init__``; ``amp.enable``,
``memory_optimize``): self time of the set-up spans ``build`` (backward,
optimizer ops, executor and feeder, program transforms), s. Moves
setup_s."""
from chipbench import setup_trace


def read(ctx):
    return setup_trace.self_s(ctx, "build")
