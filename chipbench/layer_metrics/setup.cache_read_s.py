"""Layer ``compile``: jax's ``cache.read`` phases
(``/jax/compilation_cache/cache_retrieval_time_sec``: the persistent
cache's read, hit or miss) under the program's set-up spans, s. Moves
setup_s."""
from chipbench import setup_trace


def read(ctx):
    return setup_trace.self_s(ctx, "cache.read")
