"""The persistent compile cache: jax's on-disk XLA compilation cache
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``FLAGS.compile_cache_dir``
= ``<checkout>/.jax_cache``; opt-out ``FLAGS.compile_cache=0``), so a
repeat run skips the cold compile. The in-process half is the warm-start
registry in :mod:`.executor`, keyed by (program uid, version, feed
signature)."""
from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache",
           "maybe_enable_compile_cache"]

_compile_cache_state = {"configured": False}


def compile_cache_dir():
    """Where the persistent compile cache lives:
    ``JAX_COMPILATION_CACHE_DIR`` places it from outside; unset, it is
    ``FLAGS.compile_cache_dir`` (``<checkout>/.jax_cache``)."""
    from ..flags import FLAGS
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or FLAGS.compile_cache_dir)


def enable_compile_cache():
    """Turn on jax's persistent XLA compilation cache and return its
    directory (:func:`compile_cache_dir`). Where the environment variable
    is set jax reads it itself and no directory is set in code."""
    import jax

    dirname = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(dirname, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", dirname)
    # the default threshold (1 s) would skip the long tail of small
    # programs a repeat run compiles again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    _compile_cache_state["configured"] = True
    return dirname


def maybe_enable_compile_cache():
    """Idempotent lazy hook the Executor calls before its first compile:
    honors ``FLAGS.compile_cache`` (opt-out)."""
    if _compile_cache_state["configured"]:
        return
    _compile_cache_state["configured"] = True
    from ..flags import FLAGS
    if FLAGS.compile_cache:
        enable_compile_cache()
