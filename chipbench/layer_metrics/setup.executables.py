"""Layer ``compile`` (core/executor.py: ``_TracedOnce``): the ``compile``
set-up spans that ended before the window: how many times set-up traced or
compiled a step program (its first call, or ``memory()``'s fit check).
Moves setup_s."""
from chipbench import setup_trace


def read(ctx):
    view = setup_trace.load(ctx)
    return None if view is None else view["executables"]
