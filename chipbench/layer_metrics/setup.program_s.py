"""Layer ``setup`` (paddle_tpu/profiler.py: setup_spans): the union of the
program's set-up spans (``import``, ``build``, ``init``, ``compile``) that
ended before the window, s: the part of ``setup_s`` the program owns.
Moves setup_s."""
from chipbench import setup_trace


def read(ctx):
    view = setup_trace.load(ctx)
    return None if view is None else view["program_s"]
