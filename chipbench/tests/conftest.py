"""Run by hand: JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
(not part of tier-1)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
