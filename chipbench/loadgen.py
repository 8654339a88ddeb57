"""Traffic from data. A mix is a JSON file of parameters under
``chipbench/traffic/``; its ``generator`` names the module under
``chipbench/generators/`` that reads it, found by name as the per-layer
readers are. A new mix of a shape that a generator already makes is a new
data file; a new shape of load is a new generator file, and no file that is
there changes.

A generator is ``generate(mix, seed, **sizes)``. Every seed gets the SAME
multiset of sizes and gaps in another order, so the work of a run does not
depend on the seed.
"""
from __future__ import annotations

import numpy as np

from chipbench import harness


def rng(seed, stream):
    """The seed's generator for one stream of draws (``--seed`` may pass
    2**31; ``stream`` keeps pixels, orders and token ids apart)."""
    return np.random.default_rng([int(seed), int(stream)])


def generate(mix, seed, **sizes):
    """What ``mix`` sends for ``seed``: whatever its generator returns."""
    return harness.load_module(
        "generators", mix["generator"] + ".py").generate(mix, seed, **sizes)
