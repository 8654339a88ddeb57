"""Places: where computation runs.

reference: paddle/fluid/platform/place.h:53 (boost::variant<CUDAPlace,
CPUPlace>). Here the accelerator is TPU; CPUPlace maps to the jax cpu backend
(used by the 8-virtual-device test mesh). A Place pins which jax backend the
Executor uses; multi-chip placement is expressed with meshes
(paddle_tpu.parallel), not per-device Places.
"""
from __future__ import annotations

import jax


class Place(object):
    backend = None

    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == \
            getattr(other, "device_id", 0)

    def __repr__(self):
        return type(self).__name__ + "()"


class TPUPlace(Place):
    backend = "tpu"

    def __init__(self, device_id=0):
        self.device_id = device_id


class CPUPlace(Place):
    backend = "cpu"


# alias kept for reference-API compatibility (CUDAPlace -> accelerator place)
CUDAPlace = TPUPlace


def on_tpu() -> bool:
    """The one answer to "compiled or interpreted": True when the default
    device is a TPU (Pallas kernels compile through Mosaic, AMP casts to
    bf16, the tuner times on the wall clock), False on the CPU backend
    (kernels run in interpret mode). A backend that fails to initialise
    raises here — it is never read as "no TPU"."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return True
    if platform == "cpu":
        return False
    raise RuntimeError(
        "paddle_tpu runs on 'tpu' (compiled) or 'cpu' (interpreted); "
        "default device platform is %r" % (platform,))


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())
