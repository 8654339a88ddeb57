"""layers DSL — flat namespace like ``fluid.layers.*``
(reference: python/paddle/fluid/layers/__init__.py)."""
from . import (control_flow, decoder, detection, io, nn,  # noqa: F401
               sequence, tensor)
from .io import *  # noqa: F401,F403
from .nn import *  # noqa: F401,F403
from .sequence import *  # noqa: F401,F403
from .tensor import *  # noqa: F401,F403
from .control_flow import *  # noqa: F401,F403
from .detection import *  # noqa: F401,F403
from .decoder import *  # noqa: F401,F403
from .nn import concat_nn  # noqa: F401
from . import ops as _ops_mod  # noqa: F401

__all__ = []
__all__ += io.__all__
__all__ += nn.__all__
__all__ += sequence.__all__
__all__ += tensor.__all__
__all__ += control_flow.__all__
__all__ += detection.__all__
__all__ += decoder.__all__

# auto-generated simple-op layers fill any name not hand-written above
# (reference: fluid/layers/ops.py registered after nn.py the same way)
for _n in _ops_mod.__all__:
    if _n not in globals():
        globals()[_n] = getattr(_ops_mod, _n)
        __all__.append(_n)
del _n
