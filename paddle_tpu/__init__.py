"""paddle_tpu: a TPU-native deep-learning framework with the capabilities of
the reference PaddlePaddle snapshot (see SURVEY.md), rebuilt on JAX/XLA.

Public surface mirrors ``paddle.fluid``: Program/Block/Operator/Variable IR,
layers DSL, Executor, optimizers, backward, save/load — but programs compile
to single XLA computations instead of being interpreted op-by-op, and
distribution is pjit sharding over device meshes instead of parameter servers
(reference: python/paddle/fluid/__init__.py).
"""
from __future__ import annotations

import time as _time

_IMPORT_START = _time.monotonic()   # the ``import`` set-up span starts here

# ops must register before anything builds programs
from . import ops  # noqa: F401

from .core.ir import (  # noqa: F401
    Program, Block, Operator, Variable, Parameter,
    default_main_program, default_startup_program, program_guard,
    switch_main_program, switch_startup_program, grad_var_name,
)
from .core.backward import append_backward, calc_gradient  # noqa: F401
from .core.executor import Executor, fetch_var  # noqa: F401
from .core.scope import Scope, global_scope, scope_guard  # noqa: F401
from .core.lod import LoDTensor, build_lod_tensor  # noqa: F401
from .core.types import VarType, convert_dtype  # noqa: F401
from .core import unique_name  # noqa: F401
from .place import CPUPlace, CUDAPlace, TPUPlace, Place  # noqa: F401

from . import layers  # noqa: F401
from . import nets  # noqa: F401
from . import io  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from . import parallel  # noqa: F401
from .parallel import DistributeTranspiler  # noqa: F401
from . import comm  # noqa: F401
from . import concurrency  # noqa: F401
from .concurrency import Go, Channel  # noqa: F401
from .core.executor import AsyncFetch  # noqa: F401
from . import trainer as trainer_mod  # noqa: F401
from .trainer import (Trainer, BeginPass, EndPass, BeginIteration,  # noqa: F401
                      EndIteration)
from . import kernels  # noqa: F401
from . import native  # noqa: F401
from . import nets  # noqa: F401
from .memory_optimization_transpiler import (  # noqa: F401
    memory_optimize, release_memory,
)
from . import amp  # noqa: F401
from . import analysis  # noqa: F401
from .analysis import ProgramVerifyError  # noqa: F401
from . import flags  # noqa: F401
from . import enforce  # noqa: F401
from .flags import FLAGS, set_flags, get_flags, flags_guard  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from . import checkpoint  # noqa: F401
from . import resilience  # noqa: F401
from . import elastic  # noqa: F401
from .io import (  # noqa: F401
    save_vars, save_params, save_persistables, load_vars, load_params,
    load_persistables, save_inference_model, load_inference_model,
    get_inference_program,
)
from . import learning_rate_decay  # noqa: F401
from . import evaluator  # noqa: F401
from . import profiler  # noqa: F401
from . import debugger  # noqa: F401
from . import initializer  # noqa: F401
from . import optimizer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .clip import ErrorClipByValue  # noqa: F401
from .initializer import (Constant, Normal, Uniform, Xavier, MSRA)  # noqa: F401
from .optimizer import (  # noqa: F401
    SGD, Momentum, Adagrad, Adam, Adamax, DecayedAdagrad, Adadelta, RMSProp,
    SGDOptimizer, MomentumOptimizer, AdagradOptimizer, AdamOptimizer,
    AdamaxOptimizer, DecayedAdagradOptimizer, AdadeltaOptimizer,
    RMSPropOptimizer,
)

__version__ = "0.1.0"

profiler.record_setup_span("import", _IMPORT_START)
