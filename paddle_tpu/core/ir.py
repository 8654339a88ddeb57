"""Program IR: the framework's model representation.

A ``Program`` is a list of ``Block``s; a ``Block`` holds named ``Variable``s
and a sequence of ``Operator``s (reference: paddle/fluid/framework/framework.proto:19-172,
python/paddle/fluid/framework.py:117,361,644,921). The critical TPU-first
departure: the reference *interprets* a block op-by-op in C++
(reference: paddle/fluid/framework/executor.cc:125-144); here the whole block is
traced into ONE jitted XLA computation by ``paddle_tpu.core.executor`` — ops
are symbolic nodes lowered to jax, never dispatched individually at runtime.
"""
from __future__ import annotations

import contextlib
import copy
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import types, unique_name
from .types import VarType, convert_dtype

GRAD_SUFFIX = "@GRAD"

# per-program cap on recorded build-time diagnostics (shape-infer failures,
# create_var conflicts): enough to debug with, never unbounded growth for a
# long-lived program that keeps appending ops
SHAPE_INFER_FAILURE_CAP = 64


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


def sub_block_read_names(op: "Operator", program: "Program") -> set:
    """All names a control-flow op's sub-blocks read (recursive, cycle-safe):
    keeping the op must keep its body's upstream producers. Shared by
    Program.prune and the analysis dead-op rule — the sub-block attr
    conventions (Block values, or int under 'sub_block'/'block') live here
    in one place."""

    def subs(o):
        for key, a in o.attrs.items():
            if isinstance(a, Block) and a.program is program:
                yield a
            elif isinstance(a, int) and not isinstance(a, bool) \
                    and key in ("sub_block", "block") \
                    and 0 <= a < len(program.blocks):
                yield program.blocks[a]

    names = set()
    seen = set()
    stack = list(subs(op))
    while stack:
        blk = stack.pop()
        if blk.idx in seen:  # corrupt programs may cycle; never recurse off
            continue
        seen.add(blk.idx)
        for sop in blk.ops:
            names.update(n for n in sop.input_arg_names if n)
            stack.extend(subs(sop))
    return names


class Variable(object):
    """Symbolic variable inside a Block.

    reference: python/paddle/fluid/framework.py:117 (class Variable).
    ``shape`` may contain -1 for the batch dim (resolved at feed time; XLA
    still compiles static — distinct feed shapes hit the executor's compile
    cache separately, which replaces the reference's fully-dynamic shapes).
    """

    def __init__(self, block, name=None, shape=None, dtype="float32",
                 lod_level=0, persistable=False, stop_gradient=False,
                 type=VarType.LOD_TENSOR, initializer=None, **kwargs):
        self.block = block
        self.name = name if name is not None else unique_name.generate("_generated_var")
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = convert_dtype(dtype) if type == VarType.LOD_TENSOR else dtype
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.type = type
        self.op = None  # producing operator, set by Block.append_op

    # -- convenience mirroring the reference Python Variable API ------------
    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def numel(self):
        if self.shape is None:
            return None  # shape not yet known (pre-inference var)
        n = 1
        for d in self.shape:
            n *= max(d, 1) if d != -1 else 1
        return n

    def __repr__(self):
        return "Variable(%s, shape=%s, dtype=%s, lod=%s%s)" % (
            self.name, self.shape, getattr(self.dtype, "name", self.dtype),
            self.lod_level, ", persistable" if self.persistable else "")

    __str__ = __repr__

    # operator sugar (reference exposes this via math_op_patch.py)
    def _binary(self, other, op):
        from ..layers import math_op_patch
        return math_op_patch.binary(self, other, op)

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        from ..layers import math_op_patch
        return math_op_patch.binary(self, other, "elementwise_sub", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    __div__ = __truediv__

    def __lt__(self, other):
        return self._binary(other, "less_than")

    def __le__(self, other):
        return self._binary(other, "less_equal")

    def __gt__(self, other):
        return self._binary(other, "greater_than")

    def __ge__(self, other):
        return self._binary(other, "greater_equal")


class Parameter(Variable):
    """Trainable variable (reference: python/paddle/fluid/framework.py:1082)."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        super(Parameter, self).__init__(block, shape=shape, dtype=dtype, **kwargs)


class Operator(object):
    """One op node: type + named input/output slots + attrs.

    reference: python/paddle/fluid/framework.py:361 (class Operator),
    paddle/fluid/framework/framework.proto:55-73 (OpDesc). Attrs may include
    sub-Blocks (control flow), matching attr type BLOCK.
    """

    # which part of a training step appended the op: "backward"
    # (append_backward), "update" (Optimizer.minimize's pass) or "forward"
    # (everything else). A python attribute, not a desc attr: it names the
    # op's device scope (executor.trace_ops) and is not serialised.
    phase = "forward"

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        # slot -> list[str] of var names
        self.inputs: Dict[str, List[str]] = {}
        self.outputs: Dict[str, List[str]] = {}
        self.attrs: Dict[str, Any] = dict(attrs or {})

        def _names(v):
            if v is None:
                return []
            if isinstance(v, (list, tuple)):
                return [x.name if isinstance(x, Variable) else x for x in v]
            return [v.name if isinstance(v, Variable) else v]

        for slot, v in (inputs or {}).items():
            self.inputs[slot] = _names(v)
        for slot, v in (outputs or {}).items():
            self.outputs[slot] = _names(v)

    def input(self, slot):
        return self.inputs.get(slot, [])

    def output(self, slot):
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def has_attr(self, name):
        return name in self.attrs

    def __repr__(self):
        ins = ", ".join("%s=%s" % kv for kv in sorted(self.inputs.items()))
        outs = ", ".join("%s=%s" % kv for kv in sorted(self.outputs.items()))
        return "{%s} = %s(%s)" % (outs, self.type, ins)


class Block(object):
    """Vars + op list; chains to a parent for control-flow sub-blocks.

    reference: python/paddle/fluid/framework.py:644 (class Block).
    """

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self):
        # out-of-range guards the lookups the verifier runs on corrupt
        # programs (it reports the bad index as PT010 instead of crashing)
        if self.parent_idx < 0 or self.parent_idx >= len(self.program.blocks):
            return None
        return self.program.blocks[self.parent_idx]

    # -- var management ----------------------------------------------------
    def create_var(self, **kwargs) -> Variable:
        name = kwargs.get("name")
        if name is not None and name in self.vars:
            existing = self.vars[name]
            self._check_var_redefinition(existing, kwargs)
            return existing
        var = Variable(self, **kwargs)
        self.vars[var.name] = var
        return var

    def _check_var_redefinition(self, existing, kwargs):
        """create_var on an existing name returns the existing var; if the
        request carried a conflicting shape/dtype that silent return hides
        a real bug — warn and record it for the PT012 verifier rule."""
        conflicts = []
        shape = kwargs.get("shape")
        if shape is not None and existing.shape is not None:
            req = tuple(shape)
            cur = tuple(existing.shape)
            # -1 is the batch wildcard: only fixed dims can conflict
            if len(req) != len(cur) or any(
                    a != b for a, b in zip(cur, req)
                    if a != -1 and b != -1):
                conflicts.append(("shape", cur, req))
        dtype = kwargs.get("dtype")
        if dtype is not None and existing.type == VarType.LOD_TENSOR \
                and kwargs.get("type", VarType.LOD_TENSOR) \
                == VarType.LOD_TENSOR:
            req_dt = convert_dtype(dtype)
            if req_dt != existing.dtype:
                conflicts.append(("dtype", existing.dtype, req_dt))
        if not conflicts:
            return
        rec = getattr(self.program, "_var_def_conflicts", None)
        if rec is None:
            rec = self.program._var_def_conflicts = []
        import warnings
        for field, cur, req in conflicts:
            if len(rec) < SHAPE_INFER_FAILURE_CAP:
                rec.append((self.idx, existing.name, field, cur, req))
            warnings.warn(
                "create_var(%r) requested %s %s but an existing var with "
                "%s %s was returned" % (existing.name, field, req, field,
                                        cur), RuntimeWarning)

    def create_parameter(self, **kwargs) -> Parameter:
        shape = kwargs.pop("shape")
        dtype = kwargs.pop("dtype", "float32")
        param = Parameter(self, shape, dtype, **kwargs)
        # parameters always live in the global (root) block, like the reference
        gb = self.program.global_block()
        gb.vars[param.name] = param
        param.block = gb
        return param

    def var(self, name) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise KeyError("Variable %r not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name) -> bool:
        return self._find_var_recursive(name) is not None

    def has_var_local(self, name) -> bool:
        return name in self.vars

    def _find_var_recursive(self, name) -> Optional[Variable]:
        blk = self
        seen = set()  # a corrupt parent chain may cycle; never hang on it
        while blk is not None and blk.idx not in seen:
            if name in blk.vars:
                return blk.vars[name]
            seen.add(blk.idx)
            blk = blk.parent_block
        return None

    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- op management -----------------------------------------------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.append(op)
        for slot, names in op.outputs.items():
            for n in names:
                v = self._find_var_recursive(n)
                if v is not None:
                    v.op = op
        self._infer_shape(op)
        self.program._bump_version()
        return op

    def insert_op(self, index, type, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        self._infer_shape(op)
        self.program._bump_version()
        return op

    def prepend_op(self, **kwargs) -> Operator:
        return self.insert_op(0, **kwargs)

    def _infer_shape(self, op):
        from . import registry
        opdef = registry.lookup(op.type)
        if opdef is not None and opdef.infer_shape is not None:
            try:
                opdef.infer_shape(op, self)
            except Exception as e:
                # best-effort (real shapes come from tracing) but never
                # silent: the failure is recorded for debugging (bounded —
                # analysis.verify surfaces the record as PT013
                # diagnostics), and PADDLE_TPU_DEBUG_SHAPES=1 surfaces it
                # immediately — otherwise shape bugs appear only as
                # cryptic trace errors
                import os
                rec = getattr(self.program, "_shape_infer_failures", None)
                if rec is None:
                    rec = self.program._shape_infer_failures = []
                if len(rec) < SHAPE_INFER_FAILURE_CAP:
                    rec.append((op.type, str(e)))
                else:
                    self.program._shape_infer_dropped = getattr(
                        self.program, "_shape_infer_dropped", 0) + 1
                from ..flags import FLAGS
                if (os.environ.get("PADDLE_TPU_DEBUG_SHAPES")
                        or FLAGS.debug_shapes):
                    import warnings
                    warnings.warn("shape inference failed for %s: %s"
                                  % (op, e), RuntimeWarning)

    def __repr__(self):
        lines = ["Block %d (parent %d):" % (self.idx, self.parent_idx)]
        for v in self.vars.values():
            lines.append("  " + repr(v))
        for op in self.ops:
            lines.append("  " + repr(op))
        return "\n".join(lines)


class Program(object):
    """The model: a list of Blocks, block 0 global.

    reference: python/paddle/fluid/framework.py:921 (class Program). The pair
    convention (startup program holding init ops, main program holding the
    train/infer graph) is preserved — see ``default_startup_program`` /
    ``default_main_program`` below.
    """

    _uid_counter = [0]

    def __init__(self):
        self.blocks: List[Block] = [Block(self, 0)]
        self._current_block_idx = 0
        self._version = 0
        Program._uid_counter[0] += 1
        self._uid = Program._uid_counter[0]  # stable executor cache identity
        self._seed = None  # program-level RNG seed (None -> executor default)
        # sharding annotations: var name -> jax PartitionSpec-like tuple,
        # attached by paddle_tpu.parallel (the transpiler-as-sharding-pass)
        self._shardings: Dict[str, Any] = {}
        # mesh annotation: axis name -> size, attached alongside
        # _shardings so analysis.sharding can check specs against the
        # mesh they were written for without a live jax Mesh
        self._mesh_axes: Dict[str, int] = {}
        self._is_distributed = False

    # -- block management --------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self._current_block_idx]

    def create_block(self, parent_idx=None) -> Block:
        parent = self._current_block_idx if parent_idx is None else parent_idx
        blk = Block(self, len(self.blocks), parent)
        self.blocks.append(blk)
        self._current_block_idx = blk.idx
        return blk

    def rollback(self):
        self._current_block_idx = self.current_block().parent_idx

    def _bump_version(self):
        self._version += 1

    @property
    def random_seed(self):
        return self._seed

    @random_seed.setter
    def random_seed(self, s):
        self._seed = s

    def all_parameters(self) -> List[Parameter]:
        return self.global_block().all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            for v in blk.vars.values():
                yield v

    def clone(self, for_test=False) -> "Program":
        """Deep-copy the program (reference: framework.py Program.clone).

        ``for_test=True`` flips ops' ``is_test`` attr (dropout/batch_norm
        behave in inference mode), matching reference ``inference_optimize``.
        """
        p = copy.deepcopy(self)
        Program._uid_counter[0] += 1
        p._uid = Program._uid_counter[0]
        if for_test:
            for blk in p.blocks:
                for op in blk.ops:
                    if "is_test" in _TEST_SENSITIVE_OPS.get(op.type, ()):
                        op.attrs["is_test"] = True
        return p

    def prune(self, feeds: Sequence[str], fetches: Sequence[str]) -> "Program":
        """Dead-op elimination for inference export.

        reference: paddle/fluid/framework/prune.cc + io.py:295
        (save_inference_model prunes to feed/fetch targets).
        """
        p = self.clone(for_test=True)
        blk = p.global_block()

        needed = set(fetches)
        kept = []
        for op in reversed(blk.ops):
            if set(op.output_arg_names) & needed:
                kept.append(op)
                needed |= set(op.input_arg_names)
                needed |= sub_block_read_names(op, p)
        blk.ops = list(reversed(kept))
        return p

    def to_string(self, throw_on_error=False):
        return "\n".join(repr(b) for b in self.blocks)

    __str__ = to_string
    __repr__ = to_string


_TEST_SENSITIVE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
    "lrn": ("is_test",),
    "nce": ("is_test",),
}

# -- default program pair (reference: framework.py bottom) -------------------
_main_program = Program()
_startup_program = Program()


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    """reference: python/paddle/fluid/framework.py program_guard."""
    global _main_program, _startup_program
    old_main, old_start = _main_program, _startup_program
    _main_program = main_program
    if startup_program is not None:
        _startup_program = startup_program
    try:
        yield
    finally:
        _main_program, _startup_program = old_main, old_start


def switch_main_program(program):
    global _main_program
    old = _main_program
    _main_program = program
    return old


def switch_startup_program(program):
    global _startup_program
    old = _startup_program
    _startup_program = program
    return old
