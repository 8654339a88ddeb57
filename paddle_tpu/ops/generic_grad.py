"""The generic gradient op: replays a forward lowering under jax.vjp.

Replaces the reference's per-op hand-written grad kernels (e.g.
paddle/fluid/operators/*_grad kernels registered via REGISTER_OP's
GradOpDescMaker, op_registry.h:148). One op covers every forward op whose
lowering is a pure function of its inputs; ops with internal state/randomness
(dropout) register custom grad makers instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import registry
from ..core.executor import FunctionalContext, raw_data
from ..kernels.flash_attention import KEPT

# what a recomputed op's checkpoint keeps beside its inputs
_KEEP = jax.checkpoint_policies.save_only_these_names(*KEPT)


def _zeros_like(v):
    return jnp.zeros_like(raw_data(v))


def _generic_grad_is_host(op):
    """A generic grad replays its forward lowering, so it is host-bound
    exactly when the forward op is (incl. predicate-host ops like
    sequence_pool with stride windows — the forward attrs are copied onto
    the grad op, so the forward's predicate evaluates unchanged)."""
    fwd = registry.lookup(op.attr("__fwd_type__"))
    if fwd is None:
        return False
    h = fwd.host
    return bool(h(op)) if callable(h) else bool(h)


@registry.register_op("generic_grad", host=_generic_grad_is_host)
def generic_grad(ctx):
    fwd_type = ctx.attr("__fwd_type__")
    in_slots = list(ctx.attr("__fwd_input_slots__"))
    out_slots = list(ctx.attr("__fwd_output_slots__"))
    diff_slots = ctx.attr("__diff_slots__")  # slot -> [bool per name]
    fwd_def = registry.lookup_checked(fwd_type)
    fwd_attrs = {k: v for k, v in ctx.op.attrs.items()
                 if not k.startswith("__")}

    # gather forward input values; split into differentiable / constant
    in_vals = {s: ctx.inputs(s) for s in in_slots}
    prim_index = []  # (slot, idx) in flattening order
    primals = []
    def _jax_value(v):
        from ..core.executor import TracedLoD
        return (hasattr(v, "dtype") or isinstance(v, TracedLoD)
                or isinstance(v, (list, tuple)))

    for s in in_slots:
        flags = diff_slots.get(s, [False] * len(in_vals[s]))
        for i, v in enumerate(in_vals[s]):
            if i < len(flags) and flags[i] and v is not None \
                    and _jax_value(v):
                prim_index.append((s, i))
                primals.append(v)

    fwd_outputs = {s: list(ctx.op.input(s)) for s in out_slots}
    fwd_inputs = {s: in_vals[s] for s in in_slots}

    def fwd_fn(*diff_vals):
        vals = {s: list(vs) for s, vs in fwd_inputs.items()}
        for (s, i), v in zip(prim_index, diff_vals):
            vals[s][i] = v
        fctx = FunctionalContext(ctx.op, vals, fwd_attrs,
                                 outputs=fwd_outputs, type=fwd_type)
        fwd_def.lower(fctx)
        flat = []
        for s in out_slots:
            outs = fctx.collected.get(s, [])
            names = ctx.op.input(s)  # forward outputs are grad-op inputs
            for i in range(len(names)):
                flat.append(outs[i] if i < len(outs) else None)
        return tuple(raw_data(o) if o is not None else jnp.zeros(())
                     for o in flat)

    remat_types = getattr(ctx.block.program, "_remat_types", None)
    if getattr(ctx.block.program, "_remat", False) or (
            remat_types is not None and fwd_type in remat_types):
        # memory_optimize'd program: recompute this op's forward during
        # the backward instead of keeping residuals (jax.checkpoint) —
        # selective by op type so only activation-heavy layers pay the
        # recompute. It keeps the op's inputs and, of each flash-attention
        # call its lowering makes, the o and lse the backward kernels read:
        # the backward re-runs the norms and products, not the attention
        # kernel, and the launch that made them is the forward op's own
        # (kernels/flash_attention.py: _forward_launch). An op that makes
        # no such call keeps its inputs alone.
        fwd_fn = jax.checkpoint(fwd_fn, policy=_KEEP)
    outs, vjp = jax.vjp(fwd_fn, *primals)

    # cotangents from the incoming Out@GRAD slots ('' names -> zero)
    cots = []
    k = 0
    for s in out_slots:
        gnames = ctx.op.input(s + "@GRAD")
        for i, gn in enumerate(gnames):
            if gn:
                g = raw_data(ctx.env[gn])
                cots.append(jnp.asarray(g, outs[k].dtype)
                            .reshape(outs[k].shape))
            elif jnp.issubdtype(outs[k].dtype, jnp.inexact):
                cots.append(jnp.zeros_like(outs[k]))
            else:
                # a counter beside the op's result (an integer output):
                # its cotangent has jax's own zero type
                cots.append(np.zeros(outs[k].shape, jax.dtypes.float0))
            k += 1
    gins = vjp(tuple(cots))

    for (s, i), g in zip(prim_index, gins):
        names = ctx.op.output(s + "@GRAD")
        if i < len(names) and names[i]:
            ctx.env[names[i]] = g
