"""Memory optimization pass.

reference: python/paddle/fluid/memory_optimization_transpiler.py:273 —
liveness analysis over the program's ops (ControlFlowGraph), rewriting
non-overlapping same-shape vars to share storage.

TPU-first inversion: XLA already performs buffer liveness/reuse inside the
compiled computation, and the executor donates the state buffers
(donate_argnums) so parameters update in place. What remains worth doing at
this layer is (a) the same liveness analysis — exposed for inspection and
asserted as the contract XLA honours, and (b) *rematerialisation*: marking
the program so its forward trace is wrapped in jax.checkpoint, trading
FLOPs for activation memory like the reference trades reuse for peak
memory.
"""
from __future__ import annotations

from typing import Dict, List, Set, Tuple

from .core import ir

__all__ = ["memory_optimize", "release_memory", "ControlFlowGraph"]


class ControlFlowGraph(object):
    """Liveness over a block's op list (reference: the class of the same
    name, memory_optimization_transpiler.py). The dataflow solve itself
    lives in ``analysis.memory.compute_liveness`` — the ONE liveness
    implementation, shared with the static memory planner's residency
    timeline (PT030-PT033)."""

    def __init__(self, program: ir.Program):
        self.program = program
        block = program.global_block()
        self.ops = list(block.ops)
        n = len(self.ops)
        self.uses: List[Set[str]] = [set(op.input_arg_names)
                                     for op in self.ops]
        self.defs: List[Set[str]] = [set(op.output_arg_names)
                                     for op in self.ops]
        self.live_in: List[Set[str]] = [set() for _ in range(n)]
        self.live_out: List[Set[str]] = [set() for _ in range(n)]

    def analyze(self):
        from .analysis.memory import compute_liveness
        self.live_in, self.live_out = compute_liveness(self.uses,
                                                       self.defs)
        return self

    def reuse_pairs(self) -> List[Tuple[str, str]]:
        """(dead_var, reusing_var) candidates: a var defined at op i can
        reuse storage of any same-shape var dead after op i."""
        block = self.program.global_block()
        pairs = []
        pool: List[str] = []
        persist = {v.name for v in self.program.list_vars()
                   if v.persistable}
        for i, op in enumerate(self.ops):
            # vars that die here enter the pool
            for name in self.live_in[i] - self.live_out[i]:
                if name not in persist:
                    pool.append(name)
            for name in self.defs[i]:
                if name in persist:
                    continue
                v = block._find_var_recursive(name)
                for cand in pool:
                    c = block._find_var_recursive(cand)
                    if (v is not None and c is not None
                            and v.shape == c.shape and v.dtype == c.dtype
                            and cand != name):
                        pairs.append((cand, name))
                        pool.remove(cand)
                        break
        return pairs


# activation-heavy ops whose residuals dominate training memory: the
# default selective-checkpoint set (trading their recompute FLOPs for
# activation memory is the profitable direction; cheap elementwise ops are
# NOT worth re-running)
DEFAULT_REMAT_TYPES = frozenset((
    "conv2d", "depthwise_conv2d", "mul", "matmul", "dynamic_lstm",
    "dynamic_gru", "sequence_conv", "flash_attention", "mdlstm",
    # whole half-layers of a decoder block (ops/decoder_ops.py): kept as
    # their [tokens, hidden] input, recomputed in the backward pass
    "latent_attention", "grouped_attention", "gated_ffn", "moe_ffn"))


def memory_optimize(input_program: ir.Program, print_log=False, level=0,
                    remat_types=None):
    """Enable rematerialisation for the program and report the reuse the
    liveness analysis finds (XLA applies the actual buffer sharing when it
    compiles the traced computation).

    ``remat_types``: which op types get jax.checkpoint'd in their backward
    (selective checkpointing). Default: the activation-heavy set
    DEFAULT_REMAT_TYPES; pass True for every op (the old global flag),
    False (or an empty iterable) for none, or an iterable of type names."""
    from .analysis.memory import plan_memory
    peak_before = plan_memory(input_program, vmem=False).peak_bytes
    cfg = ControlFlowGraph(input_program).analyze()
    pairs = cfg.reuse_pairs()
    input_program._memory_optimized = True
    if remat_types is True:
        input_program._remat = True
    else:
        # a later selective/disable call overrides an earlier global one
        input_program._remat = False
        input_program._remat_types = frozenset(
            () if remat_types is False
            else remat_types if remat_types is not None
            else DEFAULT_REMAT_TYPES)
    if print_log:
        for dead, reuse in pairs:
            print("memory_optimize: %s can reuse %s" % (reuse, dead))
        print("memory_optimize: %d reuse pairs (XLA buffer sharing), "
              "remat enabled" % len(pairs))
    # self-check: every program-to-program transform proves it left the
    # graph well-formed (cheap structural rules only — no deepcopy — so
    # this does not tax the training-setup path it runs on)
    from .analysis import check_after_pass
    check_after_pass(input_program, "memory_optimize")
    # ...and that a pass whose whole purpose is memory never INCREASED
    # the predicted peak — the regression the pre-planner code could
    # not see (today the pass only marks remat, so the peaks are equal;
    # this pins the contract for any future rewriting variant)
    peak_after = plan_memory(input_program, vmem=False).peak_bytes
    if peak_after > peak_before:
        raise RuntimeError(
            "memory_optimize INCREASED the predicted peak HBM: %d -> %d "
            "bytes — the pass violated its own contract"
            % (peak_before, peak_after))
    return pairs


def release_memory(input_program: ir.Program):
    """reference parity stub: early-delete pass. The executor's donated
    state buffers + XLA liveness already release eagerly."""
    input_program._memory_optimized = True
    return input_program
