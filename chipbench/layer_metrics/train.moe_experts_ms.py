"""Layer ``step``: device self time per step of the grouped products over
the held experts, forward and backward: the scopes
``forward/moe_ffn/experts`` and ``backward/moe_ffn/experts`` (the grouped
products' selects and the activation between them), and the grouped-matmul
kernels themselves BY NAME (``ragged-dot-*``): XLA:TPU makes them from
``jax.lax.ragged_dot`` in a rewrite that gives them an ``op_name`` of its
own, so the scope table calls them unscoped. Moves train_images_per_s."""
from chipbench import program_trace

SCOPES = ("forward/moe_ffn/experts", "backward/moe_ffn/experts")
KERNELS = "ragged-dot"


def read(ctx):
    view = program_trace.load(ctx)
    if not view or not view.get("scope_ns") or not ctx.get("steps"):
        return None
    ns = sum(v for k, v in view["scope_ns"].items() if k.startswith(SCOPES))
    ns += sum(v for k, v in view.get("unscoped_ns", {}).items()
              if k.startswith(KERNELS))
    return ns / ctx["steps"] / 1e6 or None
