"""Importing this package registers every op lowering (the analog of the
reference's static registrars firing at library load,
paddle/fluid/framework/op_registry.h)."""
from . import (  # noqa: F401
    common,
    generic_grad,
    tensor_ops,
    math_ops,
    nn_ops,
    loss_ops,
    optimizer_ops,
    metric_ops,
    io_ops,
    sequence_ops,
    control_flow_ops,
    attention_ops,
    decoder_ops,
    detection_ops,
    misc_ops,
    channel_ops,
    selected_rows,
    explicit_grads,  # last: attaches custom grad makers to the ops above
)

from ..core.registry import registered_ops  # noqa: F401
