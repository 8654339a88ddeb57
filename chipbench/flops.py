"""Operations and bytes that the work needs, counted from shapes. These are
the benchmark's yardstick: whatever implements a step, its share of the peak
is this count over the measured time."""
from __future__ import annotations


def conv_flops(c_in, c_out, k, stride, pad, h_in, batch=1):
    """Multiply-adds x 2 of one forward convolution on square inputs."""
    h_out = (h_in + 2 * pad - k) // stride + 1
    return 2 * k * k * c_in * c_out * h_out * h_out * batch


def resnet_forward_flops(convs, fc, batch=1):
    """Forward FLOPs of the convolutions and the dense layer (batch norm,
    activations and pooling left out: under 1% of the total)."""
    return (sum(conv_flops(*c, batch=batch) for c in convs)
            + 2 * fc[0] * fc[1] * batch)


def resnet_train_flops(convs, fc, batch=1):
    """Forward + backward of one step: the backward pass is a data and a
    filter gradient per layer, twice the forward; recompute not counted."""
    return 3 * resnet_forward_flops(convs, fc, batch)


def transformer_matmul_params(hidden, layers, ffn, vocab):
    """Parameters that a token is multiplied with: per block q, k, v, o
    (4 h^2) and the two FFN matrices (2 h ffn), and the output head. The
    embedding rows are looked up, not multiplied."""
    return layers * (4 * hidden * hidden + 2 * hidden * ffn) + hidden * vocab


def transformer_token_flops(hidden, layers, ffn, vocab):
    """FLOPs per processed token without attention's own products: 2 per
    matmul parameter."""
    return 2 * transformer_matmul_params(hidden, layers, ffn, vocab)


def decode_step_floor_s(weight_bytes, live_tokens, layers, hidden,
                        kv_bytes, peak_bytes_s):
    """Least seconds for one decode step: every weight byte streamed once
    plus K and V of every live token of the running rows read once."""
    kv = live_tokens * layers * 2 * hidden * kv_bytes
    return (weight_bytes + kv) / peak_bytes_s
