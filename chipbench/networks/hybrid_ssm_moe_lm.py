"""The decoder whose layers are Mamba-2 mixers, squared-ReLU expert layers
and grouped-query attention placed by a published pattern, of a training
configuration, built by the program's own
``paddle_tpu.models.hybrid_ssm_moe_lm`` (no stand-in), and the operations
and bytes its work needs, counted from shapes.

The counts are of what the MODEL needs, whatever a kernel visits:
forward + backward = 3 x forward, recomputation not counted. The state-space
scan's operations are a token's 2QNG + 2QPH + 4NPH a mixer at the chunk Q
(the chunk's masked C B^T, its product with x, the chunk's end state and
the carried state's read), and its bytes those of its operands in bf16.
"""
from paddle_tpu.models.hybrid_ssm_moe_lm import hybrid_ssm_moe_lm

# the half-layer ops the backward pass recomputes (memory_optimize)
RECOMPUTED = ("ssm_mixer", "grouped_attention", "moe_ffn")
_BF16 = 2


def model_config(config):
    """The configuration file's keys as the model reads them: the pattern
    of the layers this file keeps (``layers_kept`` indexes the published
    ``hybrid_override_pattern``), the share this chip holds (share 0 of the
    deployment) under the model's own two keys, and the published depth
    (``published_layers``: HF's ``rescale_prenorm_residual`` divides by
    it). Raises where the driver would look for a dense layer (none is),
    or where the kept layers are not ``num_hidden_layers``."""
    if config["first_k_dense_replace"] != 0:
        raise ValueError("first_k_dense_replace = %r: this model has no "
                         "dense layer" % (config["first_k_dense_replace"],))
    pattern, kept = config["hybrid_override_pattern"], config["layers_kept"]
    if len(kept) != config["num_hidden_layers"]:
        raise ValueError("layers_kept names %d layers, num_hidden_layers is "
                         "%d" % (len(kept), config["num_hidden_layers"]))
    return dict(
        config, hybrid_override_pattern="".join(pattern[i] for i in kept),
        published_layers=len(pattern),
        experts_held=[0, config.get("n_routed_experts_held",
                                    config["n_routed_experts"])],
        vocab_held=[0, config.get("vocab_size_held", config["vocab_size"])])


def build(layers, tokens, labels, config):
    return hybrid_ssm_moe_lm(tokens, model_config(config), labels=labels)


def _kinds(c):
    """{letter: layers of that kind} of the kept layers."""
    pattern = model_config(c)["hybrid_override_pattern"]
    return {k: pattern.count(k) for k in "ME*"}


def mixer_params(c):
    d, H = c["hidden_size"], c["mamba_num_heads"]
    inner = H * c["mamba_head_dim"]
    channels = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    return d * (inner + channels + H) + inner * d     # W_in, W_out


def attention_params(c):
    d, D = c["hidden_size"], c["head_dim"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * H * D + 2 * d * Hkv * D      # W_q, W_o; W_k, W_v


def expert_params_per_token(c):
    """Parameters of the router, the shared expert and the HELD experts
    that a token is multiplied with, in expectation: its k picks fall on
    the held experts with probability held / all."""
    d, E = c["hidden_size"], c["n_routed_experts"]
    held = c.get("n_routed_experts_held", E)
    shared = c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"]
    return (d * E + 2 * d * shared + c["num_experts_per_tok"] * held / E
            * 2 * d * c["moe_intermediate_size"])


def matmul_params_per_token(c):
    """Parameters a token is multiplied with in one forward pass (the
    embedding rows are looked up, not multiplied)."""
    n = _kinds(c)
    return (n["M"] * mixer_params(c) + n["E"] * expert_params_per_token(c)
            + n["*"] * attention_params(c)
            + c["hidden_size"] * c.get("vocab_size_held", c["vocab_size"]))


def scan_flops_per_token(c):
    """One mixer's scan, forward: 2QNG + 2QPH + 4NPH."""
    Q, N, G = c["chunk_size"], c["ssm_state_size"], c["n_groups"]
    P, H = c["mamba_head_dim"], c["mamba_num_heads"]
    return 2 * Q * N * G + 2 * Q * P * H + 4 * N * P * H


def scan_bytes_per_token(c):
    """One mixer's scan, forward + backward, bf16: x, B, C and the step in
    and y out; the backward reads x, B, C, the step and dy and writes dx,
    dB, dC and the step's gradient."""
    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    inputs = H * P + 2 * c["n_groups"] * c["ssm_state_size"] + H
    return _BF16 * (inputs + H * P + inputs + H * P + inputs)


def seen_pairs(seq):
    """(query, key) pairs of one causal row and one head."""
    return seq * (seq + 1) // 2


def attention_forward_flops_per_row(c, seq):
    """Attention's own products (q k^T and P v, both over head_dim) of one
    row, summed over the kept attention layers."""
    return (_kinds(c)["*"] * 2 * c["num_attention_heads"] * 2 * c["head_dim"]
            * seen_pairs(seq))


def train_flops_per_row(config, reference, seq=8192):
    """Forward + backward FLOPs of one row (a sequence of ``seq`` tokens):
    matmul parameters x 2 x 3 a token, attention's products and the
    mixers' scans; backward = 2 x forward, recompute not counted."""
    fwd = (2 * matmul_params_per_token(config) * seq
           + attention_forward_flops_per_row(config, seq)
           + _kinds(config)["M"] * scan_flops_per_token(config) * seq)
    return 3 * fwd


def attention_flops_per_step(config, rows, seq):
    """{"window", "full", "ssm_scan", "ssm_scan_bytes"}: the counts for the
    ``train.gqa_*`` readers (no window layer: 0) and the ``train.ssm_scan_*``
    ones (the driver's ``ctx`` has this one key for a network's counts)."""
    mixers = rows * seq * _kinds(config)["M"]
    return {"window": 0,
            "full": 3 * rows * attention_forward_flops_per_row(config, seq),
            "ssm_scan": 3 * mixers * scan_flops_per_token(config),
            "ssm_scan_bytes": mixers * scan_bytes_per_token(config)}


def expert_flops_per_step(config, held_pairs):
    """The grouped products over the held experts (up and down: two, no
    gate), forward + backward, of ``held_pairs`` (token, pick) pairs a
    step summed over the expert layers: what the steps' own ``RowsHeld``
    counted."""
    return (3 * 2 * 2 * config["hidden_size"]
            * config["moe_intermediate_size"] * held_pairs)
