"""The latent-attention / sparse-expert decoder of a training configuration,
built by the program's own ``paddle_tpu.models.latent_moe_lm`` (no stand-in),
and the operations its work needs, counted from shapes.

A network module of a language-model training configuration is
``build(layers, tokens, labels, config)`` -> the model's outputs (``loss``,
``loads``, ``rows_held``), ``train_flops_per_row(config, reference)``, and
the per-step counts of the kernels that have a roofline metric.
"""
from paddle_tpu.models.latent_moe_lm import latent_moe_lm

# the half-layer ops the backward pass recomputes (memory_optimize)
RECOMPUTED = ("latent_attention", "gated_ffn", "moe_ffn")


def model_config(config):
    """The configuration file's keys as the model reads them: the share
    this chip holds (share 0 of the deployment) under the model's own two
    keys."""
    out = {k: v for k, v in config.items()
           if k not in ("n_routed_experts_held", "vocab_size_held")}
    out["experts_held"] = [0, config.get("n_routed_experts_held",
                                         config["n_routed_experts"])]
    out["vocab_held"] = [0, config.get("vocab_size_held",
                                       config["vocab_size"])]
    return out


def build(layers, tokens, labels, config):
    return latent_moe_lm(tokens, model_config(config), labels=labels)


def attention_params(c):
    d, H = c["hidden_size"], c["num_attention_heads"]
    return (d * H * c["qk_head_dim"]
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + H * c["v_head_dim"] * d)


def expert_params_per_token(c):
    """Parameters of HELD experts that a token is multiplied with, in
    expectation: its k picks fall on the held experts with probability
    held / all."""
    held = c.get("n_routed_experts_held", c["n_routed_experts"])
    return (c["num_experts_per_tok"] * held / c["n_routed_experts"]
            * 3 * c["hidden_size"] * c["moe_intermediate_size"])


def matmul_params_per_token(c):
    """Parameters a token is multiplied with in one forward pass (the
    embedding rows are looked up, not multiplied)."""
    d = c["hidden_size"]
    dense = c["first_k_dense_replace"]
    sparse = c["num_hidden_layers"] - dense
    moe = (d * c["n_routed_experts"]
           + 3 * d * c["n_shared_experts"] * c["moe_intermediate_size"]
           + expert_params_per_token(c))
    return (c["num_hidden_layers"] * attention_params(c)
            + dense * 3 * d * c["intermediate_size"] + sparse * moe
            + d * c.get("vocab_size_held", c["vocab_size"]))


def attention_forward_flops_per_row(c, seq):
    """Causal attention's own products of one row in one layer: q k^T over
    qk_head_dim and P v over v_head_dim, half of the square."""
    return (2 * c["num_attention_heads"] * seq * seq / 2
            * (c["qk_head_dim"] + c["v_head_dim"]))


def train_flops_per_row(config, reference, seq=4096):
    """Forward + backward FLOPs of one row (a sequence of ``seq`` tokens):
    matmul parameters x 2 x 3 a token, plus causal attention's products;
    backward = 2 x forward, recompute not counted."""
    fwd = (2 * matmul_params_per_token(config) * seq
           + config["num_hidden_layers"]
           * attention_forward_flops_per_row(config, seq))
    return 3 * fwd


def attention_flops_per_step(config, rows, seq):
    return (3 * rows * config["num_hidden_layers"]
            * attention_forward_flops_per_row(config, seq))


def expert_flops_per_step(config, held_pairs):
    """The grouped products over the held experts, forward + backward, of
    ``held_pairs`` (token, pick) pairs a step summed over the expert layers:
    what the steps' own ``RowsHeld`` counted, not the expected rows x seq x
    k x held / all a layer (an uneven router holds 0.65-1.3 of that)."""
    return (3 * 2 * 3 * config["hidden_size"]
            * config["moe_intermediate_size"] * held_pairs)
