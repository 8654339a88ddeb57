"""The window / full grouped-query decoder with sparse experts of a training
configuration, built by the program's own ``paddle_tpu.models.window_moe_lm``
(no stand-in), and the operations its work needs, counted from shapes.

The counts are of what the MODEL needs, whatever a kernel visits: on a
sliding layer the (query, key) pairs of the band, on a full layer those of
the causal triangle; forward + backward = 3 x forward, recomputation not
counted.
"""
from paddle_tpu.models.window_moe_lm import window_moe_lm

# the half-layer ops the backward pass recomputes (memory_optimize)
RECOMPUTED = ("grouped_attention", "gated_ffn", "moe_ffn")
# (the driver's name, the published one) of a key the file carries twice
_TWICE = (("first_k_dense_replace", "num_dense_layers"),
          ("n_routed_experts", "num_experts"))


def model_config(config):
    """The configuration file's keys as the model reads them (it takes the
    published ones by name and leaves the benchmark's and the driver's
    alone): ``layer_types`` of the layers this file keeps (``layers_kept``
    indexes the published list), and the share this chip holds (share 0 of
    the deployment) under the model's own two keys. Raises where a key the
    driver reads under its own name disagrees with the published one."""
    for drivers, published in _TWICE:
        if config[drivers] != config[published]:
            raise ValueError("%s = %r is not %s = %r" % (
                drivers, config[drivers], published, config[published]))
    return dict(
        config,
        layer_types=[config["layer_types"][i] for i in config["layers_kept"]],
        experts_held=[0, config.get("n_routed_experts_held",
                                    config["num_experts"])],
        vocab_held=[0, config.get("vocab_size_held", config["vocab_size"])])


def build(layers, tokens, labels, config):
    return window_moe_lm(tokens, model_config(config), labels=labels)


def attention_params(c):
    d, D = c["hidden_size"], c["head_dim"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return 3 * d * H * D + 2 * d * Hkv * D      # W_q, W_g, W_o; W_k, W_v


def expert_params_per_token(c):
    """Parameters of HELD experts that a token is multiplied with, in
    expectation: its k picks fall on the held experts with probability
    held / all."""
    held = c.get("n_routed_experts_held", c["num_experts"])
    return (c["num_experts_per_tok"] * held / c["num_experts"]
            * 3 * c["hidden_size"] * c["moe_intermediate_size"])


def matmul_params_per_token(c):
    """Parameters a token is multiplied with in one forward pass (the
    embedding rows are looked up, not multiplied)."""
    d = c["hidden_size"]
    dense = c["num_dense_layers"]
    sparse = c["num_hidden_layers"] - dense
    moe = (d * c["num_experts"]
           + 3 * d * c["num_shared_experts"] * c["moe_intermediate_size"]
           + expert_params_per_token(c))
    return (c["num_hidden_layers"] * attention_params(c)
            + dense * 3 * d * c["intermediate_size"] + sparse * moe
            + d * c.get("vocab_size_held", c["vocab_size"]))


def seen_pairs(seq, window=None):
    """(query, key) pairs of one row and one head: key j <= query i and,
    under a window, i - j < window."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_forward_flops_per_row(c, seq):
    """{"window", "full"}: attention's own products (q k^T and P v, both
    over head_dim) of one row, summed over the kept layers of each kind."""
    kinds = [c["layer_types"][i] for i in c["layers_kept"]]
    per_pair = 2 * c["num_attention_heads"] * 2 * c["head_dim"]
    n_window = kinds.count("sliding_attention")
    return {"window": n_window * per_pair
            * seen_pairs(seq, c["sliding_window"]),
            "full": (len(kinds) - n_window) * per_pair * seen_pairs(seq)}


def train_flops_per_row(config, reference, seq=8192):
    """Forward + backward FLOPs of one row (a sequence of ``seq`` tokens):
    matmul parameters x 2 x 3 a token, plus attention's products;
    backward = 2 x forward, recompute not counted."""
    fwd = (2 * matmul_params_per_token(config) * seq
           + sum(attention_forward_flops_per_row(config, seq).values()))
    return 3 * fwd


def attention_flops_per_step(config, rows, seq):
    """{"window", "full"}: the two counts, for the ``train.gqa_*`` readers
    (the driver's ``ctx`` has this one key for a network's attention)."""
    return {kind: 3 * rows * flops for kind, flops in
            attention_forward_flops_per_row(config, seq).items()}


def expert_flops_per_step(config, held_pairs):
    """The grouped products over the held experts, forward + backward, of
    ``held_pairs`` (token, pick) pairs a step summed over the expert layers:
    what the steps' own ``RowsHeld`` counted."""
    return (3 * 2 * 3 * config["hidden_size"]
            * config["moe_intermediate_size"] * held_pairs)
