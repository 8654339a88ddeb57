"""The decoder whose router sits before attention (window / global
grouped-query layers, softmax top-k ReGLU experts) of a training
configuration, built by the program's own
``paddle_tpu.models.prerouted_moe_lm`` (no stand-in), and the operations its
work needs, counted from shapes.

The counts are of what the MODEL needs, whatever a kernel visits: on a
window layer the (query, key) pairs of the band, on a global layer those of
the causal triangle; forward + backward = 3 x forward, recomputation not
counted.
"""
from paddle_tpu.models.prerouted_moe_lm import prerouted_moe_lm

# the half-layer ops the backward pass recomputes (memory_optimize)
RECOMPUTED = ("grouped_attention", "moe_ffn")
# (the driver's name, the published one) of a key the file carries twice
_TWICE = (("n_routed_experts", "moe_num_primary_experts"),
          ("num_experts_per_tok", "moe_num_active_primary_experts"))
_LAYOUTS = ("sliding_window_layout", "rope_layout")


def model_config(config):
    """The configuration file's keys as the model reads them: the two
    layouts of the layers this file keeps (``layers_kept`` indexes the
    published lists), and the share this chip holds (share 0 of the
    deployment) under the model's own two keys. Raises where a key the
    driver reads under its own name disagrees with the published one, or
    where the driver would look for a dense layer (none is)."""
    if config["first_k_dense_replace"] != 0:
        raise ValueError("first_k_dense_replace = %r: every layer of this "
                         "model is an expert layer"
                         % (config["first_k_dense_replace"],))
    for drivers, published in _TWICE:
        if config[drivers] != config[published]:
            raise ValueError("%s = %r is not %s = %r" % (
                drivers, config[drivers], published, config[published]))
    kept = config["layers_kept"]
    return dict(
        config, **{k: [config[k][i] for i in kept] for k in _LAYOUTS},
        experts_held=[0, config.get("n_routed_experts_held",
                                    config["moe_num_primary_experts"])],
        vocab_held=[0, config.get("vocab_size_held", config["vocab_size"])])


def build(layers, tokens, labels, config):
    return prerouted_moe_lm(tokens, model_config(config), labels=labels)


def attention_params(c):
    d, D = c["hidden_size"], c["head_dim"]
    H, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * H * D + 2 * d * Hkv * D      # W_q, W_o; W_k, W_v


def expert_params_per_token(c):
    """Parameters of HELD experts that a token is multiplied with, in
    expectation: its k picks fall on the held experts with probability
    held / all."""
    E = c["moe_num_primary_experts"]
    held = c.get("n_routed_experts_held", E)
    return (c["moe_num_active_primary_experts"] * held / E
            * 3 * c["hidden_size"] * c["moe_ffn_hidden_size"])


def matmul_params_per_token(c):
    """Parameters a token is multiplied with in one forward pass (the
    embedding rows are looked up, not multiplied): attention, the router
    and the held experts in every layer, and the head."""
    d = c["hidden_size"]
    layer = (attention_params(c) + d * c["moe_num_primary_experts"]
             + expert_params_per_token(c))
    return (c["num_hidden_layers"] * layer
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
    windows = [c["sliding_window_layout"][i] for i in c["layers_kept"]]
    per_pair = 2 * c["num_attention_heads"] * 2 * c["head_dim"]
    n_window = sum(windows)
    return {"window": n_window * per_pair
            * seen_pairs(seq, c["sliding_window_size"]),
            "full": (len(windows) - n_window) * per_pair * seen_pairs(seq)}


def train_flops_per_row(config, reference, seq=16384):
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
            * config["moe_ffn_hidden_size"] * held_pairs)
