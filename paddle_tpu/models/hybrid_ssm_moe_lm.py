"""Decoder-only language model whose layers are of three kinds placed by a
published pattern: Mamba-2 mixers, sparse squared-ReLU expert layers with
a shared expert, and grouped-query attention without positions (the
Nemotron-H block), driven by a dict with the published ``config.json``'s
own keys.

Layer i is ``x + F_i(N_i(x))``, one half-layer, where
``hybrid_override_pattern[i]`` says which F_i is:

- ``M``: a Mamba-2 mixer (``layers.ssm_mixer``): ``mamba_num_heads`` heads
  of ``mamba_head_dim``, ``n_groups`` B/C groups of ``ssm_state_size``, a
  causal conv of ``conv_kernel`` taps with a bias, the scan in chunks of
  ``chunk_size``, the gated RMS norm over each group's channels;
- ``E``: an expert layer (``layers.moe_ffn(activation="relu2")``): p =
  sigmoid(h W_r) over all ``n_routed_experts``, the picks the
  ``num_experts_per_tok`` largest of p + b (b a fixed selection bias), the
  weights p renormalised over the picks times ``routed_scaling_factor``,
  experts ``relu(h U)^2 V`` of ``moe_intermediate_size`` and
  ``n_shared_experts`` shared experts of
  ``moe_shared_expert_intermediate_size`` alike;
- ``*``: causal attention, ``num_attention_heads`` q heads on
  ``num_key_value_heads`` k/v heads of ``head_dim``, no positions, no QK
  norms, no gate.

Norms are RMS norms of ``layer_norm_epsilon`` with a learned scale; the
embedding is not scaled and the head is untied. The layer loop is
``lm_tail.pattern_blocks``; two keys are this repo's, ``experts_held``
and ``vocab_held`` (one chip's share of an expert-parallel group:
``models/lm_tail.py``). A key whose value the model cannot honour raises:
nothing is ignored. No key gives an auxiliary loss: none is applied.
"""
from __future__ import annotations

import math

from .. import layers as L
from .lm_tail import decoder_lm, held, pattern_blocks, refuse

# what the block is written for; another value is another block
_ONLY = {"n_group": 1, "topk_group": 1, "norm_topk_prob": True,
         "tie_word_embeddings": False, "mlp_hidden_act": "relu2",
         "mamba_hidden_act": "silu", "sliding_window": None,
         "residual_in_fp32": False, "use_conv_bias": True,
         "mamba_proj_bias": False, "attention_bias": False,
         "mlp_bias": False, "use_bias": False}
_MODEL = "hybrid_ssm_moe_lm"


def check_config(config):
    """Raise for a value this block does not compute."""
    refuse(_MODEL, config, _ONLY)
    limit = config.get("time_step_limit")
    if limit is not None and (float(limit[0]) != 0.0
                              or not math.isinf(float(limit[1]))):
        raise NotImplementedError(
            "%s computes time_step_limit = (0, inf) only, the configuration "
            "says %r" % (_MODEL, limit))
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern names %d layers, "
                         "num_hidden_layers is %d"
                         % (len(pattern), config["num_hidden_layers"]))
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("num_attention_heads is no multiple of "
                         "num_key_value_heads")


def hybrid_ssm_moe_lm(tokens, config, labels=None):
    """``tokens`` [B, S] int64 -> ``lm_tail.decoder_lm``'s dict
    (``logits``, ``loads``, ``rows_held`` and, with ``labels``, ``loss``).
    Parameters are named ``embed``, then per layer in pattern order
    ``M<n>.mixer.*``, ``L<n>.ffn.*`` or ``A<n>.attn.*`` (n counting the
    layers of that kind), then ``final_norm``, ``head``."""
    check_config(config)
    eps = config["layer_norm_epsilon"]
    experts = held(config, "experts_held", config["n_routed_experts"])

    def mixer(x, prefix):
        return L.ssm_mixer(
            x, config["mamba_num_heads"], config["mamba_head_dim"],
            config["n_groups"], config["ssm_state_size"],
            conv_kernel=config["conv_kernel"],
            chunk_size=config["chunk_size"], epsilon=eps, prefix=prefix)

    def experts_layer(x, prefix):
        return L.moe_ffn(
            x, config["n_routed_experts"], config["num_experts_per_tok"],
            config["moe_intermediate_size"],
            config["n_shared_experts"]
            * config["moe_shared_expert_intermediate_size"],
            experts_held=experts, scaling=config["routed_scaling_factor"],
            epsilon=eps, score="sigmoid", activation="relu2", prefix=prefix)

    def attention(x, prefix):
        return L.grouped_attention(
            x, config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], rotary=False, epsilon=eps, qk_norm=False,
            gate=False, prefix=prefix)

    blocks = pattern_blocks(_MODEL, config["hybrid_override_pattern"], {
        "M": ("M%d.mixer", mixer), "E": ("L%d.ffn", experts_layer),
        "*": ("A%d.attn", attention)})
    return decoder_lm(tokens, labels, dict(config, rms_norm_eps=eps), blocks)
