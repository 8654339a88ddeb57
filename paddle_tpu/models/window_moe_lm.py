"""Decoder-only language model with grouped-query attention that mixes
sliding-window and full layers, and sparse experts (HF ``model_type:
afmoe``, ``modeling_afmoe.py``), driven by a dict with the published
``config.json``'s own keys.

Every layer is ``u = x + N(attn(N(x)))`` then ``y = u + N(f(N(u)))``: four
RMS norms, the second of each pair applied BEFORE the residual add.
``layer_types[i]`` says whether layer i's attention is
``"sliding_attention"`` (``sliding_window`` keys back, itself included, and
rotary positions on the whole head in the half-split layout) or
``"full_attention"`` (the whole causal triangle and NO positions);
``num_attention_heads`` query heads share ``num_key_value_heads`` key and
value heads of ``head_dim``, q and k are RMS-normed per head and the heads'
output is gated by ``sigmoid(h W_g)``. Layer i < ``num_dense_layers`` has a
gated FFN of ``intermediate_size``; every later one ``moe_ffn``: a sigmoid
router over ``num_experts`` with a selection bias, ``num_experts_per_tok``
picks renormalised (``route_norm``) and scaled by ``route_scale``, experts
of ``moe_intermediate_size`` and one shared gated FFN of
``num_shared_experts`` times that width. The embedding is scaled by
``sqrt(hidden_size)`` where ``mup_enabled``; the head is untied.

``load_balance_coeff`` names an auxiliary loss whose formula no key gives:
none is applied. Two keys are this repo's, ``experts_held`` and
``vocab_held`` (one chip's share of an expert-parallel group:
``models/lm_tail.py``). A key whose value the model cannot honour raises:
nothing is ignored.
"""
from __future__ import annotations

from .. import layers as L
from .lm_tail import decoder_lm, held, refuse

# what the block is written for; another value is another block
_ONLY = {"score_func": "sigmoid", "n_group": 1, "topk_group": 1,
         "num_expert_groups": 1, "num_limited_groups": 1,
         "rope_scaling": None, "tie_word_embeddings": False,
         "hidden_act": "silu", "route_norm": True}
_LAYER_TYPES = ("sliding_attention", "full_attention")


def check_config(config):
    """Raise for a value this block does not compute."""
    refuse("window_moe_lm", config, _ONLY)
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers, num_hidden_layers "
                         "is %d" % (len(types), config["num_hidden_layers"]))
    for t in types:
        if t not in _LAYER_TYPES:
            raise NotImplementedError(
                "window_moe_lm computes layer_types of %r only, the "
                "configuration says %r" % (_LAYER_TYPES, t))
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("num_attention_heads is no multiple of "
                         "num_key_value_heads")


def window_moe_lm(tokens, config, labels=None):
    """``tokens`` [B, S] int64 -> ``lm_tail.decoder_lm``'s dict
    (``logits``, ``loads``, ``rows_held`` and, with ``labels``, ``loss``).
    Parameters are named ``embed``, ``L<i>.attn.*``, ``L<i>.ffn.*``,
    ``final_norm``, ``head``, in that order."""
    check_config(config)
    eps = config["rms_norm_eps"]
    experts = held(config, "experts_held", config["num_experts"])

    def blocks(x):
        loads, rows_held = [], []
        for i, kind in enumerate(config["layer_types"]):
            sliding = kind == "sliding_attention"
            x = L.grouped_attention(
                x, config["num_attention_heads"],
                config["num_key_value_heads"], config["head_dim"],
                window=config["sliding_window"] if sliding else None,
                rotary=sliding, theta=config["rope_theta"], epsilon=eps,
                post_norm=True, prefix="L%d.attn" % i)
            if i < config["num_dense_layers"]:
                x = L.gated_ffn(x, config["intermediate_size"], epsilon=eps,
                                post_norm=True, prefix="L%d.ffn" % i)
                continue
            x, load, rows = L.moe_ffn(
                x, config["num_experts"], config["num_experts_per_tok"],
                config["moe_intermediate_size"],
                config["num_shared_experts"]
                * config["moe_intermediate_size"],
                experts_held=experts, scaling=config["route_scale"],
                epsilon=eps, post_norm=True, prefix="L%d.ffn" % i)
            loads.append(load)
            rows_held.append(rows)
        return x, loads, rows_held

    scale = config["hidden_size"] ** 0.5 if config.get("mup_enabled") \
        else None
    return decoder_lm(tokens, labels, config, blocks, embed_scale=scale)
