"""Decoder-only language model with latent attention and sparse experts:
the DeepSeek-V3 block (arXiv:2412.19437 section 2.1; HF ``model_type:
deepseek_v3``) without a query latent, driven by a dict with the published
``config.json``'s own keys.

Layer i < ``first_k_dense_replace`` is ``latent_attention`` + ``gated_ffn``
(width ``intermediate_size``); every later one ``latent_attention`` +
``moe_ffn``: a sigmoid router over ``n_routed_experts`` with a selection
bias (``noaux_tc``), ``num_experts_per_tok`` picks renormalised and scaled
by ``routed_scaling_factor``, experts of width ``moe_intermediate_size`` and
one shared gated FFN of ``n_shared_experts`` times that width. RMS norms,
interleaved rotary positions on the ``qk_rope_head_dim`` part of q and on
the one shared rotary key, an untied head.

Two keys are this repo's, ``experts_held`` and ``vocab_held`` (one chip's
share of an expert-parallel group: ``models/lm_tail.py``, which also holds
the embedding, the final norm, the head and the loss). A key whose value the
model cannot honour raises: nothing is ignored.
"""
from __future__ import annotations

from .. import layers as L
from .lm_tail import decoder_lm, held, refuse

# what the block is written for; another value is another block
_ONLY = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
         "scoring_func": "sigmoid", "rope_scaling": None,
         "topk_method": "noaux_tc", "norm_topk_prob": True,
         "hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False, "moe_layer_freq": 1,
         "rope_interleave": True}


def check_config(config):
    """Raise for a value this block does not compute."""
    refuse("latent_moe_lm", config, _ONLY)
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise NotImplementedError(
            "latent attention has one key and value head per query head")


def latent_moe_lm(tokens, config, labels=None):
    """``tokens`` [B, S] int64 -> ``lm_tail.decoder_lm``'s dict
    (``logits``, ``loads``, ``rows_held`` and, with ``labels``, ``loss``).
    Parameters are named ``embed``, ``L<i>.attn.*``, ``L<i>.ffn.*``,
    ``final_norm``, ``head``, in that order."""
    check_config(config)
    eps = config["rms_norm_eps"]
    experts = held(config, "experts_held", config["n_routed_experts"])

    def blocks(x):
        loads, rows_held = [], []
        for i in range(config["num_hidden_layers"]):
            x = L.latent_attention(
                x, config["num_attention_heads"], config["qk_nope_head_dim"],
                config["qk_rope_head_dim"], config["v_head_dim"],
                config["kv_lora_rank"], theta=config["rope_theta"],
                epsilon=eps, prefix="L%d.attn" % i)
            if i < config["first_k_dense_replace"]:
                x = L.gated_ffn(x, config["intermediate_size"], epsilon=eps,
                                prefix="L%d.ffn" % i)
                continue
            x, load, rows = L.moe_ffn(
                x, config["n_routed_experts"], config["num_experts_per_tok"],
                config["moe_intermediate_size"],
                config["n_shared_experts"] * config["moe_intermediate_size"],
                experts_held=experts,
                scaling=config["routed_scaling_factor"], epsilon=eps,
                prefix="L%d.ffn" % i)
            loads.append(load)
            rows_held.append(rows)
        return x, loads, rows_held

    return decoder_lm(tokens, labels, config, blocks)
