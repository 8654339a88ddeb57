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

Two keys are this repo's: ``experts_held = [first, count]`` and
``vocab_held = [first, count]`` say which experts and which vocabulary rows
THIS program holds, as one chip of an expert-parallel group does (absent:
all of them). Token ids and labels are ids of the whole vocabulary and
have to lie in the held rows; the logits and the loss are over those rows.
A key whose value the model cannot honour raises: nothing is ignored.
"""
from __future__ import annotations

from .. import layers as L
from ..param_attr import ParamAttr

# what the block is written for; another value is another block
_ONLY = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
         "scoring_func": "sigmoid", "rope_scaling": None,
         "topk_method": "noaux_tc", "norm_topk_prob": True,
         "hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False, "moe_layer_freq": 1,
         "rope_interleave": True}


def check_config(config):
    """Raise for a value this block does not compute."""
    for key, only in _ONLY.items():
        if key in config and config[key] != only:
            raise NotImplementedError(
                "latent_moe_lm computes %s = %r only, the configuration "
                "says %r" % (key, only, config[key]))
    if config["qk_head_dim"] != (config["qk_nope_head_dim"]
                                 + config["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + "
                         "qk_rope_head_dim")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise NotImplementedError(
            "latent attention has one key and value head per query head")


def held(config, key, total):
    """(first, count) of ``experts_held`` / ``vocab_held``."""
    first, count = config.get(key) or (0, total)
    if first < 0 or count < 1 or first + count > total:
        raise ValueError("%s %r lies outside 0..%d"
                         % (key, config.get(key), total))
    return int(first), int(count)


def latent_moe_lm(tokens, config, labels=None):
    """``tokens`` [B, S] int64 -> a dict: ``logits`` [B, S, held rows],
    ``loads`` and ``rows_held`` (one variable per expert layer, see
    ``layers.moe_ffn``) and, with ``labels`` [B, S] (the next token),
    ``loss``: the mean cross entropy over all tokens, f32. Parameters are
    named ``embed``, ``L<i>.attn.*``, ``L<i>.ffn.*``, ``final_norm``,
    ``head``, in that order."""
    check_config(config)
    d, eps = config["hidden_size"], config["rms_norm_eps"]
    v_first, v_count = held(config, "vocab_held", config["vocab_size"])
    experts = held(config, "experts_held", config["n_routed_experts"])

    def local_ids(ids):
        if v_first == 0:
            return ids
        return L.elementwise_sub(ids, L.fill_constant(
            shape=[1], dtype="int32", value=v_first))

    seq = tokens.shape[1]
    x = L.embedding(L.reshape(local_ids(tokens), shape=[0, seq, 1]),
                    size=[v_count, d], param_attr=ParamAttr(name="embed"))
    loads, rows_held = [], []
    for i in range(config["num_hidden_layers"]):
        x = L.latent_attention(
            x, config["num_attention_heads"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["kv_lora_rank"], theta=config["rope_theta"], epsilon=eps,
            prefix="L%d.attn" % i)
        if i < config["first_k_dense_replace"]:
            x = L.gated_ffn(x, config["intermediate_size"], epsilon=eps,
                            prefix="L%d.ffn" % i)
            continue
        x, load, rows = L.moe_ffn(
            x, config["n_routed_experts"], config["num_experts_per_tok"],
            config["moe_intermediate_size"],
            config["n_shared_experts"] * config["moe_intermediate_size"],
            experts_held=experts, scaling=config["routed_scaling_factor"],
            epsilon=eps, prefix="L%d.ffn" % i)
        loads.append(load)
        rows_held.append(rows)
    h = L.rms_norm(x, epsilon=eps, param_attr=ParamAttr(name="final_norm"))
    logits = L.fc(h, size=v_count, num_flatten_dims=2, bias_attr=False,
                  param_attr=ParamAttr(name="head"))
    out = {"logits": logits, "loads": loads, "rows_held": rows_held}
    if labels is not None:
        flat = L.reshape(logits, shape=[-1, v_count])
        out["loss"] = L.mean(L.softmax_with_cross_entropy(
            flat, L.reshape(local_ids(labels), shape=[-1, 1])))
    return out
