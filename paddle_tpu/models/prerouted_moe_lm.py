"""Decoder-only language model whose expert router sits BEFORE attention,
with grouped-query heads in window and global layers and sparse ReGLU
experts (SmallThinker's block), driven by a dict with the published
``config.json``'s own keys.

Every layer is ``u = x + W_o attn(h)``, ``h = N_attn(x)``, then ``y = u +
sum over the picks of g_k E_k(N_ffn(u))``, where the picks and their
weights come from ``softmax(h W_r)`` over all ``moe_num_primary_experts``:
the router reads the SAME normed input the attention reads, not the
expert half-layer's. The picks are the ``moe_num_active_primary_experts``
largest scores, their weights renormalised over the picks
(``norm_topk_prob``), with no bias and no scale; an expert is ``(relu(m
W_gate) * (m W_up)) W_down`` of ``moe_ffn_hidden_size`` (sparse ReGLU);
there is no shared expert and no dense layer. Layer i's attention sees
``sliding_window_size`` keys back where ``sliding_window_layout[i]`` is 1
and the whole causal triangle where it is 0; rotary positions (``rope_theta``,
the half-split layout on the whole head) apply where ``rope_layout[i]`` is
1. ``num_attention_heads`` q heads share ``num_key_value_heads`` k/v heads
of ``head_dim``; there are no QK norms, no output gate and no post norms.
The embedding is not scaled and the head is untied.

No key of the configuration defines a secondary expert, a hidden activation
or an auxiliary loss: only primary experts are built, ReLU gates them, no
auxiliary loss is applied. Two keys are this repo's, ``experts_held`` and
``vocab_held`` (one chip's share of an expert-parallel group:
``models/lm_tail.py``). A key whose value the model cannot honour raises:
nothing is ignored.
"""
from __future__ import annotations

from .. import layers as L
from ..core.ir import default_main_program
from .lm_tail import decoder_lm, held, refuse

# what the block is written for; another value is another block
_ONLY = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
         "rope_scaling": None, "tie_word_embeddings": False,
         "hidden_act": "relu"}
_LAYOUTS = ("sliding_window_layout", "rope_layout")


def check_config(config):
    """Raise for a value this block does not compute."""
    refuse("prerouted_moe_lm", config, _ONLY)
    n = config["num_hidden_layers"]
    for key in _LAYOUTS:
        if len(config[key]) != n:
            raise ValueError("%s names %d layers, num_hidden_layers is %d"
                             % (key, len(config[key]), n))
        for v in config[key]:
            if v not in (0, 1):
                raise NotImplementedError(
                    "prerouted_moe_lm computes %s entries of 0 and 1 only, "
                    "the configuration says %r" % (key, v))
    for i, (window, rope) in enumerate(zip(*(config[k] for k in _LAYOUTS))):
        if rope and not window:
            raise NotImplementedError(
                "prerouted_moe_lm puts rope_layout positions on window "
                "layers only, layer %d is global" % i)
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("num_attention_heads is no multiple of "
                         "num_key_value_heads")


def prerouted_moe_lm(tokens, config, labels=None):
    """``tokens`` [B, S] int64 -> ``lm_tail.decoder_lm``'s dict
    (``logits``, ``loads``, ``rows_held`` and, with ``labels``, ``loss``).
    Parameters are named ``embed``, ``L<i>.attn.*``, ``L<i>.ffn.*``,
    ``final_norm``, ``head``, in that order; ``L<i>.attn.norm`` is read by
    the attention and by the router."""
    check_config(config)
    eps = config["rms_norm_eps"]
    experts = held(config, "experts_held", config["moe_num_primary_experts"])
    block = default_main_program().global_block()

    def blocks(x):
        loads, rows_held = [], []
        layouts = zip(config["sliding_window_layout"], config["rope_layout"])
        for i, (window, rope) in enumerate(layouts):
            attn = "L%d.attn" % i
            y = L.grouped_attention(
                x, config["num_attention_heads"],
                config["num_key_value_heads"], config["head_dim"],
                window=config["sliding_window_size"] if window else None,
                rotary=bool(rope), theta=config["rope_theta"], epsilon=eps,
                qk_norm=False, gate=False, prefix=attn)
            y, load, rows = L.moe_ffn(
                y, config["moe_num_primary_experts"],
                config["moe_num_active_primary_experts"],
                config["moe_ffn_hidden_size"], 0, experts_held=experts,
                epsilon=eps, score="softmax", activation="relu",
                router_input=(x, block.var(attn + ".norm")),
                prefix="L%d.ffn" % i)
            x = y
            loads.append(load)
            rows_held.append(rows)
        return x, loads, rows_held

    return decoder_lm(tokens, labels, config, blocks)
