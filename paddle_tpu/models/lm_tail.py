"""What the configuration-driven decoders (``latent_moe_lm``,
``window_moe_lm``, ``prerouted_moe_lm``, ``hybrid_ssm_moe_lm``) share around
their layers: the two keys of one chip's share, the embedding of the held
vocabulary rows, the final norm, the head over those rows and the loss; and
``pattern_blocks``, the layer loop of a model whose layers are of several
kinds placed by a published pattern.

``experts_held = [first, count]`` and ``vocab_held = [first, count]`` say
which experts and which vocabulary rows THIS program holds, as one chip of
an expert-parallel group does (absent: all of them). Token ids and labels
are ids of the whole vocabulary and have to lie in the held rows; the
logits and the loss are over those rows.
"""
from __future__ import annotations

from .. import layers as L
from ..param_attr import ParamAttr


def refuse(model, config, only):
    """Raise for a key of ``config`` whose value is not the one ``model``
    computes (``only``: {key: value}): nothing is ignored."""
    for key, value in only.items():
        if key in config and config[key] != value:
            raise NotImplementedError(
                "%s computes %s = %r only, the configuration says %r"
                % (model, key, value, config[key]))


def held(config, key, total):
    """(first, count) of ``experts_held`` / ``vocab_held``."""
    first, count = config.get(key) or (0, total)
    if first < 0 or count < 1 or first + count > total:
        raise ValueError("%s %r lies outside 0..%d"
                         % (key, config.get(key), total))
    return int(first), int(count)


def decoder_lm(tokens, labels, config, blocks, embed_scale=None):
    """``tokens`` [B, S] int64 -> a dict: ``logits`` [B, S, held rows],
    ``loads`` and ``rows_held`` (one variable per expert layer, see
    ``layers.moe_ffn``) and, with ``labels`` [B, S] (the next token),
    ``loss``: the mean cross entropy over all tokens, f32. ``blocks(x)``
    builds the layers on the stream ``x`` [B, S, hidden_size] (the held
    rows' embedding, times ``embed_scale`` where given) and returns (x,
    loads, rows_held). Parameters are named ``embed``, then the blocks'
    own, ``final_norm``, ``head``, in that order."""
    d, eps = config["hidden_size"], config["rms_norm_eps"]
    v_first, v_count = held(config, "vocab_held", config["vocab_size"])

    def local_ids(ids):
        if v_first == 0:
            return ids
        return L.elementwise_sub(ids, L.fill_constant(
            shape=[1], dtype="int32", value=v_first))

    seq = tokens.shape[1]
    x = L.embedding(L.reshape(local_ids(tokens), shape=[0, seq, 1]),
                    size=[v_count, d], param_attr=ParamAttr(name="embed"))
    if embed_scale is not None:
        x = L.scale(x, scale=float(embed_scale))
    x, loads, rows_held = blocks(x)
    h = L.rms_norm(x, epsilon=eps, param_attr=ParamAttr(name="final_norm"))
    logits = L.fc(h, size=v_count, num_flatten_dims=2, bias_attr=False,
                  param_attr=ParamAttr(name="head"))
    out = {"logits": logits, "loads": loads, "rows_held": rows_held}
    if labels is not None:
        flat = L.reshape(logits, shape=[-1, v_count])
        out["loss"] = L.mean(L.softmax_with_cross_entropy(
            flat, L.reshape(local_ids(labels), shape=[-1, 1])))
    return out


def pattern_blocks(model, pattern, parts):
    """``blocks(x)`` for ``decoder_lm`` whose layer i is the part that
    ``pattern[i]`` names. ``parts``: {letter: (prefix, build)}; the n-th
    layer of a letter gets the parameter prefix ``prefix % n`` (each kind
    counts its own layers, so the kinds never share a name), and
    ``build(x, prefix)`` returns the stream, or (stream, load, rows_held)
    for an expert layer (``layers.moe_ffn``), whose two counters the model
    returns in layer order. A letter with no part raises."""
    for i, letter in enumerate(pattern):
        if letter not in parts:
            raise NotImplementedError(
                "%s builds the layer kinds %s, the pattern's layer %d is %r"
                % (model, "".join(sorted(parts)), i, letter))

    def blocks(x):
        loads, rows_held, seen = [], [], dict.fromkeys(parts, 0)
        for letter in pattern:
            prefix, build = parts[letter]
            out = build(x, prefix % seen[letter])
            seen[letter] += 1
            if isinstance(out, tuple):
                x, load, rows = out
                loads.append(load)
                rows_held.append(rows)
            else:
                x = out
        return x, loads, rows_held
    return blocks
