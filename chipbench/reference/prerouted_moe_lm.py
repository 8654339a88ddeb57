"""Plain reference: a decoder-only language model whose expert router sits
BEFORE attention, with grouped-query heads in window and global layers and
sparse ReGLU experts (SmallThinker's block; its ``config.json`` keys), its
loss, gradients and an Adam step, in straightforward ``jax.numpy``. Imports
nothing of the program under test; the precisions, the RMS norm, the
gradient sketch, the half-split rotary, the blocked dense attention and the
Adam step are ``reference/latent_moe_lm.py``'s and
``reference/window_moe_lm.py``'s own (loaded by path).

For x in R^{S x d} (one row), per layer i, with N(u; w) = RMSNorm:

- h = N(x; w_attn); q = h W_q as H heads of D, k = h W_k and v = h W_v as
  Hkv heads of D (no biases, no QK norms); where ``rope_layout[i]`` is 1,
  rotary positions on the whole head: the pair (i, i + D/2) turned by pos
  * theta^(-2i/D) (HF ``rotate_half``); global layers carry no positions;
  q head j reads k/v head j // (H / Hkv); P = softmax(q k^T / sqrt(D)) over
  the keys j <= i, where ``sliding_window_layout[i]`` is 1 only those with
  i - j < ``sliding_window_size``; u = x + concat(P v) W_o.
- the router reads the SAME h: p = softmax(h W_r) over ALL experts, f32;
  the picks are the k largest of p (ties to the lower index); g = p[picks]
  / (sum + 1e-20) (``norm_topk_prob``), no bias, no scale.
- m = N(u; w_ffn); y = u + sum over the picks that fall on a HELD expert of
  g (relu(m W_gate) * (m W_up)) W_down. There is no shared expert.
- x_0 = E[token]; logits = N(x; w_f) W_head over the held vocabulary rows;
  the loss is the mean cross entropy with the next token.

``experts_held`` / ``vocab_held`` = [first, count]: the share of one chip of
an expert-parallel group. Departures from a training recipe: no auxiliary
loss (no key gives one), no secondary experts (no key defines one).

``precision`` and ``fault`` as ``reference/latent_moe_lm.py`` has them; the
faults of THIS model are ``FAULTS`` (``router_after_attention``: the router
reads N(u; w_ffn), the expert half-layer's own input). Attention runs one
k/v head (its group of q heads) and one block of query rows at a time,
against all keys under a dense mask; every half-layer is rematerialised;
Adam's two moments live on the HOST, so that a float32 step at four layers
of the published widths and 16,384 tokens fits one 16 GB chip.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_shared_" + name[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_window = _sibling("window_moe_lm.py")
_base = _window._base
key_data, held, sketch, SKETCHES = (_base.key_data, _base.held, _base.sketch,
                                    _base.SKETCHES)
rms_norm, expert_loads = _base.rms_norm, _base.expert_loads
_at, _mm, _norms, _HI = _base._at, _base._mm, _base._norms, _base._HI
INIT_STD = _base.INIT_STD
rotary, adam_update, delta_norms = (_window.rotary, _window.adam_update,
                                    _window.delta_norms)

FAULTS = ("window_ignored", "rotary_on_global_layers",
          "router_after_attention", "silu_for_relu",
          "weights_not_renormalised")
_ATTN = 5               # leaves of an attention half-layer
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "moe_ffn_hidden_size", "moe_num_primary_experts",
         "moe_num_active_primary_experts", "num_hidden_layers",
         "sliding_window_layout", "rope_layout", "sliding_window_size",
         "rms_norm_eps", "rope_theta", "vocab_size", "experts_held",
         "vocab_held")


def leaf_specs(config):
    """Trainable leaves in the order the network makes them:
    [(name, shape)]. A 1-D leaf is a norm scale."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    Hkv, D = config["num_key_value_heads"], config["head_dim"]
    E, w = config["moe_num_primary_experts"], config["moe_ffn_hidden_size"]
    _f, n_held = held(config, "experts_held", E)
    _v, rows = held(config, "vocab_held", config["vocab_size"])
    specs = [("embed", (rows, d))]
    for i in range(config["num_hidden_layers"]):
        a, f = "L%d.attn." % i, "L%d.ffn." % i
        specs += [(a + "norm", (d,)), (a + "wq", (d, H * D)),
                  (a + "wk", (d, Hkv * D)), (a + "wv", (d, Hkv * D)),
                  (a + "wo", (H * D, d)),
                  (f + "norm", (d,)), (f + "router", (d, E)),
                  (f + "expert_gate", (n_held, d, w)),
                  (f + "expert_up", (n_held, d, w)),
                  (f + "expert_down", (n_held, w, d))]
    return specs + [("final_norm", (d,)), ("head", (d, rows))]


def _frozen(config):
    """The configuration as a hashable static argument."""
    return json.dumps({k: config.get(k) for k in _KEYS}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _init(key_words, frozen):
    config = json.loads(frozen)
    key = jax.random.wrap_key_data(key_words)
    leaves = []
    for i, (_name, shape) in enumerate(leaf_specs(config)):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaves.append(1.0 + 0.1 * n if len(shape) == 1 else INIT_STD * n)
    return leaves


def init_leaves(key_words, config):
    """All trainable leaves from the seed in ONE jitted call, float32: norm
    scales 1 + 0.1 N, everything else N(0, INIT_STD^2)."""
    return _init(key_words, _frozen(config))


def init_router_biases(key_words, config):
    """No selection bias: the router's softmax picks by its scores alone."""
    return []


# -- the layers --------------------------------------------------------------

def attention(x, leaves, config, layer, precision, fault):
    """x [S, d] of one row -> (x + attention, h = N(x; w_attn)): h is what
    the router reads."""
    w_in, wq, wk, wv, wo = leaves
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], config["rms_norm_eps"]
    S = x.shape[0]
    h = _at(rms_norm(x, w_in, eps), precision)
    q = _at(_mm(h, wq, precision), precision).reshape(S, H, D)
    k = _at(_mm(h, wk, precision), precision).reshape(S, Hkv, D)
    v = _at(_mm(h, wv, precision), precision).reshape(S, Hkv, D)
    if config["rope_layout"][layer] or fault == "rotary_on_global_layers":
        q = _at(rotary(q, config["rope_theta"]), precision)
        k = _at(rotary(k, config["rope_theta"]), precision)
    window = config["sliding_window_size"] \
        if config["sliding_window_layout"][layer] else None
    if fault == "window_ignored":
        window = None
    one = jax.checkpoint(functools.partial(
        _window._kv_head_attention, scale=D ** -0.5, window=window,
        precision=precision))
    o = jax.lax.map(lambda qkv: one(*qkv),
                    (q.reshape(S, Hkv, H // Hkv, D).transpose(1, 0, 2, 3),
                     k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = _at(o.transpose(1, 0, 2, 3).reshape(S, H * D), precision)
    return _at(x + _mm(o, wo, precision), precision), h


def route(t, w_router, config, fault):
    """(picks [T, k], weights [T, k]) of the softmax router; float32."""
    k = config["moe_num_active_primary_experts"]
    p = jax.nn.softmax(jnp.matmul(t, w_router, precision=_HI), axis=-1)
    # a stable descending sort: among equals the lower index comes first
    picks = jnp.argsort(-p, axis=-1, stable=True)[:, :k]
    g = jnp.take_along_axis(p, picks, axis=-1)
    if fault != "weights_not_renormalised":
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return picks, g


def reglu(m, w_gate, w_up, w_down, precision, act):
    """(act(m W_gate) * (m W_up)) W_down, f32 accumulation."""
    a = _at(act(_mm(m, w_gate, precision)) * _mm(m, w_up, precision),
            precision)
    return _mm(a, w_down, precision)


def expert_ffn(u, h, leaves, config, precision, fault):
    """u [T, d] after attention, h [T, d] the attention's normed input ->
    (u + the held experts' part, picks)."""
    w_pre, w_r, e_gate, e_up, e_down = leaves
    first, _count = held(config, "experts_held",
                         config["moe_num_primary_experts"])
    m = _at(rms_norm(u, w_pre, config["rms_norm_eps"]), precision)
    picks, g = route(m if fault == "router_after_attention" else h, w_r,
                     config, fault)
    act = jax.nn.silu if fault == "silu_for_relu" else jax.nn.relu

    def one_expert(y, packed):
        e, wg, wu, wd = packed
        # this expert's weight on every token: g where it was picked, else 0
        share = jnp.sum(jnp.where(picks == e, g, 0.0), axis=-1)
        return y + _at(share[:, None] * reglu(m, wg, wu, wd, precision, act),
                       precision), None

    ids = first + jnp.arange(e_gate.shape[0])
    routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                             (ids, e_gate, e_up, e_down))
    return _at(u + routed, precision), picks


def forward(leaves, tokens, config, precision="f32", fault=None):
    """tokens [B, S] (ids of the whole vocabulary, inside the held rows) ->
    (logits [B, S, held rows] float32, picks per layer [B*S, k])."""
    v_first, _rows = held(config, "vocab_held", config["vocab_size"])
    B, S = tokens.shape
    per = 2 * _ATTN
    x = _at(leaves[0][tokens.astype(jnp.int32) - v_first], precision)
    all_picks = []
    for i in range(config["num_hidden_layers"]):
        attn = leaves[1 + i * per:1 + i * per + _ATTN]
        ffn = leaves[1 + i * per + _ATTN:1 + (i + 1) * per]
        att = jax.checkpoint(functools.partial(
            attention, config=config, layer=i, precision=precision,
            fault=fault))
        u, h = jax.lax.map(lambda row: att(row, attn), x)   # row by row
        moe = jax.checkpoint(functools.partial(
            expert_ffn, config=config, precision=precision, fault=fault))
        flat, picks = moe(u.reshape(B * S, -1), h.reshape(B * S, -1), ffn)
        x = flat.reshape(B, S, -1)
        all_picks.append(picks)
    w_f, w_head = leaves[-2:]
    t = _at(rms_norm(x, w_f, config["rms_norm_eps"]), precision)
    return _at(_mm(t, w_head, precision), precision), all_picks


def loss_fn(leaves, tokens, labels, config, precision="f32", fault=None):
    """Mean over all tokens of the cross entropy with ``labels`` (the next
    token), float32; also the picks per layer."""
    v_first, _rows = held(config, "vocab_held", config["vocab_size"])
    logits, picks = forward(leaves, tokens, config, precision, fault)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = (labels.astype(jnp.int32) - v_first)[..., None]
    return -jnp.mean(jnp.take_along_axis(logp, want, axis=-1)), picks


@functools.partial(jax.jit, static_argnames=("frozen", "precision", "fault"))
def gradients(leaves, tokens, labels, frozen, precision="f32", fault=None):
    """(loss, gradient per leaf, its norm per leaf, expert loads per
    layer) of one batch."""
    config = json.loads(frozen)
    (loss, picks), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        leaves, tokens, labels, config, precision, fault)
    loads = [expert_loads(p, config["moe_num_primary_experts"])
             for p in picks]
    return loss, grads, _norms(grads), loads


def follow(seed, batches, optimizer, config, precision="f32", rows=None,
           fault=None, keep_leaves=False):
    """Follow the first ``len(batches)`` steps from the seed's weights, as
    ``reference/window_moe_lm.py: follow`` does (``rows`` = 0 keeps the
    first half of a one-row batch's positions). Returns host values:
    ``losses``; of the FIRST step ``grad_norms``, ``grad_sketch`` and
    ``loads`` [layers, experts]; ``delta_norms`` after all steps; with
    ``keep_leaves`` the ``leaves``."""
    frozen = _frozen(config)
    words = key_data(seed)
    start = _init(words, frozen)
    leaves = [jnp.array(l) for l in start]          # consumed below
    m1, m2 = [None] * len(leaves), [None] * len(leaves)
    out = {"losses": []}
    for t, (tokens, labels) in enumerate(batches, 1):
        if rows == 0:
            half = tokens.shape[1] // 2
            tokens, labels = tokens[:, :half], labels[:, :half]
        elif rows is not None:
            tokens, labels = tokens[:rows], labels[:rows]
        loss, grads, gn, loads = gradients(
            leaves, jnp.asarray(tokens), jnp.asarray(labels), frozen=frozen,
            precision=precision, fault=fault)
        out["losses"].append(float(loss))
        if t == 1:
            out["grad_norms"] = np.asarray(gn, dtype=np.float64)
            out["grad_sketch"] = np.asarray(sketch(grads, words),
                                            dtype=np.float64)
            out["loads"] = np.asarray(jnp.stack(loads))
        leaves = adam_update(leaves, grads, m1, m2, optimizer, t)
        del grads
    out["delta_norms"] = np.asarray(delta_norms(leaves, start),
                                    dtype=np.float64)
    if keep_leaves:
        out["leaves"] = [np.asarray(l) for l in leaves]
    del leaves, m1, m2, start
    return out
