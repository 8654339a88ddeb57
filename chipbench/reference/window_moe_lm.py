"""Plain reference: a decoder-only language model of grouped-query attention
layers, sliding-window and full mixed, with sparse experts (HF
``modeling_afmoe.py``; ``model_type: afmoe``), its loss, gradients and an
Adam step, in straightforward ``jax.numpy``. Imports nothing of the program
under test; the precisions, the gated FFN, the RMS norm and the gradient
sketch are ``reference/latent_moe_lm.py``'s own (loaded by path).

For x in R^{S x d} (one row), per layer, with N(u; w) = RMSNorm:

- attention: h = N(x; w_in); q = h W_q as H heads of D, k = h W_k and v = h
  W_v as Hkv heads of D; q, k each N(.; w_qn / w_kn) over the D of a head;
  on a ``sliding_attention`` layer ONLY, rotary positions on the whole head:
  the pair (i, i + D/2) turned by pos * theta^(-2i/D) (HF ``rotate_half``);
  q head j reads k/v head j // (H / Hkv); P = softmax(q k^T / sqrt(D)) over
  the keys j <= i, on a sliding layer only those with i - j <
  ``sliding_window``; u = x + N(concat(P v) * sigmoid(h W_g) W_o; w_post):
  the post norm is applied BEFORE the residual add.
- layer i < ``num_dense_layers``: y = u + N(gated(N(u; w_pre)); w_post),
  gated(t) = (silu(t W_gate) * (t W_up)) W_down.
- later layers: s = sigmoid(t W_r) over ALL experts, t = N(u; w_pre); the
  picks are the top-k of s + b (b fixed, no gradient; ties to the lower
  index); g = s[picks] / (sum + 1e-20) * route_scale; y = u + N(shared(t) +
  sum over the picks that fall on a HELD expert of g E(t); w_post). What
  the absent experts would add is left out BEFORE the norm, so a share is
  not a part of the whole layer; g stays normalised over all the picks.
- x_0 = E[token] * sqrt(d) (``mup_enabled``); logits = N(x; w_f) W_head
  over the held vocabulary rows; the loss is the mean cross entropy with
  the next token.

``experts_held`` / ``vocab_held`` = [first, count]: the share of one chip of
an expert-parallel group. Departures from a training recipe: no auxiliary
loss (``load_balance_coeff`` names one whose formula no key gives) and b is
not updated in the step.

``precision`` and ``fault`` as ``reference/latent_moe_lm.py`` has them; the
faults of THIS model are ``FAULTS``. Attention runs row by row, one k/v head
(its group of q heads) and one block of ``Q_BLOCK`` query rows at a time,
against all keys under a dense mask; every half-layer is rematerialised.
The two Adam moments live on the HOST and each leaf is updated by itself, so
that a float32 step at one dense + four expert layers of the published
widths and 8,192 tokens fits one 16 GB chip beside nothing else.
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


_base = _sibling("latent_moe_lm.py")
key_data, held, sketch, SKETCHES = (_base.key_data, _base.held, _base.sketch,
                                    _base.SKETCHES)
rms_norm, gated, expert_loads = (_base.rms_norm, _base.gated,
                                 _base.expert_loads)
_at, _mm, _norms, _HI = _base._at, _base._mm, _base._norms, _base._HI
INIT_STD, BIAS_STD = _base.INIT_STD, _base.BIAS_STD

FAULTS = ("window_ignored", "rotary_on_full_layers", "gate_left_out",
          "selection_without_bias", "weights_not_renormalised")
Q_BLOCK = 1024          # query rows whose [S] score rows are alive at once
_ATTN = 9               # leaves of an attention half-layer
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "intermediate_size", "moe_intermediate_size",
         "num_experts", "num_experts_per_tok", "num_shared_experts",
         "route_scale", "num_dense_layers", "num_hidden_layers",
         "layer_types", "sliding_window", "rms_norm_eps", "rope_theta",
         "vocab_size", "mup_enabled", "experts_held", "vocab_held")


def leaf_specs(config):
    """Trainable leaves in the order the network makes them:
    [(name, shape)]. A 1-D leaf is a norm scale."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    Hkv, D = config["num_key_value_heads"], config["head_dim"]
    E, w = config["num_experts"], config["moe_intermediate_size"]
    _f, n_held = held(config, "experts_held", E)
    _v, rows = held(config, "vocab_held", config["vocab_size"])
    specs = [("embed", (rows, d))]
    for i in range(config["num_hidden_layers"]):
        a = "L%d.attn." % i
        specs += [(a + "norm", (d,)), (a + "wq", (d, H * D)),
                  (a + "wk", (d, Hkv * D)), (a + "wv", (d, Hkv * D)),
                  (a + "q_norm", (D,)), (a + "k_norm", (D,)),
                  (a + "wg", (d, H * D)), (a + "wo", (H * D, d)),
                  (a + "post_norm", (d,))]
        f = "L%d.ffn." % i
        if i < config["num_dense_layers"]:
            m = config["intermediate_size"]
            specs += [(f + "norm", (d,)), (f + "gate", (d, m)),
                      (f + "up", (d, m)), (f + "down", (m, d))]
        else:
            sw = config["num_shared_experts"] * w
            specs += [(f + "norm", (d,)), (f + "router", (d, E)),
                      (f + "expert_gate", (n_held, d, w)),
                      (f + "expert_up", (n_held, d, w)),
                      (f + "expert_down", (n_held, w, d)),
                      (f + "shared_gate", (d, sw)),
                      (f + "shared_up", (d, sw)),
                      (f + "shared_down", (sw, d))]
        specs.append((f + "post_norm", (d,)))
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
    n_moe = config["num_hidden_layers"] - config["num_dense_layers"]
    biases = [BIAS_STD * jax.random.normal(
        jax.random.fold_in(key, 100000 + i), (config["num_experts"],),
        jnp.float32) for i in range(n_moe)]
    return leaves, biases


def init_leaves(key_words, config):
    """All trainable leaves from the seed in ONE jitted call, float32: norm
    scales 1 + 0.1 N, everything else N(0, INIT_STD^2)."""
    return _init(key_words, _frozen(config))[0]


def init_router_biases(key_words, config):
    """The selection bias b of each expert layer, N(0, BIAS_STD^2): fixed,
    not a leaf."""
    return _init(key_words, _frozen(config))[1]


# -- the layers --------------------------------------------------------------

def rotary(x, theta):
    """x [S, H, D]; the pair (i, i + D/2) of position s turned by
    s * theta^(-2i/D) (HF ``rotate_half``)."""
    S, _H, D = x.shape
    i = jnp.arange(D // 2, dtype=jnp.float32)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.power(jnp.float32(theta), -2.0 * i / D)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _kv_head_attention(q, k, v, scale, window, precision):
    """Dense masked softmax attention of one row and one k/v head: q [S, G,
    D] (the G query heads that read it), k, v [S, D] -> [S, G, D], a block
    of ``Q_BLOCK`` query rows against ALL keys at a time. Key j is seen by
    query i where j <= i and, with ``window``, i - j < window."""
    S, G, D = q.shape
    rows = Q_BLOCK if S % Q_BLOCK == 0 else S

    def block(args):
        qb, start = args
        s = jnp.einsum("qgd,kd->gqk", qb, k, precision=_HI) * scale
        i = start + jnp.arange(rows)[:, None]
        j = jnp.arange(S)[None, :]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        if precision != "f32":
            # the probabilities are an operand of the second product
            fwd, fmax, _b, _m = _base._FORMATS[precision]
            p = p + jax.lax.stop_gradient(_base._round(p, fwd, fmax) - p)
        return jnp.einsum("gqk,kd->qgd", p, v, precision=_HI)

    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(S // rows, rows, G, D),
                     jnp.arange(S // rows) * rows))
    return o.reshape(S, G, D)


def grouped_attention(x, leaves, config, sliding, precision, fault):
    """x [S, d] of one row -> x + N(attention)."""
    w_in, wq, wk, wv, w_qn, w_kn, wg, wo, w_post = leaves
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], config["rms_norm_eps"]
    S = x.shape[0]
    h = _at(rms_norm(x, w_in, eps), precision)
    q = _at(rms_norm(_mm(h, wq, precision).reshape(S, H, D), w_qn, eps),
            precision)
    k = _at(rms_norm(_mm(h, wk, precision).reshape(S, Hkv, D), w_kn, eps),
            precision)
    v = _at(_mm(h, wv, precision), precision).reshape(S, Hkv, D)
    if sliding or fault == "rotary_on_full_layers":
        q = _at(rotary(q, config["rope_theta"]), precision)
        k = _at(rotary(k, config["rope_theta"]), precision)
    window = config["sliding_window"] if sliding else None
    if fault == "window_ignored":
        window = None
    one = jax.checkpoint(functools.partial(
        _kv_head_attention, scale=D ** -0.5, window=window,
        precision=precision))
    o = jax.lax.map(lambda qkv: one(*qkv),
                    (q.reshape(S, Hkv, H // Hkv, D).transpose(1, 0, 2, 3),
                     k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = _at(o.transpose(1, 0, 2, 3).reshape(S, H * D), precision)
    if fault != "gate_left_out":
        o = _at(o * jax.nn.sigmoid(_mm(h, wg, precision)), precision)
    return _at(x + rms_norm(_mm(o, wo, precision), w_post, eps), precision)


def dense_ffn(x, leaves, config, precision):
    w_pre, w_gate, w_up, w_down, w_post = leaves
    eps = config["rms_norm_eps"]
    t = _at(rms_norm(x, w_pre, eps), precision)
    y = gated(t, w_gate, w_up, w_down, precision)
    return _at(x + rms_norm(y, w_post, eps), precision)


def route(t, w_router, bias, config, fault):
    """(picks [T, k], weights [T, k]); everything float32."""
    k = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(t, w_router, precision=_HI))
    chosen_by = s if fault == "selection_without_bias" else s + bias[None]
    # a stable descending sort: among equals the lower index comes first
    picks = jnp.argsort(-chosen_by, axis=-1, stable=True)[:, :k]
    g = jnp.take_along_axis(s, picks, axis=-1)
    if fault != "weights_not_renormalised":
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return picks, g * config["route_scale"]


def expert_ffn(x, leaves, bias, config, precision, fault):
    """x [T, d] -> (x + N(shared + the held experts' part), picks)."""
    (w_pre, w_r, e_gate, e_up, e_down, s_gate, s_up, s_down,
     w_post) = leaves
    first, _count = held(config, "experts_held", config["num_experts"])
    eps = config["rms_norm_eps"]
    t = _at(rms_norm(x, w_pre, eps), precision)
    picks, g = route(t, w_r, bias, config, fault)

    def one_expert(y, packed):
        e, wg, wu, wd = packed
        # this expert's weight on every token: g where it was picked, else 0
        share = jnp.sum(jnp.where(picks == e, g, 0.0), axis=-1)
        return y + _at(share[:, None] * gated(t, wg, wu, wd, precision),
                       precision), None

    ids = first + jnp.arange(e_gate.shape[0])
    routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                             (ids, e_gate, e_up, e_down))
    y = gated(t, s_gate, s_up, s_down, precision) + routed
    return _at(x + rms_norm(y, w_post, eps), precision), picks


def _layer_leaves(leaves, config):
    """[(attention leaves, ffn leaves)] per layer, then (final norm, head);
    the embedding is leaves[0]."""
    at, out = 1, []
    for i in range(config["num_hidden_layers"]):
        n = 5 if i < config["num_dense_layers"] else 9
        out.append((leaves[at:at + _ATTN],
                    leaves[at + _ATTN:at + _ATTN + n]))
        at += _ATTN + n
    return out, leaves[at:]


def forward(leaves, biases, tokens, config, precision="f32", fault=None):
    """tokens [B, S] (ids of the whole vocabulary, inside the held rows) ->
    (logits [B, S, held rows] float32, picks per expert layer [B*S, k])."""
    v_first, _rows = held(config, "vocab_held", config["vocab_size"])
    B, S = tokens.shape
    layers, (w_f, w_head) = _layer_leaves(leaves, config)
    x = leaves[0][tokens.astype(jnp.int32) - v_first]
    if config.get("mup_enabled"):
        x = x * jnp.sqrt(jnp.float32(config["hidden_size"]))
    x = _at(x, precision)
    all_picks = []
    for i, (attn, ffn) in enumerate(layers):
        att = jax.checkpoint(functools.partial(
            grouped_attention, config=config, precision=precision,
            sliding=config["layer_types"][i] == "sliding_attention",
            fault=fault))
        x = jax.lax.map(lambda row: att(row, attn), x)     # row by row
        if i < config["num_dense_layers"]:
            x = jax.checkpoint(functools.partial(
                dense_ffn, config=config, precision=precision))(x, ffn)
        else:
            moe = jax.checkpoint(functools.partial(
                expert_ffn, config=config, precision=precision, fault=fault))
            flat, picks = moe(x.reshape(B * S, -1), ffn,
                              biases[i - config["num_dense_layers"]])
            x = flat.reshape(B, S, -1)
            all_picks.append(picks)
    t = _at(rms_norm(x, w_f, config["rms_norm_eps"]), precision)
    return _at(_mm(t, w_head, precision), precision), all_picks


def loss_fn(leaves, biases, tokens, labels, config, precision="f32",
            fault=None):
    """Mean over all tokens of the cross entropy with ``labels`` (the next
    token), float32; also the picks per expert layer."""
    v_first, _rows = held(config, "vocab_held", config["vocab_size"])
    logits, picks = forward(leaves, biases, tokens, config, precision, fault)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = (labels.astype(jnp.int32) - v_first)[..., None]
    return -jnp.mean(jnp.take_along_axis(logp, want, axis=-1)), picks


@functools.partial(jax.jit, static_argnames=("frozen", "precision", "fault"))
def gradients(leaves, biases, tokens, labels, frozen, precision="f32",
              fault=None):
    """(loss, gradient per leaf, its norm per leaf, expert loads per
    layer) of one batch."""
    config = json.loads(frozen)
    (loss, picks), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        leaves, biases, tokens, labels, config, precision, fault)
    loads = [expert_loads(p, config["num_experts"]) for p in picks]
    return loss, grads, _norms(grads), loads


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_leaf(p, g, m1, m2, rate, beta1, beta2, eps):
    m1 = beta1 * m1 + (1.0 - beta1) * g
    m2 = beta2 * m2 + (1.0 - beta2) * g * g
    return p - rate * m1 / (jnp.sqrt(m2) + eps), m1, m2


def adam_update(leaves, grads, m1, m2, optimizer, t):
    """One Adam step with the bias correction folded into the rate, as the
    program's optimizer writes it: m1 = b1 m1 + (1 - b1) g; m2 = b2 m2 +
    (1 - b2) g^2; p = p - lr sqrt(1 - b2^t) / (1 - b1^t) m1 / (sqrt(m2) +
    eps), leaf by leaf. ``m1`` / ``m2``: lists of HOST arrays (None: zero),
    overwritten; ``leaves`` and ``grads`` are consumed. Returns the new
    leaves."""
    b1, b2 = optimizer["beta1"], optimizer["beta2"]
    rate = jnp.float32(optimizer["learning_rate"]
                       * np.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t))
    out = []
    for i in range(len(leaves)):
        p, g = leaves[i], grads[i]
        leaves[i] = grads[i] = None
        moments = [jnp.zeros_like(p) if m[i] is None else jnp.asarray(m[i])
                   for m in (m1, m2)]
        p, a, b = _adam_leaf(p, g, *moments, rate, jnp.float32(b1),
                             jnp.float32(b2),
                             jnp.float32(optimizer["epsilon"]))
        m1[i], m2[i] = np.asarray(a), np.asarray(b)
        out.append(p)
    return out


@jax.jit
def delta_norms(leaves, start):
    return _norms([a - b for a, b in zip(leaves, start)])


def follow(seed, batches, optimizer, config, precision="f32", rows=None,
           fault=None, keep_leaves=False):
    """Follow the first ``len(batches)`` steps from the seed's weights.
    ``batches``: [(tokens [B, S], labels [B, S])] host arrays; ``optimizer``:
    {"learning_rate", "beta1", "beta2", "epsilon"}; ``rows`` keeps the first
    ``rows`` of each batch (the planted fault "half of the batch left
    out"); of a batch of ONE row, whose half is ``rows`` = 0, it keeps the
    first half of the row's positions. Returns host values: ``losses`` per
    step; of the FIRST step ``grad_norms`` per leaf, ``grad_sketch``
    [leaves, SKETCHES] and ``loads`` [expert layers, experts];
    ``delta_norms`` per leaf of the parameters' change after all steps;
    with ``keep_leaves`` the ``leaves`` after them."""
    frozen = _frozen(config)
    words = key_data(seed)
    start, biases = _init(words, frozen)
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
            leaves, biases, jnp.asarray(tokens), jnp.asarray(labels),
            frozen=frozen, precision=precision, fault=fault)
        out["losses"].append(float(loss))
        if t == 1:
            out["grad_norms"] = np.asarray(gn, dtype=np.float64)
            out["grad_sketch"] = np.asarray(sketch(grads, words),
                                            dtype=np.float64)
            out["loads"] = np.asarray(jnp.stack(loads)) if loads \
                else np.zeros((0, config["num_experts"]), np.int32)
        leaves = adam_update(leaves, grads, m1, m2, optimizer, t)
        del grads
    out["delta_norms"] = np.asarray(delta_norms(leaves, start),
                                    dtype=np.float64)
    if keep_leaves:
        out["leaves"] = [np.asarray(l) for l in leaves]
    del leaves, m1, m2, start
    return out
