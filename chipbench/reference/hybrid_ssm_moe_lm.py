"""Plain reference: a decoder-only language model whose layers are Mamba-2
mixers, squared-ReLU expert layers with a shared expert, or grouped-query
attention without positions, placed by a published pattern (the
Nemotron-H block; its ``config.json`` keys), its loss, gradients and an
Adam step, in straightforward ``jax.numpy``. Imports nothing of the
program under test; the precisions, the RMS norm, the gradient sketch, the
blocked dense attention and the Adam step are
``reference/latent_moe_lm.py``'s and ``reference/window_moe_lm.py``'s own
(loaded by path).

For x in R^{S x d} (one row) and N(u; w) = RMSNorm(u) w, layer i is x <- x
+ F(N(x; w_i)), F by ``hybrid_override_pattern[i]``:

- ``M`` (Mamba-2, as HF ``modeling_mamba2.py`` / ``modeling_zamba2.py``
  compute it): [z | xBC | dt] = h W_in; xBC = silu(b_c + sum_j w_c[j]
  xBC[t - K + 1 + j]), zeros before position 0; x, B, C = split(xBC) (x
  heads of P, B and C G groups of N; head h reads group h // (H / G));
  delta = softplus(dt + dt_bias), a = -exp(a_log); the state of head h
  follows THE TOKEN RECURRENCE s[t] = exp(delta[t] a) s[t-1] + delta[t]
  x[t] B[t]^T from s[-1] = 0, one position at a time (no chunked algebra:
  ``lax.scan`` over positions, rematerialised every ``chunk_size``
  positions); y[t] = s[t] C[t] + D x[t]; u = y * silu(z); each group of
  H P / G channels divided by its own RMS and scaled by w_g; F = u W_out.
- ``E``: p = sigmoid(h W_r) over ALL experts, f32; the picks are the k
  largest of p + b (b fixed, no gradient; ties to the lower index); g =
  p[picks] / sum(p[picks]) x ``routed_scaling_factor``; F = sum over the
  picks that fall on a HELD expert of g relu(h U_e)^2 V_e + relu(h U_s)^2
  V_s (the shared expert), the held experts computed densely.
- ``*``: q = h W_q as H heads of D, k = h W_k and v = h W_v as Hkv heads
  (q head j reads k/v head j // (H / Hkv)); causal softmax(q k^T / sqrt(D))
  v, no positions; F = concat(o) W_o.
- x_0 = E[token]; logits = N(x; w_f) W_head over the held vocabulary rows;
  the loss is the mean cross entropy with the next token.

``experts_held`` / ``vocab_held`` = [first, count]: the share of one chip
of an expert-parallel group. ``precision`` and ``fault`` as
``reference/latent_moe_lm.py`` has them; the faults of THIS model are
``FAULTS``. Every half-layer is rematerialised; Adam's two moments live on
the HOST.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import math
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
INIT_STD, BIAS_STD = _base.INIT_STD, _base.BIAS_STD
adam_update, delta_norms = _window.adam_update, _window.delta_norms

FAULTS = ("state_not_carried", "norm_before_gate", "norm_over_all_channels",
          "relu_for_relu2", "no_routed_scaling", "selection_without_bias")
_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
         "ssm_state_size", "conv_kernel", "chunk_size",
         "moe_intermediate_size", "moe_shared_expert_intermediate_size",
         "n_shared_experts", "n_routed_experts", "num_experts_per_tok",
         "routed_scaling_factor", "num_hidden_layers",
         "hybrid_override_pattern", "layer_norm_epsilon", "vocab_size",
         "time_step_min", "time_step_max", "time_step_floor",
         "rescale_prenorm_residual", "published_layers", "experts_held",
         "vocab_held")


def _widths(config):
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def _layer_specs(config, letter, prefix):
    d = config["hidden_size"]
    if letter == "M":
        H, _P, _G, _N, inner, conv = _widths(config)
        return [(prefix + "norm", (d,)), (prefix + "w_in",
                                          (d, inner + conv + H)),
                (prefix + "conv_w", (config["conv_kernel"], conv)),
                (prefix + "conv_b", (conv,)), (prefix + "dt_bias", (H,)),
                (prefix + "a_log", (H,)), (prefix + "d", (H,)),
                (prefix + "gate_norm", (inner,)),
                (prefix + "w_out", (inner, d))]
    if letter == "E":
        E, w = config["n_routed_experts"], config["moe_intermediate_size"]
        sw = (config["n_shared_experts"]
              * config["moe_shared_expert_intermediate_size"])
        _f, n_held = held(config, "experts_held", E)
        return [(prefix + "norm", (d,)), (prefix + "router", (d, E)),
                (prefix + "expert_up", (n_held, d, w)),
                (prefix + "expert_down", (n_held, w, d)),
                (prefix + "shared_up", (d, sw)),
                (prefix + "shared_down", (sw, d))]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    return [(prefix + "norm", (d,)), (prefix + "wq", (d, H * D)),
            (prefix + "wk", (d, Hkv * D)), (prefix + "wv", (d, Hkv * D)),
            (prefix + "wo", (H * D, d))]


_PREFIX = {"M": "M%d.mixer.", "E": "L%d.ffn.", "*": "A%d.attn."}


def _layers(config):
    """[(letter, prefix)] in pattern order, each kind counting its own."""
    seen, out = {}, []
    for letter in config["hybrid_override_pattern"]:
        out.append((letter, _PREFIX[letter] % seen.get(letter, 0)))
        seen[letter] = seen.get(letter, 0) + 1
    return out


def leaf_specs(config):
    """Trainable leaves in the order the network makes them:
    [(name, shape)]."""
    d = config["hidden_size"]
    _v, rows = held(config, "vocab_held", config["vocab_size"])
    specs = [("embed", (rows, d))]
    for letter, prefix in _layers(config):
        specs += _layer_specs(config, letter, prefix)
    return specs + [("final_norm", (d,)), ("head", (d, rows))]


def _frozen(config):
    """The configuration as a hashable static argument."""
    return json.dumps({k: config.get(k) for k in _KEYS}, sort_keys=True)


# the out-projections that HF's rescale_prenorm_residual divides by
# sqrt(layers): one residual a layer
_OUT = ("w_out", "wo", "expert_down", "shared_down")


def _leaf(key, name, shape, config):
    part = name.rsplit(".", 1)[-1]
    if part in ("norm", "gate_norm") or name == "final_norm":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if part == "a_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if part == "d":
        return jnp.ones(shape, jnp.float32)
    if part in ("conv_w", "conv_b"):
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    if part == "dt_bias":
        lo, hi = (math.log(config[k]) for k in ("time_step_min",
                                                 "time_step_max"))
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, config["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1
    w = INIT_STD * jax.random.normal(key, shape, jnp.float32)
    if part in _OUT and config.get("rescale_prenorm_residual"):
        w = w / math.sqrt(config.get("published_layers")
                          or config["num_hidden_layers"])
    return w


@functools.partial(jax.jit, static_argnames=("frozen",))
def _init(key_words, frozen):
    config = json.loads(frozen)
    key = jax.random.wrap_key_data(key_words)
    leaves = [_leaf(jax.random.fold_in(key, i), name, shape, config)
              for i, (name, shape) in enumerate(leaf_specs(config))]
    n_moe = config["hybrid_override_pattern"].count("E")
    biases = [BIAS_STD * jax.random.normal(
        jax.random.fold_in(key, 100000 + i),
        (config["n_routed_experts"],), jnp.float32) for i in range(n_moe)]
    return leaves, biases


def init_leaves(key_words, config):
    """All trainable leaves from the seed in ONE jitted call, float32: HF
    ``_init_weights`` where a published key names it (a_log = log(1..H);
    dt_bias = softplus^-1 of a step log-uniform in [time_step_min,
    time_step_max] floored at time_step_floor; D = 1; the conv U(-0.5,
    0.5); the out-projections / sqrt(published layers)), norm scales 1 +
    0.1 N, everything else N(0, INIT_STD^2)."""
    return _init(key_words, _frozen(config))[0]


def init_router_biases(key_words, config):
    """The selection bias b of each expert layer, N(0, BIAS_STD^2): fixed,
    not a leaf."""
    return _init(key_words, _frozen(config))[1]


# -- the layers --------------------------------------------------------------

def grouped_norm(u, w, groups, eps):
    """Each of ``groups`` groups of u's last axis divided by its own RMS,
    then scaled by w."""
    S, C = u.shape
    g = u.reshape(S, groups, C // groups)
    g = g / jnp.sqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(S, C) * w


def recurrence(x, delta, a, b, c, chunk, carried=True):
    """y [S, H, P] of the token recurrence (module docstring): x [S, H, P],
    delta [S, H], a [H], b / c [S, G, N]; a ``lax.scan`` over positions,
    rematerialised every ``chunk`` positions; with ``carried`` False each
    chunk starts from the zero state."""
    S, H, P = x.shape
    G, N = b.shape[1:]
    rep = lambda v: jnp.repeat(v, H // G, axis=0)          # [H, N]

    def step(s, t):
        xt, dt, bt, ct = t
        s = (jnp.exp(dt * a)[:, None, None] * s
             + (dt[:, None] * xt)[:, :, None] * rep(bt)[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, rep(ct), precision=_HI)

    @jax.checkpoint
    def block(s, t):
        if not carried:
            s = jnp.zeros_like(s)
        return jax.lax.scan(step, s, t)

    n = S // chunk
    split = lambda v: v.reshape((n, chunk) + v.shape[1:])
    _s, y = jax.lax.scan(block, jnp.zeros((H, P, N), jnp.float32),
                         tuple(split(v) for v in (x, delta, b, c)))
    return y.reshape(S, H, P)


def mixer(x, leaves, config, precision, fault):
    """x [S, d] of one row -> x + the Mamba-2 mixer of N(x)."""
    w_n, w_in, conv_w, conv_b, dt_bias, a_log, D, w_g, w_out = leaves
    H, P, G, N, inner, conv = _widths(config)
    eps, K = config["layer_norm_epsilon"], config["conv_kernel"]
    S = x.shape[0]
    h = _at(rms_norm(x, w_n, eps), precision)
    proj = _mm(h, w_in, precision)
    z = _at(proj[:, :inner], precision)
    xbc = _at(proj[:, inner:inner + conv], precision)
    dt = proj[:, inner + conv:]
    padded = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = conv_b + sum(conv_w[j] * padded[j:j + S] for j in range(K))
    xbc = _at(jax.nn.silu(xbc), precision)
    xs = xbc[:, :inner].reshape(S, H, P)
    bs = xbc[:, inner:inner + G * N].reshape(S, G, N)
    cs = xbc[:, inner + G * N:].reshape(S, G, N)
    delta = jax.nn.softplus(dt + dt_bias)
    y = recurrence(xs, delta, -jnp.exp(a_log), bs, cs,
                   min(config["chunk_size"], S),
                   carried=fault != "state_not_carried")
    y = (y + D[:, None] * xs).reshape(S, inner)
    groups = 1 if fault == "norm_over_all_channels" else G
    if fault == "norm_before_gate":
        u = grouped_norm(y, w_g, groups, eps) * jax.nn.silu(z)
    else:
        u = grouped_norm(y * jax.nn.silu(z), w_g, groups, eps)
    return _at(x + _mm(_at(u, precision), w_out, precision), precision)


def attention(x, leaves, config, precision, fault):
    """x [S, d] of one row -> x + causal grouped-query attention of N(x),
    no positions."""
    w_n, wq, wk, wv, wo = leaves
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], config["layer_norm_epsilon"]
    S = x.shape[0]
    h = _at(rms_norm(x, w_n, eps), precision)
    q = _at(_mm(h, wq, precision), precision).reshape(S, H, D)
    k = _at(_mm(h, wk, precision), precision).reshape(S, Hkv, D)
    v = _at(_mm(h, wv, precision), precision).reshape(S, Hkv, D)
    one = jax.checkpoint(functools.partial(
        _window._kv_head_attention, scale=D ** -0.5, window=None,
        precision=precision))
    o = jax.lax.map(lambda qkv: one(*qkv),
                    (q.reshape(S, Hkv, H // Hkv, D).transpose(1, 0, 2, 3),
                     k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = _at(o.transpose(1, 0, 2, 3).reshape(S, H * D), precision)
    return _at(x + _mm(o, wo, precision), precision)


def route(t, w_router, bias, config, fault):
    """(picks [T, k], weights [T, k]); everything float32."""
    k = config["num_experts_per_tok"]
    p = jax.nn.sigmoid(jnp.matmul(t, w_router, precision=_HI))
    chosen_by = p if fault == "selection_without_bias" else p + bias[None]
    # a stable descending sort: among equals the lower index comes first
    picks = jnp.argsort(-chosen_by, axis=-1, stable=True)[:, :k]
    g = jnp.take_along_axis(p, picks, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    if fault != "no_routed_scaling":
        g = g * config["routed_scaling_factor"]
    return picks, g


def squared_relu(m, w_up, w_down, precision, fault):
    """relu(m U)^2 V (relu(m U) V under the fault), f32 accumulation."""
    a = jax.nn.relu(_mm(m, w_up, precision))
    if fault != "relu_for_relu2":
        a = jnp.square(a)
    return _mm(_at(a, precision), w_down, precision)


def expert_ffn(x, leaves, bias, config, precision, fault):
    """x [T, d] -> (x + shared + the held experts' part, picks)."""
    w_n, w_r, e_up, e_down, s_up, s_down = leaves
    first, _count = held(config, "experts_held", config["n_routed_experts"])
    t = _at(rms_norm(x, w_n, config["layer_norm_epsilon"]), precision)
    picks, g = route(t, w_r, bias, config, fault)

    def one_expert(y, packed):
        e, wu, wd = packed
        # this expert's weight on every token: g where it was picked, else 0
        share = jnp.sum(jnp.where(picks == e, g, 0.0), axis=-1)
        return y + _at(share[:, None] * squared_relu(
            t, wu, wd, precision, fault), precision), None

    ids = first + jnp.arange(e_up.shape[0])
    routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                             (ids, e_up, e_down))
    y = squared_relu(t, s_up, s_down, precision, fault) + routed
    return _at(x + y, precision), picks


def forward(leaves, biases, tokens, config, precision="f32", fault=None):
    """tokens [B, S] (ids of the whole vocabulary, inside the held rows) ->
    (logits [B, S, held rows] float32, picks per expert layer [B*S, k])."""
    v_first, _rows = held(config, "vocab_held", config["vocab_size"])
    B, S = tokens.shape
    x = _at(leaves[0][tokens.astype(jnp.int32) - v_first], precision)
    at, n_moe, all_picks = 1, 0, []
    for letter, prefix in _layers(config):
        n = len(_layer_specs(config, letter, prefix))
        mine = leaves[at:at + n]
        at += n
        if letter == "E":
            moe = jax.checkpoint(functools.partial(
                expert_ffn, config=config, precision=precision, fault=fault))
            flat, picks = moe(x.reshape(B * S, -1), mine, biases[n_moe])
            x = flat.reshape(B, S, -1)
            all_picks.append(picks)
            n_moe += 1
            continue
        half = jax.checkpoint(functools.partial(
            mixer if letter == "M" else attention, config=config,
            precision=precision, fault=fault))
        x = jax.lax.map(lambda row: half(row, mine), x)      # row by row
    w_f, w_head = leaves[at:]
    t = _at(rms_norm(x, w_f, config["layer_norm_epsilon"]), precision)
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
    loads = [expert_loads(p, config["n_routed_experts"]) for p in picks]
    return loss, grads, _norms(grads), loads


def follow(seed, batches, optimizer, config, precision="f32", rows=None,
           fault=None, keep_leaves=False):
    """Follow the first ``len(batches)`` steps from the seed's weights, as
    ``reference/window_moe_lm.py: follow`` does (``rows`` = 0 keeps the
    first half of a one-row batch's positions). Returns host values:
    ``losses``; of the FIRST step ``grad_norms``, ``grad_sketch`` and
    ``loads`` [expert layers, experts]; ``delta_norms`` after all steps;
    with ``keep_leaves`` the ``leaves``."""
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
            out["loads"] = np.asarray(jnp.stack(loads))
        leaves = adam_update(leaves, grads, m1, m2, optimizer, t)
        del grads
    out["delta_norms"] = np.asarray(delta_norms(leaves, start),
                                    dtype=np.float64)
    if keep_leaves:
        out["leaves"] = [np.asarray(l) for l in leaves]
    del leaves, m1, m2, start
    return out
