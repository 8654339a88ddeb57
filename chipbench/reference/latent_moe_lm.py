"""Plain reference: a decoder-only language model of DeepSeek-V3 blocks
without a query latent (HF ``modeling_deepseek_v3.py``; arXiv:2412.19437
section 2.1), its loss, gradients and an Adam step, in straightforward
``jax.numpy``. Imports nothing of the program under test.

For x in R^{T x d}, per layer:

- RMSNorm(u; w) = u / sqrt(mean(u^2) + eps) * w.
- Latent attention: h = RMSNorm(x; w_in); q = h W_q, per head [q_nope,
  q_rope]; [c_kv, k_rope] = h W_kva (one k_rope for all heads); [k_nope, v]
  per head = RMSNorm(c_kv; w_kv) W_kvb; rotary positions on q_rope and
  k_rope: the pair (2i, 2i+1) turned by pos * theta^(-2i/rope) (the
  interleaved layout kept as stored; HF de-interleaves q and k alike, which
  leaves q.k unchanged); P = softmax_causal(q k^T / sqrt(nope + rope));
  x' = x + concat(P v) W_o.
- Layer i < first_k_dense_replace: x'' = x' + gated(RMSNorm(x'; w_post)),
  gated(u) = (silu(u W_gate) * (u W_up)) W_down.
- Later layers: s = sigmoid(h2 W_r) over ALL experts; the picks are the
  top-k of s + b (b fixed, no gradient; ties to the lower index); g =
  s[picks] / (sum + 1e-20) * routed_scaling_factor; x'' = x' + shared(h2) +
  sum over the picks that fall on a HELD expert of g E(h2). What the absent
  experts would add is left out; g stays normalised over all the picks.
- logits = RMSNorm(x; w_f) W_head over the held vocabulary rows; the loss
  is the mean cross entropy with the next token.

``experts_held`` / ``vocab_held`` = [first, count]: the share of one chip of
an expert-parallel group. Departures from a training recipe: no auxiliary
loss (the config has none) and b is not updated in the step.

``precision``: ``"f32"`` (operands float32 at ``highest``: THE reference);
``"bf16"`` (the stated recipe: operands and the activation stream rounded
to bfloat16, as are the gradients along the stream; f32 accumulation,
router, softmax, RMS statistics, loss, master weights and moments);
``"fp8"`` the same recipe one precision down (e4m3 forward, e5m2 backward,
per-tensor scales): the CONTROL that has to come out as not correct.
``fault`` plants one of this model's faults in the forward pass
(``FAULTS``), which also have to come out as not correct.

Attention runs row by row over groups of heads and every half-layer is
rematerialised (``jax.checkpoint``), so that three float32 steps fit one
16 GB chip beside nothing else at one dense + five expert layers of the
published widths.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
FAULTS = ("selection_without_bias", "weights_not_renormalised",
          "rotary_left_out")
HEAD_GROUP = 4          # heads whose [S, S] scores are alive at once
INIT_STD = 0.02         # every matrix and the embedding
BIAS_STD = 0.02         # the router's selection bias b


def key_data(seed):
    """A threefry key from any whole-number seed (also above 2**31)."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    dtype=np.uint32)


def held(config, key, total):
    first, count = config.get(key) or (0, total)
    return int(first), int(count)


def leaf_specs(config):
    """Trainable leaves in the order the network makes them:
    [(name, shape)]. A 1-D leaf is a norm scale."""
    d, H = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, rank = config["v_head_dim"], config["kv_lora_rank"]
    E, w = config["n_routed_experts"], config["moe_intermediate_size"]
    _f, n_held = held(config, "experts_held", E)
    _v, rows = held(config, "vocab_held", config["vocab_size"])
    specs = [("embed", (rows, d))]
    for i in range(config["num_hidden_layers"]):
        a = "L%d.attn." % i
        specs += [(a + "norm", (d,)), (a + "wq", (d, H * (nope + rope))),
                  (a + "wkva", (d, rank + rope)), (a + "kv_norm", (rank,)),
                  (a + "wkvb", (rank, H * (nope + vd))),
                  (a + "wo", (H * vd, d))]
        f = "L%d.ffn." % i
        if i < config["first_k_dense_replace"]:
            m = config["intermediate_size"]
            specs += [(f + "norm", (d,)), (f + "gate", (d, m)),
                      (f + "up", (d, m)), (f + "down", (m, d))]
        else:
            sw = config["n_shared_experts"] * w
            specs += [(f + "norm", (d,)), (f + "router", (d, E)),
                      (f + "expert_gate", (n_held, d, w)),
                      (f + "expert_up", (n_held, d, w)),
                      (f + "expert_down", (n_held, w, d)),
                      (f + "shared_gate", (d, sw)),
                      (f + "shared_up", (d, sw)),
                      (f + "shared_down", (sw, d))]
    return specs + [("final_norm", (d,)), ("head", (d, rows))]


def _frozen(config):
    """The configuration as a hashable static argument."""
    keys = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
            "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "first_k_dense_replace",
            "num_hidden_layers", "rms_norm_eps", "rope_theta", "vocab_size",
            "experts_held", "vocab_held")
    return json.dumps({k: config.get(k) for k in keys}, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _init(key_words, frozen):
    config = json.loads(frozen)
    key = jax.random.wrap_key_data(key_words)
    leaves = []
    for i, (_name, shape) in enumerate(leaf_specs(config)):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        leaves.append(1.0 + 0.1 * n if len(shape) == 1 else INIT_STD * n)
    n_moe = config["num_hidden_layers"] - config["first_k_dense_replace"]
    biases = [BIAS_STD * jax.random.normal(
        jax.random.fold_in(key, 100000 + i),
        (config["n_routed_experts"],), jnp.float32) for i in range(n_moe)]
    return leaves, biases


def init_leaves(key_words, config):
    """All trainable leaves from the seed in ONE jitted call, float32: norm
    scales 1 + 0.1 N, everything else N(0, INIT_STD^2)."""
    return _init(key_words, _frozen(config))[0]


def init_router_biases(key_words, config):
    """The selection bias b of each expert layer, N(0, BIAS_STD^2): fixed,
    not a leaf."""
    return _init(key_words, _frozen(config))[1]


# -- precisions: as reference/resnet50.py has them ---------------------------

_FORMATS = {"bf16": (jnp.bfloat16, None, jnp.bfloat16, None),
            "fp8": (jnp.float8_e4m3fn, 448.0, jnp.float8_e5m2, 57344.0)}


def _round(a, dtype, largest):
    if largest is None:
        return a.astype(dtype).astype(jnp.float32)
    scale = largest / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(dtype).astype(jnp.float32) / scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _stream(a, precision):
    """One point of the activation stream: the value rounded to the forward
    format on the way up, its gradient to the backward format on the way
    down."""
    fwd, fmax, _bwd, _bmax = _FORMATS[precision]
    return _round(a, fwd, fmax)


def _stream_fwd(a, precision):
    return _stream(a, precision), None


def _stream_bwd(precision, _res, g):
    _fwd, _fmax, bwd, bmax = _FORMATS[precision]
    return (_round(g, bwd, bmax),)


_stream.defvjp(_stream_fwd, _stream_bwd)


def _at(a, precision):
    if precision == "f32":
        return a
    if precision not in _FORMATS:
        raise ValueError("unknown precision %r" % (precision,))
    return _stream(a, precision)


def _weight(w, precision):
    """The master weight as the matrix unit is fed it: rounded here, its
    gradient kept whole."""
    if precision == "f32":
        return w
    fwd, fmax, _bwd, _bmax = _FORMATS[precision]
    return w + jax.lax.stop_gradient(_round(w, fwd, fmax) - w)


def _mm(a, w, precision):
    """A stream point times a master weight, f32 accumulation."""
    return jnp.matmul(a, _weight(w, precision), precision=_HI,
                      preferred_element_type=jnp.float32)


# -- the layers --------------------------------------------------------------

def rms_norm(u, w, eps):
    return u / jnp.sqrt(jnp.mean(jnp.square(u), axis=-1, keepdims=True)
                        + eps) * w


def rotary(x, theta):
    """x [S, H, R]; pair (2i, 2i+1) of position s turned by
    s * theta^(-2i/R)."""
    S, H, R = x.shape
    i = jnp.arange(R // 2, dtype=jnp.float32)
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None]
           * jnp.power(jnp.float32(theta), -2.0 * i / R)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1)
    return turned.reshape(S, H, R)


def _heads_attention(q, k, v, scale, precision):
    """Dense causal softmax attention of one row over a group of heads:
    q/k [S, G, D], v [S, G, Dv] -> [S, G, Dv]."""
    S = q.shape[0]
    s = jnp.einsum("qgd,kgd->gqk", q, k, precision=_HI) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if precision != "f32":
        # the probabilities are an operand of the second product
        fwd, fmax, _b, _m = _FORMATS[precision]
        p = p + jax.lax.stop_gradient(_round(p, fwd, fmax) - p)
    return jnp.einsum("gqk,kgd->qgd", p, v, precision=_HI)


def latent_attention(x, leaves, config, precision, fault):
    """x [S, d] of one row -> x + attention."""
    w_in, wq, wkva, w_kv, wkvb, wo = leaves
    H = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    S = x.shape[0]
    h = _at(rms_norm(x, w_in, eps), precision)
    q = _at(_mm(h, wq, precision), precision).reshape(S, H, nope + rope)
    c = _at(_mm(h, wkva, precision), precision)
    c_kv, k_rope = c[:, :rank], c[:, rank:].reshape(S, 1, rope)
    kv = _at(_mm(_at(rms_norm(c_kv, w_kv, eps), precision), wkvb,
                 precision), precision).reshape(S, H, nope + vd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    if fault != "rotary_left_out":
        q_rope = _at(rotary(q_rope, theta), precision)
        k_rope = _at(rotary(k_rope, theta), precision)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (S, H, rope))], axis=-1)
    v = kv[..., nope:]
    G = min(HEAD_GROUP, H)
    group = lambda a: a.reshape(S, H // G, G, a.shape[-1]).transpose(
        1, 0, 2, 3)
    o = jax.lax.map(
        jax.checkpoint(lambda qkv: _heads_attention(
            *qkv, scale=(nope + rope) ** -0.5, precision=precision)),
        (group(q), group(k), group(v)))            # [H/G, S, G, vd]
    o = _at(o.transpose(1, 0, 2, 3).reshape(S, H * vd), precision)
    return _at(x + _at(_mm(o, wo, precision), precision), precision)


def gated(u, w_gate, w_up, w_down, precision):
    act = _at(jax.nn.silu(_mm(u, w_gate, precision))
              * _mm(u, w_up, precision), precision)
    return _mm(act, w_down, precision)


def dense_ffn(x, leaves, config, precision):
    w_post, w_gate, w_up, w_down = leaves
    h2 = _at(rms_norm(x, w_post, config["rms_norm_eps"]), precision)
    return _at(x + gated(h2, w_gate, w_up, w_down, precision), precision)


def route(h2, w_router, bias, config, fault):
    """(picks [T, k], weights [T, k]); everything float32."""
    k = config["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(h2, w_router, precision=_HI))
    chosen_by = s if fault == "selection_without_bias" else s + bias[None]
    # a stable descending sort: among equals the lower index comes first
    picks = jnp.argsort(-chosen_by, axis=-1, stable=True)[:, :k]
    g = jnp.take_along_axis(s, picks, axis=-1)
    if fault != "weights_not_renormalised":
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return picks, g * config["routed_scaling_factor"]


def expert_ffn(x, leaves, bias, config, precision, fault):
    """x [T, d] -> (x + shared + the held experts' part, picks)."""
    w_post, w_r, e_gate, e_up, e_down, s_gate, s_up, s_down = leaves
    first, _count = held(config, "experts_held", config["n_routed_experts"])
    h2 = _at(rms_norm(x, w_post, config["rms_norm_eps"]), precision)
    picks, g = route(h2, w_r, bias, config, fault)

    def one_expert(y, packed):
        e, wg, wu, wd = packed
        # this expert's weight on every token: g where it was picked, else 0
        share = jnp.sum(jnp.where(picks == e, g, 0.0), axis=-1)
        return y + share[:, None] * gated(h2, wg, wu, wd, precision), None

    ids = first + jnp.arange(e_gate.shape[0])
    routed, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                             (ids, e_gate, e_up, e_down))
    shared = gated(h2, s_gate, s_up, s_down, precision)
    return _at(x + _at(shared + routed, precision), precision), picks


def _layer_leaves(leaves, config):
    """[(attention leaves, ffn leaves)] per layer, then (final norm, head);
    the embedding is leaves[0]."""
    at, out = 1, []
    for i in range(config["num_hidden_layers"]):
        n = 4 if i < config["first_k_dense_replace"] else 8
        out.append((leaves[at:at + 6], leaves[at + 6:at + 6 + n]))
        at += 6 + n
    return out, leaves[at:]


def forward(leaves, biases, tokens, config, precision="f32", fault=None):
    """tokens [B, S] (ids of the whole vocabulary, inside the held rows) ->
    (logits [B, S, held rows] float32, picks per expert layer [B*S, k])."""
    v_first, _rows = held(config, "vocab_held", config["vocab_size"])
    B, S = tokens.shape
    layers, (w_f, w_head) = _layer_leaves(leaves, config)
    x = _at(leaves[0][tokens.astype(jnp.int32) - v_first], precision)
    all_picks = []
    for i, (attn, ffn) in enumerate(layers):
        att = jax.checkpoint(functools.partial(
            latent_attention, config=config, precision=precision,
            fault=fault))
        x = jax.lax.map(lambda row: att(row, attn), x)     # row by row
        if i < config["first_k_dense_replace"]:
            x = jax.checkpoint(functools.partial(
                dense_ffn, config=config, precision=precision))(x, ffn)
        else:
            moe = jax.checkpoint(functools.partial(
                expert_ffn, config=config, precision=precision, fault=fault))
            bias = biases[i - config["first_k_dense_replace"]]
            flat, picks = moe(x.reshape(B * S, -1), ffn, bias)
            x = flat.reshape(B, S, -1)
            all_picks.append(picks)
    h = _at(rms_norm(x, w_f, config["rms_norm_eps"]), precision)
    return _at(_mm(h, w_head, precision), precision), all_picks


def loss_fn(leaves, biases, tokens, labels, config, precision="f32",
            fault=None):
    """Mean over all tokens of the cross entropy with ``labels`` (the next
    token), float32; also the picks per expert layer."""
    v_first, _rows = held(config, "vocab_held", config["vocab_size"])
    logits, picks = forward(leaves, biases, tokens, config, precision, fault)
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = (labels.astype(jnp.int32) - v_first)[..., None]
    return -jnp.mean(jnp.take_along_axis(logp, want, axis=-1)), picks


def expert_loads(picks, n_experts):
    """int32[n_experts]: how many (token, pick) pairs chose each expert."""
    return jnp.sum(picks.reshape(-1)[:, None] == jnp.arange(n_experts)[None],
                   axis=0, dtype=jnp.int32)


def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(l.astype(jnp.float32))))
                      for l in leaves])


SKETCHES = 32


def sketch(arrays, key_words):
    """[leaves, SKETCHES]: each array's inner product with SKETCHES fixed
    random sign tensors made from the key. The difference of two gradients'
    sketches estimates the norm of their difference, which their norms
    alone cannot (a gradient of the right size that points elsewhere).
    Used on the reference's first gradient and, by the driver, on the
    program's."""
    key = jax.random.wrap_key_data(jnp.asarray(key_words))
    out = []
    for i, a in enumerate(arrays):
        out.append(_sketch_one(a, jax.random.fold_in(key, 7000 + i)))
    return jnp.stack(out)


@jax.jit
def _sketch_one(a, key):
    def one(j):
        signs = jax.random.rademacher(jax.random.fold_in(key, j), a.shape,
                                      jnp.float32)
        return jnp.sum(a.astype(jnp.float32) * signs)
    return jax.lax.map(one, jnp.arange(SKETCHES))


@functools.partial(jax.jit, static_argnames=("frozen", "precision", "fault"),
                   donate_argnums=(0, 1, 2))
def adam_step(leaves, m1, m2, biases, tokens, labels, lr, beta1, beta2, eps,
              t, frozen, precision="f32", fault=None):
    """One Adam step with the bias correction folded into the rate, as the
    program's optimizer writes it: m1 = b1 m1 + (1 - b1) g; m2 = b2 m2 +
    (1 - b2) g^2; p = p - lr sqrt(1 - b2^t) / (1 - b1^t) m1 / (sqrt(m2) +
    eps). Returns the new state, the loss, the per-leaf gradient norms
    and the expert loads per layer."""
    config = json.loads(frozen)
    (loss, picks), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        leaves, biases, tokens, labels, config, precision, fault)
    rate = lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
    m1 = [beta1 * m + (1.0 - beta1) * g for m, g in zip(m1, grads)]
    m2 = [beta2 * m + (1.0 - beta2) * g * g for m, g in zip(m2, grads)]
    leaves = [p - rate * a / (jnp.sqrt(b) + eps)
              for p, a, b in zip(leaves, m1, m2)]
    loads = [expert_loads(p, config["n_routed_experts"]) for p in picks]
    return leaves, m1, m2, loss, _norms(grads), loads


@jax.jit
def delta_norms(leaves, start):
    return _norms([a - b for a, b in zip(leaves, start)])


def follow(seed, batches, optimizer, config, precision="f32", rows=None,
           fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights.
    ``batches``: [(tokens [B, S], labels [B, S])] host arrays; ``optimizer``:
    {"learning_rate", "beta1", "beta2", "epsilon"}; ``rows`` keeps the first
    ``rows`` of each batch (the planted fault "half of the batch left out").
    Returns host values: ``losses`` per step; of the FIRST step
    ``grad_norms`` per leaf, ``grad_sketch`` [leaves, SKETCHES] and
    ``loads`` [expert layers, experts]; ``delta_norms`` per leaf of the
    parameters' change after all steps."""
    frozen = _frozen(config)
    words = key_data(seed)
    start, biases = _init(words, frozen)
    leaves = [jnp.array(l) for l in start]          # donated below
    m1 = [jnp.zeros_like(l) for l in leaves]
    m2 = [jnp.zeros_like(l) for l in leaves]
    out = {"losses": []}
    b1, b2 = optimizer["beta1"], optimizer["beta2"]
    for t, (tokens, labels) in enumerate(batches, 1):
        if rows is not None:
            tokens, labels = tokens[:rows], labels[:rows]
        leaves, m1, m2, loss, gn, loads = adam_step(
            leaves, m1, m2, biases, jnp.asarray(tokens), jnp.asarray(labels),
            jnp.float32(optimizer["learning_rate"]), jnp.float32(b1),
            jnp.float32(b2), jnp.float32(optimizer["epsilon"]),
            jnp.float32(t), frozen=frozen, precision=precision, fault=fault)
        out["losses"].append(float(loss))
        if t == 1:
            out["grad_norms"] = np.asarray(gn, dtype=np.float64)
            # Adam from zero: the first moment after one step IS the first
            # gradient times (1 - beta1), here as in the program
            out["grad_sketch"] = np.asarray(
                sketch(m1, words), dtype=np.float64) / (1.0 - b1)
            out["loads"] = np.asarray(jnp.stack(loads)) if loads \
                else np.zeros((0, config["n_routed_experts"]), np.int32)
    out["delta_norms"] = np.asarray(delta_norms(leaves, start),
                                    dtype=np.float64)
    del leaves, m1, m2, start
    return out
