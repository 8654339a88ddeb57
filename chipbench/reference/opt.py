"""Plain reference: an OPT-style decoder (Zhang et al., arXiv:2205.01068;
huggingface.co/facebook/opt-1.3b config.json) as one full forward pass in
straightforward ``jax.numpy``: float32 at ``highest`` matmul precision, no
cache, no batching, no kernels. Imports nothing of the program under test.

Pre-LayerNorm blocks, learned positions, ReLU feed-forward of 4 x hidden,
multi-head causal attention with 1/sqrt(head) scaling, a final LayerNorm and
an output head. Departures from the published model, which are the
program's block (``chipbench/configs/opt-1.3b.json``, ``assumed``): no linear
biases, the output head not tied to the embedding, positions from 0 (no
offset of 2).

``precision="fp8"`` is the CONTROL. The configuration multiplies in ONE
bfloat16 pass (float32 storage at the TPU's default matmul precision), so the
nearest precision below is fp8: the same pass with both operands of every
matrix product rounded to float8_e4m3 under a per-tensor scale (amax -> 448),
everything else float32. ``precision="bf16"`` (parameters, activations and
residual stream all bfloat16) was read first and does not separate: it is
only 1.7x the program's own gap (PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
_HI = jax.lax.Precision.HIGHEST
_BLOCK = ("_ln1_w", "_ln1_b", "_q", "_k", "_v", "_proj", "_ln2_w", "_ln2_b",
          "_up", "_down")


def param_shapes(vocab, hidden, layers, ffn, positions):
    """{name: shape} in declaration order."""
    out = {"tok_emb": (vocab, hidden), "pos_emb": (positions, hidden)}
    for i in range(layers):
        for s in _BLOCK:
            if s in ("_q", "_k", "_v", "_proj"):
                shape = (hidden, hidden)
            elif s == "_up":
                shape = (hidden, ffn)
            elif s == "_down":
                shape = (ffn, hidden)
            else:
                shape = (hidden,)
            out["blk%d%s" % (i, s)] = shape
    out["final_ln_w"] = (hidden,)
    out["final_ln_b"] = (hidden,)
    out["lm_head"] = (hidden, vocab)
    return out


def key_data(seed):
    """A threefry key from any whole-number seed (also above 2**31)."""
    seed = int(seed)
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    dtype=np.uint32)


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "layers",
                                             "ffn", "positions"))
def init_params(key_words, vocab, hidden, layers, ffn, positions):
    """Every parameter from the seed in ONE jitted call, float32, at the
    scales of the program's own random init: embeddings N(0, 0.05^2),
    projections N(0, 2/fan_in), LayerNorm weight 1 + 0.02 N, bias 0.02 N."""
    key = jax.random.wrap_key_data(key_words)
    out = {}
    for i, (name, shape) in enumerate(
            param_shapes(vocab, hidden, layers, ffn, positions).items()):
        n = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name in ("tok_emb", "pos_emb"):
            out[name] = 0.05 * n
        elif len(shape) == 2:
            out[name] = n * np.sqrt(2.0 / shape[0])
        elif name.endswith("_w"):
            out[name] = 1.0 + 0.02 * n
        else:
            out[name] = 0.02 * n
    return out


def _ln(x, w, b):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) / jnp.sqrt(var + LN_EPS) * w + b).astype(x.dtype)


def _fp8(a):
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(a.dtype) / scale


@functools.partial(jax.jit, static_argnames=("heads", "layers", "precision"))
def logits_at(params, tokens, rows, heads, layers, precision="f32"):
    """One sequence ``tokens`` [S] through the whole model; the logits
    [len(rows), V] (float32) of the positions ``rows``. Padding behind the
    last real token is harmless: attention is causal."""
    if precision not in ("f32", "bf16", "fp8"):
        raise ValueError("unknown precision %r" % (precision,))
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    operand = _fp8 if precision == "fp8" else (lambda a: a)

    def _mm(a, b):
        return jnp.matmul(operand(a), operand(b), precision=_HI)

    def _ein(spec, a, b):
        return jnp.einsum(spec, operand(a), operand(b), precision=_HI)
    p = {k: v.astype(dt) for k, v in params.items()}
    S = tokens.shape[0]
    H = p["tok_emb"].shape[1]
    dh = H // heads
    x = p["tok_emb"][tokens] + p["pos_emb"][:S]
    mask = jnp.tril(jnp.ones((S, S), bool))
    for i in range(layers):
        pre = "blk%d" % i
        h = _ln(x, p[pre + "_ln1_w"], p[pre + "_ln1_b"])
        q = _mm(h, p[pre + "_q"]).reshape(S, heads, dh)
        k = _mm(h, p[pre + "_k"]).reshape(S, heads, dh)
        v = _mm(h, p[pre + "_v"]).reshape(S, heads, dh)
        s = _ein("qhd,khd->hqk", q, k) * dh ** -0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
        att = _ein("hqk,khd->qhd", a, v).reshape(S, H)
        x = x + _mm(att, p[pre + "_proj"])
        h2 = _ln(x, p[pre + "_ln2_w"], p[pre + "_ln2_b"])
        x = x + _mm(jnp.maximum(_mm(h2, p[pre + "_up"]), 0), p[pre + "_down"])
    x = _ln(x, p["final_ln_w"], p["final_ln_b"])
    return _mm(x[rows], p["lm_head"]).astype(jnp.float32)


def pad_to(n, multiple=128):
    return ((int(n) + multiple - 1) // multiple) * multiple


def served_gaps(params, prompt, served, logprobs, heads, layers, pad,
                precision_control=None):
    """For one finished request: the reference's logits at every position
    that produced a served token (teacher-forced on prompt + served), and
    from them per served token

    - ``logit_gap``: how far the served token's reference logit lies below
      the reference's best (0 when it IS the best);
    - ``logprob_gap``: |served logprob - reference log-softmax at the token|
      (None where the path returned no logprobs);
    - with ``precision_control``: the gap of the token that the control's
      pass puts first at that position (``control_gap``), and the control's
      own logprob of the served token against the reference's
      (``control_logprob_gap``).

    Returns a dict of float64 arrays of len(served)."""
    seq = list(prompt) + list(served)
    n = len(served)
    tokens = np.zeros((pad,), np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros((pad_to(n, 128),), np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    ref = np.asarray(logits_at(params, jnp.asarray(tokens), jnp.asarray(rows),
                               heads=heads, layers=layers),
                     dtype=np.float64)[:n]
    best = ref.max(axis=-1)
    at = ref[np.arange(n), np.asarray(served)]
    out = {"logit_gap": best - at}

    def logprob_of(logits):
        top = logits.max(axis=-1)
        logz = np.log(np.exp(logits - top[:, None]).sum(axis=-1))
        return logits[np.arange(n), np.asarray(served)] - top - logz
    if logprobs is not None:
        out["logprob_gap"] = np.abs(np.asarray(logprobs, np.float64)
                                    - logprob_of(ref))
    if precision_control is not None:
        ctl = np.asarray(logits_at(params, jnp.asarray(tokens),
                                   jnp.asarray(rows), heads=heads,
                                   layers=layers,
                                   precision=precision_control),
                         dtype=np.float64)[:n]
        out["control_gap"] = best - ref[np.arange(n), ctl.argmax(axis=-1)]
        out["control_logprob_gap"] = np.abs(logprob_of(ctl) - logprob_of(ref))
    return out
