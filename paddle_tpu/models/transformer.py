"""Decoder-only transformer language model — the modern flagship family.

No 2018 reference equivalent (attention postdates the snapshot; its
sequence flagship was the attention-seq2seq book model,
python/paddle/fluid/tests/book/test_machine_translation.py). This is the
capability the TPU build adds on top: pre-norm causal blocks whose
attention is the ``flash_attention`` op — the Pallas kernel on TPU
(kernels/flash_attention.py), dense fallback elsewhere — with every
matmul batched for the MXU. Long sequences shard over a context-parallel
mesh axis via parallel/ring.py; tensor-parallel specs for the qkv/mlp
weights come from ShardingStrategy param_rules (see tests/test_models.py).
"""
from __future__ import annotations

from ..layers import nn as L
from ..layers import ops as OPS
from ..layers import tensor as T
from ..layers.decoder import flash_attention
from ..param_attr import ParamAttr


def causal_flash_attention(q, k, v, num_heads, scale=None):
    """[B, S, hidden] q/k and [B, S, v_hidden] v -> [B, S, v_hidden] via
    the flash_attention op (causal). The v heads may be narrower than the
    q/k heads; ``scale`` multiplies the scores (None: head size ** -0.5)."""
    seq = q.shape[-2]
    heads = lambda x: L.reshape(
        x, shape=[0, seq, num_heads, x.shape[-1] // num_heads])
    out = flash_attention(heads(q), heads(k), heads(v), causal=True,
                          scale=scale)
    return L.reshape(out, shape=[0, seq, v.shape[-1]])


def transformer_block(x, hidden, num_heads, ffn_mult=4, prefix="blk"):
    """Pre-norm block: x + attn(ln(x)); x + ffn(ln(x))."""
    h = L.layer_norm(x, begin_norm_axis=2,
                     param_attr=ParamAttr(name=prefix + "_ln1_w"),
                     bias_attr=ParamAttr(name=prefix + "_ln1_b"))
    q = L.fc(h, size=hidden, num_flatten_dims=2, bias_attr=False,
             param_attr=ParamAttr(name=prefix + "_q"))
    k = L.fc(h, size=hidden, num_flatten_dims=2, bias_attr=False,
             param_attr=ParamAttr(name=prefix + "_k"))
    v = L.fc(h, size=hidden, num_flatten_dims=2, bias_attr=False,
             param_attr=ParamAttr(name=prefix + "_v"))
    att = causal_flash_attention(q, k, v, num_heads)
    proj = L.fc(att, size=hidden, num_flatten_dims=2, bias_attr=False,
                param_attr=ParamAttr(name=prefix + "_proj"))
    x = L.elementwise_add(x, proj)
    h2 = L.layer_norm(x, begin_norm_axis=2,
                      param_attr=ParamAttr(name=prefix + "_ln2_w"),
                      bias_attr=ParamAttr(name=prefix + "_ln2_b"))
    up = L.fc(h2, size=hidden * ffn_mult, num_flatten_dims=2, act="relu",
              param_attr=ParamAttr(name=prefix + "_up"))
    down = L.fc(up, size=hidden, num_flatten_dims=2, bias_attr=False,
                param_attr=ParamAttr(name=prefix + "_down"))
    return L.elementwise_add(x, down)


def transformer_lm(tokens, vocab_size, hidden=64, num_layers=2,
                   num_heads=4, ffn_mult=4):
    """``tokens`` [B, S] int64 -> logits [B, S, vocab_size].

    Learned positional embeddings added to token embeddings, N pre-norm
    causal blocks, final layer norm, untied projection head.
    """
    seq = tokens.shape[1]
    emb = L.embedding(tokens, size=[vocab_size, hidden],
                      param_attr=ParamAttr(name="tok_emb"))
    # position ids: cumsum over a ones row - 1, per batch row
    ones = T.fill_constant_batch_size_like(tokens, shape=[-1, seq],
                                           dtype="float32", value=1.0)
    pos_ids = T.cast(L.scale(OPS.cumsum(ones, axis=1), scale=1.0, bias=-1.0),
                     "int64")
    pos = L.embedding(pos_ids, size=[seq, hidden],
                      param_attr=ParamAttr(name="pos_emb"))
    x = L.elementwise_add(emb, pos)
    for i in range(num_layers):
        x = transformer_block(x, hidden, num_heads, ffn_mult,
                              prefix="blk%d" % i)
    x = L.layer_norm(x, begin_norm_axis=2,
                     param_attr=ParamAttr(name="final_ln_w"),
                     bias_attr=ParamAttr(name="final_ln_b"))
    return L.fc(x, size=vocab_size, num_flatten_dims=2, bias_attr=False,
                param_attr=ParamAttr(name="lm_head"))


# ---------------------------------------------------------------------------
# Autoregressive serving face: the SAME weights transformer_lm trains,
# re-expressed as pure jax functions the generation engine
# (paddle_tpu.serving.generator) can jit once and drive per token.
#
# Three entry points, one math:
#
# - ``forward(params, tokens, config)``: full-sequence logits — the
#   pure-jax mirror of the transformer_lm Program (anchored by a parity
#   test against the Executor path), and the reference decoder for the
#   continuous-batching bit-parity proof.
# - ``prefill_step(...)``: one prompt through the full forward, its
#   per-layer K/V scattered into the paged pool through the sequence's
#   block table, last-real-position logits returned. Traced once per
#   prompt-length bucket.
# - ``decode_step(...)``: ONE token for every running sequence at once —
#   single-token attention that reads K/V *through the block table*
#   (gather) and writes the new position's K/V *through it* (scatter).
#   All operands have fixed [max_running, ...] shapes, so the engine's
#   hot loop is trace-free at any mix of sequence lengths.
#
# The math mirrors the op lowerings exactly (ops/attention_ops dense
# reference, ops/nn_ops layer_norm eps=1e-5, mul's flatten-then-gemm):
# masked-out cache columns contribute exp(-inf)=0 — exact zeros — so a
# cached single-token step computes the same attention row the full
# forward does, and greedy decode through the cache is token-identical
# to full-sequence recompute (proven in tests/test_generation.py).

LN_EPS = 1e-5


class TransformerConfig(object):
    """Static hyperparameters of one decoder-only LM — everything the
    serving tier needs to rebuild the jax functions around a params
    dict (JSON round-trip for the generative artifact)."""

    __slots__ = ("vocab_size", "hidden", "num_layers", "num_heads",
                 "ffn_mult", "max_seq", "eos_id")

    def __init__(self, vocab_size, hidden=64, num_layers=2, num_heads=4,
                 ffn_mult=4, max_seq=128, eos_id=None):
        if hidden % num_heads:
            raise ValueError("hidden=%d not divisible by num_heads=%d"
                             % (hidden, num_heads))
        self.vocab_size = int(vocab_size)
        self.hidden = int(hidden)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.ffn_mult = int(ffn_mult)
        self.max_seq = int(max_seq)
        self.eos_id = None if eos_id is None else int(eos_id)

    @property
    def head_dim(self):
        return self.hidden // self.num_heads

    def to_dict(self):
        return {"vocab_size": self.vocab_size, "hidden": self.hidden,
                "num_layers": self.num_layers, "num_heads": self.num_heads,
                "ffn_mult": self.ffn_mult, "max_seq": self.max_seq,
                "eos_id": self.eos_id}

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


def param_names(config):
    """Declaration-ordered parameter names — exactly the ParamAttr names
    transformer_lm creates, so trained scopes export losslessly."""
    names = ["tok_emb", "pos_emb"]
    for i in range(config.num_layers):
        p = "blk%d" % i
        names += [p + s for s in ("_ln1_w", "_ln1_b", "_q", "_k", "_v",
                                  "_proj", "_ln2_w", "_ln2_b", "_up",
                                  "_down")]
    names += ["final_ln_w", "final_ln_b", "lm_head"]
    return names


def init_params(config, seed=0):
    """Random float32 params (benchmarks/tests that don't train first).
    Scaled-normal projections, unit layer norms — the shapes
    transformer_lm's ParamAttrs would create."""
    import numpy as np
    rng = np.random.RandomState(seed)
    H, V, S = config.hidden, config.vocab_size, config.max_seq
    F = H * config.ffn_mult

    def w(shape, scale):
        return (rng.randn(*shape) * scale).astype(np.float32)

    p = {"tok_emb": w((V, H), 0.05), "pos_emb": w((S, H), 0.05)}
    for i in range(config.num_layers):
        pre = "blk%d" % i
        p[pre + "_ln1_w"] = np.ones((H,), np.float32)
        p[pre + "_ln1_b"] = np.zeros((H,), np.float32)
        for s in ("_q", "_k", "_v", "_proj"):
            p[pre + s] = w((H, H), (2.0 / H) ** 0.5)
        p[pre + "_ln2_w"] = np.ones((H,), np.float32)
        p[pre + "_ln2_b"] = np.zeros((H,), np.float32)
        p[pre + "_up"] = w((H, F), (2.0 / H) ** 0.5)
        p[pre + "_down"] = w((F, H), (2.0 / F) ** 0.5)
    p["final_ln_w"] = np.ones((H,), np.float32)
    p["final_ln_b"] = np.zeros((H,), np.float32)
    p["lm_head"] = w((H, V), (2.0 / H) ** 0.5)
    return p


def params_from_scope(config, scope=None):
    """Extract the trained transformer_lm weights from ``scope`` (default
    global scope) as the {name: np.ndarray} dict the serving face runs
    on. Raises with every missing name listed."""
    import numpy as np
    from ..core.scope import global_scope
    scope = scope or global_scope()
    out, missing = {}, []
    for n in param_names(config):
        v = scope.find_var(n) if scope.has_var(n) else None
        if v is None:
            missing.append(n)
        else:
            out[n] = np.asarray(v)
    if missing:
        raise ValueError(
            "scope is missing transformer params %s — was transformer_lm "
            "built with this config and the startup program run?" % missing)
    return out


def _ln(x, w, b):
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * w + b


def _dense_causal_attention(q, k, v, num_heads):
    """[B, S, H] q/k/v -> [B, S, H]; the ops/attention_ops dense lowering
    verbatim (einsum scores, tril -inf mask, jax.nn.softmax)."""
    import jax
    import jax.numpy as jnp
    B, S, H = q.shape
    dh = H // num_heads
    t = lambda a: (a.reshape(B, S, num_heads, dh)
                   .transpose(0, 2, 1, 3).reshape(B * num_heads, S, dh))
    s = jnp.einsum("bqd,bkd->bqk", t(q), t(k)) * dh ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, t(v))
    return (o.reshape(B, num_heads, S, dh)
            .transpose(0, 2, 1, 3).reshape(B, S, H))


def _forward_kv(params, tokens, config):
    """Full forward over ``tokens`` [B, S] -> (logits [B, S, V],
    k [L, B, S, nh, dh], v [L, B, S, nh, dh])."""
    import jax.numpy as jnp
    nh, dh = config.num_heads, config.head_dim
    B, S = tokens.shape
    ids = tokens.astype(jnp.int32)
    x = jnp.take(params["tok_emb"], ids, axis=0) \
        + jnp.take(params["pos_emb"], jnp.arange(S, dtype=jnp.int32),
                   axis=0)[None]
    ks, vs = [], []
    for i in range(config.num_layers):
        pre = "blk%d" % i
        h = _ln(x, params[pre + "_ln1_w"], params[pre + "_ln1_b"])
        q = h @ params[pre + "_q"]
        k = h @ params[pre + "_k"]
        v = h @ params[pre + "_v"]
        ks.append(k.reshape(B, S, nh, dh))
        vs.append(v.reshape(B, S, nh, dh))
        att = _dense_causal_attention(q, k, v, nh)
        x = x + att @ params[pre + "_proj"]
        h2 = _ln(x, params[pre + "_ln2_w"], params[pre + "_ln2_b"])
        up = jnp.maximum(h2 @ params[pre + "_up"], 0.0)
        x = x + up @ params[pre + "_down"]
    x = _ln(x, params["final_ln_w"], params["final_ln_b"])
    logits = x @ params["lm_head"]
    return logits, jnp.stack(ks), jnp.stack(vs)


def forward(params, tokens, config):
    """Full-sequence logits [B, S, V] — the pure-jax mirror of the
    transformer_lm Program (parity test: tests/test_generation.py)."""
    return _forward_kv(params, tokens, config)[0]


def prefill_step(params, k_pages, v_pages, tokens, length, pages, config):
    """One prompt (``tokens`` [S_bucket], real length ``length``) through
    the full forward; per-layer K/V scattered into the paged pool at the
    sequence's ``pages`` ([max_blocks], trash-padded) and the logits of
    the last REAL position returned (the first sampled token's
    distribution). Positions >= length route to the trash page — padding
    never lands in live cache. Jit once per prompt bucket; donate the
    pools."""
    import jax.numpy as jnp
    T = k_pages.shape[2]
    trash = k_pages.shape[1] - 1
    logits, k, v = _forward_kv(params, tokens[None], config)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    page = jnp.where(pos < length, pages[pos // T], trash)
    slot = pos % T
    k_pages = k_pages.at[:, page, slot].set(k[:, 0])
    v_pages = v_pages.at[:, page, slot].set(v[:, 0])
    return logits[0, length - 1], k_pages, v_pages


def decode_step(params, k_pages, v_pages, block_tables, positions, tokens,
                active, config, attn_config=None):
    """ONE fused token step for the whole running batch.

    ``k_pages``/``v_pages``: [L, num_pages+1, page_tokens, nh, dh] (the
    last page is the trash page — writes for inactive rows land there).
    ``block_tables``: [R, max_blocks] int32 page ids, trash-padded.
    ``positions``: [R] int32 — the new token's 0-based position (== how
    many tokens the row has cached). ``tokens``: [R] int32 — the last
    sampled token per row. ``active``: [R] bool.

    Returns (logits [R, V], k_pages, v_pages). Every operand shape is
    fixed by (max_running, pool shape), so the engine compiles this ONCE
    and runs it at any mix of sequence lengths. Attention reads the
    row's K/V through its block table and masks columns > position:
    exp(-inf)=0 exactly, so each row computes the same softmax row a
    full-sequence forward would. ``attn_config`` is a paddle_tpu.tune
    "paged_attention" pick routing the read through the Pallas paged-
    attention kernel; None (or an invalid pick) runs the always-legal
    block-table gather."""
    import jax.numpy as jnp
    from ..kernels.paged_attention import (paged_attention,
                                           paged_attention_reference,
                                           resolve_block_config)
    nh, dh = config.num_heads, config.head_dim
    R = tokens.shape[0]
    T = k_pages.shape[2]
    trash = k_pages.shape[1] - 1
    rows = jnp.arange(R, dtype=jnp.int32)
    pos = positions.astype(jnp.int32)
    x = jnp.take(params["tok_emb"], tokens.astype(jnp.int32), axis=0) \
        + jnp.take(params["pos_emb"], pos, axis=0)
    page = jnp.where(active, block_tables[rows, pos // T], trash)
    slot = pos % T
    # resolve the kernel pick ONCE per trace: invalid/stale configs
    # degrade to the gather here, so a bad cache entry can never fail
    # the decode trace mid-serving
    use_kernel = resolve_block_config(attn_config, R,
                                      block_tables.shape[1]) is not None
    for i in range(config.num_layers):
        pre = "blk%d" % i
        h = _ln(x, params[pre + "_ln1_w"], params[pre + "_ln1_b"])
        q = (h @ params[pre + "_q"]).reshape(R, nh, dh)
        k_new = (h @ params[pre + "_k"]).reshape(R, nh, dh)
        v_new = (h @ params[pre + "_v"]).reshape(R, nh, dh)
        k_pages = k_pages.at[i, page, slot].set(k_new)
        v_pages = v_pages.at[i, page, slot].set(v_new)
        if use_kernel:
            att = paged_attention(q, k_pages[i], v_pages[i], block_tables,
                                  pos, config=attn_config)
        else:
            att = paged_attention_reference(q, k_pages[i], v_pages[i],
                                            block_tables, pos)
        x = x + att.reshape(R, nh * dh) @ params[pre + "_proj"]
        h2 = _ln(x, params[pre + "_ln2_w"], params[pre + "_ln2_b"])
        up = jnp.maximum(h2 @ params[pre + "_up"], 0.0)
        x = x + up @ params[pre + "_down"]
    x = _ln(x, params["final_ln_w"], params["final_ln_b"])
    return x @ params["lm_head"], k_pages, v_pages


def device_sample(logits, temperatures, seeds, counters):
    """Seeded per-row sampling INSIDE the jitted step: ``logits``
    [R, V]; ``temperatures`` [R] f32 (<= 0 = greedy argmax);
    ``seeds``/``counters`` [R] int32. Each row's key is
    ``fold_in(PRNGKey(seed), counter)`` with counter = the sampled
    token's position in the FULL sequence (prompt + generated) — the
    stream is a pure function of (seed, position), so it is independent
    of batch slot and RESUMES at the right point after a preemption
    recompute. Returns (tokens [R] int32, logprobs [R] f32 — the
    UNtempered log-softmax at the chosen token, what the retire path
    reads instead of re-materializing logits)."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _sampled(_):
        def one(row, temp, seed, ctr):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), ctr)
            return jax.random.categorical(
                key, row / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
        return jax.vmap(one)(logits, temperatures, seeds, counters)

    # the categorical draw prices the FULL [R, V] gumbel trick — behind
    # a batch-level cond so an all-greedy step (the common serving
    # steady state, and the parity gates) never pays it; tempered rows
    # keep the exact per-row stream (the cond branch is the same vmap)
    sampled = jax.lax.cond(jnp.any(temperatures > 0.0), _sampled,
                           lambda _: greedy, None)
    toks = jnp.where(temperatures > 0.0, sampled, greedy)
    logps = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), toks[:, None], axis=-1)[:, 0]
    return toks, logps


def decode_step_sampled(params, k_pages, v_pages, block_tables, positions,
                        tokens, active, temperatures, seeds,
                        config, attn_config=None):
    """The fused decode FAST PATH: decode_step + :func:`device_sample`
    in one jit, returning ([R] int32 sampled tokens, [R] f32 logprobs,
    k_pages, v_pages) — the host transfer per step shrinks from
    [R, V] logits to two [R] rows and the host loop becomes pure
    bookkeeping. The per-row RNG counter is derived ON DEVICE as
    ``positions + 1``: at decode time the row's token offset always
    equals its cached length + 1 (one token accepted per step, and a
    preemption resume re-prefills the full prefix), so the fused step
    adds NO per-step host->device operands beyond the host path —
    temperatures/seeds only change when the running set changes and
    the engine caches their device copies."""
    import jax.numpy as jnp
    logits, k_pages, v_pages = decode_step(
        params, k_pages, v_pages, block_tables, positions, tokens,
        active, config, attn_config=attn_config)
    toks, logps = device_sample(logits, temperatures, seeds,
                                jnp.asarray(positions, jnp.int32) + 1)
    return toks, logps, k_pages, v_pages


def prefill_step_sampled(params, k_pages, v_pages, tokens, length, pages,
                         temperature, seed, config):
    """prefill_step + device sampling of the FIRST token: returns
    (token int32, logprob f32, k_pages, v_pages) — no [V] logits row
    crosses to the host on the fused path. The RNG counter is the
    sampled token's position in the FULL sequence (= ``length``, the
    fed prefix), matching the decode step's on-device ``positions + 1``
    derivation — so a preemption resume, which re-prefills
    prompt+progress, continues the exact stream the decode steps were
    drawing from."""
    import jax.numpy as jnp
    last, k_pages, v_pages = prefill_step(params, k_pages, v_pages,
                                          tokens, length, pages, config)
    toks, logps = device_sample(
        last[None], jnp.asarray([temperature], jnp.float32),
        jnp.asarray([seed], jnp.int32),
        jnp.asarray([length], jnp.int32))
    return toks[0], logps[0], k_pages, v_pages


# ---------------------------------------------------------------------------
# Speculative decoding faces (serving/speculative.py drives these).
#
# One round: the DRAFT model proposes k tokens autoregressively
# (``draft_propose_step`` — a lax.scan of k+1 decode steps over the
# draft's OWN page pool, one trace total), then the TARGET model runs
# ONE k+1-lane verify step (``verify_step_sampled``) that scatters all
# k+1 positions' K/V and attends every lane at once, accepts the
# longest valid draft prefix, and samples the correction/bonus token on
# device. Only a packed [R, 2(k+1)+1] f32 row crosses to the host —
# draft logits never leave the device (gen_host_logit_syncs stays 0).
#
# RNG discipline: every draw is keyed by the drawn token's absolute
# position in the full sequence — ``fold_in(PRNGKey(seed), position)``
# for the plain/bonus draw (the SAME key the non-speculative fused step
# uses at that position, so a cap-0 row is bit-identical to plain
# decode), and salted variants of it for the draft proposal, the accept
# uniform, and the residual draw. Pure functions of (seed, position)
# means a preemption resume — which re-prefills prompt+progress and
# restarts the round at the same position — replays the exact
# accept/reject history.
#
# Stale-write safety: verify scatters K/V for ALL k+1 lanes, including
# drafts that end up rejected. No rollback is needed — attention masks
# columns past each query's position, and every overshot position is
# re-scattered (with its true token) by a later round before any
# unmasked read, because rounds always restart at the first unaccepted
# position. The engine only trims page-table overshoot (allocator
# bookkeeping), never cache contents.

_DRAFT_SALT = 0x5D    # the draft model's own proposal draws
_ACCEPT_SALT = 0x5A   # the accept/reject uniform per draft position
_RESID_SALT = 0x5E    # the residual draw after a rejection


def draft_propose_step(params, k_pages, v_pages, block_tables, positions,
                       tokens, active, temperatures, seeds, spec_caps,
                       k, config):
    """Propose ``k`` tokens per row from the DRAFT model: a lax.scan of
    k+1 :func:`decode_step` substeps over the draft's own paged pool.
    Substep j feeds the row's current token at position ``positions+j``
    (substep 0 feeds the pending last sampled token, later substeps
    feed the row's own proposals), writes its K/V live only while
    ``j <= spec_caps[r]`` (capped/plain rows route overshoot to the
    trash page), and samples the next proposal — greedy argmax, or a
    categorical keyed ``fold_in(fold_in(PRNGKey(seed), position+j+1),
    _DRAFT_SALT)`` for tempered rows. The final substep only writes
    K/V, keeping the draft cache exactly caught up with the target's.
    Returns (drafts [R, k] int32, draft_logits [R, k, V] f32, k_pages,
    v_pages); ONE trace per (k, geometry) — the scan body is traced
    once."""
    import jax
    import jax.numpy as jnp
    pos0 = jnp.asarray(positions, jnp.int32)

    def substep(carry, j):
        kp, vp, cur = carry
        write_ok = active & (j <= spec_caps)
        logits, kp, vp = decode_step(params, kp, vp, block_tables,
                                     pos0 + j, cur, write_ok, config)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def one(row, temp, seed, idx):
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(seed), idx),
                _DRAFT_SALT)
            return jax.random.categorical(
                key, row / jnp.maximum(temp, 1e-6)).astype(jnp.int32)

        sampled = jax.lax.cond(
            jnp.any(temperatures > 0.0),
            lambda _: jax.vmap(one)(logits, temperatures, seeds,
                                    pos0 + j + 1),
            lambda _: greedy, None)
        nxt = jnp.where(temperatures > 0.0, sampled, greedy)
        return (kp, vp, nxt), (nxt, logits)

    (k_pages, v_pages, _), (toks, logits) = jax.lax.scan(
        substep, (k_pages, v_pages, jnp.asarray(tokens, jnp.int32)),
        jnp.arange(k + 1, dtype=jnp.int32))
    drafts = jnp.transpose(toks[:k])                     # [R, k]
    draft_logits = jnp.transpose(logits[:k], (1, 0, 2))  # [R, k, V]
    return drafts, draft_logits, k_pages, v_pages


def verify_step(params, k_pages, v_pages, block_tables, positions, tokens,
                active, spec_caps, config, attn_config=None):
    """ONE target-model step over ``K1 = k+1`` lanes per row: lane i
    feeds ``tokens[r, i]`` at position ``positions[r]+i`` (lane 0 is
    the pending last sampled token, lanes 1..k the draft proposals).
    Per layer, ALL lanes' K/V scatter first, then every lane attends
    through the block table with its own position mask — so lane i
    computes exactly the logits a plain decode step would after
    accepting lanes < i. Lanes past ``spec_caps[r]`` (and inactive
    rows) write to the trash page. Returns (logits [R, K1, V],
    k_pages, v_pages)."""
    import jax.numpy as jnp
    from ..kernels.paged_attention import paged_attention_kwide
    nh, dh = config.num_heads, config.head_dim
    R, K1 = tokens.shape
    T = k_pages.shape[2]
    trash = k_pages.shape[1] - 1
    rows = jnp.arange(R, dtype=jnp.int32)
    lanes = jnp.arange(K1, dtype=jnp.int32)
    pos = positions.astype(jnp.int32)[:, None] + lanes[None, :]  # [R, K1]
    live = active[:, None] & (lanes[None, :] <= spec_caps[:, None])
    x = jnp.take(params["tok_emb"], tokens.astype(jnp.int32), axis=0) \
        + jnp.take(params["pos_emb"], pos, axis=0)
    page = jnp.where(live, block_tables[rows[:, None], pos // T], trash)
    slot = pos % T
    for i in range(config.num_layers):
        pre = "blk%d" % i
        h = _ln(x, params[pre + "_ln1_w"], params[pre + "_ln1_b"])
        q = (h @ params[pre + "_q"]).reshape(R, K1, nh, dh)
        k_new = (h @ params[pre + "_k"]).reshape(R, K1, nh, dh)
        v_new = (h @ params[pre + "_v"]).reshape(R, K1, nh, dh)
        k_pages = k_pages.at[i, page, slot].set(k_new)
        v_pages = v_pages.at[i, page, slot].set(v_new)
        att = paged_attention_kwide(q, k_pages[i], v_pages[i],
                                    block_tables, pos, config=attn_config)
        x = x + att.reshape(R, K1, nh * dh) @ params[pre + "_proj"]
        h2 = _ln(x, params[pre + "_ln2_w"], params[pre + "_ln2_b"])
        up = jnp.maximum(h2 @ params[pre + "_up"], 0.0)
        x = x + up @ params[pre + "_down"]
    x = _ln(x, params["final_ln_w"], params["final_ln_b"])
    return x @ params["lm_head"], k_pages, v_pages


def speculative_accept(logits, drafts, draft_logits, positions,
                       temperatures, seeds, spec_caps):
    """The accept/reject rule, on device. ``logits`` [R, K1, V] target
    verify logits; ``drafts`` [R, K] / ``draft_logits`` [R, K, V] the
    proposals; ``spec_caps`` [R] int32 — draft i only counts while
    ``i < cap`` (cap 0 = plain row).

    Greedy rows (temp <= 0) accept the longest prefix with
    ``drafts[i] == argmax(logits[:, i])`` and emit
    ``argmax(logits[:, a])`` as the correction/bonus — by construction
    the exact token sequence non-speculative greedy decode emits.
    Tempered rows use canonical rejection sampling: draft i accepts iff
    ``log u <= log q(d) - log p(d)`` (q = tempered target, p = tempered
    draft, u keyed ``_ACCEPT_SALT`` at the draft's position); the first
    rejection resamples from ``norm(max(q - p, 0))`` keyed
    ``_RESID_SALT``; a fully-accepted row draws its bonus with the
    PLAIN position key — the same key the non-speculative fused step
    uses, so cap-0 rows reproduce the plain stream bit-exactly.

    Returns (emitted [R, K1] int32, n_out [R] int32 in 1..K1,
    logprobs [R, K1] f32 — UNtempered target log-softmax at the emitted
    token, the same convention as :func:`device_sample`)."""
    import jax
    import jax.numpy as jnp
    R, K1, V = logits.shape
    K = K1 - 1
    pos0 = jnp.asarray(positions, jnp.int32)
    lanes = jnp.arange(K, dtype=jnp.int32)
    lanes1 = jnp.arange(K1, dtype=jnp.int32)
    temp = jnp.maximum(temperatures, 1e-6)[:, None, None]
    is_greedy = temperatures <= 0.0

    greedy_t = jnp.argmax(logits, axis=-1).astype(jnp.int32)   # [R, K1]
    g_acc = drafts == greedy_t[:, :K]
    lq = jax.nn.log_softmax(logits[:, :K] / temp, axis=-1)
    lp = jax.nn.log_softmax(draft_logits / temp, axis=-1)
    lq_d = jnp.take_along_axis(lq, drafts[..., None], axis=-1)[..., 0]
    lp_d = jnp.take_along_axis(lp, drafts[..., None], axis=-1)[..., 0]
    didx = pos0[:, None] + 1 + lanes[None, :]  # draft i's position

    def _accept_u(seed, idx):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), idx),
            _ACCEPT_SALT)
        return jax.random.uniform(key)

    u = jax.vmap(lambda s, ix: jax.vmap(
        lambda j: _accept_u(s, j))(ix))(seeds, didx)
    t_acc = jnp.log(u) <= lq_d - lp_d
    acc = jnp.where(is_greedy[:, None], g_acc, t_acc)
    acc = acc & (lanes[None, :] < spec_caps[:, None])
    a = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)  # [R]

    # correction/bonus token, from lane a's distributions
    lt_a = jnp.take_along_axis(logits, a[:, None, None], axis=1)[:, 0]
    ld_a = jnp.take_along_axis(
        draft_logits, jnp.minimum(a, K - 1)[:, None, None], axis=1)[:, 0]
    qa = jax.nn.softmax(lt_a / temp[:, :, 0], axis=-1)
    pa = jax.nn.softmax(ld_a / temp[:, :, 0], axis=-1)
    resid = jnp.maximum(qa - pa, 0.0)
    rsum = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(rsum > 0.0, resid, qa)

    def _final_t(seed, idx, rejected, log_resid, lt_scaled):
        base = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
        t_resid = jax.random.categorical(
            jax.random.fold_in(base, _RESID_SALT), log_resid)
        t_plain = jax.random.categorical(base, lt_scaled)
        return jnp.where(rejected, t_resid, t_plain).astype(jnp.int32)

    final_t = jax.lax.cond(
        jnp.any(temperatures > 0.0),
        lambda _: jax.vmap(_final_t)(
            seeds, pos0 + a + 1, a < spec_caps,
            jnp.log(resid + 1e-38), lt_a / temp[:, :, 0]),
        lambda _: jnp.take_along_axis(greedy_t, a[:, None],
                                      axis=1)[:, 0], None)
    final_g = jnp.take_along_axis(greedy_t, a[:, None], axis=1)[:, 0]
    final = jnp.where(is_greedy, final_g, final_t)

    drafts_pad = jnp.concatenate([drafts, drafts[:, :1]], axis=1)
    emitted = jnp.where(lanes1[None, :] < a[:, None], drafts_pad,
                        final[:, None])
    logps = jnp.take_along_axis(
        jax.nn.log_softmax(logits, axis=-1), emitted[..., None],
        axis=-1)[..., 0]
    return emitted, a + 1, logps


def verify_step_sampled(params, k_pages, v_pages, block_tables, positions,
                        tokens, drafts, draft_logits, active, temperatures,
                        seeds, spec_caps, config, attn_config=None):
    """The fused speculative verify: :func:`verify_step` over
    ``[last_token, drafts...]`` + :func:`speculative_accept` in one
    jit. Returns (packed [R, 2*K1+1] f32 — emitted tokens [K1], n_out,
    logprobs [K1] per row, ONE host transfer — , k_pages, v_pages)."""
    import jax.numpy as jnp
    tokens_k1 = jnp.concatenate(
        [jnp.asarray(tokens, jnp.int32)[:, None], drafts], axis=1)
    logits, k_pages, v_pages = verify_step(
        params, k_pages, v_pages, block_tables, positions, tokens_k1,
        active, spec_caps, config, attn_config=attn_config)
    emitted, n_out, logps = speculative_accept(
        logits, drafts, draft_logits, positions, temperatures, seeds,
        spec_caps)
    packed = jnp.concatenate(
        [emitted.astype(jnp.float32), n_out.astype(jnp.float32)[:, None],
         logps], axis=1)
    return packed, k_pages, v_pages


class TransformerLM(object):
    """Weights + config bound into the serving face the generation
    engine drives: ``forward`` for references/parity, ``prefill_step``/
    ``decode_step`` for the paged hot path. Params are moved to device
    once (a generation process must not re-upload weights per step)."""

    def __init__(self, params, config):
        import jax
        if isinstance(config, dict):
            config = TransformerConfig.from_dict(config)
        self.config = config
        missing = [n for n in param_names(config) if n not in params]
        if missing:
            raise ValueError("params dict is missing %s" % missing)
        self.params = {n: jax.device_put(params[n])
                       for n in param_names(config)}

    # -- pool geometry the engine builds around ------------------------------
    @property
    def kv_spec(self):
        """(num_layers, num_heads, head_dim) of one cached position."""
        c = self.config
        return (c.num_layers, c.num_heads, c.head_dim)

    # -- entry points (pure; the engine jits them) ---------------------------
    def forward(self, tokens):
        return forward(self.params, tokens, self.config)

    def prefill_fn(self):
        cfg = self.config

        def fn(params, k_pages, v_pages, tokens, length, pages):
            return prefill_step(params, k_pages, v_pages, tokens, length,
                                pages, cfg)
        return fn

    def decode_fn(self, attn_config=None):
        cfg = self.config

        def fn(params, k_pages, v_pages, block_tables, positions, tokens,
               active):
            return decode_step(params, k_pages, v_pages, block_tables,
                               positions, tokens, active, cfg,
                               attn_config=attn_config)
        return fn

    # -- fused (device-sampling) faces ---------------------------------------
    def prefill_sample_fn(self):
        cfg = self.config

        def fn(params, k_pages, v_pages, tokens, length, pages,
               temperature, seed):
            return prefill_step_sampled(params, k_pages, v_pages, tokens,
                                        length, pages, temperature, seed,
                                        cfg)
        return fn

    def decode_sample_fn(self, attn_config=None):
        cfg = self.config

        def fn(params, k_pages, v_pages, block_tables, positions, tokens,
               active, temperatures, seeds):
            import jax.numpy as jnp
            toks, logps, k_pages, v_pages = decode_step_sampled(
                params, k_pages, v_pages, block_tables, positions,
                tokens, active, temperatures, seeds, cfg,
                attn_config=attn_config)
            # ONE [2R] f32 row crosses to the host per step (tokens are
            # exact in f32 up to vocab 2^24), not two fetches
            packed = jnp.concatenate([toks.astype(jnp.float32), logps])
            return packed, k_pages, v_pages
        return fn

    # -- speculative faces ---------------------------------------------------
    def draft_propose_fn(self, k):
        """This model as the DRAFT: propose ``k`` tokens per row over
        its own page pool (serving/speculative.py jits this once per
        (k, geometry))."""
        cfg = self.config

        def fn(params, k_pages, v_pages, block_tables, positions, tokens,
               active, temperatures, seeds, spec_caps):
            return draft_propose_step(params, k_pages, v_pages,
                                      block_tables, positions, tokens,
                                      active, temperatures, seeds,
                                      spec_caps, k, cfg)
        return fn

    def verify_sample_fn(self, attn_config=None):
        """This model as the TARGET: one fused k+1-lane verify +
        accept/reject + device sampling step (k is carried by the
        drafts operand's shape, so the engine jits this once per
        (k, geometry))."""
        cfg = self.config

        def fn(params, k_pages, v_pages, block_tables, positions, tokens,
               drafts, draft_logits, active, temperatures, seeds,
               spec_caps):
            return verify_step_sampled(params, k_pages, v_pages,
                                       block_tables, positions, tokens,
                                       drafts, draft_logits, active,
                                       temperatures, seeds, spec_caps,
                                       cfg, attn_config=attn_config)
        return fn
