"""Flash attention (forward + backward) as Pallas TPU kernels.

Forward streams k/v blocks through VMEM against a resident q block,
maintaining the online-softmax (running max / numerator / denominator)
decomposition, and emits the per-row logsumexp — so the [S, S] score matrix
never materialises in HBM. Backward is the FlashAttention-2 recompute
scheme as two Pallas kernels: a dK/dV kernel (grid over k blocks, loop over
q blocks) and a dQ kernel (grid over q blocks, loop over k blocks); every
score/probability tile lives only as a [block_q, block_k] VMEM tile.

Ragged sequence lengths (S % 128 != 0) are handled by padding to the block
size and masking padded k positions inside the kernels; padded q rows are
sliced off (and contribute exactly zero to dK/dV because their dO rows are
zero-padded).

The logsumexp output is what lets parallel/ring.py chain per-ring-step
flash calls with the numerically exact merge
``o = (o_a * exp(lse_a - lse) + o_b * exp(lse_b - lse))`` — gradients flow
through both o and lse (the dlse term folds into the backward's delta).

No reference equivalent (attention postdates the 2018 codebase); this is a
capability the TPU build adds, used by nets.scaled_dot_product_attention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..place import on_tpu

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30

DEFAULT_CONFIG = {"block_q": BLOCK_Q, "block_k": BLOCK_K}


def _blocks_from_config(config, Sq, Sk):
    """Resolve (block_q, block_k) for the call shape: configured blocks
    (a paddle_tpu.tune "flash_attention" pick) clamp to the sequence
    lengths and fall back to the 128 defaults when they don't divide the
    padded sequence — a stale cache entry must degrade, not fail."""
    cfg = dict(DEFAULT_CONFIG)
    cfg.update(dict(config) if config else {})
    bq = min(int(cfg["block_q"]), max(Sq, 1))
    bk = min(int(cfg["block_k"]), max(Sk, 1))
    if bq < 1 or bk < 1:
        bq, bk = min(BLOCK_Q, Sq), min(BLOCK_K, Sk)
    return bq, bk


def _dense_reference(q, k, v, causal, scale):
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


# ---------------------------------------------------------------------------
# forward kernel: one q block vs streamed k/v blocks -> o block + lse rows

def _masked_scores(q, k_blk, q_start, k_start, *, causal, scale, valid_len,
                   kv_len):
    """Scaled q@k^T tile with the causal and padded-k masks applied — the
    single source of masking truth shared by forward and both backward
    kernels (they must never disagree)."""
    s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32) * scale
    bq, bk = s.shape
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    if valid_len < kv_len:
        s = jnp.where(kpos < valid_len, s, NEG_INF)
    return s




def _fa_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, *, causal, scale, block_k,
               kv_len, valid_len):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)              # [BLOCK_Q, D]
    bq = q.shape[0]
    dv = v_ref.shape[-1]                          # v heads may be narrower
    n_k = kv_len // block_k

    def body(ki, acc):
        m, num, den = acc
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = _masked_scores(q, k_blk, qi * bq, ki * block_k, causal=causal,
                           scale=scale, valid_len=valid_len, kv_len=kv_len)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - new_m)
        alpha = jnp.exp(m - new_m)
        num = num * alpha + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32)
        den = den * alpha + jnp.sum(p, axis=-1, keepdims=True)
        return new_m, num, den

    # per-row stats stay [bq, 1] columns (sublane-major, like the score
    # tile's rows) from the loop carry to the lse store
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    num0 = jnp.zeros((bq, dv), jnp.float32)
    den0 = jnp.zeros((bq, 1), jnp.float32)
    if causal and bq == block_k:
        # blocks strictly above the diagonal contribute nothing
        n_k = qi + 1
    m, num, den = jax.lax.fori_loop(0, n_k, body, (m0, num0, den0))
    den_safe = jnp.maximum(den, 1e-20)
    o_ref[0] = (num / den_safe).astype(o_ref.dtype)
    l_ref[0] = m + jnp.log(den_safe)


def _fa_forward(q3, k3, v3, causal, scale, valid_len, interpret,
                config=None):
    """q3 [BH, Sq, D], k3 [BH, Sk, D], v3 [BH, Sk, Dv] -> (o [BH, Sq, Dv],
    lse [BH, Sq]): the scores use D, the values and the output Dv (latent
    attention has 192-wide q/k heads and 128-wide v heads).
    Sq may differ from Sk (ring-attention block chaining); causal requires
    Sq == Sk (aligned positions).

    Inside the pallas_calls the per-row operands (lse, delta) ride as
    [BH, S, 1] columns: Mosaic wants a block's last two dims divisible by
    (8, 128) or equal to the array's, and (block_q, 1) over [S, 1] is —
    the natural (1, block_q) block over [BH, S] is not."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    BH, Sq, D = q3.shape
    Sk, Dv = k3.shape[1], v3.shape[2]
    block_q, block_k = _blocks_from_config(config, Sq, Sk)
    kernel = functools.partial(_fa_kernel, causal=causal, scale=scale,
                               block_k=block_k, kv_len=Sk,
                               valid_len=valid_len)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, Dv), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, Dv), q3.dtype),
            jax.ShapeDtypeStruct((BH, Sq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return o, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 recompute scheme)
#
# With p = exp(s - lse):  dv = p^T dO;  dp = dO v^T;
# ds = p * (dp - delta) * scale where delta = rowsum(dO * o) - dlse;
# dq = ds k;  dk = ds^T q.  All tiles [block_q, block_k] in VMEM.


def _dot_tn(a, b):
    """a^T @ b as one dot_general contracting the row axis of both — the
    transposed-lhs form Mosaic feeds the MXU directly (no [bk, bq]
    transpose materialised in VMEM)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, dl_ref,
                       dk_ref, dv_ref, *, causal, scale, block_q,
                       q_len, kv_len, valid_len):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)          # [BLOCK_K, D]
    v_blk = v_ref[0].astype(jnp.float32)          # [BLOCK_K, Dv]
    bk, d = k_blk.shape
    n_q = q_len // block_q

    def body(qi, acc):
        dk, dv = acc
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = l_ref[0, pl.ds(qi * block_q, block_q), :]       # [bq, 1]
        delta = dl_ref[0, pl.ds(qi * block_q, block_q), :]
        s = _masked_scores(q, k_blk, qi * block_q, ki * bk, causal=causal,
                           scale=scale, valid_len=valid_len, kv_len=kv_len)
        p = jnp.exp(s - lse)
        dv = dv + _dot_tn(p, do)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk = dk + _dot_tn(ds, q)
        return dk, dv

    start = (ki * bk) // block_q if (causal and bk == block_q) else 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, v_blk.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(start, n_q, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, dl_ref,
                      dq_ref, *, causal, scale, block_k, kv_len,
                      valid_len):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)              # [BLOCK_Q, D]
    do = do_ref[0].astype(jnp.float32)            # [BLOCK_Q, Dv]
    lse = l_ref[0]                                # [BLOCK_Q, 1]
    delta = dl_ref[0]
    bq, d = q.shape
    n_k = kv_len // block_k

    def body(ki, dq):
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = _masked_scores(q, k_blk, qi * bq, ki * block_k, causal=causal,
                           scale=scale, valid_len=valid_len, kv_len=kv_len)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    if causal and bq == block_k:
        n_k = qi + 1
    dq = jax.lax.fori_loop(0, n_k, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _fa_backward(q3, k3, v3, do3, lse, delta, causal, scale, valid_len,
                 interpret, config=None):
    from jax.experimental import pallas as pl

    BH, Sq, D = q3.shape
    Sk, Dv = k3.shape[1], v3.shape[2]
    block_q, block_k = _blocks_from_config(config, Sq, Sk)
    lse = lse[:, :, None]          # [BH, Sq, 1] columns, see _fa_forward
    delta = delta[:, :, None]
    dkv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, q_len=Sq, kv_len=Sk,
                          valid_len=valid_len),
        grid=(BH, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0)),     # q
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),  # k blk
            pl.BlockSpec((1, block_k, Dv), lambda b, i: (b, i, 0)),  # v blk
            pl.BlockSpec((1, Sq, Dv), lambda b, i: (b, 0, 0)),    # do
            pl.BlockSpec((1, Sq, 1), lambda b, i: (b, 0, 0)),     # lse
            pl.BlockSpec((1, Sq, 1), lambda b, i: (b, 0, 0)),     # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k3.dtype),
            jax.ShapeDtypeStruct((BH, Sk, Dv), v3.dtype),
        ],
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, causal=causal, scale=scale,
                          block_k=block_k, kv_len=Sk, valid_len=valid_len),
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),  # q blk
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),     # k
            pl.BlockSpec((1, Sk, Dv), lambda b, i: (b, 0, 0)),    # v
            pl.BlockSpec((1, block_q, Dv), lambda b, i: (b, i, 0)),  # do blk
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # lse
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),  # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)
    return dq, dkv[0], dkv[1]


# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q3, k3, v3, causal, scale, valid_len, config=None):
    """q/k [BH, S, D], v [BH, S, Dv] -> (o [BH, S, Dv], lse [BH, S]);
    S % block == 0."""
    return _fa_forward(q3, k3, v3, causal, scale, valid_len,
                       interpret=not on_tpu(), config=config)


def _flash_fwd(q3, k3, v3, causal, scale, valid_len, config=None):
    o, lse = _flash(q3, k3, v3, causal, scale, valid_len, config)
    return (o, lse), (q3, k3, v3, o, lse)


def _flash_bwd(causal, scale, valid_len, config, res, cots):
    q3, k3, v3, o, lse = res
    do3, dlse = cots
    # delta folds the lse cotangent: ds = p * (dp - rowsum(do*o) + dlse)
    delta = jnp.einsum("bsd,bsd->bs", do3.astype(jnp.float32),
                       o.astype(jnp.float32))
    if dlse is not None:
        delta = delta - dlse
    dq, dk, dv = _fa_backward(q3, k3, v3, do3, lse, delta, causal, scale,
                              valid_len, interpret=not on_tpu(),
                              config=config)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _pad_seq(x, S_pad):
    B, S, H, D = x.shape
    if S == S_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             config=None):
    """q/k: [batch, seq, heads, D], v: [batch, seq, heads, Dv] (Dv may
    differ from D) -> (out [B, S, H, Dv], lse [B, H, S]). ``scale``
    multiplies the scores; None means D ** -0.5.

    Any sequence length: S pads up to the block width internally; padded
    k positions are masked inside the kernels and padded q rows sliced off.
    The lse output makes per-block results mergeable (ring attention).
    ``config`` is a paddle_tpu.tune "flash_attention" pick
    ({block_q, block_k}); None keeps the 128x128 defaults.
    """
    B, S, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[3]
    if causal and S != Sk:
        raise ValueError("causal flash attention needs q/k aligned lengths")
    scale = scale if scale is not None else D ** -0.5
    bq, bk = _blocks_from_config(config, S, Sk)
    S_pad = ((S + bq - 1) // bq) * bq
    Sk_pad = ((Sk + bk - 1) // bk) * bk
    frozen = tuple(sorted(dict(config).items())) if config else None
    q3 = _pad_seq(q, S_pad).transpose(0, 2, 1, 3).reshape(B * H, S_pad, D)
    k3 = _pad_seq(k, Sk_pad).transpose(0, 2, 1, 3).reshape(B * H, Sk_pad, D)
    v3 = _pad_seq(v, Sk_pad).transpose(0, 2, 1, 3).reshape(B * H, Sk_pad, Dv)
    o3, lse = _flash(q3, k3, v3, causal, float(scale), Sk, frozen)
    o = o3.reshape(B, H, S_pad, Dv)[:, :, :S].transpose(0, 2, 1, 3)
    return o, lse.reshape(B, H, S_pad)[:, :, :S]


def flash_attention(q, k, v, causal=False, scale=None, config=None):
    """q/k: [batch, seq, heads, D], v: [batch, seq, heads, Dv] ->
    [batch, seq, heads, Dv].

    Pallas streamed-softmax forward on TPU (interpret mode elsewhere),
    Pallas recompute backward (dq/dk/dv kernels) — no [S, S] buffer in
    either direction, any sequence length."""
    return flash_attention_with_lse(q, k, v, causal, scale, config)[0]
