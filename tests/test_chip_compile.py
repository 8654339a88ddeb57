"""The six Pallas kernels, compiled by the TPU's own compiler for a
*described* v5e (no chip attached), at the real widths the chip smoke and
the benchmark cells use. A compile that passes here is not a chip run: it
proves Mosaic accepts the kernel (block shapes, dot forms, VMEM budget),
nothing about results or times. ``chip_smoke.py`` is the chip run.

Everything that touches the TPU library lives in the module-scoped
fixtures below (never at import or collection time): only the xdist
worker that is handed this file loads libtpu, and it compiles in its own
process. Keep every described-topology compile in THIS file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the first described chip; the persistent compile cache
    is off around these compiles (an entry compiled for a described chip
    is written but cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernel_module(name):
    # the package re-exports some kernels under their module's own name
    import importlib
    return importlib.import_module("paddle_tpu.kernels." + name)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


FLASH_SHAPES = [((96, 1024, 64), jnp.bfloat16), ((96, 1024, 64), jnp.float32),
                ((32, 2048, 128), jnp.bfloat16),
                ((32, 2048, 128), jnp.float32)]


@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES)
def test_flash_attention_fwd_compiles(one_chip, shape, dtype):
    fa = _kernel_module("flash_attention")

    def fwd(q, k, v):
        return fa._fa_forward(q, k, v, True, shape[-1] ** -0.5, shape[1],
                              interpret=False)

    _compile(fwd, one_chip, *[(shape, dtype)] * 3)


@pytest.mark.parametrize("shape,dtype", FLASH_SHAPES)
def test_flash_attention_bwd_compiles(one_chip, shape, dtype):
    """Both backward kernels (dK/dV and dQ) in one program."""
    fa = _kernel_module("flash_attention")

    def bwd(q, k, v, do, lse, delta):
        return fa._fa_backward(q, k, v, do, lse, delta, True,
                               shape[-1] ** -0.5, shape[1], interpret=False)

    row = (shape[:2], jnp.float32)
    text = _compile(bwd, one_chip, *[(shape, dtype)] * 4, row, row)
    assert text.count("tpu_custom_call") >= 2


# latent attention's heads: q/k 192 wide (128 + 64 rotary), v 128, at the
# benchmark cell's own shape (2 rows x 32 heads, 4,096 tokens), and the
# 24 + 8 / 16 heads of the small tests
LATENT_SHAPES = [(64, 4096, 192, 128, jnp.bfloat16),
                 (8, 256, 32, 16, jnp.float32)]


@pytest.mark.parametrize("bh,s,d,dv,dtype", LATENT_SHAPES)
def test_flash_attention_narrower_v_heads_compile(one_chip, bh, s, d, dv,
                                                  dtype):
    """Forward and both backward kernels with Dv != D: the scores use D,
    ``num`` / ``o`` / ``dV`` are [block, Dv]; nothing is padded to D."""
    fa = _kernel_module("flash_attention")

    def fwd(q, k, v):
        return fa._fa_forward(q, k, v, True, d ** -0.5, s, interpret=False)

    def bwd(q, k, v, do, lse, delta):
        return fa._fa_backward(q, k, v, do, lse, delta, True, d ** -0.5, s,
                               interpret=False)

    qk, vv, row = ((bh, s, d), dtype), ((bh, s, dv), dtype), \
        ((bh, s), jnp.float32)
    text = _compile(fwd, one_chip, qk, qk, vv)
    assert "%d,%d,%d" % (bh, s, dv) in text.replace(" ", "")
    text = _compile(bwd, one_chip, qk, qk, vv, vv, row, row)
    assert text.count("tpu_custom_call") >= 2


def _flash_programs(fa, bh, s, d, dv, config=None):
    """(fn, shapes) of the forward and of both backward kernels, bf16,
    causal, at batch x heads ``bh`` (large enough that XLA leaves the
    operands in HBM, as a training step's are)."""
    def fwd(q, k, v):
        return fa._fa_forward(q, k, v, True, d ** -0.5, s, interpret=False,
                              config=config)

    def bwd(q, k, v, do, lse, delta):
        return fa._fa_backward(q, k, v, do, lse, delta, True, d ** -0.5, s,
                               interpret=False, config=config)

    qk, vv, row = ((bh, s, d), jnp.bfloat16), ((bh, s, dv), jnp.bfloat16), \
        ((bh, s), jnp.float32)
    return (fwd, (qk, qk, vv)), (bwd, (qk, qk, vv, vv, row, row))


@pytest.mark.parametrize("bh,s,d,dv", [(32, 8192, 192, 128),
                                       (32, 8192, 64, 64),
                                       (128, 2048, 128, 128)])
def test_flash_attention_rule_picks_compile(one_chip, bh, s, d, dv):
    """What ``default_blocks`` picks on a tune-cache miss (512 x 512 where
    its VMEM reckoning grants it, a size down where 12 MB of whole-sequence
    operands leave no room) Mosaic compiles, forward and backward."""
    fa = _kernel_module("flash_attention")
    for fn, shapes in _flash_programs(fa, bh, s, d, dv):
        _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("window", [2048, None], ids=["w2048", "full"])
@pytest.mark.parametrize("kernels", ["fwd", "bwd"])
def test_flash_attention_grouped_heads_and_window_compile(one_chip, kernels,
                                                          window):
    """The three kernels at the window / full cell's own shape: 32 query
    heads on 4 key/value heads of 128, 8,192 positions, bf16, under a
    2,048-key window and under none. k and v enter with 4 heads (nothing
    repeats them), the rule's blocks are 512 x 512, and the dK/dV kernel
    leaves a q head's part in f32."""
    fa = _kernel_module("flash_attention")
    h, hkv, s, d = 32, 4, 8192, 128
    for kernel in fa.KERNELS:
        assert fa.default_blocks(kernel, s, s, d, d, jnp.bfloat16, True,
                                 h // hkv, window) == (512, 512)
        assert fa.vmem_bytes(kernel, 512, 512, s, s, d, d, 2,
                             h // hkv) <= fa.VMEM_LIMIT

    def fwd(q, k, v):
        return fa._fa_forward(q, k, v, True, d ** -0.5, s, interpret=False,
                              window=window)

    def bwd(q, k, v, do, lse, delta):
        return fa._fa_backward(q, k, v, do, lse, delta, True, d ** -0.5, s,
                               interpret=False, window=window)

    q, kv = ((h, s, d), jnp.bfloat16), ((hkv, s, d), jnp.bfloat16)
    row = ((h, s), jnp.float32)
    if kernels == "fwd":
        text = _compile(fwd, one_chip, q, kv, kv)
    else:
        text = _compile(bwd, one_chip, q, kv, kv, q, row, row)
        assert text.count("tpu_custom_call") >= 2
        assert "f32[%d,%d,%d]" % (h, s, d) in text      # per q head, f32


def test_flash_attention_vmem_reckoning_refuses_before_mosaic(one_chip,
                                                              monkeypatch):
    """8,192 positions of 192 / 128-wide heads at 512 x 512: Mosaic
    refuses the forward and the dK/dV kernel (scoped vmem; my chip run,
    PR 33, agrees), and ``vmem_bytes`` says so first, so ``_blocks``
    degrades such a winner instead of handing it on."""
    fa = _kernel_module("flash_attention")
    bh, s, d, dv = 32, 8192, 192, 128
    for kernel in ("fwd", "dkv"):
        assert fa.vmem_bytes(kernel, 512, 512, s, s, d, dv, 2) \
            > fa.VMEM_LIMIT
    cfg = {"block_q": 512, "block_k": 512}
    for fn, shapes in _flash_programs(fa, bh, s, d, dv, cfg):
        _compile(fn, one_chip, *shapes)         # degraded: compiles
    monkeypatch.setattr(fa, "VMEM_LIMIT", 1 << 40)  # hand it on anyway
    for fn, shapes in _flash_programs(fa, bh, s, d, dv, cfg):
        with pytest.raises(Exception, match="vmem"):
            _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("kernel", ["lstm", "gru"])
def test_fused_rnn_compiles(one_chip, kernel):
    """The stacked-LSTM cell shape: T100, N64, D512, f32."""
    T, N, D = 100, 64, 512
    f32 = jnp.float32
    if kernel == "lstm":
        from paddle_tpu.kernels.fused_lstm import _forward

        def fwd(xs, w, h0, c0, mask):
            return _forward(xs, w, h0, c0, mask, False)[:2]

        shapes = [((T, N, 4 * D), f32), ((D, 4 * D), f32), ((N, D), f32),
                  ((N, D), f32), ((T, N), f32)]
    else:
        from paddle_tpu.kernels.fused_gru import _forward

        def fwd(xs, w, h0, mask):
            return _forward(xs, w, h0, mask, False)[0]

        shapes = [((T, N, 3 * D), f32), ((D, 3 * D), f32), ((N, D), f32),
                  ((T, N), f32)]
    _compile(fwd, one_chip, *shapes)


@pytest.mark.parametrize("R,MB,T,nh,dh,dtype", [
    (8, 8, 16, 2, 16, jnp.float32),
    (8, 64, 16, 12, 64, jnp.float32),
    (8, 64, 16, 12, 64, jnp.bfloat16),
    (32, 128, 16, 16, 128, jnp.bfloat16),
])
def test_paged_attention_compiles(one_chip, R, MB, T, nh, dh, dtype):
    pa = _kernel_module("paged_attention")

    pages = R * MB + 1
    br, bkv = pa.resolve_block_config(pa.DEFAULT_CONFIG, R, MB)

    def fwd(q, kp, vp, tables, pos):
        return pa._pa_pallas(q, kp, vp, tables, pos, br, bkv, False)

    _compile(fwd, one_chip, ((R, nh, dh), dtype),
             ((pages, T, nh, dh), dtype), ((pages, T, nh, dh), dtype),
             ((R, MB), jnp.int32), ((R,), jnp.int32))


@pytest.mark.parametrize("H,C", [(56, 64), (28, 128), (14, 256), (7, 512)])
def test_conv3x3_compiles(one_chip, H, C):
    from paddle_tpu.kernels.conv3x3 import _conv3x3_fwd

    def fwd(x, w):
        return _conv3x3_fwd(x, w, interpret=False)

    _compile(fwd, one_chip, ((128, H, H, C), jnp.bfloat16),
             ((3, 3, C, C), jnp.bfloat16))


@pytest.mark.parametrize("n,config", [
    (1024, None),
    (4096, (("block_k", 512), ("block_m", 256), ("block_n", 256))),
])
def test_matmul_compiles(one_chip, n, config):
    from paddle_tpu.kernels.matmul import _matmul_fwd

    def fwd(x, w):
        return _matmul_fwd(x, w, interpret=False, config=config)

    _compile(fwd, one_chip, ((n, n), jnp.bfloat16), ((n, n), jnp.bfloat16))
