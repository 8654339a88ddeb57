"""The nine Pallas kernels, compiled by the TPU's own compiler for a
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
import re

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


@pytest.mark.parametrize("kernels", ["fwd", "bwd"])
def test_flash_attention_groups_of_16_compile(one_chip, kernels):
    """The three kernels at the hybrid cell's attention layer: 32 query
    heads on 2 key/value heads of 128 (groups of 16), 8,192 positions,
    bf16, causal, no window, at the blocks the rule picks and the scoped
    VMEM it reckons."""
    fa = _kernel_module("flash_attention")
    h, hkv, s, d = 32, 2, 8192, 128
    blocks = {kernel: fa.default_blocks(kernel, s, s, d, d, jnp.bfloat16,
                                        True, h // hkv)
              for kernel in fa.KERNELS}
    assert blocks["fwd"] == (512, 512)

    def fwd(q, k, v):
        return fa._fa_forward(q, k, v, True, d ** -0.5, s, interpret=False)

    def bwd(q, k, v, do, lse, delta):
        return fa._fa_backward(q, k, v, do, lse, delta, True, d ** -0.5, s,
                               interpret=False)

    q, kv = ((h, s, d), jnp.bfloat16), ((hkv, s, d), jnp.bfloat16)
    row = ((h, s), jnp.float32)
    if kernels == "fwd":
        _compile(fwd, one_chip, q, kv, kv)
    else:
        assert _compile(bwd, one_chip, q, kv, kv, q, row, row).count(
            "tpu_custom_call") >= 2


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


def _long_row_programs(fa, window):
    """(fn, shapes) of the forward and of both backward kernels at the
    published heads of 28 q / 4 k/v heads of 128, 16,384 positions, bf16,
    causal, under ``window``."""
    h, hkv, s, d = 28, 4, 16384, 128

    def fwd(q, k, v):
        return fa._fa_forward(q, k, v, True, d ** -0.5, s, interpret=False,
                              window=window)

    def bwd(q, k, v, do, lse, delta):
        return fa._fa_backward(q, k, v, do, lse, delta, True, d ** -0.5, s,
                               interpret=False, window=window)

    q, kv = ((h, s, d), jnp.bfloat16), ((hkv, s, d), jnp.bfloat16)
    row = ((h, s), jnp.float32)
    return (fwd, (q, kv, kv)), (bwd, (q, kv, kv, q, row, row))


@pytest.mark.parametrize("window", [4096, None], ids=["w4096", "full"])
def test_flash_attention_rows_of_16384_compile(one_chip, window):
    """The three kernels at the prerouted cell's shape, 28 q heads on 4 k/v
    heads of 128 and 16,384 positions, under its 4,096-key window and under
    none: the rule's blocks are 512 x 512, which no scoped VMEM of Mosaic's
    default holds, and each launch asks for its reckoning."""
    fa = _kernel_module("flash_attention")
    for kernel in fa.KERNELS:
        assert fa.default_blocks(kernel, 16384, 16384, 128, 128,
                                 jnp.bfloat16, True, 7, window) == (512, 512)
        assert fa.vmem_limit(kernel, 512, 512, 16384, 16384, 128, 128, 2,
                             7) > fa.VMEM_LIMIT
    (fwd, f_shapes), (bwd, b_shapes) = _long_row_programs(fa, window)
    _compile(fwd, one_chip, *f_shapes)
    assert _compile(bwd, one_chip, *b_shapes).count("tpu_custom_call") >= 2


@pytest.mark.parametrize("less", [None, 4 * 2 ** 20],
                         ids=["default", "reckoning_less_4MiB"])
def test_flash_attention_rows_of_16384_need_what_is_reckoned(
        one_chip, monkeypatch, less):
    """The same launches at 512 x 512 under Mosaic's default scoped VMEM,
    and with 4 MiB less than ``vmem_bytes`` reckons: Mosaic refuses every
    one (the reckoning errs high by 1-3 MiB at this shape)."""
    fa = _kernel_module("flash_attention")
    real = fa.vmem_limit
    monkeypatch.setattr(fa, "vmem_limit", lambda *a: None if less is None
                        else real(*a) - less)
    fa._forward_launch.cache_clear()
    try:
        for fn, shapes in _long_row_programs(fa, 4096):
            with pytest.raises(Exception, match="vmem"):
                _compile(fn, one_chip, *shapes)
    finally:
        fa._forward_launch.cache_clear()


# the expert layers of the language-model cells: (d, f, held experts) and
# their first and top rungs
GMM_CELLS = {"K": (2048, 768, 16, (12288, 49152)),
             "T": (2048, 1024, 16, (16384, 65536)),
             "P": (2560, 768, 8, (24576, 98304)),
             "N": (2688, 1856, 8, (6144, 49152))}


@pytest.mark.parametrize("cell,rung", [(c, i) for c in GMM_CELLS
                                       for i in (0, 1)],
                         ids=["%s-%s" % (c, r) for c in GMM_CELLS
                              for r in ("first", "top")])
def test_grouped_matmul_kernels_compile(one_chip, monkeypatch, cell, rung):
    """The three grouped-matmul kernels (product, left operand's gradient,
    weight gradient) of an expert layer's gate and down products, bf16 rows
    and f32 master weights (cast in VMEM), at the cell's first and top
    rung, at the tilings the rule picks: one differentiated program, six
    launches."""
    gm = _kernel_module("grouped_matmul")
    monkeypatch.setattr(gm, "on_tpu", lambda: True)
    d, f, groups, rungs = GMM_CELLS[cell]
    rows = rungs[rung]

    def step(x, w_in, w_out, sizes):
        def loss(x, w_in, w_out):
            h = gm.grouped_matmul(x, w_in, sizes).astype(jnp.bfloat16)
            return jnp.sum(gm.grouped_matmul(h, w_out, sizes))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w_in, w_out)

    text = _compile(step, one_chip, ((rows, d), jnp.bfloat16),
                    ((groups, d, f), jnp.float32),
                    ((groups, f, d), jnp.float32), ((groups,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') >= 6


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


def _forward_kernels(text):
    """Forward flash launches of a compiled module: the Pallas calls whose
    result is (o, lse as a [BH, S, 1] f32 column)."""
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and re.search(
                r"= \(\w+\[[\d,]+\]\S*, f32\[\d+,\d+,1\]", line)]


@pytest.mark.parametrize("keep", [True, False], ids=["kept", "recomputed"])
@pytest.mark.parametrize("model", ["latent", "window"])
def test_a_recomputed_layer_shares_the_forward_op_s_flash_launch(
        one_chip, monkeypatch, model, keep):
    """A tiny decoder's whole training step (latent attention; grouped
    heads on two window layers and a full one) under pure AMP and
    ``memory_optimize``: with its o and lse kept, each of the 3 layers has
    ONE forward launch after XLA (the forward op's and the one its
    gradient replays are the same kernel text, and XLA merges them);
    recomputing everything, two."""
    import paddle_tpu as pt
    from paddle_tpu import layers as L
    from paddle_tpu.core.executor import RngSource, trace_ops
    from paddle_tpu.models.latent_moe_lm import latent_moe_lm
    from paddle_tpu.models.window_moe_lm import window_moe_lm
    from paddle_tpu.ops import generic_grad
    import test_latent_moe_lm
    import test_window_moe_lm
    build, tests = {"latent": (latent_moe_lm, test_latent_moe_lm),
                    "window": (window_moe_lm, test_window_moe_lm)}[model]
    fa = _kernel_module("flash_attention")
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    if not keep:
        monkeypatch.setattr(generic_grad, "_KEEP", None)
    seq = 256
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        tokens = L.data("tokens", shape=[seq], dtype="int64")
        labels = L.data("labels", shape=[seq], dtype="int64")
        out = build(tokens, tests.tiny(False), labels=labels)
        pt.amp.enable(main, pure=True)
        pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(out["loss"])
        pt.memory_optimize(main, remat_types=tests.HALVES)
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        state = {n: jax.ShapeDtypeStruct(
            scope.find_var(n).shape, scope.find_var(n).dtype,
            sharding=one_chip) for n in exe._state_inputs(
                main, scope, {"tokens", "labels"})}
    feed = {n: jax.ShapeDtypeStruct((2, seq), jnp.int32, sharding=one_chip)
            for n in ("tokens", "labels")}

    def step(state, feed):
        env = dict(feed, **state)
        trace_ops(main.global_block(), env, RngSource(jax.random.PRNGKey(0)))
        return {n: env[n] for n in state}

    prev = pt.amp.force(True)
    try:
        text = jax.jit(step).lower(state, feed).compile().as_text()
    finally:
        pt.amp.force(prev)
    assert len(_forward_kernels(text)) == (3 if keep else 6)


def test_the_grouped_products_carry_the_experts_scope_in_both_phases(
        one_chip, monkeypatch):
    """A training step of one ``moe_ffn`` that holds 4 of 32 experts (three
    rungs behind the switch), recomputed, under pure AMP: every
    grouped-matmul launch after XLA is a custom call whose scope is
    ``forward/moe_ffn/experts`` or ``backward/moe_ffn/experts``, and each
    branch of each phase has its rung's launches (forward: 3 products;
    backward: the 3 replayed, 3 left-operand and 3 weight gradients)."""
    import collections
    import paddle_tpu as pt
    from paddle_tpu import layers as L, profiler
    from paddle_tpu.core.executor import RngSource, trace_ops
    gm = _kernel_module("grouped_matmul")
    monkeypatch.setattr(gm, "on_tpu", lambda: True)
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[384, 64], dtype="float32")
        x.stop_gradient = False
        out, _load, _held = L.moe_ffn(x, 32, 4, 32, 64, experts_held=(6, 4),
                                      scaling=2.448, prefix="m")
        pt.amp.enable(main, pure=True)
        pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
            L.mean(out * out))
        pt.memory_optimize(main, remat_types=("moe_ffn",))
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        state = {n: jax.ShapeDtypeStruct(
            scope.find_var(n).shape, scope.find_var(n).dtype,
            sharding=one_chip) for n in exe._state_inputs(main, scope,
                                                          {"x"})}
    feed = {"x": jax.ShapeDtypeStruct((2, 384, 64), jnp.float32,
                                      sharding=one_chip)}

    def step(state, feed):
        env = dict(feed, **state)
        trace_ops(main.global_block(), env, RngSource(jax.random.PRNGKey(0)))
        return {n: env[n] for n in state}

    prev = pt.amp.force(True)
    try:
        text = jax.jit(step).lower(state, feed).compile().as_text()
    finally:
        pt.amp.force(prev)
    _module, table = profiler.scopes_of_module(text)
    launches = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?(grouped_(\w+?))\.\d+ = ", line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            name = line.split("=", 1)[0].strip().lstrip("%")
            launches[(table[name], m.group(2))] += 1
    rungs = 3
    assert launches == {("forward/moe_ffn/experts", "gmm"): 3 * rungs,
                        ("backward/moe_ffn/experts", "gmm"): 3 * rungs,
                        ("backward/moe_ffn/experts", "gmm_t"): 3 * rungs,
                        ("backward/moe_ffn/experts", "tgmm"): 3 * rungs}


# an expert layer of each language-model cell: tokens, picks, width, held
# experts, its rungs (first, top)
PICK_CELLS = {"K": (8192, 6, 2048, 16, (12288, 49152)),
              "T": (8192, 8, 2048, 16, (16384, 65536)),
              "P": (16384, 6, 2560, 8, (24576, 98304)),
              "N": (8192, 6, 2688, 8, (6144, 49152))}


@pytest.mark.parametrize("cell,rung", [(c, i) for c in PICK_CELLS
                                       for i in (0, 1)],
                         ids=["%s-%s" % (c, r) for c in PICK_CELLS
                              for r in ("first", "top")])
def test_pick_sum_kernel_compiles(one_chip, monkeypatch, cell, rung):
    """The held-pick sums of an expert layer, bf16 rows, at the cell's
    first and top rung and the token tile the rule picks: the combine's
    (an f32 result) and the handed rows' gradient's (bf16, added up in an
    f32 scratch), each the runs found before the launch and the launch (a
    raised scoped VMEM where ``vmem_bytes`` passes Mosaic's default)."""
    ps = _kernel_module("pick_sum")
    monkeypatch.setattr(ps, "on_tpu", lambda: True)
    T, k, d, groups, rungs = PICK_CELLS[cell]

    def both(a, inv, sizes):
        return (ps.pick_sum(a, inv, sizes),
                ps.pick_sum(a, inv, sizes, jnp.bfloat16))

    text = _compile(both, one_chip, ((rungs[rung], d), jnp.bfloat16),
                    ((T, k), jnp.int32), ((groups,), jnp.int32))
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_the_pick_sums_carry_the_route_scope_and_leave_no_pick_copy(
        one_chip, monkeypatch):
    """A training step of one ``moe_ffn`` that holds 4 of 32 experts (three
    rungs behind the switch), recomputed, under pure AMP: each rung's sum
    of the held picks is a ``pick_sum`` launch in scope
    ``forward/moe_ffn/route`` (the combine) and ``backward/moe_ffn/route``
    (the dispatch's gradient; the replayed combine is dead there), and no
    instruction of the step makes a [T, k, d] array (768 tokens x 4 picks
    x 64)."""
    import collections
    import paddle_tpu as pt
    from paddle_tpu import layers as L, profiler
    from paddle_tpu.core.executor import RngSource, trace_ops
    from paddle_tpu.ops import decoder_ops
    for name in ("grouped_matmul", "pick_sum"):
        monkeypatch.setattr(_kernel_module(name), "on_tpu", lambda: True)
    decoder_ops._held_part.cache_clear()
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[384, 64], dtype="float32")
        x.stop_gradient = False
        out, _load, _held = L.moe_ffn(x, 32, 4, 32, 64, experts_held=(6, 4),
                                      scaling=2.448, prefix="m")
        pt.amp.enable(main, pure=True)
        pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
            L.mean(out * out))
        pt.memory_optimize(main, remat_types=("moe_ffn",))
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        state = {n: jax.ShapeDtypeStruct(
            scope.find_var(n).shape, scope.find_var(n).dtype,
            sharding=one_chip) for n in exe._state_inputs(main, scope,
                                                          {"x"})}
    feed = {"x": jax.ShapeDtypeStruct((2, 384, 64), jnp.float32,
                                      sharding=one_chip)}

    def step(state, feed):
        env = dict(feed, **state)
        trace_ops(main.global_block(), env, RngSource(jax.random.PRNGKey(0)))
        return {n: env[n] for n in state}

    prev = pt.amp.force(True)
    try:
        text = jax.jit(step).lower(state, feed).compile().as_text()
    finally:
        pt.amp.force(prev)
        decoder_ops._held_part.cache_clear()
    _module, table = profiler.scopes_of_module(text)
    launches = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?(pick_sum)\.\d+ = ", line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            launches[table[line.split("=", 1)[0].strip().lstrip("%")]] += 1
    rungs = 3
    assert launches == {"forward/moe_ffn/route": rungs,
                        "backward/moe_ffn/route": rungs}
    assert not re.search(r"\[768,4,64\]", text)


# the mixers' scan of the hybrid cell: 8,192 positions in chunks of 128, 64
# heads of 64 over 8 B/C groups of a 128-wide state, bf16 under AMP
SCAN_N = dict(seq=8192, chunk=128, heads=64, head_dim=64, groups=8,
              state=128)


def _scan_programs(ss, seq, chunk, heads, head_dim, groups, state):
    """(fn, shapes) of the primal launch alone and of the forward rule's
    launch with the backward's."""
    bf16 = jnp.bfloat16
    scan = lambda *v: ss.chunked_scan(*v, chunk, bf16)

    def vjp(x, delta, a, b, c):
        y, back = jax.vjp(scan, x, delta, a, b, c)
        return back(y)

    shapes = (((1, seq, heads, head_dim), bf16),
              ((1, seq, heads), jnp.float32), ((heads,), jnp.float32),
              ((1, seq, groups, state), bf16),
              ((1, seq, groups, state), bf16))
    return (scan, shapes), (vjp, shapes)


@pytest.mark.parametrize("program", ["primal", "vjp"])
def test_ssd_scan_kernels_compile(one_chip, monkeypatch, program):
    """The scan's forward kernel alone (the primal launch, y only), and the
    forward rule's launch (y and the entering states) with the backward
    kernel, at the hybrid cell's widths."""
    ss = _kernel_module("ssd_scan")
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    primal, vjp = _scan_programs(ss, **SCAN_N)
    fn, shapes = primal if program == "primal" else vjp
    text = _compile(fn, one_chip, *shapes)
    assert text.count('custom_call_target="tpu_custom_call"') == (
        1 if program == "primal" else 2)


def test_ssd_scan_vmem_reckoning_stays_under_mosaic_s_default(one_chip,
                                                              monkeypatch):
    """At the hybrid cell's widths the kernels reckon 2.15 and 3.16 MiB
    (the blocks twice, the carried state, chunk x chunk blocks and a
    head's blocks), under the 16 MiB Mosaic grants by default, so a launch asks for
    nothing and compiles under that default; a launch told to ask for its
    reckoning, rounded up to a MiB, compiles too."""
    ss = _kernel_module("ssd_scan")
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    widths = (SCAN_N["chunk"], SCAN_N["heads"] // SCAN_N["groups"],
              SCAN_N["head_dim"], SCAN_N["state"], 2)
    for kernel in ss.KERNELS:
        need = ss.vmem_bytes(kernel, *widths)
        assert 2 * 2 ** 20 < need < ss.VMEM_LIMIT
        assert ss.vmem_limit(kernel, *widths) is None
    real = ss.vmem_limit
    monkeypatch.setattr(ss, "vmem_limit", lambda *a: -(
        -ss.vmem_bytes(*a) // 2 ** 20) * 2 ** 20)
    ss._launch.cache_clear()
    try:
        for fn, shapes in _scan_programs(ss, **SCAN_N):
            _compile(fn, one_chip, *shapes)
    finally:
        monkeypatch.setattr(ss, "vmem_limit", real)
        ss._launch.cache_clear()


def test_the_scan_kernels_carry_the_scan_scope_in_both_phases(one_chip,
                                                              monkeypatch):
    """A training step of one ``ssm_mixer`` (8 heads of 64 over 2 groups
    of a 128-wide state, 512 positions in chunks of 128), recomputed, under
    pure AMP: the forward op makes the primal launch under
    ``forward/ssm_mixer/scan``; its gradient the forward rule's launch and
    the backward kernel under ``backward/ssm_mixer/scan``."""
    import collections
    import paddle_tpu as pt
    from paddle_tpu import layers as L, profiler
    from paddle_tpu.core.executor import RngSource, trace_ops
    ss = _kernel_module("ssd_scan")
    monkeypatch.setattr(ss, "on_tpu", lambda: True)
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        x = L.data("x", shape=[512, 256], dtype="float32")
        x.stop_gradient = False
        out = L.ssm_mixer(x, 8, 64, 2, 128, chunk_size=128, prefix="m")
        pt.amp.enable(main, pure=True)
        pt.optimizer.SGDOptimizer(learning_rate=0.0).minimize(
            L.mean(out * out))
        pt.memory_optimize(main, remat_types=("ssm_mixer",))
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        state = {n: jax.ShapeDtypeStruct(
            scope.find_var(n).shape, scope.find_var(n).dtype,
            sharding=one_chip) for n in exe._state_inputs(main, scope,
                                                          {"x"})}
    feed = {"x": jax.ShapeDtypeStruct((1, 512, 256), jnp.float32,
                                      sharding=one_chip)}

    def step(state, feed):
        env = dict(feed, **state)
        trace_ops(main.global_block(), env, RngSource(jax.random.PRNGKey(0)))
        return {n: env[n] for n in state}

    prev = pt.amp.force(True)
    try:
        text = jax.jit(step).lower(state, feed).compile().as_text()
    finally:
        pt.amp.force(prev)
    _module, table = profiler.scopes_of_module(text)
    launches = collections.Counter()
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?(ssd_scan_(\w+?))\.\d+ = ", line)
        if m and 'custom_call_target="tpu_custom_call"' in line:
            name = line.split("=", 1)[0].strip().lstrip("%")
            launches[(table[name], m.group(2))] += 1
    assert launches == {("forward/ssm_mixer/scan", "fwd"): 1,
                        ("backward/ssm_mixer/scan", "fwd"): 1,
                        ("backward/ssm_mixer/scan", "bwd"): 1}
