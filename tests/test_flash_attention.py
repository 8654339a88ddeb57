"""Pallas flash attention vs dense reference (interpret mode on CPU)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import tune
from paddle_tpu.kernels import flash_attention
from paddle_tpu.kernels.flash_attention import (_dense_reference,
                                                flash_attention_with_lse)

# the package attribute ``flash_attention`` is the function; this is the module
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def dense(q, k, v, causal):
    B, S, H, D = q.shape
    o = _dense_reference(
        q.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        k.transpose(0, 2, 1, 3).reshape(B * H, S, D),
        v.transpose(0, 2, 1, 3).reshape(B * H, S, D), causal, D ** -0.5)
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [128, 256])
def test_flash_matches_dense(causal, seq):
    rng = np.random.RandomState(0)
    B, H, D = 2, 2, 64
    q = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    want = dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_flash_grads_match_dense():
    rng = np.random.RandomState(1)
    B, S, H, D = 1, 128, 2, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)

    g1 = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(
            q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(
        dense(q, k, v, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_flash_odd_seq_fallback():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 100, 2, 16), jnp.float32)
    out = flash_attention(q, q, q, causal=True)
    want = dense(q, q, q, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_op_in_program():
    import paddle_tpu as fluid
    q = fluid.layers.data("q", shape=[128, 2, 32], dtype="float32")
    out_var = fluid.layers.data("qq", shape=[1], dtype="float32")  # unused
    helper_block = fluid.default_main_program().global_block()
    out = helper_block.create_var(name="attn_out", dtype="float32")
    helper_block.append_op(type="flash_attention",
                           inputs={"Q": ["q"], "K": ["q"], "V": ["q"]},
                           outputs={"Out": [out]},
                           attrs={"causal": True})
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(3)
    qv = rng.randn(2, 128, 2, 32).astype(np.float32)
    r, = exe.run(feed={"q": qv, "qq": np.zeros((1, 1), np.float32)},
                 fetch_list=["attn_out"])
    want = dense(jnp.asarray(qv), jnp.asarray(qv), jnp.asarray(qv), True)
    np.testing.assert_allclose(np.asarray(r), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [100, 256, 200])
def test_flash_bwd_kernel_grads_match_dense(causal, seq):
    """Pallas dq/dk/dv kernels (incl. ragged padding) vs dense vjp."""
    rng = np.random.RandomState(7)
    B, H, D = 2, 2, 32
    q = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)
    co = jnp.asarray(rng.randn(B, seq, H, D), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * co)

    def loss_dense(q, k, v):
        return jnp.sum(dense(q, k, v, causal) * co)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_flash_bwd_no_quadratic_buffer():
    """The backward jaxpr must not materialise any [S, S] tensor — the
    whole point of the recompute kernels (VERDICT r1 weak item 6). S is
    longer than the widest tile, which is a [block_q, block_k] VMEM value
    inside the kernels' own jaxprs."""
    S = 2048
    q = jnp.zeros((1, S, 2, 32), jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)

    def walk(jp):
        for eqn in jp.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                shape = tuple(getattr(var.aval, "shape", ()))
                assert not (len(shape) >= 2 and shape[-1] == S
                            and shape[-2] == S), \
                    "quadratic buffer %s in %s" % (shape, eqn.primitive)
            for sub in eqn.params.values():
                if hasattr(sub, "eqns"):
                    walk(sub)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    walk(sub.jaxpr)

    walk(jaxpr.jaxpr)


def test_flash_lse_merge_matches_full():
    """Two half-sequence flash calls merged via lse equal one full call —
    the ring-attention chaining identity, gradients included."""
    rng = np.random.RandomState(9)
    B, S, H, D = 1, 256, 2, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    co = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    q = q[:, :S // 2]        # one device's local q chunk (ring layout)
    co = co[:, :S // 2]

    def merged(q, k, v):
        o1, l1 = flash_attention_with_lse(q, k[:, :S // 2], v[:, :S // 2])
        o2, l2 = flash_attention_with_lse(q, k[:, S // 2:], v[:, S // 2:])
        lse = jnp.logaddexp(l1, l2)                    # [B, H, S]
        w1 = jnp.exp(l1 - lse).transpose(0, 2, 1)[..., None]
        w2 = jnp.exp(l2 - lse).transpose(0, 2, 1)[..., None]
        return o1 * w1 + o2 * w2

    def loss_m(q, k, v):
        return jnp.sum(merged(q, k, v) * co)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v) * co)

    np.testing.assert_allclose(np.asarray(merged(q, k, v)),
                               np.asarray(flash_attention(q, k, v)),
                               rtol=2e-4, atol=2e-5)
    g1 = jax.grad(loss_m, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


# -- v heads narrower than q/k heads (latent attention), explicit scale ------

def _dense_lse(q, k, v, causal, scale):
    """The dense composition for q/k [B, S, H, D], v [B, S, H, Dv]: (out
    [B, S, H, Dv], lse [B, H, S])."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        S = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s,
                      -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v),
            jax.scipy.special.logsumexp(s, axis=-1))


def _dense_dv(q, k, v, causal, scale):
    return _dense_lse(q, k, v, causal, scale)[0]


def _qkv_dv(seed, S, D, Dv):
    rng = np.random.RandomState(seed)
    B, H = 1, 2
    return (jnp.asarray(rng.randn(B, S, H, D), jnp.float32),
            jnp.asarray(rng.randn(B, S, H, D), jnp.float32),
            jnp.asarray(rng.randn(B, S, H, Dv), jnp.float32))


@pytest.mark.parametrize("D,Dv,S", [(192, 128, 256), (24, 16, 96)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_with_narrower_v_heads_matches_dense(D, Dv, S, causal):
    q, k, v = _qkv_dv(7, S, D, Dv)
    scale = 0.37 * D ** -0.5            # given, not the default
    out = flash_attention(q, k, v, causal=causal, scale=scale)
    assert out.shape == (1, S, 2, Dv)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_dense_dv(q, k, v, causal, scale)),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("D,Dv,S", [(192, 128, 128), (24, 16, 96)])
def test_flash_grads_with_narrower_v_heads_match_dense(D, Dv, S):
    q, k, v = _qkv_dv(8, S, D, Dv)
    scale = D ** -0.5
    w = jnp.asarray(np.random.RandomState(9).randn(1, S, 2, Dv), jnp.float32)
    g1 = jax.grad(lambda q, k, v: jnp.sum(w * flash_attention(
        q, k, v, causal=True, scale=scale)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(w * _dense_dv(
        q, k, v, True, scale)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4, err_msg="d" + name)


def test_flash_attention_layer_passes_scale_and_head_sizes():
    """``layers.flash_attention`` (the helper ``models.transformer`` and the
    latent block share) hands the op its scale; the output takes v's head
    size."""
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data("q", shape=[64, 2, 24], dtype="float32")
        v = fluid.layers.data("v", shape=[64, 2, 16], dtype="float32")
        out = fluid.layers.flash_attention(q, q, v, causal=True, scale=0.11)
    assert tuple(out.shape[1:]) == (64, 2, 16)
    qv, _k, vv = _qkv_dv(10, 64, 24, 16)
    got, = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"q": np.asarray(qv), "v": np.asarray(vv)},
        fetch_list=[out])
    np.testing.assert_allclose(
        got, np.asarray(_dense_dv(qv, qv, vv, True, 0.11)), rtol=2e-4,
        atol=2e-5)


# -- how the kernels tile the score matrix (PR 33) ---------------------------

# (Sq, Sk, D, Dv, causal, blocks): blocks None = the rule's own pick. Every
# shape the rule returns (one short tile, 128, 256, 512 wide), the
# non-square ones a tune winner may bring, lengths that are and are not a
# multiple of the tile (300 pads to 384, 640 = 5 x 128), both head shapes,
# the ring's Sq != Sk chunks
TILINGS = [
    (100, 100, 64, 64, True, None),
    (300, 300, 64, 64, True, None),
    (300, 300, 192, 128, False, None),
    (512, 512, 64, 64, True, None),
    (512, 512, 192, 128, True, None),
    (512, 512, 64, 64, False, None),
    (640, 640, 64, 64, True, None),
    (640, 640, 192, 128, False, None),
    (1024, 1024, 64, 64, True, None),
    (1024, 1024, 64, 64, True, (512, 512)),
    (512, 512, 192, 128, True, (256, 128)),
    (512, 512, 64, 64, True, (128, 256)),
    (512, 512, 64, 64, False, (128, 256)),
    (1024, 1024, 64, 64, True, (512, 256)),
    (1024, 1024, 64, 64, True, (256, 512)),
    (300, 300, 64, 64, True, (128, 384)),
    (300, 300, 64, 64, False, (384, 128)),
    (256, 384, 64, 64, False, None),
    (384, 640, 192, 128, False, (128, 128)),
    (512, 256, 64, 64, False, (256, 128)),
]


@pytest.mark.parametrize("Sq,Sk,D,Dv,causal,blocks", TILINGS)
def test_every_tiling_matches_dense(Sq, Sk, D, Dv, causal, blocks):
    """Output, lse and all three gradients (with a cotangent on lse too)
    against the dense composition."""
    rng = np.random.RandomState(Sq + Sk + D)
    B, H = 1, 2
    mk = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = mk(B, Sq, H, D), mk(B, Sk, H, D), mk(B, Sk, H, Dv)
    co, cl = mk(B, Sq, H, Dv), mk(B, H, Sq)
    cfg = blocks and {"block_q": blocks[0], "block_k": blocks[1]}
    scale = D ** -0.5
    tune.reset_counters()

    def loss(f):
        def inner(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * co) + jnp.sum(lse * cl)
        return inner

    flash = lambda q, k, v: flash_attention_with_lse(
        q, k, v, causal=causal, config=cfg)
    dense_ = lambda q, k, v: _dense_lse(q, k, v, causal, scale)
    for name, a, b in zip(("o", "lse"), flash(q, k, v), dense_(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(dense_), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4, err_msg="d" + name)
    if blocks:      # the blocks asked for are the blocks that ran
        want = "%dx%d" % (min(blocks[0], fa.padded_len(Sq)),
                          min(blocks[1], fa.padded_len(Sk)))
        counted = tune.counters()["flash_blocks"]
        assert {n.split()[1] for n in counted} == {want}, counted
        assert {n.split()[0] for n in counted} == set(fa.KERNELS)


@pytest.mark.parametrize("bq", [128, 256, 512])
@pytest.mark.parametrize("bk", [128, 256, 512])
@pytest.mark.parametrize("valid", [1024, 900])
def test_causal_kernels_visit_the_triangle_and_mask_its_edge(bq, bk, valid):
    """Whatever the two widths, a causal kernel visits exactly the tiles
    that hold a position at or under the diagonal, none above it, and
    masks exactly those the diagonal crosses (forward / dQ: k tiles per q
    block; dK/dV: q tiles per k block)."""
    S = 1024
    reach = lambda qi, ki: (qi + 1) * bq - 1 >= ki * bk      # any q >= k
    whole = lambda qi, ki: qi * bq >= (ki + 1) * bk - 1      # every q >= k
    want = {(qi, ki): not whole(qi, ki) for qi in range(S // bq)
            for ki in range(S // bk) if reach(qi, ki)}

    def visited(segments, pair):
        got = {}
        for masks, lo, hi in segments:
            for t in range(lo, hi):
                assert pair(t) not in got
                got[pair(t)] = bool(masks.get("causal"))
                # the padded-k mask rides only on tiles that hold padding
                assert (masks.get("valid_len") is not None) <= (
                    valid < S and bool(masks.get("causal")))
        return got

    got = {}
    for qi in range(S // bq):
        got.update(visited(fa._k_segments(qi * bq, bq, bk, S, valid, True),
                           lambda ki: (qi, ki)))
    assert got == want
    got = {}
    for ki in range(S // bk):
        got.update(visited(
            fa._q_segments(ki * bk, bk, bq, S // bq, True, None),
            lambda qi: (qi, ki)))
    assert got == want


def test_padded_k_mask_rides_only_on_the_tiles_that_hold_padding():
    # not causal, 900 of 1,024 positions valid, 256-wide k tiles: tiles
    # 0-2 are clear, tile 3 ([768, 1024)) masks its padded columns
    assert fa._k_segments(0, 256, 256, 1024, 900, False) == (
        ({}, 0, 3), ({"valid_len": 900}, 3, 4))
    assert fa._k_segments(0, 256, 256, 1024, 1024, False) == (
        ({}, 0, 4), ({"valid_len": None}, 4, 4))
    # dK/dV: only the k block that holds padding masks, and then every tile
    assert fa._q_segments(768, 256, 128, 8, False, 900) == (
        ({"causal": False, "valid_len": 900}, 0, 8),)
    assert fa._q_segments(512, 256, 128, 8, False, None) == (
        ({"causal": True}, 0, 0), ({}, 0, 8))


BF16 = jnp.bfloat16


@pytest.mark.parametrize("shape,want", [
    # the benchmark cell: 4,096 positions, 192 / 128-wide heads, bf16
    ((4096, 4096, 192, 128, BF16, True), (512, 512)),
    ((4096, 4096, 128, 128, BF16, False), (512, 512)),
    ((256, 256, 64, 64, BF16, True), (256, 256)),
    ((768, 768, 64, 64, BF16, True), (256, 256)),
    # 300 positions run at 384 = 3 x 128: nothing wider divides it
    ((384, 384, 64, 64, BF16, True), (128, 128)),
    ((640, 640, 64, 64, jnp.float32, False), (128, 128)),
    # one short tile is the whole sequence
    ((100, 100, 64, 64, BF16, True), (100, 100)),
    ((128, 128, 64, 64, BF16, True), (128, 128)),
    # the ring's chunks: each side by its own length
    ((256, 384, 64, 64, BF16, False), (256, 128)),
])
def test_default_blocks_table(shape, want):
    for kernel in fa.KERNELS:
        got = fa.default_blocks(kernel, *shape)
        assert got == want, kernel
        Sq, Sk = shape[:2]
        assert Sq % got[0] == 0 and Sk % got[1] == 0
        assert fa.vmem_bytes(kernel, *got, *shape[:4],
                             jnp.dtype(shape[4]).itemsize) <= fa.VMEM_LIMIT


def test_padded_len_never_exceeds_a_round_up_to_128():
    assert [fa.padded_len(s) for s in (1, 100, 128, 129, 300, 4096)] == [
        1, 100, 128, 256, 384, 4096]


def test_vmem_reckoning_refuses_what_the_chip_refused_and_falls_back():
    cell = (4096, 4096, 192, 128, 2)
    # PR 32's chip refused 512 x 512 in the backward: lse and delta rode as
    # [S, 1] f32 columns, 512 B a position, each held twice
    columns = 2 * 2 * fa._block_bytes(4096, 1, 4)
    assert columns == 8 * 2 ** 20
    assert fa.vmem_bytes("dkv", 512, 512, *cell) + columns > fa.VMEM_LIMIT
    # as rows they cost 16 KB a sequence and the same shape is granted
    assert fa.vmem_bytes("dkv", 512, 512, *cell) <= fa.VMEM_LIMIT
    # where the whole-sequence operands leave no room the rule falls back
    # a size, first on the side the kernel's grid walks: 8,192 positions of
    # 192 / 128-wide heads keep 12 MB of k and v (q and dO) resident
    long = (8192, 8192, 192, 128)
    picks = {k: fa.default_blocks(k, *long, BF16, True) for k in fa.KERNELS}
    assert picks == {"fwd": (256, 512), "dq": (256, 256), "dkv": (256, 256)}
    for kernel, pick in picks.items():
        assert fa.vmem_bytes(kernel, 512, 512, *long, 2) > fa.VMEM_LIMIT
        assert fa.vmem_bytes(kernel, *pick, *long, 2) <= fa.VMEM_LIMIT
    # nothing fits 16,384 positions: the narrowest tile is what is left
    assert fa.default_blocks("dkv", 16384, 16384, 64, 64, BF16, True) == (
        128, 128)


def _traced_blocks(S, D, Dv, config=None, dtype=BF16):
    """flash_blocks after TRACING (nothing runs) forward + backward."""
    tune.reset_counters()
    q = jax.ShapeDtypeStruct((1, S, 2, D), dtype)
    v = jax.ShapeDtypeStruct((1, S, 2, Dv), dtype)
    jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, config=config).astype(jnp.float32)),
        argnums=(0, 1, 2)), q, q, v)
    return tune.counters()["flash_blocks"]


def test_a_tune_winner_overrides_the_rule_and_a_bad_one_degrades():
    rule = {k: "%dx%d" % fa.default_blocks(k, 1024, 1024, 64, 64, BF16, True)
            for k in fa.KERNELS}
    # a winner that divides the padded length runs as given
    got = _traced_blocks(1024, 64, 64, {"block_q": 128, "block_k": 512})
    assert {n.split()[1] for n in got} == {"128x512"}
    # one that does not divide it (1,024 = 2.67 x 384), or that the VMEM
    # reckoning refuses, degrades to the rule
    for cfg, S, D in (({"block_q": 384, "block_k": 128}, 1024, 64),
                      ({"block_q": 64, "block_k": 100}, 1024, 64)):
        got = _traced_blocks(S, D, D, cfg)
        assert {tuple(n.split()) for n in got} == set(rule.items()), got
    assert fa.vmem_bytes("dq", 512, 512, 8192, 8192, 192, 128, 2) \
        > fa.VMEM_LIMIT
    got = _traced_blocks(8192, 192, 128, {"block_q": 512, "block_k": 512})
    assert "dq 512x512" not in got and any(n.startswith("dq ") for n in got)


def _decoder_step_blocks(seq):
    """flash_blocks after TRACING one training step (forward, recomputed
    half-layers, backward, Adam) of a small latent-attention / sparse-
    expert decoder at ``seq`` positions under pure AMP: ``jax.eval_shape``
    over the main block's ops, nothing runs but the startup program."""
    import paddle_tpu as pt
    from paddle_tpu import layers as L
    from paddle_tpu.core.executor import RngSource, trace_ops
    from paddle_tpu.models.latent_moe_lm import latent_moe_lm
    from test_latent_moe_lm import HALVES, tiny
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        tokens = L.data("tokens", shape=[seq], dtype="int64")
        labels = L.data("labels", shape=[seq], dtype="int64")
        out = latent_moe_lm(tokens, tiny(True), labels=labels)
        pt.amp.enable(main, pure=True)
        pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(out["loss"])
        pt.memory_optimize(main, remat_types=HALVES)
    exe = pt.Executor(pt.CPUPlace())
    with pt.scope_guard(scope):
        exe.run(startup)
        state = {n: scope.find_var(n) for n in exe._state_inputs(
            main, scope, {"tokens", "labels"})}
    feed = {n: jax.ShapeDtypeStruct((2, seq), jnp.int64)
            for n in ("tokens", "labels")}

    def step(state, feed):
        env = dict(feed, **state)
        trace_ops(main.global_block(), env, RngSource(jax.random.PRNGKey(0)))
        return env[out["loss"].name]

    prev = pt.amp.force(True)
    tune.reset_counters()
    try:
        jax.eval_shape(step, state, feed)
    finally:
        pt.amp.force(prev)
    return tune.counters()["flash_blocks"]


def test_flash_blocks_counts_only_the_large_shape_for_the_decoder_at_4096():
    counted = _decoder_step_blocks(4096)
    # three layers: the forward, the recomputed forward, dQ and dK/dV of each
    assert {n.split()[0] for n in counted} == set(fa.KERNELS)
    assert {n.split()[1] for n in counted} == {"512x512"}, counted
    assert all(c >= 3 for c in counted.values()), counted


def test_flash_blocks_at_128_positions_is_one_tile():
    counted = _decoder_step_blocks(128)
    assert {n.split()[1] for n in counted} == {"128x128"}, counted


# ---------------------------------------------------------------------------
# grouped k/v heads and a sliding window


def _needs(S, bq, bk, window):
    """{(q tile, k tile): whether it has to be masked} of the tiles that
    hold a seen pair (k <= q, q - k < window), from the positions."""
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    out = {}
    for qi in range(S // bq):
        for ki in range(S // bk):
            tile = seen[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
            if tile.any():
                out[qi, ki] = not tile.all()
    return out


@pytest.mark.parametrize("bk", [128, 256, 512])
@pytest.mark.parametrize("bq", [128, 256, 512])
@pytest.mark.parametrize("window", [1, 100, 128, 129, 300, 512, 1022, 1023,
                                    2048, 5000])
def test_banded_kernels_visit_the_band_and_mask_its_edges(bq, bk, window):
    """Whatever the widths and the window: a banded kernel visits exactly
    the tiles that hold a seen pair, none under the band's lower edge and
    none above the diagonal; it masks exactly those an edge crosses, with
    the mask of THAT edge; and ``flash_tiles`` counts the same tiles
    (forward / dQ by q block, dK/dV by k block)."""
    S = 2048
    want = _needs(S, bq, bk, window)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]

    def edges(qi, ki):
        """(the diagonal crosses the tile, the band's lower edge does)."""
        d = (i - j)[qi * bq:(qi + 1) * bq, ki * bk:(ki + 1) * bk]
        return bool((d < 0).any()), bool((d >= window).any())

    def visited(segments, pair):
        got = {}
        for masks, lo, hi in segments:
            for t in range(lo, hi):
                assert pair(t) not in got
                assert (bool(masks.get("causal")),
                        masks.get("window") is not None) == edges(*pair(t))
                assert masks.get("window") in (None, window)
                got[pair(t)] = bool(masks.get("causal")
                                    or masks.get("window"))
        return got

    by_q, by_k = {}, {}
    for qi in range(S // bq):
        by_q.update(visited(
            fa._k_segments(qi * bq, bq, bk, S, S, True, window),
            lambda ki: (qi, ki)))
    for ki in range(S // bk):
        by_k.update(visited(
            fa._q_segments(ki * bk, bk, bq, S // bq, True, None, window),
            lambda qi: (qi, ki)))
    assert by_q == want and by_k == want
    for kernel in fa.KERNELS:
        assert fa.tile_counts(kernel, bq, bk, S, S, S, True, window) == {
            "visited": len(want), "masked": sum(want.values()),
            "square": (S // bq) * (S // bk)}


def test_the_cells_banded_launch_visits_70_of_256_tiles():
    """8,192 positions, a 2,048-key window, 512 x 512: 70 tiles a head
    (28 of them masked) where the triangle has 136 (16 masked)."""
    for kernel in fa.KERNELS:
        assert fa.tile_counts(kernel, 512, 512, 8192, 8192, 8192, True,
                              2048) == {"visited": 70, "masked": 28,
                                        "square": 256}
        assert fa.tile_counts(kernel, 512, 512, 8192, 8192, 8192, True) == {
            "visited": 136, "masked": 16, "square": 256}


def test_a_window_narrower_than_its_tiles_narrows_them():
    """The rule under a window: no block wider than the window rounded up
    to 128; a 2,048-key window leaves it as it is; the group changes the
    dK/dV kernel's result blocks (f32) and nothing else."""
    args = (8192, 8192, 128, 128, BF16, True)
    for kernel in fa.KERNELS:
        assert fa.default_blocks(kernel, *args, 8, 2048) == (512, 512)
        assert fa.default_blocks(kernel, *args, 8, 200) == (256, 256)
        assert fa.default_blocks(kernel, *args, 1, 64) == (128, 128)
    assert fa.vmem_bytes("dkv", 512, 512, 8192, 8192, 128, 128, 2, 8) \
        - fa.vmem_bytes("dkv", 512, 512, 8192, 8192, 128, 128, 2) \
        == 2 * 2 * 512 * 128 * (4 - 2)
    for kernel in ("fwd", "dq"):
        assert fa.vmem_bytes(kernel, 512, 512, 8192, 8192, 128, 128, 2, 8) \
            == fa.vmem_bytes(kernel, 512, 512, 8192, 8192, 128, 128, 2)


@pytest.mark.parametrize("bad", [
    dict(window=0), dict(window=16, causal=False), dict(hkv=3)])
def test_a_call_the_kernels_cannot_honour_raises(bad):
    q = jnp.zeros((1, 64, 4, 16))
    kv = jnp.zeros((1, 64, bad.get("hkv", 2), 16))
    with pytest.raises(ValueError):
        fa.flash_attention(q, kv, kv, causal=bad.get("causal", True),
                           window=bad.get("window"))


def test_the_attention_op_takes_grouped_heads_and_a_window():
    """Through the layers DSL: the ``flash_attention`` op with 4 q heads on
    2 k/v heads and a window of 24, against the dense composition; the
    tune key carries ``hkv`` and ``window``."""
    import paddle_tpu as pt
    from paddle_tpu import layers as L
    from test_flash_attention_grouped import _dense_grouped
    S, D = 96, 16
    main, startup, scope = pt.Program(), pt.Program(), pt.Scope()
    with pt.program_guard(main, startup):
        q = L.data("q", shape=[S, 4, D], dtype="float32")
        k = L.data("k", shape=[S, 2, D], dtype="float32")
        v = L.data("v", shape=[S, 2, D], dtype="float32")
        out = L.flash_attention(q, k, v, causal=True, window=24)
    rng = np.random.RandomState(3)
    feed = {n: rng.randn(2, S, h, D).astype(np.float32)
            for n, h in (("q", 4), ("k", 2), ("v", 2))}
    keys = []
    real = tune.lookup
    try:
        tune.lookup = lambda name, key, **kw: (keys.append(key),
                                               real(name, key, **kw))[1]
        with pt.scope_guard(scope):
            got, = pt.Executor(pt.CPUPlace()).run(main, feed=feed,
                                                  fetch_list=[out])
    finally:
        tune.lookup = real
    want, _lse = _dense_grouped(*(jnp.asarray(feed[n]) for n in "qkv"), 24)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-5)
    assert keys and keys[0]["hkv"] == 2 and keys[0]["window"] == 24
    assert keys[0]["h"] == 4
