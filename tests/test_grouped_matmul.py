"""The grouped-matmul kernels (``kernels/grouped_matmul.py``) in interpret
mode: the product, the left operand's gradient and the weight gradient
against a dense reference that multiplies every row by a one-hot of its
group, with NaN planted in the rows past ``sum(sizes)`` of the operand and
of the result's gradient wherever there are such rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import tune
from paddle_tpu.kernels import grouped_matmul as gm

# the expert layers of the language-model cells: (d, f, held experts,
# first rung's rows)
CELLS = {"K": (2048, 768, 16, 12288), "T": (2048, 1024, 16, 16384),
         "P": (2560, 768, 8, 24576), "N": (2688, 1856, 8, 6144)}


def _tilings(m, k, n):
    """The rule's tilings of the three launches of a differentiated
    product of bf16 rows [m, k] and f32 weights [groups, k, n], as an
    expert layer makes them under AMP."""
    return (gm.default_tiling("gmm", m, k, n, 2, 4, 4),
            gm.default_tiling("gmm_t", m, n, k, 2, 2, 4),
            gm.default_tiling("tgmm", m, k, n, 2, 4, 2))


def _like_the_cell(cell, product):
    """(m, k, n, tilings): the fewest rows at which the rule tiles the
    cell's product (``gate``: [rows, d] x [G, d, f]; ``down``: [rows, f] x
    [G, f, d]) as it does at the cell's own first rung."""
    d, f, _groups, rung = CELLS[cell]
    k, n = (d, f) if product == "gate" else (f, d)
    want = _tilings(rung, k, n)
    for m in range(128, 4097, 128):
        if _tilings(m, k, n) == want:
            return m, k, n, want
    raise AssertionError("no small call tiles as %s's %s product" % (
        cell, product))


def _sizes(rng, m, groups, held):
    cut = np.sort(rng.choice(held + 1, groups - 1))
    return np.diff(np.concatenate([[0], cut, [held]])).astype(np.int32)


def _reference(lhs, rhs, sizes):
    """Every row times a one-hot of its group (zero past the groups)."""
    m, groups = lhs.shape[0], sizes.shape[0]
    group = np.repeat(np.arange(groups), sizes)
    onehot = np.zeros((m, groups), np.float32)
    onehot[np.arange(group.size), group] = 1.0
    return jnp.einsum("mk,mg,gkn->mn", lhs, onehot, rhs,
                      precision=jax.lax.Precision.HIGHEST)


CASES = {
    # name: (m, k, n, sizes, dtype of lhs, of rhs)
    "empty_groups": (256, 64, 32, [0, 100, 0, 60, 0], "float32", "float32"),
    "boundaries_inside_tiles": (512, 128, 256, [100, 37, 200, 175],
                                "float32", "float32"),
    "nan_past_the_groups": (384, 64, 128, [90, 40, 120], "float32",
                            "float32"),
    "no_row_held": (256, 32, 64, [0, 0, 0], "float32", "float32"),
    "every_row_held": (640, 128, 64, [300, 0, 323, 17], "float32",
                       "float32"),
    "fewer_rows_than_a_tile": (48, 16, 24, [5, 30, 0], "float32", "float32"),
    "rows_padded_to_tiles": (300, 32, 64, [100, 0, 150], "float32",
                             "float32"),
    "bf16_rows_f32_weights": (512, 128, 256, [130, 0, 250], "bfloat16",
                              "float32"),
    "bf16_rows_bf16_weights": (384, 128, 128, [20, 200, 0, 100], "bfloat16",
                               "bfloat16"),
}
for _cell in CELLS:
    for _product in ("gate", "down"):
        CASES["%s_%s" % (_cell, _product)] = (_cell, _product)


@pytest.mark.parametrize("case", sorted(CASES))
def test_grouped_matmul_matches_the_one_hot_reference(case):
    rng = np.random.default_rng(sorted(CASES).index(case))
    spec = CASES[case]
    want_blocks = None
    if len(spec) == 2:
        m, k, n, tilings = _like_the_cell(*spec)
        # a tail past the groups, and boundaries inside the tiles
        groups = 3
        sizes = _sizes(rng, m, groups, m - 100)
        dtype, rhs_dtype = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
        want_blocks = {"%s %dx%dx%d g%d" % ((kernel,) + t + (groups,))
                       for kernel, t in zip(gm.KERNELS, tilings)}
    else:
        m, k, n, sizes, dtype, rhs_dtype = spec
        sizes, groups = np.asarray(sizes, np.int32), len(sizes)
        dtype, rhs_dtype = jnp.dtype(dtype), jnp.dtype(rhs_dtype)
    held = int(sizes.sum())
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = (0.3 * rng.standard_normal((groups, k, n))).astype(np.float32)
    ct = rng.standard_normal((m, n)).astype(np.float32)
    # the values each operand's dtype holds, and those the products see
    lhs = np.asarray(jnp.asarray(lhs, dtype).astype(jnp.float32))
    rhs = np.asarray(jnp.asarray(rhs, rhs_dtype).astype(jnp.float32))
    seen = np.asarray(jnp.asarray(rhs, dtype).astype(jnp.float32))
    planted_lhs, planted_ct = lhs.copy(), ct.copy()
    planted_lhs[held:] = np.nan
    planted_ct[held:] = np.nan

    tune.reset_counters()
    out, vjp = jax.vjp(
        lambda a, b: gm.grouped_matmul(a, b, jnp.asarray(sizes)),
        jnp.asarray(planted_lhs, dtype), jnp.asarray(rhs, rhs_dtype))
    d_lhs, d_rhs = vjp(jnp.asarray(planted_ct))
    if want_blocks is not None:
        assert set(tune.counters()["gmm_blocks"]) == want_blocks

    clean = lambda a: jnp.asarray(np.where(
        np.arange(m)[:, None] < held, a, 0.0))
    want, ref_vjp = jax.vjp(
        lambda a, b: _reference(a, b, sizes),
        clean(lhs), jnp.asarray(seen))
    # the result's gradient reaches the products in the rows' dtype
    ct_seen = np.asarray(jnp.asarray(ct, dtype).astype(jnp.float32))
    want_lhs, want_rhs = ref_vjp(clean(ct_seen))

    assert out.dtype == jnp.float32
    assert d_lhs.dtype == dtype and d_rhs.dtype == rhs_dtype
    # the operands' values, f32 sums: a bf16 result is rounded once
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    for name, got, ref in (("out", out, want), ("d_lhs", d_lhs, want_lhs),
                           ("d_rhs", d_rhs, want_rhs)):
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all(), name
        if name != "d_rhs":
            assert (got[held:] == 0).all(), name
        scale = float(np.abs(ref).max()) or 1.0
        np.testing.assert_allclose(got, np.asarray(ref), rtol=tol,
                                   atol=tol * scale, err_msg=name)
    # an empty group's weight gradient is exactly 0
    for g in np.flatnonzero(sizes == 0):
        assert (np.asarray(d_rhs[g], np.float32) == 0).all()


def test_a_launch_wider_than_a_launch_may_ask_for_is_refused():
    """Blocks are as wide as their operands wherever those widths fit
    ``VMEM_MOST`` (the widest such launch of K, T and P, T's weight
    gradient, needs ~41 MiB); where they do not, a narrower row tile, then
    result blocks of whole lanes; where none of those fits either, the rule
    raises."""
    need = gm.vmem_bytes("tgmm", 256, 2048, 1024, 2, 4, 2)
    assert 32 * 2 ** 20 < need <= gm.VMEM_MOST
    assert gm.default_tiling("tgmm", 16384, 2048, 1024, 2, 4, 2) == (
        256, 2048, 1024)
    assert gm.default_tiling("tgmm", 16384, 4096, 2048, 2, 4, 2) == (
        256, 4096, 512)
    with pytest.raises(ValueError, match="tgmm of widths 4000 x 3000"):
        gm.default_tiling("tgmm", 16384, 4000, 3000, 2, 4, 2)


@pytest.mark.parametrize("kernel,k,n,want", [
    ("gmm", 2688, 1856, (256, 2688, 1856)),
    ("gmm", 1856, 2688, (128, 1856, 2688)),
    ("gmm_t", 1856, 2688, (256, 1856, 2688)),
    ("gmm_t", 2688, 1856, (256, 2688, 1856)),
    ("tgmm", 2688, 1856, (256, 896, 1856)),
    ("tgmm", 1856, 2688, (256, 1856, 896))])
def test_expert_widths_past_the_vmem_of_whole_blocks_are_cut(kernel, k, n,
                                                              want):
    """The published 2,688 x 1,856 experts (1,856 = 14.5 lanes): the rows'
    product and both gradients of the up and down products, bf16 rows and
    f32 master weights, at a rung of 6,144 rows. Whole widths where they
    fit, a row tile of 128 where 256 does not, and the weight gradients in
    result blocks of whole lanes along whichever width has them."""
    out, rhs = {"gmm": (4, 4), "gmm_t": (2, 4), "tgmm": (4, 2)}[kernel]
    got = gm.default_tiling(kernel, 6144, k, n, 2, out, rhs)
    assert got == want
    assert gm.vmem_bytes(kernel, *got, 2, out, rhs) <= gm.VMEM_MOST
