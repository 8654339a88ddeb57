"""The held-pick sum kernel (``kernels/pick_sum.py``) against the jnp form
it replaced, in interpret mode: every pick of a token that fell on a held
expert, read back from the sorted rows and summed in f32; nothing past the
held rows reaches a result."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import tune
from paddle_tpu.kernels import pick_sum as ps


def _layer(T, k, groups, share, seed, held_from=0):
    """(inv [T, k], sizes [groups]) of an expert layer whose (token, pick)
    pairs fall on one of its ``groups`` held experts with chance ``share``
    (tokens before ``held_from`` hold none): the stable sort of the pairs
    by held expert, the others last."""
    rng = np.random.default_rng(seed)
    expert = rng.integers(0, groups, size=(T, k))
    held = (rng.random((T, k)) < share) & (np.arange(T) >= held_from)[:, None]
    key = np.where(held, expert, groups).reshape(-1)
    order = np.argsort(key, kind="stable")
    inv = np.empty(T * k, np.int32)
    inv[order] = np.arange(T * k, dtype=np.int32)
    sizes = np.bincount(key, minlength=groups + 1)[:groups].astype(np.int32)
    return inv.reshape(T, k), sizes


def _check(T, k, d, groups, share, rows=None, dtype=jnp.bfloat16, seed=0,
           held_from=0, out_dtype=jnp.float32):
    """The kernel against ``reference`` with NaN in every row from
    ``held`` on; returns the number of held pairs."""
    inv, sizes = _layer(T, k, groups, share, seed, held_from)
    held = int(sizes.sum())
    rows = T * k if rows is None else max(rows, held)
    a = np.random.default_rng(seed + 1).standard_normal((rows, d))
    a[held:] = np.nan
    a = jnp.asarray(a, dtype)
    got = ps.pick_sum(a, jnp.asarray(inv), jnp.asarray(sizes), out_dtype)
    want = ps.reference(a, jnp.asarray(inv), held)
    assert got.shape == (T, d) and got.dtype == out_dtype
    assert bool(jnp.all(jnp.isfinite(got)))
    if out_dtype != jnp.float32:
        # the f32 sum cast once: what the jnp form's sum gives, cast
        want = want.astype(out_dtype)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2 ** -8, atol=0)
        return held
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    # the same exact terms, summed in another order: f32 rounding apart
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=4e-7 * scale * k)
    return held


@pytest.mark.parametrize("k", [6, 8])
@pytest.mark.parametrize("share", [0.0, 0.12, 1.0],
                         ids=["none", "some", "all"])
@pytest.mark.parametrize("short", [False, True], ids=["rows_all", "rows_rung"])
def test_the_kernel_sums_what_the_jnp_form_sums(k, share, short):
    """k = 6 and 8; no pair, some and every pair held; the sorted rows as
    many as the pairs, or a rung of them (a multiple of 512 that holds the
    held rows)."""
    T, groups = 256, 4
    rows = None
    if short:
        inv, sizes = _layer(T, k, groups, share, 0)
        rows = max(512, -(-int(sizes.sum()) // 512) * 512)
    held = _check(T, k, 256, groups, share, rows=rows)
    assert (held == 0) == (share == 0.0)
    assert (held == T * k) == (share == 1.0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_a_width_of_21_lanes(dtype):
    """N's width, 2,688 = 21 x 128, whole lanes but no power of two."""
    _check(128, 6, 2688, 8, 0.3, dtype=dtype, seed=3)


@pytest.mark.parametrize("T", [128, 512], ids=["one_tile", "two_tiles"])
def test_a_bf16_result_is_the_f32_sum_cast_once(T):
    """The gradient of the rows an expert layer hands its experts is
    wanted in the stream's bf16: the kernel adds each tile up in f32 and
    casts it once, as the jnp form's f32 sum cast to bf16."""
    _check(T, 6, 256, 4, 0.5, seed=9, out_dtype=jnp.bfloat16)


def test_a_token_tile_with_no_held_pick():
    """Tokens 0-255 (the first tile of 256) hold no pair: that tile copies
    nothing and its rows are 0; the second tile's are its picks'."""
    T, k = 512, 6
    assert ps.default_tile(T, k, 256, 4, 2) == 256
    _check(T, k, 256, 4, 0.5, seed=5, held_from=256)


def test_tokens_rows_and_a_width_that_no_tile_divides():
    """300 tokens (padded to 384), 1,800 rows (not whole chunks) and a
    width of 200 (not whole lanes) are padded and cut off again."""
    _check(300, 6, 200, 4, 0.4, rows=1800, dtype=jnp.float32, seed=7)


@pytest.mark.parametrize("T,k,groups", [(512, 6, 8), (256, 8, 16),
                                        (768, 4, 4)])
def test_a_tile_stages_no_more_than_its_slot_holds(T, k, groups):
    """Every pair held and each held expert's run as ragged as the chunks
    allow: no tile stages past ``staged_rows``, no copy reads past the
    rows, and each held pick's staged row lies inside its tile's copies."""
    rng = np.random.default_rng(11)
    for share in (0.05, 0.5, 1.0):
        inv, sizes = _layer(T, k, groups, share, int(rng.integers(1 << 30)))
        rows = T * k
        tile = ps.default_tile(T, k, 128, groups, 2)
        table, pos = ps._runs(jnp.asarray(inv), jnp.asarray(sizes), tile,
                              rows)
        table = np.asarray(table).reshape(T // tile, 3, groups)
        first, chunks, stage = table[:, 0], table[:, 1], table[:, 2]
        staged = chunks.sum(axis=1) * ps.CHUNK
        assert staged.max() <= ps.staged_rows(tile, k, groups)
        assert ((first + chunks) * ps.CHUNK <= rows).all()
        pos = np.asarray(pos).T.reshape(T // tile, tile, k)
        inv = inv.reshape(T // tile, tile, k)
        held = inv < sizes.sum()
        assert ((pos >= 0) == held).all()
        group = np.searchsorted(np.cumsum(sizes), inv, side="right")
        for i in range(T // tile):
            g, src, at = group[i][held[i]], inv[i][held[i]], pos[i][held[i]]
            # each held pick's sorted row lies in its run's chunks, and is
            # staged at its own row of the tile's slot
            lo = first[i, g] * ps.CHUNK
            assert ((lo <= src) & (src < lo + chunks[i, g] * ps.CHUNK)).all()
            assert (at == stage[i, g] + src - lo).all()
            assert np.unique(at).size == at.size and (at < staged[i]).all()


def test_each_shape_is_counted_once_a_traced_launch():
    """``tune.counters()["pick_sums"]``: one entry a shape, one count each
    time a call is traced, none for a call that jit's cache answers."""
    tune.reset_counters()
    inv, sizes = _layer(256, 6, 4, 0.2, 13)
    a = jnp.zeros((1536, 256), jnp.bfloat16)
    f = jax.jit(ps.pick_sum)
    for _ in range(3):
        f(a, jnp.asarray(inv), jnp.asarray(sizes)).block_until_ready()
    name = "256x6x256 rows1536 tile256"
    assert tune.counters()["pick_sums"] == {name: 1}
    f(a[:1024], jnp.asarray(inv), jnp.asarray(sizes)).block_until_ready()
    assert tune.counters()["pick_sums"] == {
        name: 1, "256x6x256 rows1024 tile256": 1}
    tune.reset_counters()
    assert tune.counters()["pick_sums"] == {}
